#!/usr/bin/env python3
"""Bring a recorded ``.xplane.pb`` under the size a fixture may have: drop
the host plane's lines of the runtime's linearizing worker threads, the
``futex-default-SDomainT/*`` lines that hold a ``Transpose`` event (a 2.4 GB
frame leaves 28,000 events on fifteen of them: a megabyte that no reader
looks at). Every other line, every device plane and all metadata stay as
recorded.

    python3 chipbench/tests/trim_trace.py chiprun_out/rec37/pca_200000rows_link.xplane.pb \
        chipbench/tests/data/pca_200000rows_link.xplane.pb

prints what went; ``record_trace.py`` writes the input (and the
``.spans.json`` beside it, which is kept whole).
"""
import sys

HOST_PLANE = "/host:CPU"
WORKER_LINE, WORKER_EVENT = "futex-default-SDomainT/", "Transpose"


def main() -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    src, dst = sys.argv[1:3]
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    lines = events = 0
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        keep = [
            ln for ln in plane.lines
            if not (ln.name.startswith(WORKER_LINE) and any(names[e.metadata_id] == WORKER_EVENT for e in ln.events))
        ]
        lines += len(plane.lines) - len(keep)
        events += sum(len(ln.events) for ln in plane.lines) - sum(len(ln.events) for ln in keep)
        kept = [xplane_pb2.XLine.FromString(ln.SerializeToString()) for ln in keep]
        del plane.lines[:]
        plane.lines.extend(kept)
    out = space.SerializeToString()
    with open(dst, "wb") as f:
        f.write(out)
    print(f"dropped {events} events on {lines} lines of {HOST_PLANE}: {len(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
