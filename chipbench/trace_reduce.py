"""The one reducer from the profiler's ``.xplane.pb`` to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What it
takes from the trace:

* the device planes (``/device:TPU:<n>``), line ``XLA Ops``: one event per
  operation that ran on the chip, start and duration in nanoseconds;
* the host plane (``/host:CPU``): the ``TraceAnnotation`` ranges the program
  writes around its phases (``utils/profiling.annotate``: ``<Estimator>.preprocess``,
  ``<Estimator>.fit`` around the solver dispatch, ``<Model>.transform``) and
  the benchmark's own ``chipbench.job`` around the traced job; and, on the
  runtime's transfer thread, the ranges in which it moves a host buffer to the
  device (``TRANSFER``: linearizing the buffer, then the DMA).

Busy time is the union of the device-op intervals (averaged over the device
planes); the traced window is the ``chipbench.job`` range. Busy time inside
a phase is the union clipped to that phase's host ranges — device and host
events are on one clock in the xplane. Transfer time inside a phase is the
union of the ``TRANSFER`` ranges clipped the same way.
"""

from __future__ import annotations

import glob
import os

JOB = "chipbench.job"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TRANSFER = ("XlaLinearize", "H2D Dispatch")   # PJRT's host-to-device path: layout change, then the DMA


def start(trace_dir: str) -> None:
    """Start the profiler with the Python call tracer off: a job runs tens of
    seconds of Python, and only device ops and annotations are read."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(merged, ranges):
    """The part of merged intervals that lies inside any of ``ranges``."""
    out = []
    for rlo, rhi in ranges:
        for lo, hi in merged:
            if hi <= rlo or lo >= rhi:
                continue
            out.append([max(lo, rlo), min(hi, rhi)])
    return union(out)


def short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the trace names an
    operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def self_times(ops):
    """Seconds·1e9 per operation name with nested operations taken out of
    their parents (a ``while`` event spans the events of its body)."""
    out, stack = {}, []          # stack of [name, end, self]
    for name, lo, hi in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and lo >= stack[-1][1]:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(hi, stack[-1][1]) - lo
        stack.append([name, hi, hi - lo])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def read_planes(path: str):
    """(device_ops, host_ranges): per device plane a list of (name, start_ns,
    end_ns); host_ranges name -> list of (start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_ranges = {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((short(e.name), e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            device_ops[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == JOB or e.name in TRANSFER or "." in e.name and not e.name.startswith("$"):
                        host_ranges.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    return device_ops, host_ranges


def reduce(path: str, annotations: dict, top: int = 10):
    """Summary of one traced job; ``None`` where no operation ran on a device."""
    device_ops, host_ranges = read_planes(path)
    device_ops = {k: v for k, v in device_ops.items() if v}
    if not device_ops:
        return None
    job = host_ranges.get(JOB)
    if job:
        w_lo, w_hi = min(r[0] for r in job), max(r[1] for r in job)
    else:
        w_lo = min(o[1] for ops in device_ops.values() for o in ops)
        w_hi = max(o[2] for ops in device_ops.values() for o in ops)
    phases = {key: host_ranges.get(annotations.get("trace_" + key, ""), []) for key in ("preprocess", "dispatch", "transform")}
    transfer = union([r for name in TRANSFER for r in host_ranges.get(name, [])])
    n_dev = len(device_ops)
    busy, busy_in, by_op, gaps = 0.0, {k: 0.0 for k in phases}, {}, []
    for ops in device_ops.values():
        merged = clip(union([(lo, hi) for _, lo, hi in ops]), [(w_lo, w_hi)])
        busy += total(merged)
        for key, ranges in phases.items():
            busy_in[key] += total(clip(merged, ranges))
        for name, t in self_times(ops).items():
            by_op[name] = by_op.get(name, 0.0) + t
        edges = [w_lo] + [x for iv in merged for x in iv] + [w_hi]
        gaps.extend((edges[i + 1] - edges[i], edges[i], edges[i + 1]) for i in range(0, len(edges), 2))
    # idle time by the program phase that covered it on the host
    idle_by = {}
    for length, lo, hi in gaps:
        rest = length
        for key, ranges in phases.items():
            inside = total(clip([[lo, hi]], ranges))
            if inside > 0:
                idle_by[key] = idle_by.get(key, 0.0) + inside
                rest -= inside
        if rest > 0:
            idle_by["outside_phases"] = idle_by.get("outside_phases", 0.0) + rest
    ns = 1e-9
    return {
        "busy_s": busy / n_dev * ns,
        "window_s": (w_hi - w_lo) * ns,
        "busy_in_s": {k: v / n_dev * ns for k, v in busy_in.items()},
        "transfer_in_s": {k: total(clip(transfer, r)) * ns for k, r in phases.items()},
        "phase_s": {k: total(union(r)) * ns for k, r in phases.items()},
        "phase_count": {k: len(r) for k, r in phases.items()},
        "devices": n_dev,
        "breakdown": {
            "device_ops": [[k, v / n_dev * ns] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v / n_dev * ns] for k, v in sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def dump(path: str, limit: int = 12) -> None:
    """Planes, lines and their first events: for looking at a trace by hand."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:limit]:
                print(f"     {e.name[:90]}  start {e.start_ns:.0f}  dur {e.duration_ns:.0f}")


if __name__ == "__main__":
    import sys

    dump(sys.argv[1])
