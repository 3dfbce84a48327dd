#!/usr/bin/env python3
"""Record a small chip trace for ``test_span_reduce.py``: one traced run of
the harness itself, with the profiler's ``.xplane.pb`` kept and, beside it,
the window's spans as the program's sink handed them over (what readers get
as ``ctx["spans"]``: the ``tpuml:`` events in the trace carry ids, the
attributes live here).

    chiprun -- python3 chipbench/tests/record_trace.py chiprun_out/rec/logreg_32768rows_spans \
        --workload logreg_dbx.job --seed 2900000017 --seconds 1 --rows 32768

writes ``<prefix>.xplane.pb`` and ``<prefix>.spans.json``; the readers' values
on that trace are in the run's stderr (``rehearsal values`` with ``--rows``).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    prefix, argv = sys.argv[1], sys.argv[2:]
    from chipbench import run as harness
    from spark_rapids_ml_tpu.runtime import telemetry

    seen: list = []
    telemetry.add_span_sink(lambda ev, thread: seen.append(ev))
    rc = harness.main(argv + ["--trace", "1", "--keep-trace", prefix + ".xplane.pb"])
    # the window's spans: everything after the warm job, which ends with the
    # first close of a ``<Model>.transform.call`` root
    warm_end = next(i for i, ev in enumerate(seen) if ev["name"].endswith(".transform.call"))
    with open(prefix + ".spans.json", "w") as f:
        json.dump(seen[warm_end + 1:], f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
