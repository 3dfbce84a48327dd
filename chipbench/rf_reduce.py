"""The forest's programs and kernels in the traced job.

A random-forest fit is several programs one after another — the quantile
sketch (``jit_quantile_edges``), ``jit_binize``, then one run of
``jit_build_forest`` a dispatch group — and its histogram is a Pallas call
inside the last (``rf_hist_sel_pass`` / ``rf_hist_pass`` among the device's
``XLA Ops``). The transform is one run of the descent's program a batch, inside
the program's ``forest.descent`` spans. This module finds them by name inside
the traced job's ``<Estimator>.fit`` root span, or inside its spans of a given
name, on the profiler's clock (``span_reduce.read``).

Where the trace has no such span, program or kernel (the CPU rehearsal has no
device plane; the parent's program has none of these spans) every function
returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

from chipbench import span_reduce
from chipbench import trace_reduce as tr

HIST_KERNELS = ("rf_hist_sel_pass", "rf_hist_pass")


def _windows(ctx, span_name):
    trace = span_reduce.traced(ctx)
    if not trace:
        return None, []
    return trace, [(s["lo"], s["hi"]) for s in trace["spans"] if s["name"] == span_name]


def _seconds(events_by_plane, windows, wanted):
    """Mean over the device planes of the summed durations of the events whose
    name ``wanted`` accepts and that start inside one of ``windows``; and
    their count on the first plane."""
    planes = [p for p in events_by_plane.values() if p]
    if not planes or not windows:
        return None
    total, count = 0.0, 0
    for i, events in enumerate(planes):
        mine = [(lo, hi) for name, lo, hi in events if wanted(name) and any(w_lo <= lo <= w_hi for w_lo, w_hi in windows)]
        total += sum(hi - lo for lo, hi in mine)
        count = count or len(mine)
    return (total / len(planes) * span_reduce.NS, count) if count else None


def fit_modules(ctx, needle: str):
    """(device seconds, runs) of the programs named ``*needle*`` inside the traced fit."""
    trace, windows = _windows(ctx, ctx["config"]["annotations"]["fit"])
    return _seconds(trace["modules"], windows[:1], lambda name: needle in name) if trace else None


def fit_hist_kernel(ctx):
    """(device seconds, events) of the histogram kernel inside the traced fit."""
    trace, windows = _windows(ctx, ctx["config"]["annotations"]["fit"])
    if not trace:
        return None
    return _seconds(trace["ops"], windows[:1], lambda name: any(k in tr.short(name) for k in HIST_KERNELS))


def descent_modules(ctx):
    """(device seconds, runs) of the programs inside the traced job's
    ``forest.descent`` spans, the batch's ``binize`` left out."""
    trace, windows = _windows(ctx, "forest.descent")
    return _seconds(trace["modules"], windows, lambda name: "binize" not in name) if trace else None


def transforms(ctx) -> int:
    trace, windows = _windows(ctx, span_reduce.transform_call(ctx))
    return len(windows)
