"""The least-squares fit's two programs in the traced fit, and the Gram's work.

A ``LinearRegression`` fit that folds its frame names two programs on its
``solver.launch`` span, ``program="<fold>,<finish>"``: the fold that runs once
a row block as the block lands (the Gram of the block and its label's sums),
and the finish after the last block (psum, mirror, re-centring, the solve).
:func:`fit_programs` sums, over the device's ``XLA Modules`` events inside the
traced fit, the runs of each by name (``jit_<function>``).

Where the trace has no such span or program (the CPU rehearsal has no device
plane; a program whose launch names one program only) it returns ``None``
and the metrics that read it are left out of the line.
"""

from __future__ import annotations

import re

from chipbench import span_reduce


def gram_work(rows: int, cols: int) -> dict:
    """Operations and bytes the Gram of ``[x | 1 | y]`` needs, whatever
    implements it: its symmetric half, (cols+2)(cols+3)/2 entries of ``rows``
    products each — rows·(cols+2)·(cols+3) operations — and one read of X
    (the label is small beside it)."""
    return {"flops": float(rows) * (cols + 2) * (cols + 3), "bytes": 4.0 * rows * cols}


def fit_programs(ctx) -> dict | None:
    """``fold_s`` and ``finish_s`` (device seconds of the runs of the first
    and the last program the launch names, summed, the mean over the device
    planes), ``folds`` (runs of the fold on the first plane), ``devices``."""
    fit = span_reduce.traced_fit(ctx)
    if not fit:
        return None
    names = [p for p in str(span_reduce.attrs(ctx, fit["launch"]).get("program", "")).split(",") if p]
    if len(names) < 2:
        return None
    match = [re.compile(rf"^jit_{re.escape(p)}\b") for p in (names[0], names[-1])]
    lo, hi = fit["enqueue"]["lo"], fit["fetch"]["hi"]
    planes = [events for events in fit["trace"]["modules"].values() if events]
    totals, folds = [0.0, 0.0], None
    for events in planes:
        runs = [[(b - a) for name, a, b in events if lo <= a <= hi and m.search(name)] for m in match]
        totals = [t + sum(r) for t, r in zip(totals, runs)]
        folds = len(runs[0]) if folds is None else folds
    if not planes or not folds:
        return None
    ns = span_reduce.NS
    return {"fold_s": totals[0] / len(planes) * ns, "finish_s": totals[1] / len(planes) * ns,
            "folds": folds, "devices": len(planes)}
