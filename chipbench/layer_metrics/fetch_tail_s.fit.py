"""Estimator API, host glue: from the end on the device of the solver
program's last module event to the close of the traced fit's ``solver.fetch``
span — the results' way back to the host (D2H of the coefficients, their
conversion) and whatever else the host does before it lets go. With
``input_wait_s.fit`` and ``solver_device_s.fit`` it tiles the interval from
the first ``h2d.enqueue`` to the close of ``solver.fetch``; the three, their
sum and the spans they tile are printed on stderr."""
import sys

from chipbench import span_reduce


def read(ctx):
    split = span_reduce.fit_split(ctx)
    if not split:
        return None
    fit = span_reduce.traced_fit(ctx)
    ann = ctx["config"]["annotations"]
    by = {s["name"]: s for s in reversed(fit["spans"])}
    pre, dis = by.get(ann["preprocess"]), by.get(ann["dispatch"])
    line = (f"chipbench: fit split: input_wait {split['input_wait']:.6f} s + solver_device {split['solver_device']:.6f} s"
            f" + fetch_tail {split['fetch_tail']:.6f} s = {sum(split.values()):.6f} s")
    if pre and dis:
        ns = span_reduce.NS
        line += (f"; spans: {ann['preprocess']} {(pre['hi'] - pre['lo']) * ns:.6f} s, between {(dis['lo'] - pre['hi']) * ns:.6f} s,"
                 f" {ann['dispatch']} {(dis['hi'] - dis['lo']) * ns:.6f} s"
                 f" (of it solver.launch {(fit['launch']['hi'] - fit['launch']['lo']) * ns:.6f} s,"
                 f" solver.fetch {(fit['fetch']['hi'] - fit['fetch']['lo']) * ns:.6f} s)")
    print(line, file=sys.stderr, flush=True)
    return split["fetch_tail"]
