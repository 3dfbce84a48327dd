"""The Lloyd program of the traced fit, split at its loop.

``span_reduce.traced_fit`` finds the device's run of the program that the
fit's ``solver.launch`` span names (``_kmeans_lloyd_1d``: a ``while`` over the
Lloyd iterations, then the pass that computes the reported cost). Among the
device's ``XLA Ops`` inside that run, the OUTERMOST ``while`` is the Lloyd
loop, whatever implements an iteration inside it (a Pallas custom call, or
XLA's own chunk loop: a ``while`` nested in it); what the program runs after
that ``while`` has ended is the cost pass.

Where the trace has no such program, span or loop (the CPU rehearsal, a
program without these spans) :func:`lloyd_loop` returns ``None`` and the
metrics that read it are left out of the line.
"""

from __future__ import annotations

from chipbench import span_reduce


def lloyd_loop(ctx) -> dict | None:
    """``loop_s`` (device seconds of the outermost ``while``), ``after_s``
    (from its end to the end of the program's run), ``n_iter`` (the traced
    fit's own count, from its ``solver.fetch`` span), ``devices`` (the planes
    that ran the loop); times are means over them."""
    fit = span_reduce.traced_fit(ctx)
    if not fit:
        return None
    n_iter = fit["fetch_attrs"].get("n_iter")
    loops = []
    for ops in fit["trace"]["ops"].values():
        whiles = [
            (lo, hi) for name, lo, hi in ops
            if fit["device_lo"] <= lo and hi <= fit["device_hi"] and span_reduce.opcode_and_operands(name)[0] == "while"
        ]
        if whiles:
            loops.append(max(whiles, key=lambda w: w[1] - w[0]))
    if not loops or not n_iter:
        return None
    ns = span_reduce.NS
    return {
        "loop_s": sum(hi - lo for lo, hi in loops) / len(loops) * ns,
        "after_s": sum(fit["device_hi"] - hi for _, hi in loops) / len(loops) * ns,
        "n_iter": int(n_iter),
        "devices": len(loops),
    }
