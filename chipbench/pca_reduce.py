"""The PCA fit program of the traced fit, split at the end of its Gram.

``span_reduce.traced_fit`` finds the device's run of the program that the
fit's ``solver.launch`` span names (``_pca_fit_kernel``: the mean's sample,
the Gram pass over the frame, the rank-one correction, then the eigen-solve
and the finish). Among the device's ``XLA Ops`` inside that run, the Gram's
are those that take the FRAME as an operand, whatever implements the pass:
an operand ``<dtype>[<rows on a device>,<cols>]`` or, where the pass reads a
rows-minor frame as its transpose, ``<dtype>[<cols>,<rows on a device>]`` —
the mean sample's slices, the Pallas call ``pca_gram_pass``, or the ``while``
of XLA's blocked pass and the fusions in its body. The Gram lasts from the
first of them to the end of the last; what the program runs after that is
the eigen-solve and the finish.

Where the trace has no such program or span (the CPU rehearsal, a program
without these spans) or no operation names the frame, :func:`gram_split`
returns ``None`` and the metrics that read it are left out of the line.
"""

from __future__ import annotations

import re

from chipbench import span_reduce
from chipbench import trace_reduce as tr


def gram_split(ctx) -> dict | None:
    """``gram_s`` (device-busy seconds from the first operation that takes
    the frame to the end of the last), ``eig_s`` (from there to the end of the
    program's run), ``ops`` (names counted), ``devices``; means over the
    device planes that ran the program."""
    fit = span_reduce.traced_fit(ctx)
    if not fit:
        return None
    config = ctx["config"]
    dt, cols = span_reduce.HLO_DTYPES.get(config["dtype"]), int(config["cols"])
    shape = re.compile(rf"\b{dt}\[(?:(\d+),{cols}|{cols},(\d+))\]")
    n_dev = max(1, len(fit["trace"]["ops"]))
    gram_s, eig_s, names, planes = 0.0, 0.0, {}, 0
    for ops in fit["trace"]["ops"].values():
        mine = [(name, lo, hi) for name, lo, hi in ops if fit["device_lo"] <= lo and hi <= fit["device_hi"]]
        takes = []
        for name, lo, hi in mine:
            operands = span_reduce.opcode_and_operands(name)[1]
            if any(int(a or b) * n_dev >= ctx["rows"] for a, b in shape.findall(operands)):
                takes.append((name, lo, hi))
        if not takes:
            continue
        g_lo, g_hi = min(t[1] for t in takes), max(t[2] for t in takes)
        gram_s += tr.total(tr.clip(tr.union([(lo, hi) for _, lo, hi in mine]), [(g_lo, g_hi)]))
        eig_s += fit["device_hi"] - g_hi
        planes += 1
        for name, _, _ in takes:
            names[tr.short(name)] = names.get(tr.short(name), 0) + 1
    if not planes:
        return None
    ns = span_reduce.NS
    return {"gram_s": gram_s / planes * ns, "eig_s": eig_s / planes * ns, "ops": names, "devices": planes}
