"""Solver and kernels: the Gram's own share of its roofline — the least time
the chip could take for the Gram's work (``work/<config>.py``'s ``gram_work``
from the published shapes: the symmetric half, rows·cols·(cols+1) operations,
each product once, and one read of X; ``peaks.json``) over ``gram_s.fit``, in
percent. ``None``, never 0, where the Gram is not found; which roof bounds it
is printed on stderr."""
import sys

from chipbench import pca_reduce


def read(ctx):
    split, peaks = pca_reduce.gram_split(ctx), ctx["peaks"]
    gram_work = getattr(ctx["work"], "gram_work", None)
    if not split or not peaks or gram_work is None or split["gram_s"] <= 0.0:
        return None
    work = gram_work(ctx["rows"], int(ctx["config"]["cols"]))
    t_flops = work["flops"] / (peaks["flops_per_s"] * split["devices"])
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * split["devices"])
    print(f"chipbench: gram roofline: least {max(t_flops, t_bytes):.5f} s "
          f"({'compute' if t_flops >= t_bytes else 'hbm'}-bound; compute {t_flops:.5f} s, hbm {t_bytes:.5f} s) "
          f"over {split['gram_s']:.5f} s", file=sys.stderr, flush=True)
    return 100.0 * max(t_flops, t_bytes) / split["gram_s"]
