"""Solver and kernels: the least-squares Gram's share of its roofline — the
least time the chip could take for ``linreg_reduce.gram_work`` (the
symmetric half of [x | 1 | y], rows·(cols+2)·(cols+3) operations, and one
read of X; ``peaks.json``) over ``ls_gram_s.fit``, in percent. ``None``,
never 0, where the fold is not found; which roof bounds it is printed on
stderr."""
import sys

from chipbench import linreg_reduce


def read(ctx):
    found, peaks = linreg_reduce.fit_programs(ctx), ctx["peaks"]
    if not found or not peaks or found["fold_s"] <= 0.0:
        return None
    work = linreg_reduce.gram_work(ctx["rows"], int(ctx["config"]["cols"]))
    t_flops = work["flops"] / (peaks["flops_per_s"] * found["devices"])
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * found["devices"])
    print(f"chipbench: least-squares gram roofline: least {max(t_flops, t_bytes):.5f} s "
          f"({'compute' if t_flops >= t_bytes else 'hbm'}-bound; compute {t_flops:.5f} s, hbm {t_bytes:.5f} s) "
          f"over {found['fold_s']:.5f} s", file=sys.stderr, flush=True)
    return 100.0 * max(t_flops, t_bytes) / found["fold_s"]
