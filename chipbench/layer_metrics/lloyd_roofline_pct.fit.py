"""Solver and kernels: the Lloyd iteration's own share of its roofline — the
least time the chip could take for one iteration's work
(``work/<config>.py``'s ``iter_work`` from the published shapes: 4·rows·cols·k
operations, one read of X; ``peaks.json``) over ``lloyd_iter_s.fit``, in
percent. ``None``, never 0, where the loop is not found; which roof bounds it
is printed on stderr."""
import sys

from chipbench import lloyd_reduce


def read(ctx):
    loop, peaks, job = lloyd_reduce.lloyd_loop(ctx), ctx["peaks"], ctx["traced_job"]
    iter_work = getattr(ctx["work"], "iter_work", None)
    if not loop or not peaks or not job or iter_work is None or loop["loop_s"] <= 0.0:
        return None
    work = iter_work(ctx["rows"], int(ctx["config"]["cols"]), int(job["model"]["cluster_centers"].shape[0]))
    t_flops = work["flops"] / (peaks["flops_per_s"] * loop["devices"])
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * loop["devices"])
    iter_s = loop["loop_s"] / loop["n_iter"]
    print(f"chipbench: lloyd roofline: least {max(t_flops, t_bytes):.5f} s an iteration "
          f"({'compute' if t_flops >= t_bytes else 'hbm'}-bound; compute {t_flops:.5f} s, hbm {t_bytes:.5f} s) "
          f"over {iter_s:.5f} s ({loop['n_iter']} iterations in {loop['loop_s']:.4f} s)", file=sys.stderr, flush=True)
    return 100.0 * max(t_flops, t_bytes) / iter_s
