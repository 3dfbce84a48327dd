"""Solver and kernels: the histogram kernel's share of its roofline — the
least time the chip could take for the forest's histogram updates
(``work/<config>.py``'s ``hist_work`` from the served forest's own node
counts: one update and one bin byte a weighted row, level and sampled
feature; ``peaks.json``) over ``hist_s.fit``, in percent. ``None``, never 0,
where the kernel is not found; which roof bounds it is printed on stderr."""
import sys

from chipbench import rf_reduce


def read(ctx):
    found, peaks, job = rf_reduce.fit_hist_kernel(ctx), ctx["peaks"], ctx["traced_job"]
    hist_work = getattr(ctx["work"], "hist_work", None)
    if not found or not peaks or not job or hist_work is None or found[0] <= 0.0:
        return None
    work = hist_work(int(ctx["config"]["cols"]), job["model"])
    t_flops, t_bytes = work["flops"] / peaks["flops_per_s"], work["bytes"] / peaks["hbm_bytes_per_s"]
    print(f"chipbench: histogram roofline: least {max(t_flops, t_bytes):.5f} s ({'compute' if t_flops >= t_bytes else 'hbm'}-bound; "
          f"{work['flops']:.4g} updates, {work['bytes']:.4g} bytes) over {found[0]:.4f} s in {found[1]} kernel events", file=sys.stderr, flush=True)
    return 100.0 * max(t_flops, t_bytes) / found[0]
