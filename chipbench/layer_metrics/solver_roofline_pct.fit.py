"""Solver and kernels: the least time the chip could take for the fit's
work (``work/<config>.py`` from the shapes and the traced job's iteration
count, ``peaks.json``) over the device-busy time inside the solver-dispatch
annotation of the traced job, in percent. Nothing to read → nothing."""
import sys


def read(ctx):
    trace, peaks, job = ctx["trace"], ctx["peaks"], ctx["traced_job"]
    if not trace or not peaks or not job:
        return None
    busy = trace["busy_in_s"].get("dispatch", 0.0)
    if busy <= 0.0:
        return None
    work = ctx["work"].fit_work(ctx["rows"], int(ctx["config"]["cols"]), job["model"])
    t_flops = work["flops"] / (peaks["flops_per_s"] * trace["devices"])
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * trace["devices"])
    roof = "compute" if t_flops >= t_bytes else "hbm"
    print(f"chipbench: solver roofline: least {max(t_flops, t_bytes):.4f} s ({roof}-bound; "
          f"compute {t_flops:.4f} s, hbm {t_bytes:.4f} s) over busy {busy:.4f} s", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / busy
