"""Solver and kernels: device seconds of the histogram kernel's events (the
Pallas calls ``rf_hist_sel_pass`` / ``rf_hist_pass``, one a tree and a level)
inside the traced fit (``rf_reduce.py``). No trace, no such kernel (a level
that fell to XLA's scatter has none) → nothing."""
from chipbench import rf_reduce


def read(ctx):
    found = rf_reduce.fit_hist_kernel(ctx)
    return found[0] if found else None
