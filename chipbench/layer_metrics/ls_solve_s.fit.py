"""Solver and kernels: device seconds of the least-squares finish's run in
the traced fit — the last program ``solver.launch`` names: psum, mirror,
re-centring, the solve and the back-transform (``linreg_reduce.py``). No
trace, no such spans or program → nothing."""
from chipbench import linreg_reduce


def read(ctx):
    found = linreg_reduce.fit_programs(ctx)
    return found["finish_s"] if found else None
