"""Solver and kernels: device-idle seconds inside the traced fit's
``solver_device`` interval (``span_reduce.fit_split``: first start to last
end of the solver's programs): a program waiting for a row block, the gap
between two dispatch groups. Idle time as ``device_idle_pct`` counts it. No
trace, no such spans → nothing."""
from chipbench import link_reduce


def read(ctx):
    return link_reduce.idle_in_solver(ctx)
