"""Solver and kernels: first start to last end, on the device, of the module
events of ``solver.launch``'s ``program`` in the traced fit (for
LogisticRegression one run of ``jit_logreg_fit``: moments, the L-BFGS loop,
the back-transform). No trace, no such spans → nothing."""
from chipbench import span_reduce


def read(ctx):
    split = span_reduce.fit_split(ctx)
    return split["solver_device"] if split else None
