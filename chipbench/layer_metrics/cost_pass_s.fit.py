"""Solver and kernels: device seconds of the Lloyd program after its
``while`` has ended, in the traced fit — the pass that computes the reported
cost at ``Precision.HIGHEST`` (``lloyd_reduce.py``). No trace, no such spans,
no loop → nothing."""
from chipbench import lloyd_reduce


def read(ctx):
    loop = lloyd_reduce.lloyd_loop(ctx)
    return loop["after_s"] if loop else None
