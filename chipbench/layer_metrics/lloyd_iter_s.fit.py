"""Solver and kernels: device seconds of one trip of the Lloyd program's
``while`` in the traced fit — the outermost ``while`` event of the program
``solver.launch`` names, over the fit's ``n_iter`` (``lloyd_reduce.py``). One
trip is one pass over X that assigns every row and sums every centre, plus
the centres' update. No trace, no such spans, no loop → nothing."""
from chipbench import lloyd_reduce


def read(ctx):
    loop = lloyd_reduce.lloyd_loop(ctx)
    return loop["loop_s"] / loop["n_iter"] if loop else None
