"""Solver and kernels: device seconds from the end of the Gram's last
operation to the end of the fit program's run, in the traced fit — the
rank-one correction, the eigen-solve and the finish (``pca_reduce.py``). No
trace, no such spans, no operation that names the frame → nothing."""
from chipbench import pca_reduce


def read(ctx):
    split = pca_reduce.gram_split(ctx)
    return split["eig_s"] if split else None
