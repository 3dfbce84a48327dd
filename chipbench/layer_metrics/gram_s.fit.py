"""Solver and kernels: device seconds of the Gram's operations in the traced
fit — inside the run of the program ``solver.launch`` names, from the first
operation that takes the frame as an operand (the mean's sample) to the end
of the last (the Gram pass, a Pallas call or XLA's loop: ``pca_reduce.py``).
No trace, no such spans, no operation that names the frame → nothing."""
import sys

from chipbench import pca_reduce


def read(ctx):
    split = pca_reduce.gram_split(ctx)
    if not split:
        return None
    print(f"chipbench: pca fit program: gram {split['gram_s']:.5f} s + eig and finish {split['eig_s']:.5f} s; operations that take the frame: "
          + ", ".join(f"{k} x{v}" for k, v in sorted(split["ops"].items(), key=lambda kv: -kv[1])[:6]), file=sys.stderr, flush=True)
    return split["gram_s"]
