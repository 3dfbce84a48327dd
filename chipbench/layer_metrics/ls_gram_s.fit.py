"""Solver and kernels: device seconds of the least-squares fold's runs in the
traced fit — the first program ``solver.launch`` names, one run a row block:
the Gram of the block at HIGHEST and its label's sums (``linreg_reduce.py``).
No trace, no such spans or program → nothing."""
import sys

from chipbench import linreg_reduce


def read(ctx):
    found = linreg_reduce.fit_programs(ctx)
    if not found:
        return None
    print(f"chipbench: least-squares fit: {found['folds']} folds {found['fold_s']:.5f} s, finish {found['finish_s']:.5f} s",
          file=sys.stderr, flush=True)
    return found["fold_s"]
