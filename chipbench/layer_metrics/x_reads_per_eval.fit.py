"""Solver and kernels: reads of the frame per loss+gradient evaluation in the
traced fit — device operations inside a ``while`` event of the solver's
program that take an operand of the frame's own shape
(``span_reduce.frame_reads``), over that fit's ``n_evals``. Autodiff's forward
and backward pass read X twice (≈ 2: the evaluation at the start lies before
the loop); a fused pass reads it once."""
import sys

from chipbench import span_reduce


def read(ctx):
    reads = span_reduce.frame_reads(ctx)
    if not reads:
        return None
    n_evals = span_reduce.traced_fit(ctx)["fetch_attrs"].get("n_evals")
    if not n_evals:
        return None
    print(f"chipbench: reads of {reads['shape']} inside the solver's loop: {reads['reads']:g} over {n_evals} evaluations; "
          + ", ".join(f"{k} x{v}" for k, v in sorted(reads["ops"].items(), key=lambda kv: -kv[1])[:6]), file=sys.stderr, flush=True)
    return reads["reads"] / n_evals
