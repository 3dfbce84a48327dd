"""Solver and kernels: loss+gradient evaluations per fit — ``n_evals`` on the
``solver.fetch`` span (the L-BFGS's own count: one at the start, one for the
first trial of each iteration, one per backtracking trial), mean over the
window's fits. The device's work is proportional to it."""
from chipbench import span_reduce


def read(ctx):
    counts = [s["args"]["n_evals"] for s in span_reduce.named(ctx, "solver.fetch") if "n_evals" in s["args"]]
    return sum(counts) / len(counts) if counts else None
