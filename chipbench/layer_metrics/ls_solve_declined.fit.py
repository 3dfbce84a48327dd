"""Solver and kernels: the share of the window's least-squares fits whose
normal solve fell back from Cholesky to the least-norm eigen-solve —
``solve`` on the ``solver.fetch`` span is ``eigh`` (``cholesky`` where it
did not; 0 is expected on data with no collinear columns). No such span
attribute → nothing."""
from chipbench import span_reduce


def read(ctx):
    solves = [s["args"]["solve"] for s in span_reduce.named(ctx, "solver.fetch") if "solve" in s["args"]]
    return sum(v == "eigh" for v in solves) / len(solves) if solves else None
