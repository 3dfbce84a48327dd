"""Solver and kernels: the share of a forest's row-sets that are live — rows
of positive bootstrap weight in a node of the level, over trees x levels x
rows — ``live_share`` on the ``forest.grow_group`` span, mean over the
window's groups. A level costs what its live rows cost, so it moves with
``fit_s`` across seeds. No such attribute → nothing."""
from chipbench import span_reduce


def read(ctx):
    shares = [s["args"]["live_share"] for s in span_reduce.named(ctx, "forest.grow_group") if "live_share" in s["args"]]
    return sum(shares) / len(shares) if shares else None
