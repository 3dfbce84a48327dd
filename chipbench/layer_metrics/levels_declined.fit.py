"""Solver and kernels: levels of a tree whose histogram did not take a Pallas
kernel — ``levels_declined`` on the ``forest.grow_group`` span (its
``declined`` says why, level by level), mean over the window's groups; 0 is
expected on a TPU. No such span → nothing."""
from chipbench import span_reduce


def read(ctx):
    counts = [s["args"]["levels_declined"] for s in span_reduce.named(ctx, "forest.grow_group") if "levels_declined" in s["args"]]
    return sum(counts) / len(counts) if counts else None
