"""Solver and kernels: bins columns a level's histogram reads a live row —
``hist_cols`` on the ``forest.grow_group`` span (the slots of a fused
selection, the width of a pre-gathered subset, the whole row's columns where
a histogram masks its subset afterwards), mean over the window's groups. The
counter that says a later change flipped the histogram's path; ``hist_calls``
(kernel calls a chunk of live rows) is printed beside it. No such attribute
(the parent's program has none) -> nothing."""
import sys

from chipbench import span_reduce


def read(ctx):
    groups = [s["args"] for s in span_reduce.named(ctx, "forest.grow_group") if "hist_cols" in s["args"]]
    if not groups:
        return None
    print(f"chipbench: histogram reads {groups[0]['hist_cols']} bins columns a live row in {groups[0].get('hist_calls')} kernel call(s) a chunk",
          file=sys.stderr, flush=True)
    return sum(g["hist_cols"] for g in groups) / len(groups)
