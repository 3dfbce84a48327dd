"""Solver and kernels: device seconds inside the grow groups — the runs of
``build_forest`` (one a dispatch group: per level the sort by node, the
node-sorted copy, the histogram kernel, the reduction over sub-blocks, the
gain search, the routing) inside the traced fit (``rf_reduce.py``). No trace,
no such program → nothing."""
import sys

from chipbench import rf_reduce


def read(ctx):
    found = rf_reduce.fit_modules(ctx, "build_forest")
    if not found:
        return None
    print(f"chipbench: forest growth: {found[0]:.4f} s on the device in {found[1]} runs of build_forest", file=sys.stderr, flush=True)
    return found[0]
