"""Data plane: the longest single ``h2d.wait`` of the window, over all its
fits (the program's span sink): a run in which the link stalled shows here,
with the block it stalled on printed on stderr. No such span → nothing."""
import sys

from chipbench import span_reduce


def read(ctx):
    waits = span_reduce.named(ctx, "h2d.wait")
    if not waits:
        return None
    worst = max(waits, key=lambda s: s["dur"])
    print(f"chipbench: longest h2d.wait {worst['dur'] * 1e-6:.4f} s (block {worst['args'].get('block')}, "
          f"span {worst['args'].get('span_id')}) of {len(waits)} in the window", file=sys.stderr, flush=True)
    return worst["dur"] * 1e-6
