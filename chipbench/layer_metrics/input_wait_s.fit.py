"""Data plane: how long the solver's program waited for the frame — from the
opening of the traced fit's first ``h2d.enqueue`` span (``parallel/mesh.shard_rows``
hands X and the mask to the runtime) to the start ON THE DEVICE of the first
``XLA Modules`` event of ``solver.launch``'s ``program``. ``h2d_s.fit`` counts
the transfer thread's own ranges; this counts the whole wait, DMA still in
flight after those ranges included. No trace, no such spans → nothing."""
from chipbench import span_reduce


def read(ctx):
    split = span_reduce.fit_split(ctx)
    return split["input_wait"] if split else None
