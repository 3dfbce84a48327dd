"""Data plane: the host's own seconds in handing the frame's row blocks to
the runtime — the ``h2d.put`` spans (``jax.device_put`` of a block and the
dispatch of its write) per fit over every fit of the window (the program's
span sink). The span lumps the two, so the metric alone cannot say which
pays: on the chip (PR 37, the profiler's host plane) the ``device_put`` of a
786 MB block returns in 0.2-0.4 ms, its linearizing on the runtime's worker
threads, and the 13-15 ms a block are the dispatch of ``_write_block``, its
4-byte ``row0`` going up inline and the deferred allocator. No such span →
nothing."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["h2d.put"], ctx["config"]["annotations"]["fit"])
