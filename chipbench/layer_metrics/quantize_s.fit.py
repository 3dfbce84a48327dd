"""Data plane: the ``forest.sketch`` span — the quantile sketch of a forest
fit, from its dispatch to the bin edges on the host (a sample sorted on the
device, the edges fetched) — seconds per fit over every fit of the window
(the program's span sink). No such span → nothing."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["forest.sketch"], ctx["config"]["annotations"]["fit"])
