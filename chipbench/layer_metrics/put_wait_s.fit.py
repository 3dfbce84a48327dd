"""Data plane: seconds the host is blocked on the link inside the row-block
loop — the ``h2d.wait`` spans (until the write of the put two before has
run) per fit over every fit of the window (the program's span sink). With
``put_host_s.fit`` (and PCA's ``h2d.fold``) it makes up the ``h2d.enqueue``
span but for the zero fill and the mask. No such span → nothing."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["h2d.wait"], ctx["config"]["annotations"]["fit"])
