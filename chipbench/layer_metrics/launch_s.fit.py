"""Estimator API, host glue: the ``solver.launch`` span — the scalar puts and
the call of the jitted fit function, to its return (it only enqueues) —
seconds per fit over every fit of the window (the program's span sink). A
retrace or a compile per fit shows here."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["solver.launch"], ctx["config"]["annotations"]["fit"])
