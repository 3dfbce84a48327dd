"""Estimator API, host glue: the ``kmeans.init`` span — the seeding: the rows
drawn on the host, gathered from the frame on the device (so the wait for
the frame to be there lands here) and handed back as the first centres —
seconds per fit over every fit of the window (the program's span sink)."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["kmeans.init"], ctx["config"]["annotations"]["fit"])
