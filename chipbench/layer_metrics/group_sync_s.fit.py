"""Estimator API, host glue: host seconds in the fetches between the grow
groups — the ``forest.fetch_group`` spans (a group's trees to the host after
its program has ended; the device idles meanwhile) — per fit over every fit
of the window (the program's span sink). No such span → nothing."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["forest.fetch_group"], ctx["config"]["annotations"]["fit"])
