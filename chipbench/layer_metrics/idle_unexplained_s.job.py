"""Device: the closure term — device-idle seconds of the traced job outside
the fit's input wait, solver interval and fetch tail and outside every
batch's input wait and fetch tail (``link_reduce.idle_tiling``, whose terms
are printed on stderr and add up to the job's idle seconds). No trace, or
either half of the job without its spans → nothing."""
from chipbench import link_reduce


def read(ctx):
    found = link_reduce.idle_tiling(ctx)
    return found["unexplained"] if found else None
