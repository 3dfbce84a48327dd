"""Transform path: every ``transform.fetch`` — the host blocked on a batch's
program and the copy of its output columns back (``np.asarray``) — seconds
per ``Model.transform`` call over the window's calls."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["transform.fetch"], span_reduce.transform_call(ctx))
