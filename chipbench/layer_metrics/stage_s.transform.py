"""Transform path: ``transform.extract`` (the features as one contiguous
array) plus every ``transform.stage`` (slice, or ``device_put``, of the next
batch), seconds per ``Model.transform`` call over the window's calls."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["transform.extract", "transform.stage"], span_reduce.transform_call(ctx))
