"""Transform path: ``transform.assemble`` — ``np.concatenate`` of the batches'
chunks, then the ``withColumn`` loop that builds the output frame — seconds
per ``Model.transform`` call over the window's calls."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.seconds_per(ctx, ["transform.assemble"], span_reduce.transform_call(ctx))
