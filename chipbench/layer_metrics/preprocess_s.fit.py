"""Data plane: the ``preprocess`` span (contiguous copy, pad, ``device_put``
of X, y and mask), seconds per fit."""
from chipbench import spans


def read(ctx):
    return spans.mean_child_seconds(ctx, "preprocess")
