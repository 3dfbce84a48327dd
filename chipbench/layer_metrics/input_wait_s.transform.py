"""Transform path: how long the device waited for its batches — for every
batch of the traced transform, from the opening of its ``transform.apply`` to
the start ON THE DEVICE of the first ``XLA Ops`` event that names the batch by
shape (``<dtype>[<rows>,<cols>]``, either orientation; the first program of
the batch's window where none does), summed over the batches
(``link_reduce.transform_waits``). No trace, no ``transform.d2h`` → nothing."""
from chipbench import link_reduce


def read(ctx):
    found = link_reduce.transform_waits(ctx)
    return found["input_wait"] if found else None
