"""Transform path: the answer's way back — for every batch of the traced
transform, from the end of its last device operation to the close of its last
``transform.d2h`` span, summed over the batches
(``link_reduce.transform_waits``). No trace, no such span → nothing."""
from chipbench import link_reduce


def read(ctx):
    found = link_reduce.transform_waits(ctx)
    return found["fetch_tail"] if found else None
