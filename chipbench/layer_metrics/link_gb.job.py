"""Data plane: gigabytes the host hands to the link a job — ``host_bytes`` of
every ``h2d.enqueue`` (the frame, its mask, the labels) plus ``bytes`` of
every ``transform.h2d`` (the batches of the second crossing) of the window,
over its jobs (the program's span sink). 12.0 where the frame crosses twice.
No ``host_bytes`` → nothing."""
from chipbench import link_reduce


def read(ctx):
    found = link_reduce.link_bytes_per_job(ctx)
    return found / 1e9 if found is not None else None
