"""Data plane: how long the frame takes to land — from the opening of the
traced fit's first ``h2d.enqueue`` to the end ON THE DEVICE of the last ``XLA
Modules`` event of that span's ``write_program`` (``_write_block``: a row
block written into the shard) before ``solver.fetch`` closes. What
``input_wait_s.fit`` holds beside it — a seeding, a sketch, ``binize`` — is
not in it. A frame of one put has no ``write_program``, a trace without the
spans nothing to read → nothing (``link_reduce.py``)."""
from chipbench import link_reduce


def read(ctx):
    found = link_reduce.crossing(ctx)
    return found["seconds"] if found else None
