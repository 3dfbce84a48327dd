"""Data plane: device seconds of the ``binize`` program (every value compared
with its column's edges, the uint8 bins written) inside the traced fit
(``rf_reduce.py``). No trace, no such program → nothing."""
from chipbench import rf_reduce


def read(ctx):
    found = rf_reduce.fit_modules(ctx, "binize")
    return found[0] if found else None
