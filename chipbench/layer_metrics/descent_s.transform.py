"""Transform path: device seconds of the descent's program (every row down
every tree) inside the traced job's ``forest.descent`` spans, per transform;
a batch's ``binize`` is left out (``rf_reduce.py``). No trace, no such span
→ nothing."""
from chipbench import rf_reduce


def read(ctx):
    found, calls = rf_reduce.descent_modules(ctx), rf_reduce.transforms(ctx)
    return found[0] / calls if found and calls else None
