"""Row blocks of the frame on the device, shared by the plain references.

The references run once the window has closed and the program's arrays are
freed, so their own copy of X (rows × cols f32) fits beside nothing else. X
goes up in blocks of ``BLOCK`` rows and stays resident as a list; every pass
is a Python loop over the blocks with one small jitted function — plain
``jax.numpy`` at ``highest`` matmul precision, no kernel, no scan.

``control=True`` is the lower-precision stand-in used only for the control:
the block is rounded to bfloat16 on its way up, as a later PR that places X
narrow would do, and the references' passes then run one-pass bf16 products
with f32 accumulation (explicit bf16 operands, so the CPU computes the same).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 14


def place(X: np.ndarray, control: bool = False) -> list:
    blocks = []
    for lo in range(0, len(X), BLOCK):
        xb = jnp.asarray(X[lo : lo + BLOCK])
        blocks.append(xb.astype(jnp.bfloat16) if control else xb)
    return blocks


def f64(x) -> np.ndarray:
    return np.asarray(x, np.float64)
