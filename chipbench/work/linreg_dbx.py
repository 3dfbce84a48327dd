"""Operations and bytes that an ordinary-least-squares fit by the normal
equations NEEDS, from the shapes — whatever implements them.

The Gram of [x | 1 | y] is symmetric: (d+2)(d+3)/2 entries, each a sum of n
products: n·(d+2)·(d+3) operations, and one read of X (n·d·4 bytes; y is
small beside it). The solve is a Cholesky of d×d: d³/3 operations on
d²·4 bytes. The peak in ``peaks.json`` is the chip's bf16 rate; a float32
product at the precision the configuration states takes several MXU passes,
so this share reads low by that factor by construction.
"""


def fit_work(rows: int, cols: int, model: dict) -> dict:
    return {
        "flops": float(rows) * (cols + 2) * (cols + 3) + cols**3 / 3.0,
        "bytes": 4.0 * rows * cols + 4.0 * cols * cols,
    }
