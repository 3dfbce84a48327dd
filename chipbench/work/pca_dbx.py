"""Operations and bytes that a PCA fit NEEDS, from the published shapes —
whatever implements them.

The fit is the centred Gram of n rows of d columns and the k leading
eigenpairs of the d × d covariance. The Gram is symmetric, so its half is all
the algorithm needs: n·d·(d+1)/2 multiply-adds = n·d·(d+1) operations, and one
read of X (n·d·4 bytes; the accumulator is small beside it). Each product
counts once at the chip's peak whatever precision the program runs it at
(``Precision.HIGHEST`` is six bf16 passes on the MXU, and a pass over the
whole product and not its half is twice the work again): the algorithm's
floor, so no share of it can pass 100%. The published 3000 columns and
500,000 rows count, not the padding the program adds. The eigen-solve, about
(4/3)·d³ operations for a tridiagonalization (3.6e10 at d=3000, 0.8% of the
Gram's 4.5e12), and the mean's sample are left out.
"""


def gram_work(rows: int, cols: int) -> dict:
    """The Gram's symmetric half and one read of X."""
    return {"flops": float(rows) * cols * (cols + 1), "bytes": 4.0 * rows * cols}


def fit_work(rows: int, cols: int, model: dict) -> dict:
    return gram_work(rows, cols)
