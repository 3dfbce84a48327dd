"""Operations and bytes that a binary logistic-regression fit NEEDS, from the
shapes and the model's reported iteration count — whatever implements them.

An L-BFGS fit of ``n_iter`` iterations needs at least ``n_iter + 1``
evaluations of loss and gradient (one per accepted point, one at the
start). One evaluation is z = Xw (2·n·d operations) and g = Xᵀ(σ(z) − y)
(2·n·d), and a fused pass reads X once (n·d·4 bytes; y, z and w are small
beside it). Standardization needs the column means and variances: one more
read of X, 3·n·d operations. Backtracking trials, the two-loop recursion
(O(history·d)) and the back-transform are not counted: they are the
implementation's, not the algorithm's floor.
"""


def fit_work(rows: int, cols: int, model: dict) -> dict:
    evals = int(model["n_iter"]) + 1
    return {
        "flops": evals * 4.0 * rows * cols + 3.0 * rows * cols,
        "bytes": (evals + 1) * 4.0 * rows * cols,
    }
