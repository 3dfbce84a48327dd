"""Operations and bytes that a KMeans fit NEEDS, from the shapes and the
model's reported iteration count — whatever implements them.

One Lloyd iteration over n rows of d columns with k centres is the distances
(x·cᵀ: 2·n·d·k operations) and the centres' sums (one-hotᵀ·x: 2·n·d·k), and a
fused pass reads X once (n·d·4 bytes; the centres, counts and assignments are
small beside it). The reported cost is one more pass of distances alone. The
published 3000 columns and 500,000 rows count, not the lane padding or the
row padding the program adds, and each product counts once at the chip's
peak whatever precision the program runs it at (``Precision.HIGHEST`` is six
bf16 passes on the MXU): the algorithm's floor, so no share of it can pass
100%. Seeding (k rows gathered) is not counted.
"""


def iter_work(rows: int, cols: int, k: int) -> dict:
    """One Lloyd iteration."""
    return {"flops": 4.0 * rows * cols * k, "bytes": 4.0 * rows * cols}


def fit_work(rows: int, cols: int, model: dict) -> dict:
    n_iter = int(model["n_iter"])
    k = int(model["cluster_centers"].shape[0])
    one = iter_work(rows, cols, k)
    return {
        "flops": n_iter * one["flops"] + 2.0 * rows * cols * k,
        "bytes": (n_iter + 1) * one["bytes"],
    }
