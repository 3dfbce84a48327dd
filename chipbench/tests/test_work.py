"""work/<config>.py against counts worked out by hand."""
from chipbench.work import linreg_dbx, logreg_dbx


def test_logreg_work_by_hand():
    # n=1000, d=10, 4 iterations: 5 evaluations of 4nd operations + 3nd for the
    # moments; 5 reads of X for the evaluations + 1 for the moments, 4 bytes each
    w = logreg_dbx.fit_work(1000, 10, {"n_iter": 4})
    assert w["flops"] == 5 * 4 * 1000 * 10 + 3 * 1000 * 10 == 230_000
    assert w["bytes"] == 6 * 4 * 1000 * 10 == 240_000


def test_linreg_work_by_hand():
    # n=1000, d=10: symmetric Gram of [x|1|y]: 12·13/2 = 78 sums of 1000
    # products = 2·78·1000 operations = 1000·12·13; Cholesky 10³/3
    w = linreg_dbx.fit_work(1000, 10, {})
    assert abs(w["flops"] - (156_000 + 1000 / 3.0)) < 1e-6
    assert w["bytes"] == 4 * 1000 * 10 + 4 * 100


def test_full_size_floor_seconds():
    # the cell's own shape on one v5e: logreg at 50 iterations is HBM-bound,
    # 52 reads of 6 GB at 819 GB/s = 0.381 s
    w = logreg_dbx.fit_work(500_000, 3000, {"n_iter": 50})
    assert abs(w["bytes"] / 819e9 - 0.38095) < 1e-4
    assert w["flops"] / 197e12 < w["bytes"] / 819e9
    w = linreg_dbx.fit_work(500_000, 3000, {})
    assert w["flops"] / 197e12 > w["bytes"] / 819e9   # compute-bound
