"""Gang-scheduled batched fitting (TPUML_GANG_FIT).

Contract layering (see docs/gang_fit.md):

- The FREEZE is bitwise: once a lane converges its state never changes,
  even while other lanes keep iterating — asserted by varying OTHER lanes'
  traced tol inside the SAME compiled program and checking the converged
  lane's output is bit-identical. Identical-param lanes inside one gang are
  likewise bitwise equal.
- Gang vs SOLO is tight-tolerance + iteration lockstep, NOT bitwise: the
  batched and solo programs are different XLA computations and fusion
  choices legitimately differ by ulps.
- Defaults are inert: with the env unset, fitMultiple/CV run the sequential
  path and no gang counters move.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.core import resolve_gang_fit
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.data.dataframe import kfold, kfold_ids
from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.ops.lbfgs import minimize_lbfgs, minimize_lbfgs_batched
from spark_rapids_ml_tpu.ops.linreg_kernels import (
    linreg_suffstats,
    solve_elasticnet,
    solve_elasticnet_batched,
)
from spark_rapids_ml_tpu.runtime import counters
from spark_rapids_ml_tpu.runtime.envspec import EnvSpecError
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder


def _quad_problem(seed=0, n=256, p=8):
    """A strongly-convex least-squares objective with a batch axis: lane b's
    loss depends only on row b of W, so per-lane gradients are exact."""
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, p))
    x_true = rng.normal(size=p)
    y = A @ x_true + 0.1 * rng.normal(size=n)
    Aj, yj = jnp.asarray(A, jnp.float32), jnp.asarray(y, jnp.float32)

    def fun_batched(W):  # (B, p) -> (B,)
        r = W @ Aj.T - yj[None, :]
        return 0.5 * (r * r).mean(axis=1)

    def fun_solo(w):
        r = Aj @ w - yj
        return 0.5 * (r * r).mean()

    return fun_batched, fun_solo, p


def _clf_data(seed=0, n=3000, d=10, classes=2):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    if classes == 2:
        w = rng.normal(size=d)
        y = (X @ w + 0.5 * rng.normal(size=n) > 0).astype(float)
    else:
        W = rng.normal(size=(d, classes))
        y = np.argmax(X @ W + 0.5 * rng.normal(size=(n, classes)), axis=1).astype(
            float
        )
    return DataFrame({"features": X, "label": y})


def _grid(est, reg_values, enet_values):
    return (
        ParamGridBuilder()
        .addGrid(est.getParam("regParam"), list(reg_values))
        .addGrid(est.getParam("elasticNetParam"), list(enet_values))
        .build()
    )


# ---------------------------------------------------------------------------
# solver-level contracts
# ---------------------------------------------------------------------------


def test_freeze_bitwise_under_other_lane_tol_change():
    """The correctness core: a converged lane's output must be bit-identical
    whether the while_loop stops right after it converges or keeps running
    for OTHER lanes. tol is traced, so both runs are the SAME compiled
    program — any difference is a freeze bug, not fusion noise."""
    fun_b, _, p = _quad_problem()
    B = 3
    w0 = jnp.zeros((B, p), jnp.float32)
    # lane 0 is the probe; lanes 1-2 get loose then brutal tolerances
    tol_short = jnp.asarray([1e-4, 1e-3, 1e-3], jnp.float32)
    tol_long = jnp.asarray([1e-4, 1e-12, 1e-12], jnp.float32)
    short = minimize_lbfgs_batched(fun_b, w0, max_iter=100, tol=tol_short)
    long = minimize_lbfgs_batched(fun_b, w0, max_iter=100, tol=tol_long)
    assert int(long.n_iter[1]) > int(short.n_iter[1])  # loop really ran longer
    np.testing.assert_array_equal(np.asarray(short.w[0]), np.asarray(long.w[0]))
    np.testing.assert_array_equal(np.asarray(short.f[0]), np.asarray(long.f[0]))
    assert int(short.n_iter[0]) == int(long.n_iter[0])


def test_identical_lanes_bitwise_equal():
    """Lanes with identical params inside ONE gang see the same op sequence
    and must agree bitwise."""
    fun_b, _, p = _quad_problem(seed=3)
    B = 4
    w0 = jnp.zeros((B, p), jnp.float32)
    tol = jnp.full((B,), 1e-8, jnp.float32)
    out = minimize_lbfgs_batched(fun_b, w0, max_iter=100, tol=tol)
    for b in range(1, B):
        np.testing.assert_array_equal(np.asarray(out.w[0]), np.asarray(out.w[b]))
        assert int(out.n_iter[0]) == int(out.n_iter[b])


def test_gang_vs_solo_lockstep_and_tolerance():
    fun_b, fun_s, p = _quad_problem(seed=1)
    B = 3
    tols = [1e-5, 1e-7, 1e-9]
    out = minimize_lbfgs_batched(
        fun_b,
        jnp.zeros((B, p), jnp.float32),
        max_iter=200,
        tol=jnp.asarray(tols, jnp.float32),
    )
    for b, t in enumerate(tols):
        solo = minimize_lbfgs(
            fun_s, jnp.zeros((p,), jnp.float32), max_iter=200, tol=t
        )
        assert abs(int(out.n_iter[b]) - int(solo.n_iter)) <= 1
        np.testing.assert_allclose(
            np.asarray(out.w[b]), np.asarray(solo.w), rtol=1e-3, atol=1e-5
        )


def test_owlqn_lane_mixing_l1_magnitudes():
    """OWL-QN lanes with DIFFERENT l1 strengths in one gang each match
    their solo OWL-QN solve (the per-lane orthant projection and sign-fix
    must not leak across lanes)."""
    fun_b, fun_s, p = _quad_problem(seed=2)
    l1s = [0.001, 0.05, 0.5]
    B = len(l1s)
    l1w = jnp.asarray(l1s, jnp.float32)[:, None] * jnp.ones((B, p), jnp.float32)
    out = minimize_lbfgs_batched(
        fun_b,
        jnp.zeros((B, p), jnp.float32),
        max_iter=200,
        tol=jnp.full((B,), 1e-9, jnp.float32),
        l1_weights=l1w,
    )
    for b, l1 in enumerate(l1s):
        solo = minimize_lbfgs(
            fun_s,
            jnp.zeros((p,), jnp.float32),
            max_iter=200,
            tol=1e-9,
            l1_weights=jnp.full((p,), l1, jnp.float32),
        )
        np.testing.assert_allclose(
            np.asarray(out.w[b]), np.asarray(solo.w), rtol=1e-3, atol=1e-5
        )
        # the strong-l1 lane must actually be sparse — proves the orthant
        # machinery ran per-lane rather than being averaged away
        if l1 == 0.5:
            assert np.sum(np.asarray(out.w[b]) == 0.0) > 0


def test_elasticnet_batched_matches_solo():
    rng = np.random.default_rng(4)
    n, d = 2000, 8
    X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(
        rng.normal(size=n) + np.asarray(X[:, 0]) * 2.0, jnp.float32
    )
    mask = jnp.ones((n,), jnp.float32)
    stats = linreg_suffstats(X, mask, y, None, fit_intercept=True)
    lanes = [(0.1, 0.05), (0.01, 0.2), (0.3, 0.0)]
    bl1 = jnp.asarray([a for a, _ in lanes], jnp.float32)
    bl2 = jnp.asarray([b for _, b in lanes], jnp.float32)
    btol = jnp.full((len(lanes),), 1e-7, jnp.float32)
    beta_b, int_b, it_b = solve_elasticnet_batched(
        stats, bl1, bl2, standardization=True, max_iter=500, tol=btol
    )
    for i, (l1, l2) in enumerate(lanes):
        beta, inter, it = solve_elasticnet(
            stats,
            jnp.asarray(l1, jnp.float32),
            jnp.asarray(l2, jnp.float32),
            standardization=True,
            max_iter=500,
            tol=1e-7,
        )
        assert abs(int(it_b[i]) - int(it)) <= 2
        np.testing.assert_allclose(
            np.asarray(beta_b[i]), np.asarray(beta), rtol=1e-4, atol=1e-6
        )
        np.testing.assert_allclose(
            float(int_b[i]), float(inter), rtol=1e-4, atol=1e-6
        )


# ---------------------------------------------------------------------------
# resolver / env validation
# ---------------------------------------------------------------------------


def test_resolver_off_auto_int(monkeypatch):
    monkeypatch.delenv("TPUML_GANG_FIT", raising=False)
    assert resolve_gang_fit(8, 1.0) == 1
    monkeypatch.setenv("TPUML_GANG_FIT", "off")
    assert resolve_gang_fit(8, 1.0) == 1
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    assert resolve_gang_fit(8, 1.0) == 8
    monkeypatch.setenv("TPUML_GANG_FIT", "3")
    assert resolve_gang_fit(8, 1.0) == 3


def test_resolver_budget_clamp(monkeypatch):
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    monkeypatch.setenv("TPUML_GANG_FIT_BUDGET", "1000")
    assert resolve_gang_fit(8, 250.0) == 4  # 1000 // 250
    assert resolve_gang_fit(8, 5000.0) == 1  # budget < one lane: degrade to 1
    monkeypatch.setenv("TPUML_GANG_FIT_BUDGET", "1e12")
    assert resolve_gang_fit(8, 250.0) == 8


def test_resolver_env_validation(monkeypatch):
    monkeypatch.setenv("TPUML_GANG_FIT", "bogus")
    with pytest.raises(EnvSpecError, match="TPUML_GANG_FIT"):
        resolve_gang_fit(4, 1.0)
    monkeypatch.setenv("TPUML_GANG_FIT", "0")
    with pytest.raises(EnvSpecError, match=">= 1"):
        resolve_gang_fit(4, 1.0)
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    monkeypatch.setenv("TPUML_GANG_FIT_BUDGET", "-5")
    with pytest.raises(EnvSpecError):
        resolve_gang_fit(4, 1.0)


def test_static_bucket_grouping():
    lr = LogisticRegression(maxIter=25)
    param_sets = []
    for reg, enet in [(0.1, 0.0), (0.01, 0.0), (0.1, 0.5), (0.01, 1.0)]:
        est = lr.copy()
        lr._copy_tpu_params(est)
        est._set_params(regParam=reg, elasticNetParam=enet)
        param_sets.append(dict(est._tpu_params))
    groups = dict(lr._gang_fit_groups(param_sets))
    # plain-L2 lanes and OWL-QN lanes compile different programs: 2 buckets
    assert len(groups) == 2
    by_use_l1 = {key[2]: idxs for key, idxs in groups.items()}
    assert by_use_l1[False] == [0, 1]
    assert by_use_l1[True] == [2, 3]


def test_linreg_groups_exclude_cholesky_lanes():
    ln = LinearRegression(maxIter=100)
    param_sets = []
    for reg, enet in [(0.1, 0.0), (0.1, 0.5), (0.2, 1.0)]:
        est = ln.copy()
        ln._copy_tpu_params(est)
        est._set_params(regParam=reg, elasticNetParam=enet)
        param_sets.append(dict(est._tpu_params))
    groups = dict(ln._gang_fit_groups(param_sets))
    (idxs,) = groups.values()
    assert idxs == [1, 2]  # the l1 == 0 Cholesky lane stays sequential


# ---------------------------------------------------------------------------
# end-to-end fitMultiple / CV
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("classes", [2, 3])
def test_gang_fitmultiple_matches_sequential(monkeypatch, classes):
    df = _clf_data(seed=5, classes=classes)
    lr = LogisticRegression(maxIter=40, tol=1e-8)
    grid = _grid(lr, [0.01, 0.1, 1.0], [0.0, 0.5])
    monkeypatch.delenv("TPUML_GANG_FIT", raising=False)
    seq = [m for _, m in lr.fitMultiple(df, grid)]
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    gang = [m for _, m in lr.fitMultiple(df, grid)]
    for a, b in zip(seq, gang):
        ca, cb = np.asarray(a.coef_), np.asarray(b.coef_)
        assert abs(a.n_iter_ - b.n_iter_) <= 1
        np.testing.assert_allclose(
            cb, ca, rtol=5e-3, atol=1e-5 * max(1.0, np.abs(ca).max())
        )
        assert b._fit_report["gang_lanes"] >= 2
        assert b._fit_report["gang_groups"] == 2
        # sequential models carry no gang report (only the solver's count)
        assert set(a._fit_report) == {"n_evals"}


def test_gang_fitmultiple_linreg(monkeypatch):
    rng = np.random.default_rng(6)
    X = rng.normal(size=(2000, 10))
    y = X @ rng.normal(size=10) + 0.3 * rng.normal(size=2000)
    df = DataFrame({"features": X, "label": y})
    ln = LinearRegression(maxIter=300, tol=1e-10)
    grid = _grid(ln, [0.01, 0.1], [0.5, 1.0])
    monkeypatch.delenv("TPUML_GANG_FIT", raising=False)
    seq = [m for _, m in ln.fitMultiple(df, grid)]
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    gang = [m for _, m in ln.fitMultiple(df, grid)]
    for a, b in zip(seq, gang):
        np.testing.assert_allclose(
            np.asarray(b.coefficients),
            np.asarray(a.coefficients),
            rtol=1e-5,
            atol=1e-8,
        )
        assert b._fit_report["gang_lanes"] == 4


def test_gang_budget_clamp_splits_dispatches(monkeypatch):
    df = _clf_data(seed=7)
    lr = LogisticRegression(maxIter=20, tol=1e-6)
    grid = _grid(lr, [0.01, 0.1, 1.0, 10.0], [0.0])
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    # budget fits exactly two lanes of this dataset's (n, B, 1) residents
    monkeypatch.setenv(
        "TPUML_GANG_FIT_BUDGET", str(2 * 16.0 * 3008)
    )  # n=3000 padded to 8-device multiple
    counters.reset()
    gang = [m for _, m in lr.fitMultiple(df, grid)]
    assert all(m._fit_report["gang_lanes"] == 2 for m in gang)
    snap = counters.snapshot()
    assert snap["gang_dispatches"] == 2
    assert snap["gang_lanes_total"] == 4


def test_defaults_inert(monkeypatch):
    """Env unset: sequential path, bit-identical across runs, no gang
    counters, no gang report."""
    monkeypatch.delenv("TPUML_GANG_FIT", raising=False)
    df = _clf_data(seed=8)
    lr = LogisticRegression(maxIter=25, tol=1e-7)
    grid = _grid(lr, [0.01, 0.1], [0.0, 0.5])
    counters.reset()
    a = [m for _, m in lr.fitMultiple(df, grid)]
    b = [m for _, m in lr.fitMultiple(df, grid)]
    for x, z in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x.coef_), np.asarray(z.coef_))
        np.testing.assert_array_equal(
            np.asarray(x.intercept_), np.asarray(z.intercept_)
        )
        assert set(x._fit_report) == {"n_evals"}  # no gang report
    snap = counters.snapshot()
    assert snap.get("gang_dispatches", 0) == 0
    assert snap.get("gang_lanes_total", 0) == 0


def test_kfold_ids_matches_kfold():
    df = _clf_data(seed=9, n=500)
    ids = kfold_ids(df.count(), 3, seed=11)
    folds = kfold(df, 3, seed=11)
    for f, (_, val) in enumerate(folds):
        assert val.count() == int(np.sum(ids == f))


def test_gang_cv_matches_sequential(monkeypatch):
    """Fold-masked gang CV vs the materialized per-fold sequential path.
    Tolerance-only: the sequential path reduces over contiguous fold
    subsets while the masked lanes reduce over the full row order (see
    docs/gang_fit.md), so coefficients agree tightly but not bitwise."""
    df = _clf_data(seed=10, n=2400)
    lr = LogisticRegression(maxIter=40, tol=1e-8)
    grid = _grid(lr, [0.01, 0.1], [0.0, 0.5])
    eva = MulticlassClassificationEvaluator(metricName="logLoss")
    monkeypatch.delenv("TPUML_GANG_FIT", raising=False)
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid, evaluator=eva, numFolds=3,
        seed=13, collectSubModels=True,
    )
    m_seq = cv.fit(df)
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    m_gang = cv.fit(df)
    np.testing.assert_allclose(
        np.asarray(m_gang.avgMetrics), np.asarray(m_seq.avgMetrics),
        rtol=5e-3, atol=5e-4,
    )
    assert np.argmin(m_seq.avgMetrics) == np.argmin(m_gang.avgMetrics)
    # per-lane models: tight coefficient agreement + gang provenance
    for f in range(3):
        for a, b in zip(m_seq.subModels[f], m_gang.subModels[f]):
            ca, cb = np.asarray(a.coef_), np.asarray(b.coef_)
            np.testing.assert_allclose(
                cb, ca, rtol=2e-2, atol=1e-4 * max(1.0, np.abs(ca).max())
            )
            assert b._fit_report["gang_lanes"] >= 2
            assert b._fit_report["gang_fold"] == f


def test_gang_cv_counters(monkeypatch):
    df = _clf_data(seed=12, n=1200)
    lr = LogisticRegression(maxIter=15, tol=1e-6)
    grid = _grid(lr, [0.01, 0.1], [0.0])
    eva = MulticlassClassificationEvaluator(metricName="accuracy")
    monkeypatch.setenv("TPUML_GANG_FIT", "auto")
    counters.reset()
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid, evaluator=eva, numFolds=3,
        seed=1,
    )
    cv.fit(df)
    snap = counters.snapshot()
    # 3 folds × 2 maps = 6 lanes in one static bucket = one dispatch
    assert snap["gang_lanes_total"] >= 6
    assert snap["gang_dispatches"] >= 1
