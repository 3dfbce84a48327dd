"""``linreg_dbx``: LinearRegression's folded fit — PCA's Gram with the label
beside it, the exact solve and its least-norm fall-back, the spans — against
``chipbench``'s plain reference on the source's regression set.

Everything here is arithmetic, XLA on the CPU or the Pallas interpreter; what
Mosaic makes of the fold and the finish at the cell's shape is
``tests/test_chip_compile.py``'s, and what the chip makes of them PERF.md's.
"""

import json
import os

import numpy as np
import pytest

import jax

from spark_rapids_ml_tpu.ops import linalg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS = 4096, 300


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", "linreg_dbx.json")) as f:
        return json.load(f)


def _frame(seed=41):
    from chipbench.data import gen_data

    return gen_data.make(seed, ROWS, COLS, _config()["data"]["params"])


def _df(columns):
    from spark_rapids_ml_tpu.data import DataFrame

    return DataFrame({"features": columns["features"]}).withColumn("label", columns["label"])


def _job(model, df):
    return {
        "model": {k: np.asarray(v) for k, v in model._get_model_attributes().items()},
        "outputs": {"prediction": np.asarray(model.transform(df).column("prediction"))},
    }


def _recorded(fn):
    """``fn()`` with the program's spans recorded: (its value, the spans)."""
    from spark_rapids_ml_tpu.runtime import telemetry

    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        return fn(), spans
    finally:
        telemetry.remove_span_sink(sink)


def _two_blocks(monkeypatch, pallas):
    """The frame goes up in two row blocks a fit, each folded as it lands;
    ``pallas``: the Gram kernel in interpret mode, else XLA's blocked pass."""
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", COLS * 4 * (ROWS // 2))
    monkeypatch.setattr(linalg, "FORCE_INTERPRET", pallas)
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time, not cached


# ---- the estimator against the plain reference ------------------------------


@pytest.fixture(scope="module", params=[False, True], ids=["xla_blocked", "pallas_interpret"])
def fitted(request):
    """Two jobs of ``LinearRegression(regParam=0)`` on the source's regression
    set, the frame in two row blocks: the jobs as the harness's runner
    returns them, and the program's spans."""
    from spark_rapids_ml_tpu.regression import LinearRegression

    mp = pytest.MonkeyPatch()
    _two_blocks(mp, request.param)
    config, columns = _config(), _frame()
    df = _df(columns)
    try:
        jobs, spans = _recorded(lambda: [
            _job(LinearRegression(num_workers=1, **config["estimator"]["params"]).fit(df), df) for _ in range(2)
        ])
    finally:
        mp.undo()
        jax.clear_caches()
    return request.param, config, columns, jobs, spans


@pytest.mark.parametrize("name", ["rss_excess", "out_err"])
def test_estimator_against_the_plain_reference(fitted, name):
    from chipbench.references import linreg_dbx as ref

    _, config, columns, jobs, _ = fitted
    numbers = dict(ref.check(config, columns, jobs))
    assert np.isfinite(numbers[name]) and numbers[name] <= config["limits"][name], numbers


def test_fit_folds_the_label_beside_the_gram(fitted):
    """The frame's placement is the fit function's: ``h2d.enqueue`` opens
    under ``fit.dispatch`` and folds both blocks; the launch names the fold
    and the finish with the fold's attributes; the fetch says which solve
    ran."""
    pallas, _, _, _, spans = fitted
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev["name"], []).append(ev["args"])
    dispatch = by_name["fit.dispatch"][0]["span_id"]
    enqueue, launch, fetch = by_name["h2d.enqueue"][0], by_name["solver.launch"][0], by_name["solver.fetch"][0]
    assert enqueue["parent_id"] == launch["parent_id"] == fetch["parent_id"] == dispatch
    assert len(by_name["h2d.enqueue"]) == len(by_name["fit.dispatch"]) == 2      # one placement a fit: no label put
    assert enqueue["folded_blocks"] == enqueue["blocks"] == 2
    assert launch["program"] == "gram_fold,_linreg_finish" and launch["gram_under_put"] is True
    assert launch["precision"] == "highest" and launch["gram"] == ("pallas" if pallas else "xla")
    assert launch["rows_minor"] is False
    if pallas:
        assert launch["tile"] == linalg.gram_tile(COLS)[0] and "declined" not in launch
    else:
        assert set(launch["declined"].split(",")) == {"backend", "rows_minor"}
        assert launch["tile"] == linalg.gram_block_rows(ROWS // 2, COLS, ROWS)
    assert fetch["solve"] == "cholesky" and fetch["n_iter"] == 1


def test_transform_program_is_shared_between_models(fitted):
    """The coefficients are arguments of the prediction: a second model's
    transform builds nothing (a closure over them was a program a model)."""
    from spark_rapids_ml_tpu.models.regression import _predict
    from spark_rapids_ml_tpu.regression import LinearRegressionModel

    jobs, df = fitted[3], _df(fitted[2])
    sizes = []
    for job, icpt in ((jobs[0], 1.5), (jobs[1], -2.0)):
        model = LinearRegressionModel(coefficients=job["model"]["coefficients"], intercept=icpt, n_iter=1)
        model.transform(df).column("prediction")
        sizes.append(_predict._cache_size())
    assert sizes[1] == sizes[0] >= 1


# ---- faults the comparison catches -------------------------------------------


def test_the_parents_jitter_ridge_is_caught():
    """The parent added ``eps·trace(A)`` to the standardized system: on a
    d-wide standardized Gram that is a ridge of d·eps, whose cost grows with
    d² — at 4,096 x 300 it reads 1.8e-4, under the limit; at 4,096 x 1,100
    (110 informative coefficients) 1.2e-2, 19 times over it. The program run
    with that ``regParam`` is not correct by ``rss_excess``."""
    from chipbench.data import gen_data
    from chipbench.references import linreg_dbx as ref
    from spark_rapids_ml_tpu.regression import LinearRegression

    config, cols = _config(), 1100
    columns = gen_data.make(42, ROWS, cols, config["data"]["params"])
    df = _df(columns)
    ridge = float(np.finfo(np.float32).eps) * cols
    model = LinearRegression(num_workers=1, regParam=ridge, elasticNetParam=0.0).fit(df)
    numbers = dict(ref.check(config, columns, [_job(model, df)]))
    assert numbers["rss_excess"] > config["limits"]["rss_excess"], numbers


def test_a_one_pass_bf16_gram_is_caught(monkeypatch):
    """The fold's Gram as one bf16 pass (the parent's ``xs.T @ xb`` at default
    precision on a TPU), the solve exact: not correct by ``rss_excess``."""
    import jax.numpy as jnp
    from jax import lax

    from chipbench.references import linreg_dbx as ref
    from spark_rapids_ml_tpu.regression import LinearRegression

    def one_pass(Xl, ml, mean_hat, *, block, c0=0, bw=None):
        xs = (Xl - mean_hat[None, :]) * ml[:, None]
        xb = xs.astype(jnp.bfloat16)
        G = lax.dot_general(xb, xb, (((0,), (0,)), ((), ())), preferred_element_type=Xl.dtype)
        return G, xs.sum(axis=0), ml.sum()

    monkeypatch.setattr(linalg, "_shifted_gram_xla", one_pass)
    jax.clear_caches()
    config, columns = _config(), _frame()
    df = _df(columns)
    try:
        model = LinearRegression(num_workers=1, **config["estimator"]["params"]).fit(df)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    numbers = dict(ref.check(config, columns, [_job(model, df)]))
    assert numbers["rss_excess"] > config["limits"]["rss_excess"], numbers


# ---- the least-norm fall-back --------------------------------------------------


@pytest.mark.parametrize("standardization", [True, False])
def test_collinear_columns_take_the_least_norm_eigen_solve(standardization):
    """Two exactly equal columns make the normal equations singular: the
    finish falls back to the eigen-solve (``solve`` = ``eigh``) and serves
    the least-norm coefficients — the two columns share their weight equally
    — with the predictions of the float64 least-squares fit."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(12)
    X = rng.normal(size=(600, 6))
    X = np.concatenate([X, X[:, 2:3]], axis=1).astype(np.float32)
    y = (X[:, :6] @ np.arange(1.0, 7.0) + 3.0 + 0.05 * rng.normal(size=600)).astype(np.float32)
    df = DataFrame({"features": X, "label": y})
    model, spans = _recorded(lambda: LinearRegression(standardization=standardization).fit(df))
    (fetch,) = [ev["args"] for ev in spans if ev["name"] == "solver.fetch"]
    assert fetch["solve"] == "eigh"
    coef = np.asarray(model.coefficients, np.float64)
    assert np.isfinite(coef).all() and np.isfinite(model.intercept)
    assert abs(coef[2] - coef[6]) < 1e-3 * abs(coef[2])
    x64 = X.astype(np.float64)
    A = np.concatenate([x64, np.ones((600, 1))], axis=1)
    want = A @ np.linalg.lstsq(A, y.astype(np.float64), rcond=None)[0]
    pred = x64 @ coef + model.intercept
    assert np.abs(pred - want).max() < 1e-3 * np.abs(want).max()
    np.testing.assert_allclose(coef[[0, 1, 3, 4, 5]], [1.0, 2.0, 4.0, 5.0, 6.0], rtol=0, atol=2e-2)
    assert abs(coef[2] + coef[6] - 3.0) < 2e-2


def test_a_constant_column_gets_no_weight_and_no_fall_back():
    """A constant column's centred Gram row is zero: its coefficient is 0
    (its least-norm value) and the Cholesky still runs."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(13)
    X = rng.normal(size=(400, 4)).astype(np.float32)
    X[:, 1] = 2.5
    y = (X @ np.asarray([1.0, 0.0, -2.0, 0.5], np.float32) + 1.0).astype(np.float32)
    model, spans = _recorded(lambda: LinearRegression().fit(DataFrame({"features": X, "label": y})))
    (fetch,) = [ev["args"] for ev in spans if ev["name"] == "solver.fetch"]
    assert fetch["solve"] == "cholesky"
    np.testing.assert_allclose(model.coefficients, [1.0, 0.0, -2.0, 0.5], atol=1e-4)


# ---- fitMultiple -----------------------------------------------------------------


def test_a_regparam_grid_places_and_folds_once():
    """A ``regParam`` grid over one frame: ONE placement and one fold; the
    first lane runs the finish, the later ones the solve on its statistics —
    each model the model of its own fit."""
    from spark_rapids_ml_tpu.regression import LinearRegression

    columns = _frame(43)
    df = _df(columns)
    est = LinearRegression(num_workers=1)
    grid = [{est.regParam: r} for r in (0.0, 1e-3, 1e-1)]
    models, spans = _recorded(lambda: [m for _, m in sorted(est.fitMultiple(df, grid), key=lambda t: t[0])])
    enqueues = [ev for ev in spans if ev["name"] == "h2d.enqueue"]
    programs = [ev["args"]["program"] for ev in spans if ev["name"] == "solver.launch"]
    assert len(enqueues) == 1 and enqueues[0]["args"]["folded_blocks"] >= 1
    assert programs == ["gram_fold,_linreg_finish", "solve_normal", "solve_normal"]
    for r, m in zip((0.0, 1e-3, 1e-1), models):
        alone = LinearRegression(num_workers=1, regParam=r).fit(df)
        np.testing.assert_allclose(m.coefficients, alone.coefficients, rtol=1e-5, atol=1e-4)
        assert m.intercept == pytest.approx(alone.intercept, rel=1e-5, abs=1e-3)


def test_fit_intercept_off_finishes_the_same_folds_uncentred():
    """``fitIntercept=False`` from the folds: the uncentred normal equations,
    against float64 least squares through the origin."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(14)
    X = (rng.normal(size=(3000, 5)) + 4.0).astype(np.float32)
    y = (X @ np.asarray([1.0, -1.0, 2.0, 0.5, 0.0]) + 0.1 * rng.normal(size=3000)).astype(np.float32)
    model = LinearRegression(fitIntercept=False, num_workers=2).fit(DataFrame({"features": X, "label": y}))
    x64 = X.astype(np.float64)
    want = np.linalg.lstsq(x64, y.astype(np.float64), rcond=None)[0]
    np.testing.assert_allclose(model.coefficients, want, rtol=0, atol=2e-4)
    assert model.intercept == 0.0


# ---- the benchmark's reader ------------------------------------------------------


def test_gram_work_is_the_symmetric_half_of_x_one_y():
    from chipbench import linreg_reduce

    w = linreg_reduce.gram_work(500_000, 3000)
    assert w["flops"] == 500_000 * 3002 * 3003 and w["bytes"] == 4 * 500_000 * 3000


def test_reference_takes_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references", "linreg_dbx.py")) as f:
        source = f.read()
    assert "import spark_rapids_ml_tpu" not in source and "from spark_rapids_ml_tpu" not in source
