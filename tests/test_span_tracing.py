"""Spans on the profiler's clock, the span tree of a resident fit and a
batched transform, and the solver's evaluation count.

One file on purpose: the ``jax.profiler`` capture below belongs to one
process, so one xdist worker (``--dist loadfile``) holds it.

(a) under a sink and a capture every span has its ``tpuml:`` twin in the
``.xplane.pb``; (b) the span tree of one LogisticRegression fit and one
transform of three batches; (c) ``n_evals`` from the jitted, the batched
and the host-driven L-BFGS, with the solution held bitwise to the solver
as it stood before the counter; (d) with no sink every new site gets the
shared no-op and outputs are bitwise those of the traced run; (e) the
link's spans: a frame that goes up in row blocks has one ``h2d.put`` a
block, one ``h2d.wait`` a put but the last two and one ``h2d.fold`` a block
where the caller folds, and a transform has one ``transform.h2d`` and at
least one ``transform.d2h`` a batch in every model, the puts' bytes those of
the frame.
"""

import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu import core
from spark_rapids_ml_tpu.classification import (
    LogisticRegression,
    LogisticRegressionModel,
    RandomForestClassifier,
)
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.ops import lbfgs, logreg_pallas
from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
from spark_rapids_ml_tpu.regression import LinearRegression
from spark_rapids_ml_tpu.runtime import telemetry

ROWS, COLS, BATCH = 4096, 64, 1500  # three transform batches: 1500, 1500, 1096
OUTPUTS = ("prediction", "probability", "rawPrediction")
LEGACY = (
    "LogisticRegression.preprocess",
    "LogisticRegression.fit",
    "LogisticRegressionModel.transform",
)
NEW_SITES = {
    "h2d.enqueue", "solver.launch", "solver.fetch",
    "LogisticRegressionModel.transform.call", "transform.extract",
    "transform.stage", "transform.apply", "transform.fetch",
    "transform.assemble", "transform.h2d", "transform.d2h",
}
BLOCK_ROWS, BLOCK_FRAME_ROWS = 1024, 4500  # five puts: 4 x 1024 rows and 404
BLOCK_SITES = {"h2d.enqueue", "h2d.put", "h2d.wait", "h2d.fold"}


def _frame(cols=COLS, rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    w = rng.normal(size=cols).astype(np.float32) / np.sqrt(cols)
    y = (X @ w + rng.normal(size=rows) > 0).astype(np.float32)
    return X, y, DataFrame({"features": X}).withColumn("label", y)


def _job(df):
    model = LogisticRegression(maxIter=40, regParam=1e-3, num_workers=1).fit(df)
    out = model.transform(df)
    return model, {c: np.asarray(out.column(c)) for c in OUTPUTS}


def _host_events(xplane):
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(telemetry.ANNOTATION_PREFIX) or e.name in LEGACY:
                    stats = dict(e.stats) if e.name not in LEGACY else {}
                    events.append((e.name, e.start_ns, e.start_ns + e.duration_ns, stats))
    return events


def _untraced(mp, job):
    """``job()`` with nothing recording, and every ``span()`` call it made by
    what the call returned."""
    seen = []
    real_span = telemetry.span

    def spy(name, **attrs):
        s = real_span(name, **attrs)
        seen.append((name, s))
        return s

    mp.setattr(telemetry, "span", spy)
    try:
        return job(), seen
    finally:
        mp.setattr(telemetry, "span", real_span)


def _captured(trace_dir, job):
    """``job()`` under a span sink and a profiler capture: its result, the
    sink's spans and the capture's host events."""
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        result = job()
    finally:
        jax.profiler.stop_trace()
        telemetry.remove_span_sink(sink)
    xplane = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")
    assert xplane, "the capture wrote no .xplane.pb"
    return result, spans, _host_events(xplane[0])


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One job with nothing recording, then the same job under a sink and
    a profiler capture (both after a warm job, so neither compiles)."""
    telemetry.reset_telemetry()
    mp = pytest.MonkeyPatch()
    mp.delenv("TPUML_TRACE", raising=False)
    mp.setattr(LogisticRegressionModel, "_transform_batch_rows", lambda self: BATCH)
    X, y, df = _frame()
    try:
        _job(df)
        # (d): every span() call of an untraced job, by what it returned
        (plain_model, plain_out), seen = _untraced(mp, lambda: _job(df))
        (model, out), spans, events = _captured(str(tmp_path_factory.mktemp("capture")), lambda: _job(df))
        yield {
            "X": X, "y": y, "spans": spans, "events": events,
            "model": model, "out": out, "plain_model": plain_model,
            "plain_out": plain_out, "untraced_calls": seen,
        }
    finally:
        mp.undo()
        telemetry.reset_telemetry()


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def _one(spans, name):
    (s,) = _by_name(spans, name)
    return s


# --- (a) the spans on the profiler's clock -----------------------------------


def test_every_span_has_its_profiler_event(runs):
    twins = {}
    for name, lo, hi, stats in runs["events"]:
        if name.startswith(telemetry.ANNOTATION_PREFIX):
            assert stats["span_id"] not in twins
            twins[stats["span_id"]] = (name, lo, hi, stats)
    # 7 of the fit, 26 of the transform: a transform.h2d and three transform.d2h a batch
    assert len(runs["spans"]) == 33
    _assert_twins(runs["spans"], twins)


def _assert_twins(spans, twins):
    for s in spans:
        name, lo, hi, stats = twins[s["args"]["span_id"]]
        assert name == telemetry.ANNOTATION_PREFIX + s["name"]
        assert stats.get("parent_id") == s["args"].get("parent_id")
        dur_us = (hi - lo) * 1e-3
        assert abs(dur_us - s["dur"]) <= max(1000.0, 0.05 * s["dur"]), (name, dur_us, s["dur"])
        if "parent_id" in stats:
            _, plo, phi, _ = twins[stats["parent_id"]]
            assert plo <= lo and hi <= phi, (name, "not inside its parent")
    assert len(twins) == len(spans)


def test_legacy_annotations_once_each_and_unprefixed(runs):
    names = [name for name, *_ in runs["events"]]
    for legacy in LEGACY:
        assert names.count(legacy) == 1, legacy
    # the span named like a legacy annotation is another event: the root
    assert names.count(telemetry.ANNOTATION_PREFIX + "LogisticRegression.fit") == 1


# --- (b) the span tree --------------------------------------------------------


def test_span_tree_of_a_fit(runs):
    spans = runs["spans"]
    root = _one(spans, "LogisticRegression.fit")
    pre, dis = _one(spans, "preprocess"), _one(spans, "fit.dispatch")
    assert "parent_id" not in root["args"]
    assert pre["args"]["parent_id"] == dis["args"]["parent_id"] == root["args"]["span_id"]
    puts = _by_name(spans, "h2d.enqueue")
    assert [p["args"]["parent_id"] for p in puts] == [pre["args"]["span_id"]] * 2
    x_put, y_put = puts
    # X (4096 x 64 f32) with its f32 mask, then y: one device, nothing padded
    assert (x_put["args"]["bytes"], x_put["args"]["arrays"]) == (ROWS * COLS * 4 + ROWS * 4, 2)
    assert (y_put["args"]["bytes"], y_put["args"]["arrays"]) == (ROWS * 4, 1)
    assert x_put["args"]["devices"] == y_put["args"]["devices"] == 1
    # a frame of one block: one put of X, no assembly on the device
    assert (x_put["args"]["blocks"], x_put["args"]["block_bytes"]) == (1, ROWS * COLS * 4)
    assert "blocks" not in y_put["args"]
    launch, fetch = _one(spans, "solver.launch"), _one(spans, "solver.fetch")
    assert launch["args"]["parent_id"] == fetch["args"]["parent_id"] == dis["args"]["span_id"]
    assert launch["args"]["program"] == "logreg_fit"
    order = [s["args"]["span_id"] for s in (root, pre, x_put, y_put, dis, launch, fetch)]
    assert order == sorted(order)
    assert launch["ts"] + launch["dur"] <= fetch["ts"] + 1.0
    model = runs["model"]
    assert fetch["args"]["n_iter"] == model.n_iter_ >= 2
    assert fetch["args"]["n_evals"] == model._fit_report["n_evals"] >= model.n_iter_ + 1
    # provenance only: neither a constructor argument nor a persisted attribute
    assert "n_evals" not in model._get_model_attributes()
    assert "_fit_report" not in model._get_model_attributes()


def test_span_tree_of_a_transform_in_three_batches(runs):
    spans = runs["spans"]
    call = _one(spans, "LogisticRegressionModel.transform.call")
    inner = _one(spans, "LogisticRegressionModel.transform")
    extract = _one(spans, "transform.extract")
    assert "parent_id" not in call["args"]
    assert inner["args"]["parent_id"] == extract["args"]["parent_id"] == call["args"]["span_id"]
    assert extract["args"]["bytes"] == ROWS * COLS * 4 and extract["args"]["copied"] is False
    batched = [s for s in spans if s["name"] in ("transform.stage", "transform.apply", "transform.fetch")]
    assert all(s["args"]["parent_id"] == inner["args"]["span_id"] for s in batched)
    batched.sort(key=lambda s: s["args"]["span_id"])
    # the next batch is staged before this one's outputs are fetched
    assert [(s["name"].split(".")[1], s["args"]["batch"], s["args"]["rows"]) for s in batched] == [
        ("stage", 0, 1500), ("stage", 1, 1500), ("apply", 0, 1500), ("fetch", 0, 1500),
        ("stage", 2, 1096), ("apply", 1, 1500), ("fetch", 1, 1500),
        ("apply", 2, 1096), ("fetch", 2, 1096),
    ]
    concat, frame = _by_name(spans, "transform.assemble")
    assert concat["args"]["parent_id"] == inner["args"]["span_id"]
    assert frame["args"]["parent_id"] == call["args"]["span_id"]
    out_bytes = sum(v.nbytes for v in runs["out"].values())
    assert concat["args"] == dict(concat["args"], columns=3, bytes=out_bytes)
    assert frame["args"] == dict(frame["args"], columns=3, bytes=out_bytes)
    assert extract["args"]["span_id"] < inner["args"]["span_id"] < frame["args"]["span_id"]


@pytest.mark.parametrize(
    "cols, forced, loss_grad", [(64, False, "xla_autodiff"), (128, True, "pallas_fused"), (300, True, "pallas_fused")]
)
def test_launch_span_says_which_loss_grad_runs(monkeypatch, cols, forced, loss_grad):
    telemetry.reset_telemetry()
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    monkeypatch.setattr(logreg_pallas, "FORCE_INTERPRET", forced)
    _, _, df = _frame(cols=cols, rows=384, seed=3)
    # FORCE_INTERPRET is read at trace time and is no part of the jit key
    jax.clear_caches()
    try:
        LogisticRegression(maxIter=3, regParam=1e-3, num_workers=1).fit(df)
    finally:
        jax.clear_caches()
        telemetry.reset_telemetry()
    args = _one(spans, "solver.launch")["args"]
    assert args["loss_grad"] == loss_grad
    if forced:
        # a binary fit takes the fused pass at any width, and says its tile
        assert "declined" not in args
        assert args["tile"] == logreg_pallas.binary_tile(cols, False)[0]
    else:
        # on this backend: not a TPU (the width is no term of a binary fit's gate)
        assert args["declined"] == "backend" and "tile" not in args


def test_linear_regression_gets_the_same_pair():
    telemetry.reset_telemetry()
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    X, _, _ = _frame(rows=512)
    df = DataFrame({"features": X}).withColumn("label", X[:, 0] * 2.0 + 1.0)
    try:
        LinearRegression(num_workers=1).fit(df)
    finally:
        telemetry.reset_telemetry()
    dis = _one(spans, "fit.dispatch")
    launch, fetch = _one(spans, "solver.launch"), _one(spans, "solver.fetch")
    assert launch["args"]["parent_id"] == fetch["args"]["parent_id"] == dis["args"]["span_id"]
    assert launch["args"]["program"].split(",")[-1] == "solve_normal"
    assert launch["args"]["program"].split(",")[0].startswith("linreg_suffstats")
    assert fetch["args"]["n_iter"] == 1


# --- (c) the evaluation count -------------------------------------------------


def _bowl(scale):
    a = jnp.linspace(0.02, 0.05, 6)
    return (lambda w: 0.5 * scale * jnp.sum((w - a) ** 2)), jnp.zeros((6,), jnp.float32)


def _solve(fun, w0, **kw):
    solve = jax.jit(lambda w: lbfgs.minimize_lbfgs(fun, w, max_iter=50, tol=1e-9, **kw))
    return solve(w0)


def test_n_evals_is_n_iter_plus_one_when_every_first_trial_passes():
    # unit curvature: the first step is short (t0 = 1/|d| = 1 at |g| < 1
    # gives the exact minimum), every later one is the exact Newton step
    res = _solve(*_bowl(1.0))
    assert int(res.n_iter) >= 1
    assert int(res.n_evals) == int(res.n_iter) + 1


def test_n_evals_counts_backtracking_trials():
    # curvature 1e4: the first trial overshoots the bowl tenfold and Armijo
    # halves the step several times
    res = _solve(*_bowl(1e4))
    assert int(res.n_evals) > int(res.n_iter) + 1


def test_one_batched_lane_counts_what_its_solo_solve_counts():
    lanes = [1.0, 1e4, 30.0]
    a = jnp.linspace(0.02, 0.05, 6)
    scales = jnp.asarray(lanes, jnp.float32)

    def fun_b(W):
        return 0.5 * scales * jnp.sum((W - a[None, :]) ** 2, axis=1)

    solve_b = jax.jit(
        lambda W: lbfgs.minimize_lbfgs_batched(fun_b, W, max_iter=50, tol=jnp.full((3,), 1e-9))
    )
    out = solve_b(jnp.zeros((3, 6), jnp.float32))
    for b, scale in enumerate(lanes):
        solo = _solve(*_bowl(scale))
        assert int(out.n_evals[b]) == int(solo.n_evals), b
        assert int(out.n_iter[b]) == int(solo.n_iter), b
    assert int(out.n_evals[1]) > int(out.n_iter[1]) + 1


def test_host_solver_counts_its_value_grad_calls():
    fun, w0 = _bowl(1e4)
    vg = jax.jit(jax.value_and_grad(fun))
    calls = []

    def value_grad(w):
        calls.append(1)
        f, g = vg(jnp.asarray(w, jnp.float32))
        return float(f), np.asarray(g, np.float64)

    res = lbfgs.minimize_lbfgs_host(value_grad, np.zeros(6), max_iter=50, tol=1e-9)
    assert int(res.n_evals) == len(calls) > int(res.n_iter) + 1


def _solver_before_the_counter(fun, w0, *, max_iter, tol, history=10, max_ls=30):
    """``ops/lbfgs.minimize_lbfgs`` (plain L-BFGS branch) as it stood
    before ``n_evals`` joined its loop state: the reference that holds the
    solution bitwise."""
    dtype = w0.dtype
    p = w0.shape[0]
    vg = jax.value_and_grad(fun)
    f0, g0 = vg(w0)
    S0 = jnp.zeros((history, p), dtype)
    Y0 = jnp.zeros((history, p), dtype)
    state0 = (w0, f0, g0, S0, Y0, jnp.asarray(0), jnp.asarray(0), jnp.asarray(False))
    c1 = jnp.asarray(1e-4, dtype)

    def cond(state):
        _, _, _, _, _, _, it, converged = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(converged))

    def body(state):
        w, f, g, S, Y, k, it, _ = state
        d = -lbfgs._two_loop(g, S, Y, k)
        dir_deriv = jnp.vdot(g, d)
        d_norm = jnp.sqrt(jnp.vdot(d, d))
        t0 = jnp.where(k == 0, 1.0 / jnp.maximum(d_norm, 1.0), jnp.asarray(1.0, dtype))

        def ls_cond(carry):
            t, f_t, _, n_try = carry
            ok = f_t <= f + c1 * t * dir_deriv
            return jnp.logical_and(jnp.logical_not(ok), n_try < max_ls)

        def ls_body(carry):
            t, _, _, n_try = carry
            t = t * 0.5
            f_t, g_t = vg(w + t * d)
            return t, f_t, g_t, n_try + 1

        f_t0, g_t0 = vg(w + t0 * d)
        t, f_new, g_new, _ = lax.while_loop(ls_cond, ls_body, (t0, f_t0, g_t0, jnp.asarray(0)))
        w_new = w + t * d
        s = w_new - w
        yv = g_new - g
        store = jnp.vdot(s, yv) > jnp.asarray(1e-10, dtype)
        idx = k % history
        S = jnp.where(store, S.at[idx].set(s), S)
        Y = jnp.where(store, Y.at[idx].set(yv), Y)
        k = jnp.where(store, k + 1, k)
        denom = jnp.maximum(jnp.maximum(jnp.abs(f), jnp.abs(f_new)), 1.0)
        converged = jnp.logical_or((f - f_new) / denom <= tol, dir_deriv >= 0.0)
        return (w_new, f_new, g_new, S, Y, k, it + 1, converged)

    w, f, _, _, _, _, it, _ = lax.while_loop(cond, body, state0)
    return w, f, it


def test_counter_leaves_the_solution_bitwise(runs):
    X, y = jnp.asarray(runs["X"]), jnp.asarray(runs["y"])

    def loss(w):
        z = X @ w[:COLS] + w[COLS]
        return jnp.mean(jax.nn.softplus(z) - y * z) + 0.5e-3 * jnp.vdot(w[:COLS], w[:COLS])

    w0 = jnp.zeros((COLS + 1,), jnp.float32)
    kw = dict(max_iter=40, tol=1e-7)
    solve_new = jax.jit(lambda w: lbfgs.minimize_lbfgs(loss, w, **kw))
    solve_old = jax.jit(lambda w: _solver_before_the_counter(loss, w, **kw))
    new = solve_new(w0)
    w_old, f_old, it_old = solve_old(w0)
    assert int(new.n_iter) == int(it_old) >= 5
    np.testing.assert_array_equal(np.asarray(new.w), np.asarray(w_old))
    np.testing.assert_array_equal(np.asarray(new.f), np.asarray(f_old))
    assert int(new.n_evals) >= int(new.n_iter) + 1


# --- (d) nothing recording ----------------------------------------------------


def test_untraced_job_gets_the_shared_null_at_every_site(runs):
    calls = runs["untraced_calls"]
    assert NEW_SITES <= {name for name, _ in calls}
    assert all(s is telemetry._NULL for _, s in calls)
    # three more span() calls in a fit, fifteen in a transform of three batches
    names = [name for name, _ in calls]
    assert names.count("h2d.enqueue") == 2 and names.count("transform.stage") == 3
    # ... and the link's: a put and three fetches a batch
    assert names.count("transform.h2d") == 3 and names.count("transform.d2h") == 9


def test_untraced_block_loop_gets_the_shared_null_at_every_site(block_runs):
    for kind, puts in (("logreg", 5), ("pca", 5)):
        calls = block_runs[kind]["untraced_calls"]
        assert all(s is telemetry._NULL for _, s in calls), kind
        names = [name for name, _ in calls]
        assert names.count("h2d.put") == puts and names.count("h2d.wait") == puts - mesh_mod._PUTS_IN_FLIGHT
        assert names.count("h2d.fold") == (puts if kind == "pca" else 0)
    assert BLOCK_SITES <= {name for name, _ in block_runs["pca"]["untraced_calls"]}


def test_tracing_leaves_outputs_bitwise(runs):
    for attr in ("coef_", "intercept_"):
        np.testing.assert_array_equal(
            np.asarray(getattr(runs["model"], attr)), np.asarray(getattr(runs["plain_model"], attr))
        )
    assert runs["model"].n_iter_ == runs["plain_model"].n_iter_
    for c in OUTPUTS:
        np.testing.assert_array_equal(runs["out"][c], runs["plain_out"][c])
        assert runs["out"][c].shape[0] == ROWS
    assert core._TpuModel._transform_batch_rows(runs["model"]) == 1 << 17


# --- (e) the link's spans -----------------------------------------------------


def _fit_attrs(model):
    return {k: np.asarray(v) for k, v in model._get_model_attributes().items() if v is not None}


@pytest.fixture(scope="module")
def block_runs(tmp_path_factory):
    """A LogisticRegression fit (no fold) and a PCA fit (a fold a block) of a
    frame that goes up in five row blocks: once with nothing recording, once
    under a sink and a profiler capture."""
    telemetry.reset_telemetry()
    mp = pytest.MonkeyPatch()
    mp.delenv("TPUML_TRACE", raising=False)
    mp.setattr(mesh_mod, "_PUT_BLOCK_BYTES", BLOCK_ROWS * COLS * 4)
    X, y, df = _frame(rows=BLOCK_FRAME_ROWS, seed=5)
    fits = {
        "logreg": lambda: LogisticRegression(maxIter=5, regParam=1e-3, num_workers=1).fit(df),
        "pca": lambda: PCA(k=3, inputCol="features", num_workers=1).fit(df),
    }
    out = {"X": X}
    try:
        for kind, fit in fits.items():
            fit()  # warm: neither run below compiles
            plain, seen = _untraced(mp, fit)
            model, spans, events = _captured(str(tmp_path_factory.mktemp("capture_" + kind)), fit)
            out[kind] = {"spans": spans, "events": events, "model": model, "plain_model": plain, "untraced_calls": seen}
        yield out
    finally:
        mp.undo()
        telemetry.reset_telemetry()


@pytest.mark.parametrize("kind", ["logreg", "pca"])
def test_block_loop_has_a_put_a_block_and_a_wait_a_put_but_the_last_two(block_runs, kind):
    spans, X = block_runs[kind]["spans"], block_runs["X"]
    enqueue = _by_name(spans, "h2d.enqueue")[0]  # the frame's; the labels' comes after it
    args = enqueue["args"]
    puts, waits, folds = (_by_name(spans, n) for n in ("h2d.put", "h2d.wait", "h2d.fold"))
    assert args["blocks"] == len(puts) == 5 and args["block_bytes"] == BLOCK_ROWS * COLS * 4
    assert args["write_program"] == "_write_block"
    # what crosses: the frame at the host's width and its mask; on the devices no less
    assert X.nbytes + BLOCK_FRAME_ROWS * 4 <= args["host_bytes"] <= args["bytes"]
    assert [p["args"]["block"] for p in puts] == list(range(5))
    assert [p["args"]["bytes"] for p in puts] == [BLOCK_ROWS * COLS * 4] * 4 + [404 * COLS * 4]
    assert sum(p["args"]["bytes"] for p in puts) == X.nbytes
    # the wait that was there: for the write of the put two before, from the third put on
    assert [w["args"]["block"] for w in waits] == list(range(5 - mesh_mod._PUTS_IN_FLIGHT))
    assert [f["args"]["block"] for f in folds] == (list(range(5)) if kind == "pca" else [])
    assert ("folded_blocks" in args) == (kind == "pca")
    children = puts + waits + folds
    assert all(c["args"]["parent_id"] == args["span_id"] for c in children)
    order = sorted(children, key=lambda c: c["args"]["span_id"])
    expect = []
    for i in range(5):
        expect += ([("h2d.wait", i - 2)] if i >= 2 else []) + [("h2d.put", i)] + ([("h2d.fold", i)] if kind == "pca" else [])
    assert [(c["name"], c["args"]["block"]) for c in order] == expect
    # one wait: nothing recorded inside the enqueue but these
    inside = [s for s in spans if s["args"].get("parent_id") == args["span_id"]]
    assert len(inside) == len(children)


@pytest.mark.parametrize("kind", ["logreg", "pca"])
def test_block_loop_spans_have_their_profiler_events(block_runs, kind):
    twins = {}
    for name, lo, hi, stats in block_runs[kind]["events"]:
        if name.startswith(telemetry.ANNOTATION_PREFIX):
            twins[stats["span_id"]] = (name, lo, hi, stats)
    assert {telemetry.ANNOTATION_PREFIX + n for n in ("h2d.put", "h2d.wait")} <= {t[0] for t in twins.values()}
    _assert_twins(block_runs[kind]["spans"], twins)


@pytest.mark.parametrize("kind", ["logreg", "pca"])
def test_block_loop_tracing_leaves_the_fit_bitwise(block_runs, kind):
    traced, plain = _fit_attrs(block_runs[kind]["model"]), _fit_attrs(block_runs[kind]["plain_model"])
    assert set(traced) == set(plain) and traced
    for k in traced:
        np.testing.assert_array_equal(traced[k], plain[k], err_msg=k)


def test_host_bytes_on_the_one_put_block_and_aligned_paths(monkeypatch):
    telemetry.reset_telemetry()
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    m = mesh_mod.make_mesh(1)
    X = np.arange(600 * 24, dtype=np.float32).reshape(600, 24)
    try:
        xd, md = mesh_mod.shard_rows(X, m)                                   # one put
        mesh_mod.shard_aligned(np.ones(600, np.float32), m, xd.shape[0])     # the labels' path
        monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", 256 * 24 * 4)
        xb, mb = mesh_mod.shard_rows(X, m)                                   # three blocks: 256, 256, 88
        xw, mw = mesh_mod.shard_rows(X, m, cols=32)                          # ... into a wider buffer
    finally:
        telemetry.reset_telemetry()
    one, aligned, blocks, wide = (s["args"] for s in _by_name(spans, "h2d.enqueue"))
    assert one["host_bytes"] == one["bytes"] == X.nbytes + md.nbytes and "write_program" not in one
    assert aligned["host_bytes"] == aligned["bytes"] == 600 * 4
    assert blocks["host_bytes"] == X.nbytes + mb.nbytes == blocks["bytes"] and blocks["blocks"] == 3
    # the device pads the columns: the host hands over its own width
    assert wide["host_bytes"] == X.nbytes + mw.nbytes and wide["bytes"] == 600 * 32 * 4 + mw.nbytes
    assert sum(p["args"]["bytes"] for p in _by_name(spans, "h2d.put")) == 2 * X.nbytes
    np.testing.assert_array_equal(np.asarray(xb), np.asarray(xd))
    np.testing.assert_array_equal(np.asarray(xw)[:, :24], X)


def _rf():
    return RandomForestClassifier(numTrees=3, maxDepth=4, maxBins=16, seed=7, num_workers=1)


MODELS = {
    "LogisticRegression": (lambda: LogisticRegression(maxIter=5, regParam=1e-3, num_workers=1), OUTPUTS),
    "KMeans": (lambda: KMeans(k=4, maxIter=3, seed=1, num_workers=1), ("prediction",)),
    "PCA": (lambda: PCA(k=3, inputCol="features", outputCol="pca_features", num_workers=1), ("pca_features",)),
    "LinearRegression": (lambda: LinearRegression(num_workers=1), ("prediction",)),
    "RandomForestClassifier": (_rf, OUTPUTS),
}


@pytest.mark.parametrize("name", list(MODELS))
def test_every_batch_has_its_put_and_its_fetch(monkeypatch, name):
    make, columns = MODELS[name]
    monkeypatch.delenv("TPUML_TRACE", raising=False)
    monkeypatch.setenv("TPUML_RF_APPLY", "bins")  # the engine a TPU takes; the CPU's default is legacy
    X, _, df = _frame(rows=1000, seed=11)
    telemetry.reset_telemetry()
    model = make().fit(df)
    monkeypatch.setattr(type(model), "_transform_batch_rows", lambda self: 400)  # 400, 400, 200
    plain = model.transform(df)
    plain = {c: np.asarray(plain.column(c)) for c in columns}
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    try:
        out = model.transform(df)
        out = {c: np.asarray(out.column(c)) for c in columns}
    finally:
        telemetry.reset_telemetry()
    by_id = {s["args"]["span_id"]: s for s in spans}

    def batch_of(s):  # the batch of the nearest ancestor that names one
        while "batch" not in s["args"]:
            s = by_id[s["args"]["parent_id"]]
        return s["args"]["batch"], s["name"]

    ups, backs = _by_name(spans, "transform.h2d"), _by_name(spans, "transform.d2h")
    assert [(batch_of(u)[0], u["args"]["bytes"]) for u in ups] == [
        (0, 400 * COLS * 4), (1, 400 * COLS * 4), (2, 200 * COLS * 4)]
    assert sum(u["args"]["bytes"] for u in ups) == X.nbytes
    staged = name == "RandomForestClassifier"  # the one model that puts batch i+1 ahead of batch i's fetch
    assert {batch_of(u)[1] for u in ups} == {"transform.stage" if staged else "transform.apply"}
    per_batch = [sum(1 for b in backs if batch_of(b)[0] == i) for i in range(3)]
    assert min(per_batch) >= 1 and len(set(per_batch)) == 1
    assert {batch_of(b)[1] for b in backs} <= {"transform.apply", "transform.fetch"}
    if staged:
        (engine,) = {s["args"]["engine"] for s in _by_name(spans, "forest.descent")}
        assert engine == "bins"
    for c in columns:
        np.testing.assert_array_equal(out[c], plain[c])
        assert out[c].shape[0] == 1000
