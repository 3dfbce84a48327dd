"""The fused Lloyd kernel's tile rule, its cost-only pass, the spans of a
KMeans fit, and the estimator against ``chipbench``'s plain reference.

The rule (``ops.kmeans_pallas.lloyd_tile``) is a function of the shape alone,
so every case here is arithmetic or the Pallas interpreter on the CPU; what
Mosaic makes of the same shapes is ``tests/test_chip_compile.py``'s, and what
the chip makes of them PERF.md's (PR 29).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import kmeans_pallas as kp
from spark_rapids_ml_tpu.ops import kmeans_kernels as kk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16 = jnp.bfloat16


# ---- the rule ---------------------------------------------------------------


@pytest.mark.parametrize(
    "d,k,mm,exact,stats,tile",
    [
        (256, 1024, None, False, True, 2048),    # chip_smoke's Lloyd pass: today's program
        (256, 1024, None, True, True, 2048),     # ... and the exact pass test_chip_compile compiles
        (256, 1024, None, True, False, 2048),    # ... and its cost pass
        (256, 1024, BF16, False, True, 2048),
        (3072, 1000, None, False, True, 1024),   # the reference's width, lane-padded
        (3072, 1000, None, True, False, 1024),   # the cost pass that did not compile at 2048
        (3072, 1000, BF16, False, True, 1024),
        (3072, 1000, None, True, True, 0),       # sums AND split operands: no tile the mask's layout allows
        (16384, 1024, None, False, True, 0),
    ],
)
def test_tile_follows_the_shape(d, k, mm, exact, stats, tile):
    got, need = kp.lloyd_tile(d, k, mm, exact, stats)
    assert got == tile
    if tile:
        assert need == kp.lloyd_vmem_bytes(tile, d, kp._k_pad(k), mm, exact, stats) <= kp._VMEM_LIMIT
        # the next tile up is over the limit, or there is none
        assert tile == kp._TILE or kp.lloyd_vmem_bytes(2 * tile, d, kp._k_pad(k), mm, exact, stats) > kp._VMEM_LIMIT
    else:
        assert need > kp._VMEM_LIMIT


def test_vmem_count_matches_the_compilers_reading():
    """Mosaic's own figures for the one shape it refuses with a total (v5e,
    d=3072, k_pad=1024, tile 2048, exact): 162.47 MiB with the sums, 146.0 to
    158.0 MiB without (PERF.md, PR 29). The count may not fall under them."""
    mib = 1 << 20
    assert kp.lloyd_vmem_bytes(2048, 3072, 1024, None, True, True) / mib == pytest.approx(162.0, abs=0.6)
    assert 146.0 <= kp.lloyd_vmem_bytes(2048, 3072, 1024, None, True, False) / mib <= 158.1


@pytest.mark.parametrize("d,k,exact,stats", [(256, 1024, False, True), (3072, 1000, False, True), (3072, 1000, True, False), (3072, 1000, True, True)])
def test_gate_and_kernel_read_one_rule(monkeypatch, d, k, exact, stats):
    """The gate admits a shape exactly where the rule finds a tile, and the
    kernel is traced at that tile (its grid is the rows over it)."""
    monkeypatch.setattr(kp, "FORCE_INTERPRET", True)
    tile, _ = kp.lloyd_tile(d, k, None, exact, stats)
    n = 3 * 2048 + 8
    declined = kp.kmeans_pallas_declined(n, d, k, jnp.float32, None, exact, stats)
    assert (declined == "") == (tile > 0)
    assert kp.kmeans_pallas_ok(n, d, k, jnp.float32, None, exact, stats) == (tile > 0)
    S = jax.ShapeDtypeStruct
    args = (S((n, d), jnp.float32), S((n,), jnp.float32), S((k, d), jnp.float32))
    if not tile:
        assert "tile" in declined
        with pytest.raises(ValueError, match="no row tile"):
            jax.eval_shape(lambda *a: kp.lloyd_step_pallas(*a, exact=exact, interpret=True), *args)
        return
    fn = kp.lloyd_step_pallas if stats else kp.lloyd_cost_pallas
    kw = {"exact": exact} if stats else {}
    jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a, interpret=True, **kw))(*args))
    assert f"grid=({-(-n // tile)},)" in jaxpr and f"Blocked(block_size={tile})" in jaxpr


@pytest.mark.parametrize("n_local,reason", [(2047, "rows>=tile"), (2048, "")])
def test_gate_wants_one_tile_of_rows(monkeypatch, n_local, reason):
    monkeypatch.setattr(kp, "FORCE_INTERPRET", True)
    assert kp.kmeans_pallas_declined(n_local, 256, 1024, jnp.float32) == reason
    assert kp.kmeans_pallas_declined(n_local, 250, 1024, jnp.float32) == ",".join(filter(None, ["d%128", reason]))


# ---- the kernel through the interpreter ---------------------------------------


# XLA's chunked step and cost pass, jitted once (csize shapes the program)
_STATS = jax.jit(kk._chunk_stats, static_argnames=("csize",))
_COST = jax.jit(kk._chunk_cost, static_argnames=("csize",))


def _xla(fn):
    """``fn`` with the gate shut, so ``_chunk_stats`` / ``_chunk_cost`` take
    XLA's chunks whatever the backend."""
    orig = kp.kmeans_pallas_ok
    kp.kmeans_pallas_ok = lambda *a: False
    try:
        return fn()
    finally:
        kp.kmeans_pallas_ok = orig


@pytest.fixture
def small_tile(monkeypatch):
    """A 256-row tile for test-size inputs; the jit caches hold whatever tile a
    trace read, so they are dropped on the way in and out."""
    monkeypatch.setattr(kp, "_TILE", 256)
    jax.clear_caches()
    yield 256
    jax.clear_caches()


@pytest.mark.parametrize("n,d,k", [(1024, 384, 37), (1000, 384, 130), (1280, 128, 64)], ids=["d384", "ragged_rows_k130", "d128"])
def test_pallas_pass_at_another_tile_and_width_matches_xla(small_tile, n, d, k):
    """Sums, counts and cost of the fused pass at a 256-row tile, a width that
    is not 256 and (ragged) rows that do not divide by the tile, against
    XLA's chunked step — masked rows and lane-padded centres included."""
    rng = np.random.default_rng(n + d)
    X = (rng.normal(size=(n, d)) + 4.0 * rng.integers(0, 6, (n, 1))).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-77:] = 0.0
    centers = X[rng.choice(n - 77, k, replace=False)]
    csize = n // 4 if n % 4 == 0 else n // 8
    sums_x, counts_x, cost_x = _xla(lambda: _STATS(X, mask, centers, csize=csize))
    sums_p, counts_p, cost_p = kp.lloyd_step_pallas(X, mask, centers, interpret=True)
    np.testing.assert_array_equal(np.asarray(counts_p), np.asarray(counts_x))
    np.testing.assert_allclose(np.asarray(sums_p), np.asarray(sums_x), rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(float(cost_p), float(cost_x), rtol=1e-5)


@pytest.mark.parametrize("n", [1024, 1000], ids=["whole_tiles", "ragged_rows"])
def test_cost_only_pass_equals_the_full_pass(small_tile, n):
    """The kernel's cost alone (no sums, no one-hot) is its full exact pass's
    cost, bit for bit; XLA's chunks give the same to float32, and at the
    default precision (the CPU's float32) the Lloyd step's own cost too."""
    rng = np.random.default_rng(n)
    X = (rng.normal(size=(n, 384)) + 3.0 * rng.integers(0, 5, (n, 1))).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-50:] = 0.0
    centers = X[rng.choice(n - 50, 40, replace=False)]
    full = float(kp.lloyd_step_pallas(X, mask, centers, exact=True, interpret=True)[2])
    alone = float(kp.lloyd_cost_pallas(X, mask, centers, interpret=True))
    assert alone == full
    csize = n // 8
    step_x = float(_xla(lambda: _STATS(X, mask, centers, csize=csize))[2])
    alone_x = float(_xla(lambda: _COST(X, mask, centers, csize=csize)))
    assert alone_x == step_x
    np.testing.assert_allclose(alone, alone_x, rtol=1e-5)


def test_lloyd_program_asks_for_the_cost_alone(monkeypatch):
    """With the kernel engaged (interpreter, lane-padded ingestion) a fit runs
    the stats pass inside its loop and the cost-only pass after it, never
    the stats pass at ``exact``."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.clustering import KMeans

    rng = np.random.default_rng(4)
    X = np.concatenate([rng.normal(loc=c, scale=0.3, size=(256, 5)) for c in (-3.0, 0.0, 3.0, 6.0)]).astype(np.float32)
    seen = []
    orig = kp._lloyd_pass

    def spy(*a, **kw):
        seen.append((kw["exact"], kw["stats"]))
        return orig(*a, **kw)

    monkeypatch.setenv("TPUML_LANE_PAD", "128")
    monkeypatch.setattr(kp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(kp, "_TILE", 128)
    monkeypatch.setattr(kp, "_lloyd_pass", spy)
    jax.clear_caches()
    try:
        model = KMeans(k=4, maxIter=12, seed=1, initMode="random", num_workers=2).fit(DataFrame({"features": X}))
    finally:
        jax.clear_caches()
    assert set(seen) == {(False, True), (True, False)}
    assert model.trainingCost == pytest.approx(float(((X - model.cluster_centers_[model.transform(DataFrame({"features": X})).column("prediction")]) ** 2).sum()), rel=1e-4)


# ---- the estimator: spans, and the plain reference ----------------------------


@pytest.fixture(scope="module")
def fitted():
    """``KMeans(initMode="random")`` on seeded blobs at 4,096 x 384, k=64, two
    jobs as the harness's runner returns them, and the program's spans."""
    from chipbench.data import gen_blobs
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.clustering import _assign_nearest
    from spark_rapids_ml_tpu.runtime import telemetry

    with open(os.path.join(ROOT, "chipbench", "configs", "kmeans_dbx.json")) as f:
        config = json.load(f)
    config["estimator"]["params"].update(k=64, maxIter=30)
    columns = gen_blobs.make(29, 4096, 384, {"centers": 64, "cluster_std": 1.0})
    df = DataFrame({"features": columns["features"]})
    spans, jobs, programs = [], [], []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        for _ in range(2):
            model = KMeans(num_workers=1, **config["estimator"]["params"]).fit(df)
            jobs.append({
                "model": {k: np.asarray(v) for k, v in model._get_model_attributes().items()},
                "outputs": {"prediction": np.asarray(model.transform(df).column("prediction"))},
            })
            programs.append(_assign_nearest._cache_size())
    finally:
        telemetry.remove_span_sink(sink)
    return config, columns, jobs, spans, programs


def test_fit_opens_init_launch_and_fetch_under_dispatch(fitted):
    _, _, jobs, spans, _ = fitted
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev["name"], []).append(ev["args"])
    dispatch = by_name["fit.dispatch"][0]["span_id"]
    init, launch, fetch = (by_name[name][0] for name in ("kmeans.init", "solver.launch", "solver.fetch"))
    assert init["parent_id"] == launch["parent_id"] == fetch["parent_id"] == dispatch
    assert (init["mode"], init["k"], init["rows_gathered"]) == ("random", 64, 64)
    assert launch["program"] == "_kmeans_lloyd_1d" and launch["kernel"] == "xla"
    assert launch["tile"] == 4096 and "backend" in launch["declined"]
    n_iter = int(jobs[0]["model"]["n_iter"])
    assert (fetch["n_iter"], fetch["n_evals"]) == (n_iter, n_iter + 1)


def test_transform_program_is_shared_between_models(fitted):
    """The centres are an argument of the assign program: a second model's
    transform builds nothing (a closure over them was a program a model)."""
    programs = fitted[4]
    assert programs[1] == programs[0]


@pytest.fixture(scope="module")
def judged(fitted):
    from chipbench.references import kmeans_dbx as ref

    config, columns, jobs, _, _ = fitted
    return config, dict(ref.check(config, columns, jobs))


@pytest.mark.parametrize("name", ["cost_err", "out_err", "obj_excess", "repeat_err", "step_err"])
def test_estimator_against_the_plain_reference(judged, name):
    config, numbers = judged
    assert np.isfinite(numbers[name]) and numbers[name] <= config["limits"][name], numbers


def test_reference_takes_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references", "kmeans_dbx.py")) as f:
        source = f.read()
    assert "import spark_rapids_ml_tpu" not in source and "from spark_rapids_ml_tpu" not in source


def test_reference_catches_a_wrong_row_a_wrong_cost_and_half_of_the_rows(fitted):
    """The comparison itself: the reference in the program's place is sound;
    one altered prediction, a cost that is not the served centres', and a
    fit on half of the rows are not, each by the number the configuration's
    file names for it."""
    from chipbench.references import kmeans_dbx as ref

    config, columns, _, _, _ = fitted
    limits = config["limits"]
    job = ref.reference_job(config, columns)
    sound = dict(ref.check(config, columns, [job]))
    assert all(sound[k] <= limits[k] for k in limits), sound
    pred = job["outputs"]["prediction"].copy()
    pred[7] = (pred[7] + 1) % 64
    wrong_row = dict(ref.check(config, columns, [{"model": job["model"], "outputs": {"prediction": pred}}]))
    assert wrong_row["out_err"] > limits["out_err"]
    off = dict(job["model"], training_cost=np.float32(float(job["model"]["training_cost"]) * (1 + 10 * limits["cost_err"])))
    wrong_cost = dict(ref.check(config, columns, [{"model": off, "outputs": job["outputs"]}]))
    assert wrong_cost["cost_err"] > limits["cost_err"]
    half = dict(ref.check(config, columns, [ref.reference_job(config, columns, fit_rows=2048)]))
    assert half["step_err"] > limits["step_err"] and half["cost_err"] <= limits["cost_err"]


def test_lloyd_loop_ends_where_the_centres_alternate(monkeypatch):
    """A step that sends the centres back and forth between two states (what
    a reduced product does to one near-tied row on the chip) ends the loop at
    the first return, not at ``maxIter``; a step that keeps moving runs on."""
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    def alternating(X, m, c, csize, mm=None):
        k, d = c.shape
        target = jnp.where(c[0, 0] == 1.0, 2.0, 1.0)
        return jnp.full((k, d), target), jnp.ones((k,), jnp.int32), jnp.zeros(())

    def moving(X, m, c, csize, mm=None):
        k, d = c.shape
        return c + 1.0, jnp.ones((k,), jnp.int32), jnp.zeros(())

    X = np.zeros((64, 8), np.float32)
    mask = np.ones((64,), np.float32)
    c0 = np.zeros((4, 8), np.float32)
    kw = dict(mesh=make_mesh(1), csize=64, tol=1e-20)
    try:   # the jit cache would keep the stand-in steps' traces
        monkeypatch.setattr(kk, "_chunk_stats", alternating)
        centers, _, n_iter = kk._kmeans_lloyd_1d(X, mask, c0, max_iter=30, **kw)
        assert int(n_iter) == 3 and float(centers[0, 0]) == 1.0     # 0 -> 1 -> 2 -> 1
        monkeypatch.setattr(kk, "_chunk_stats", moving)
        _, _, n_iter = kk._kmeans_lloyd_1d(X, mask, c0, max_iter=29, **kw)
        assert int(n_iter) == 29
    finally:
        jax.clear_caches()
