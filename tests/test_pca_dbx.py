"""``pca_dbx``: the Gram pass, the eigen-solve, the spans of a PCA fit, the
benchmark's generator and work counts, and the estimator against
``chipbench``'s plain reference.

Everything here is arithmetic, XLA on the CPU or the Pallas interpreter; what
Mosaic makes of the kernel at the cell's shape is ``tests/test_chip_compile.py``'s,
and what the chip makes of it PERF.md's (PR 33).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import linalg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"effective_rank": 10, "tail_strength": 0.5}
_topk_eigh = jax.jit(linalg.topk_eigh, static_argnums=1)
_subspace_topk = jax.jit(linalg._subspace_topk, static_argnums=1)


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs", "pca_dbx.json")) as f:
        return json.load(f)


# ---- the estimator against the plain reference ------------------------------


@pytest.fixture(scope="module", params=[(4096, 300), (2048, 384)], ids=["4096x300", "2048x384"])
def fitted(request):
    """Two jobs of ``PCA(k=3)`` on the source's low-rank set at a width that
    is no lane multiple and at one that is: the jobs as the harness's runner
    returns them, and the program's spans."""
    from chipbench.data import low_rank
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.models.feature import _project
    from spark_rapids_ml_tpu.runtime import telemetry

    rows, cols = request.param
    config = _config()
    columns = low_rank.make(33, rows, cols, PARAMS)
    df = DataFrame({"features": columns["features"]})
    spans, jobs, programs = [], [], []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        for _ in range(2):
            model = PCA(num_workers=1, **config["estimator"]["params"]).fit(df)
            jobs.append({
                "model": {k: np.asarray(v) for k, v in model._get_model_attributes().items()},
                "outputs": {"pca_features": np.asarray(model.transform(df).column("pca_features"))},
            })
            programs.append(_project._cache_size())
    finally:
        telemetry.remove_span_sink(sink)
    return config, columns, jobs, spans, programs


@pytest.fixture(scope="module")
def judged(fitted):
    from chipbench.references import pca_dbx as ref

    config, columns, jobs, _, _ = fitted
    return config, dict(ref.check(config, columns, jobs))


@pytest.mark.parametrize("name", ["resid_err", "ortho_err", "top_err", "evr_err", "sign_err", "out_err", "repeat_err"])
def test_estimator_against_the_plain_reference(judged, name):
    config, numbers = judged
    assert np.isfinite(numbers[name]) and numbers[name] <= config["limits"][name], numbers


def test_fit_opens_launch_and_fetch_under_dispatch(fitted):
    """The frame's placement is the fit function's: ``h2d.enqueue`` opens
    under ``fit.dispatch`` (and ``preprocess`` places nothing), the launch
    names the fold and the finish, and both say that the fold engaged."""
    _, columns, _, spans, _ = fitted
    rows, cols = columns["features"].shape
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev["name"], []).append(ev["args"])
    dispatch = by_name["fit.dispatch"][0]["span_id"]
    enqueue, launch, fetch = by_name["h2d.enqueue"][0], by_name["solver.launch"][0], by_name["solver.fetch"][0]
    assert enqueue["parent_id"] == launch["parent_id"] == fetch["parent_id"] == dispatch
    assert len(by_name["h2d.enqueue"]) == len(by_name["fit.dispatch"]) == 2      # one placement a fit
    assert [ev["name"] for ev in spans if ev["name"] in ("h2d.enqueue", "solver.launch", "solver.fetch")][:3] == [
        "h2d.enqueue", "solver.launch", "solver.fetch"
    ]
    assert enqueue["folded_blocks"] == enqueue["blocks"] == 1 and enqueue["bytes"] == rows * (cols * 4 + 4)
    assert launch["program"] == "gram_fold,_pca_finish" and launch["gram_under_put"] is True
    assert launch["gram"] == "xla" and launch["precision"] == "highest"
    assert launch["rows_minor"] is False and set(launch["declined"].split(",")) == {"backend", "rows_minor"}
    assert launch["tile"] == linalg.gram_block_rows(rows, cols, rows) == rows   # one block: the frame is small
    assert (fetch["k"], fetch["d"]) == (3, cols)


def test_transform_program_is_shared_between_models(fitted):
    """The components are an argument of the projection: a second model's
    transform builds nothing (a closure over them was a program a model)."""
    programs = fitted[4]
    assert programs[1] == programs[0]


def test_reference_takes_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references", "pca_dbx.py")) as f:
        source = f.read()
    assert "import spark_rapids_ml_tpu" not in source and "from spark_rapids_ml_tpu" not in source


@pytest.fixture(scope="module")
def reference_frame():
    from chipbench.data import low_rank
    from chipbench.references import pca_dbx as ref

    config = _config()
    columns = low_rank.make(34, 4096, 300, PARAMS)
    job = ref.reference_job(config, columns)
    return config, columns, job, dict(ref.check(config, columns, [job]))


def test_reference_in_the_programs_place_is_sound(reference_frame):
    config, _, _, sound = reference_frame
    assert all(sound[k] <= config["limits"][k] for k in config["limits"]), sound


def _half_rows(ref, config, columns, job):
    return ref.reference_job(config, columns, fit_rows=2048), "resid_err"


def _one_wrong_pair(ref, config, columns, job):
    return ref.reference_job(config, columns, skip=1), "top_err"


def _mean_removed(ref, config, columns, job):
    X = columns["features"]
    out = (X - X.mean(axis=0)) @ job["model"]["components"].T
    return {"model": job["model"], "outputs": {"pca_features": out.astype(np.float32)}}, "out_err"


def _flipped_sign(ref, config, columns, job):
    comp = job["model"]["components"].copy()
    comp[1] *= -1.0
    out = job["outputs"]["pca_features"].copy()
    out[:, 1] *= -1.0
    return {"model": dict(job["model"], components=comp), "outputs": {"pca_features": out}}, "sign_err"


@pytest.mark.parametrize("fault", [_half_rows, _one_wrong_pair, _mean_removed, _flipped_sign], ids=lambda f: f.__name__.strip("_"))
def test_reference_catches(reference_frame, fault):
    """A fit on half of the rows, k−1 right pairs and one wrong, a transform
    with the mean removed and a flipped sign are not correct, each by the
    number the configuration's file names for it."""
    from chipbench.references import pca_dbx as ref

    config, columns, job, _ = reference_frame
    if fault is _mean_removed:      # the set's own mean is nought: give the frame one
        columns = {"features": columns["features"] + np.float32(1e-3), "label": columns["label"]}
        job = ref.reference_job(config, columns)
    bad, name = fault(ref, config, columns, job)
    numbers = dict(ref.check(config, columns, [bad]))
    assert numbers[name] > config["limits"][name], numbers


# ---- the Gram pass ----------------------------------------------------------


def _offset_frame(n, d, seed=5):
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(n, d)) * np.linspace(0.5, 2.0, d) + 1e3).astype(np.float32)   # |mean| >> sigma
    mask = (np.arange(n) < n - 37).astype(np.float32)
    return X, mask


def _cov64(X, mask):
    x = X[mask > 0].astype(np.float64)
    return x.mean(axis=0), np.cov(x.T)


@pytest.mark.parametrize("interpret", [False, True], ids=["xla_blocked", "pallas_interpret"])
def test_gram_pass_against_the_float64_covariance(monkeypatch, interpret):
    """|mean| = 1000 σ, 37 masked rows, rows that divide neither by the
    kernel's tile (2048 at this width) nor by XLA's block (96): the shifted
    sums and the rank-one correction give the float64 covariance to float32."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1)
    n, d = 4500, 300
    X, mask = _offset_frame(n, d)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))  # noqa: E731
    monkeypatch.setattr(linalg, "FORCE_INTERPRET", interpret)
    assert (linalg.gram_pallas_declined(n, d, jnp.float32) == "") == interpret
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time, not cached
    try:
        mean, cov, cnt = linalg.mean_and_cov_chunked(put(X), put(mask), mesh, 96)
    finally:
        jax.clear_caches()
    mean64, cov64 = _cov64(X, mask)
    assert float(cnt) == n - 37
    assert np.abs(np.asarray(mean, np.float64) - mean64).max() < 1e-3
    assert np.abs(np.asarray(cov, np.float64) - cov64).max() / np.abs(cov64).max() < 2e-5


def test_xla_pass_masks_the_rows_its_last_block_shares():
    n, d, block = 1000, 40, 384     # blocks at 0, 384 and (moved back) 616: rows 616..767 are in two
    X, mask = _offset_frame(n, d, seed=6)
    mu = jnp.asarray(X[:64].mean(axis=0))
    G, s, cnt = linalg._shifted_gram_xla(jnp.asarray(X), jnp.asarray(mask), mu, block=block)
    xs = (X.astype(np.float64) - np.asarray(mu, np.float64)) * mask[:, None]
    assert float(cnt) == mask.sum()
    assert np.abs(np.asarray(G, np.float64) - xs.T @ xs).max() / np.abs(xs.T @ xs).max() < 2e-6
    assert np.abs(np.asarray(s, np.float64) - xs.sum(axis=0)).max() < 0.5


def test_mean_sample_is_spread_over_the_shard():
    """Sorted data: runs of rows from all over the shard estimate the mean,
    a leading chunk would not."""
    n, d = 8192, 4
    X = np.repeat(np.linspace(0.0, 100.0, n, dtype=np.float32)[:, None], d, axis=1)
    s, c = linalg._mean_sample(jnp.asarray(X), jnp.ones((n,), jnp.float32), 256)
    assert float(c) == 256
    assert abs(float(s[0] / c) - 50.0) < 1.0
    s, c = linalg._mean_sample(jnp.asarray(X), jnp.ones((n,), jnp.float32), 8192)   # runs of whole lane tiles
    assert float(c) == 8192 and abs(float(s[0] / c) - 50.0) < 1e-2


def test_host_mean_sample_is_spread_over_the_array():
    """μ̂ for the fold comes from the host array before block 0 is there:
    runs of rows from its first to its last, so sorted data gives the mean
    of all of it and not of its leading rows; a small array is read whole."""
    n, d = 100_000, 4
    X = np.repeat(np.linspace(0.0, 100.0, n, dtype=np.float32)[:, None], d, axis=1)
    s, c = linalg.host_mean_sample(X)
    assert c == 4096 and s.dtype == np.float64 and abs(s[0] / c - 50.0) < 0.1
    s, c = linalg.host_mean_sample(X[:1000])
    assert c == 1000 and s[0] / c == pytest.approx(X[:1000, 0].astype(np.float64).mean())
    s, c = linalg.host_mean_sample(X[:0])
    assert c == 0 and not s.any()


def _sorted_offset_frame(n, d, seed=7):
    """Rows sorted by a drift of 25 σ from first to last, on a mean of 1000 σ:
    a leading block's mean is nowhere near the frame's."""
    rng = np.random.default_rng(seed)
    drift = np.linspace(0.0, 50.0, n)[:, None] * np.linspace(1.0, 0.2, d)
    return (rng.normal(size=(n, d)) * np.linspace(0.5, 2.0, d) + drift + 1e3).astype(np.float32)


@pytest.fixture(scope="module")
def sorted_frame():
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA

    X = _sorted_offset_frame(9000, 40)
    df = DataFrame({"features": X})
    one_put = PCA(k=3, num_workers=1, inputCol="features").fit(df)
    return X, df, one_put, np.cov(X.astype(np.float64).T)


@pytest.mark.parametrize(
    "workers,block_rows,blocks",
    [(1, None, 1), (1, 3200, 3), (1, 1200, 8), (2, 1600, 3), (8, 600, 2)],
    ids=["one_put", "3_blocks", "8_blocks", "2_devices_3_blocks", "8_devices_2_blocks"],
)
def test_fit_through_blocks_agrees_with_the_one_put_fit(monkeypatch, sorted_frame, workers, block_rows, blocks):
    """The covariance folded block by block — 1, 3 and 8 blocks a device, a
    ragged tail each, the partials of 2 and 8 devices — is the one-put fit's
    to 2e-6 of λ₁ and the float64 covariance's to float32, on a sorted frame
    with a large mean: the host's μ̂ is of the whole frame."""
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
    from spark_rapids_ml_tpu.runtime import telemetry

    X, df, one_put, cov64 = sorted_frame
    if block_rows:
        monkeypatch.setattr(mesh_mod, "_put_block_rows", lambda row_bytes: block_rows)
    puts = []
    sink = lambda ev, thread: puts.append(ev["args"]) if ev["name"] == "h2d.enqueue" else None  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        model = PCA(k=3, num_workers=workers, inputCol="features").fit(df)
    finally:
        telemetry.remove_span_sink(sink)
    (put,) = puts
    assert put["folded_blocks"] == put["blocks"] == workers * blocks
    lam, lam_one = np.asarray(model.explained_variance_, np.float64), np.asarray(one_put.explained_variance_, np.float64)
    assert np.abs(lam - lam_one).max() <= 2e-6 * lam_one[0]
    w, V = np.linalg.eigh(cov64)
    assert np.abs(lam / w[::-1][:3] - 1.0).max() < 2e-5
    comp = np.asarray(model.components_, np.float64)
    assert np.linalg.norm(cov64 @ comp.T - comp.T * lam, axis=0).max() / w[-1] < 2e-5
    assert np.abs(np.asarray(model.mean_, np.float64) - X.astype(np.float64).mean(axis=0)).max() < 1e-3


@pytest.mark.parametrize("pallas", [False, True], ids=["xla_blocked", "pallas_interpret"])
def test_folded_covariance_against_the_float64_covariance(monkeypatch, pallas):
    """``gram_fold`` over the blocks of ``shard_rows`` and its finish, as the
    estimator strings them, against numpy's float64 covariance — the check of
    ``test_gram_pass_against_the_float64_covariance`` on the sorted frame."""
    from spark_rapids_ml_tpu.models.feature import _GramFold
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh, shard_rows

    n, d = 4500, 300
    X = _sorted_offset_frame(n, d)
    mesh = make_mesh(2)
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", d * 4 * 1024)     # three blocks a device, the last of 202 rows
    monkeypatch.setattr(linalg, "FORCE_INTERPRET", pallas)
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time, not cached
    try:
        fold = _GramFold(X, 563)
        _, _, states = shard_rows(X, mesh, 563, fold=fold)
        assert fold.pallas == pallas and fold.attrs["gram"] == ("pallas" if pallas else "xla")
        finish = jax.jit(lambda G, s, c, mu: linalg.cov_from_gram_folds(G, s, c, mu, mesh, d, pallas))
        mean, cov, cnt = finish(*fold.sums(states, mesh), fold.mean_hat)
    finally:
        jax.clear_caches()
    x64 = X.astype(np.float64)
    cov64 = np.cov(x64.T)
    assert float(cnt) == n
    assert np.abs(fold.mean_hat.astype(np.float64) - x64.mean(axis=0)).max() < 1.0     # 25 σ of drift, and μ̂ within one
    assert np.abs(np.asarray(mean, np.float64) - x64.mean(axis=0)).max() < 1e-3
    assert np.abs(np.asarray(cov, np.float64) - cov64).max() / np.abs(cov64).max() < 2e-5


def test_pallas_fold_over_two_blocks_is_the_whole_frames_triangle(monkeypatch):
    """The kernel's upper block triangle adds up over row blocks as it is:
    two folds (the second a ragged block, 37 of its rows past ``valid``)
    against one call on the whole transposed frame."""
    n, d, split = 1500, 300, 1024
    X, mask = _offset_frame(n, d)
    mu = jnp.asarray(X[:64].mean(axis=0))
    monkeypatch.setattr(linalg, "FORCE_INTERPRET", True)
    jax.clear_caches()
    try:
        G, s = linalg._gram_triangle_pallas(jnp.asarray(X).T, jnp.asarray(mask), mu)
        acc = linalg.gram_fold_zeros(d, jnp.float32, True, jax.devices()[0])
        assert acc[0].shape == G.shape == (1, 384, 384) and acc[1].shape == s.shape == (384, 128)
        acc = linalg.gram_fold(acc, jnp.asarray(X[:split]), mu, np.int32(split), pallas=True, block=0)
        acc = linalg.gram_fold(acc, jnp.asarray(X[split:]), mu, np.int32(n - 37 - split), pallas=True, block=0)
    finally:
        jax.clear_caches()
    assert float(acc[2][0]) == n - 37
    scale = np.abs(np.asarray(G)).max()
    assert np.abs(np.asarray(acc[0]) - np.asarray(G)).max() / scale < 2e-6
    assert np.abs(np.asarray(acc[1]).sum(axis=1) - np.asarray(s).sum(axis=1)).max() < 0.5
    full, _ = linalg._mirror_gram_triangle(acc[0], acc[1], d)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(full).T)


def test_fit_multiple_over_two_k_places_and_folds_once(sorted_frame):
    """The later lanes of a ``fitMultiple`` take the first lane's covariance:
    one ``h2d.enqueue``, one fold, and each lane the model its own fit gives."""
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.runtime import telemetry

    X, df, one_put, _ = sorted_frame
    spans = []
    sink = lambda ev, thread: spans.append((ev["name"], ev["args"]))  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        est = PCA(k=3, num_workers=2, inputCol="features")
        models = dict(est.fitMultiple(df, [{est.k: 3}, {est.k: 5}]))
    finally:
        telemetry.remove_span_sink(sink)
    assert [name for name, _ in spans].count("h2d.enqueue") == 1
    launches = [args for name, args in spans if name == "solver.launch"]
    assert [a["program"] for a in launches] == ["gram_fold,_pca_finish", "_pca_from_cov"]
    assert [a["gram_under_put"] for a in launches] == [True, False]
    assert models[0].components_.shape == (3, X.shape[1]) and models[1].components_.shape == (5, X.shape[1])
    np.testing.assert_allclose(models[1].explained_variance_[:3], models[0].explained_variance_, rtol=1e-6)
    np.testing.assert_allclose(models[0].explained_variance_, one_put.explained_variance_, rtol=2e-6)
    alone = PCA(k=5, num_workers=2, inputCol="features").fit(df)
    np.testing.assert_array_equal(models[1].components_, alone.components_)


@pytest.mark.parametrize(
    "d,tile,block,fits",
    [(3000, 256, 512, True), (256, 2048, 256, True), (300, 2048, 384, True), (4000, 128, 512, True), (6000, 128, 512, False)],
)
def test_gram_tile_follows_the_width(d, tile, block, fits):
    got_tile, got_block, need = linalg.gram_tile(d)
    assert (got_tile, got_block) == (tile, block)
    assert (need <= linalg._GRAM_VMEM_LIMIT) == fits
    dp = -(-d // block) * block
    assert need > dp * dp * 4     # the accumulator, whole, is among what is counted


def test_gate_says_why_it_declines(monkeypatch):
    f32 = jnp.float32
    assert set(linalg.gram_pallas_declined(500_000, 3000, f32).split(",")) == {"backend", "rows_minor"}
    monkeypatch.setattr(linalg, "FORCE_INTERPRET", True)
    assert linalg.gram_pallas_declined(500_000, 3000, f32) == ""
    assert linalg.gram_pallas_declined(500_000, 3000, jnp.float64) == "dtype"
    assert linalg.gram_pallas_declined(500_000, 6000, f32) == "vmem"
    assert linalg.gram_pallas_declined(500_000, 3000, f32, mp_blocks=True) == "mp"


# ---- the eigen-solve --------------------------------------------------------


def _decaying_cov(d, seed=0):
    from chipbench.data import low_rank

    rng = np.random.default_rng(seed)
    V, _ = np.linalg.qr(rng.normal(size=(d, d)))
    return (V * low_rank.profile(d, 10, 0.5) ** 2) @ V.T


@pytest.mark.parametrize("d,k", [(200, 3), (300, 3), (600, 5)], ids=["direct_eigh", "subspace_300", "subspace_600_k5"])
def test_topk_eigh_gives_the_leading_pairs_to_float32(d, k):
    C = _decaying_cov(d)
    lam, V = _topk_eigh(jnp.asarray(C, jnp.float32), k)
    lam, V = np.asarray(lam, np.float64), np.asarray(V, np.float64)
    want = np.linalg.eigvalsh(C)[::-1][:k]
    assert np.abs(lam / want - 1.0).max() < 2e-6
    assert np.linalg.norm(C @ V - V * lam, axis=0).max() / want[0] < 2e-6
    assert np.abs(V.T @ V - np.eye(k)).max() < 2e-6
    assert (V[np.abs(V).argmax(axis=0), np.arange(k)] > 0).all()


def test_subspace_iteration_ends_at_the_floor_in_a_handful_of_steps():
    theta, x, steps, done = _subspace_topk(jnp.asarray(_decaying_cov(600), jnp.float32), 3)
    assert bool(done) and 4 <= int(steps) <= 16


def test_topk_eigh_of_a_flat_spectrum_comes_from_the_host(monkeypatch):
    """Where the block does not converge in its steps the pairs are LAPACK's,
    in float64: exact, and never an unconverged block."""
    rng = np.random.default_rng(1)
    d, k = 300, 3
    V, _ = np.linalg.qr(rng.normal(size=(d, d)))
    C = (V * (1.0 + 1e-4 * np.arange(d)[::-1] / d)) @ V.T       # eigenvalues within 1e-4 of one another
    monkeypatch.setattr(linalg, "_EIG_MAX_STEPS", 8)
    _, _, steps, done = linalg._subspace_topk(jnp.asarray(C, jnp.float32), k)
    assert not bool(done) and int(steps) == 8
    lam, Vk = linalg.topk_eigh(jnp.asarray(C, jnp.float32), k)
    want = np.linalg.eigvalsh(C)[::-1][:k]
    assert np.abs(np.asarray(lam, np.float64) / want - 1.0).max() < 1e-6
    Vk = np.asarray(Vk, np.float64)
    assert np.linalg.norm(C @ Vk - Vk * np.asarray(lam, np.float64), axis=0).max() < 1e-6


# ---- the benchmark's own files ----------------------------------------------


def test_low_rank_set_is_the_same_for_one_and_eight_threads():
    from chipbench.data import low_rank

    one = low_rank.make(35, 40_000, 64, PARAMS, threads=1)
    eight = low_rank.make(35, 40_000, 64, PARAMS, threads=8)
    assert np.array_equal(one["features"], eight["features"])
    assert one["features"].dtype == np.float32 and one["features"].shape == (40_000, 64)
    assert not np.array_equal(one["features"], low_rank.make(36, 40_000, 64, PARAMS)["features"])
    assert not one["label"].any()


def test_low_rank_set_has_the_sources_spectrum():
    from chipbench.data import low_rank

    s = low_rank.profile(3000, 10, 0.5)
    assert s[0] == 1.0 and s[1] == pytest.approx(np.exp(-0.01)) and s[10] == pytest.approx(0.5 * np.exp(-1) + 0.5 * np.exp(-0.1))
    X = low_rank.make(37, 30_000, 200, PARAMS)["features"].astype(np.float64)
    lam = np.linalg.eigvalsh(X.T @ X)[::-1]           # rows are (u·s)·Vᵀ / √rows: XᵀX ≈ V·diag(s²)·Vᵀ
    assert np.abs(lam[:4] / low_rank.profile(200, 10, 0.5)[:4] ** 2 - 1.0).max() < 0.05


def test_work_counts_against_a_hand_count():
    from chipbench.work import pca_dbx as work

    w = work.gram_work(500_000, 3000)
    assert w["flops"] == 500_000 * 3000 * 3001 == 4.5015e12
    assert w["bytes"] == 500_000 * 3000 * 4 == 6.0e9
    assert work.fit_work(500_000, 3000, {}) == w
    # the floor: half of the whole product's 2·n·d² (and a little, the diagonal)
    assert w["flops"] / (2 * 500_000 * 3000**2) == pytest.approx(0.5, abs=1e-3)


def _traced_fit(ops):
    return {"trace": {"ops": {"/device:TPU:0": ops}}, "device_lo": 1_000, "device_hi": 301_000}


@pytest.mark.parametrize(
    "gram_ops",
    [
        [("%pca_gram_pass.1 = (f32[6,512,3072]{2,1,0}, f32[3072,128]{1,0}) custom-call(f32[3000,500000]{1,0} %bitcast.2, f32[1,500000]{1,0} %m, f32[3072,1]{1,0} %mu)", 21_000, 199_000)],
        [("%while.3 = (s32[], f32[500000,3000]{0,1}, f32[3000,3000]{1,0}) while((s32[], f32[500000,3000]{0,1}, f32[3000,3000]{1,0}) %tuple.4)", 21_000, 199_000),
         ("%fusion.9 = f32[3000,3000]{1,0} fusion(f32[500000,3000]{0,1} %gte.1, f32[3000]{0} %mu), kind=kOutput", 30_000, 60_000)],
    ],
    ids=["pallas_call_on_the_transposed_view", "xla_loop_on_the_frame"],
)
def test_reader_splits_the_fit_program_at_the_end_of_the_gram(monkeypatch, gram_ops):
    """``gram_s.fit`` runs from the first operation that takes the frame (in
    either orientation) to the end of the last, ``eig_s.fit`` from there to
    the end of the program; the roofline share is the symmetric half once at
    the peak over it; a trace without the frame among its operands reads
    nothing, never 0."""
    import importlib.util

    from chipbench import pca_reduce, span_reduce
    from chipbench.work import pca_dbx as work

    sample = ("%fusion.1 = f32[3000]{0} fusion(f32[500000,3000]{0,1} %X, f32[500000]{0} %m), kind=kLoop", 1_000, 11_000)
    after = [("%fusion.20 = f32[3000,64]{1,0} fusion(f32[3000,3000]{1,0} %cov, f32[3000,64]{1,0} %q), kind=kOutput", 200_000, 240_000),
             ("%custom-call.5 = f32[64,64]{1,0} custom-call(f32[64,64]{1,0} %t)", 250_000, 301_000)]
    ctx = {"config": {"dtype": "float32", "cols": 3000}, "rows": 500_000, "work": work,
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    monkeypatch.setattr(span_reduce, "traced_fit", lambda c: _traced_fit([sample] + gram_ops + after))
    split = pca_reduce.gram_split(ctx)
    # busy inside [1,000, 199,000] ns: the sample's 10,000 and the pass's 178,000
    assert split["gram_s"] == pytest.approx(188_000e-9) and split["eig_s"] == pytest.approx(102_000e-9)

    def reader(name):
        spec = importlib.util.spec_from_file_location("m", os.path.join(ROOT, "chipbench", "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    assert reader("gram_s.fit")(ctx) == split["gram_s"] and reader("eig_s.fit")(ctx) == split["eig_s"]
    least = 500_000 * 3000 * 3001 / 197e12
    assert reader("gram_roofline_pct.fit")(ctx) == pytest.approx(100 * least / 188_000e-9)
    monkeypatch.setattr(span_reduce, "traced_fit", lambda c: _traced_fit(after))
    assert all(reader(n)(ctx) is None for n in ("gram_s.fit", "eig_s.fit", "gram_roofline_pct.fit"))
    monkeypatch.setattr(span_reduce, "traced_fit", lambda c: None)      # the parent's program: no solver.launch span
    assert all(reader(n)(ctx) is None for n in ("gram_s.fit", "eig_s.fit", "gram_roofline_pct.fit"))
