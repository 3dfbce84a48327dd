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
    _, columns, _, spans, _ = fitted
    rows, cols = columns["features"].shape
    by_name = {}
    for ev in spans:
        by_name.setdefault(ev["name"], []).append(ev["args"])
    dispatch = by_name["fit.dispatch"][0]["span_id"]
    launch, fetch = by_name["solver.launch"][0], by_name["solver.fetch"][0]
    assert launch["parent_id"] == fetch["parent_id"] == dispatch
    assert launch["program"] == "_pca_fit_kernel" and launch["gram"] == "xla" and launch["precision"] == "highest"
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
