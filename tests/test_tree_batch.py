"""Tree-batched forest growth: the batched builder must be BIT-identical
to the sequential per-tree builder at the same keys, for every histogram
strategy — the contract that lets TPUML_RF_TREE_BATCH=auto engage by
default without changing any fitted forest."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.rf_pallas as rfp
import spark_rapids_ml_tpu.ops.tree_kernels as tk
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.regression import RandomForestRegressor
from spark_rapids_ml_tpu.runtime.envspec import EnvSpecError


def _data(seed=0, n=600, d=16, nb=32):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    edges = tk.make_bin_edges(X, nb)
    bins = tk._pack_bins(tk.binize(jnp.asarray(X), jnp.asarray(edges), d_pad=tk.next_pow2(d)))
    valid = jnp.ones((n,), jnp.float32)
    y = (X[:, 0] + 0.5 * X[:, 3] > 0).astype(np.int32)
    cls_stats = jax.nn.one_hot(jnp.asarray(y), 2, dtype=jnp.float32)
    yr = jnp.asarray((X[:, 0] + 0.1 * rng.normal(size=n)).astype(np.float32))
    reg_stats = jnp.stack([jnp.ones(n), yr, yr * yr], axis=1)
    return bins, valid, cls_stats, reg_stats


def _cfg(**kw):
    base = dict(
        max_depth=4, n_bins=32, n_features=16, n_stats=2, impurity="gini",
        k_features=16, min_samples_leaf=1, min_info_gain=0.0,
        min_samples_split=2, bootstrap=True,
    )
    base.update(kw)
    return tk.ForestConfig(**base)


def _assert_batched_bit_identical(
    bins, valid, stats, cfg, n_trees=4, seed=7, gain_ulp=0
):
    """Every table of the batched build equals the per-tree build bit for
    bit. ``gain_ulp`` relaxes ONLY the reported ``gain`` values to that many
    units in the last place; structure, thresholds and leaf payloads stay
    exact. The counts beside the tables (``live_rows``, ``closed_at_birth``,
    ``sel_tiles``) are equal where nothing is handed down (variance):
    on class counts the per-tree builder closes a node when it makes it and
    the batched one does not (``tests/test_rf_live_frontier.py``). A node
    that does not split holds threshold bin 0, in both builders."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    seqs = [tk._build_tree(bins, stats, valid, k, cfg) for k in keys]
    bat = tk._build_trees_batched(bins, stats, valid, keys, cfg)
    assert set(bat) == set(seqs[0])
    counts = ("live_rows", "closed_at_birth", "sel_tiles")
    for i, s in enumerate(seqs):
        for field in s:
            a, b = np.asarray(s[field]), np.asarray(bat[field][i])
            if field in counts and cfg.impurity != "variance":
                continue
            if field == "gain" and gain_ulp:
                np.testing.assert_array_max_ulp(a, b, maxulp=gain_ulp)
                continue
            np.testing.assert_array_equal(
                a, b, err_msg=f"tree {i} field {field}"
            )
        for built in (s["threshold_bin"], bat["threshold_bin"][i]):
            assert not np.asarray(built)[np.asarray(s["feature"]) < 0].any()


@pytest.mark.parametrize("strategy", ["scatter", "matmul"])
@pytest.mark.parametrize("k_features", [16, 4])
def test_bit_identity_classification(strategy, k_features):
    bins, valid, cls_stats, _ = _data()
    cfg = _cfg(hist_strategy=strategy, k_features=k_features)
    _assert_batched_bit_identical(bins, valid, cls_stats, cfg)


@pytest.mark.parametrize("strategy", ["scatter", "matmul"])
@pytest.mark.parametrize("k_features", [16, 4])
def test_bit_identity_regression(strategy, k_features):
    """Variance stats are the hard case: f32 accumulation order must be
    preserved exactly (the fused tall-skinny matmul is NOT used there —
    see _hist_matmul_b).

    Numerics contract: the histograms, and with them every split (feature,
    threshold) and leaf payload, are bit-identical. The reported variance
    ``gain`` is a difference of three f32 terms (parent and child sums of
    squares over counts, each up to ~2x the gain) whose multiply-add
    contraction XLA's CPU backend (jax 0.9.0) chooses per compiled program,
    so the batched and per-tree programs may round each term differently:
    up to 3 x half an ulp at twice the gain's magnitude = 3 ulp of the gain.
    Seen: only the root's gain differs, by 1-3 ulp (6e-8..1.8e-7 at ~0.68).
    Gain is held to 4 ulp, everything else to equality."""
    bins, valid, _, reg_stats = _data()
    cfg = _cfg(
        hist_strategy=strategy, k_features=k_features,
        n_stats=3, impurity="variance",
    )
    _assert_batched_bit_identical(bins, valid, reg_stats, cfg, gain_ulp=4)


@pytest.mark.parametrize("impurity", ["gini", "variance"])
# k=128 doubles the interpret-mode kernel cost for the same code path as
# k=11; keep it under --runslow so tier-1 stays inside its wall-clock cap.
@pytest.mark.parametrize(
    "k_features", [pytest.param(128, marks=pytest.mark.slow), 11]
)
def test_bit_identity_compact(monkeypatch, impurity, k_features):
    """Compact (Pallas sub-block) strategy, interpret-forced on CPU: the
    flattened one-kernel-call batch must equal per-tree calls exactly
    (BLOCK_ROWS-aligned per-tree row counts keep grid blocks tree-pure)."""
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    calls = []
    real = rfp.subblock_hist
    monkeypatch.setattr(
        rfp, "subblock_hist",
        lambda *a, **k: calls.append(1) or real(*a, **k),
    )
    bins, valid, cls_stats, reg_stats = _data(d=128)
    n_stats = 2 if impurity == "gini" else 3
    stats = cls_stats if impurity == "gini" else reg_stats
    cfg = _cfg(
        hist_strategy="compact", n_features=128, k_features=k_features,
        impurity=impurity, n_stats=n_stats,
    )
    try:
        _assert_batched_bit_identical(bins, valid, stats, cfg)
        assert calls, "compact strategy never engaged the Pallas kernel"
    finally:
        jax.clear_caches()


# gini rides the same fused kernel as variance with n_stats=2; the compact
# tests above keep gini covered in tier-1, so only variance runs non-slow.
@pytest.mark.parametrize(
    "impurity", [pytest.param("gini", marks=pytest.mark.slow), "variance"]
)
def test_bit_identity_fused_selection(monkeypatch, impurity):
    """Fused-selection variant (in-kernel per-node column select) through
    the batched wrapper: one flattened subblock_hist_sel call per level."""
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(tk, "_SEL_MIN_DPAD", 0)
    calls = []
    real = rfp.subblock_hist_sel
    monkeypatch.setattr(
        rfp, "subblock_hist_sel",
        lambda *a, **k: calls.append(1) or real(*a, **k),
    )
    bins, valid, cls_stats, reg_stats = _data(d=128)
    n_stats = 2 if impurity == "gini" else 3
    stats = cls_stats if impurity == "gini" else reg_stats
    cfg = _cfg(
        hist_strategy="compact", n_features=128, k_features=11,
        impurity=impurity, n_stats=n_stats,
    )
    try:
        _assert_batched_bit_identical(bins, valid, stats, cfg)
        assert calls, "fused-selection kernel never engaged"
    finally:
        jax.clear_caches()


def test_no_bootstrap_and_masked_rows():
    """bootstrap=False and invalid rows (padding) must batch identically
    too — the mask rides the stat weights."""
    bins, valid, cls_stats, _ = _data()
    valid = valid.at[550:].set(0.0)
    cfg = _cfg(hist_strategy="scatter", bootstrap=False)
    _assert_batched_bit_identical(bins, valid, cls_stats, cfg)


# ---------------------------------------------------------------------------
# resolver: env validation + HBM-budgeted auto
# ---------------------------------------------------------------------------


def test_resolve_tree_batch_auto_default():
    cfg = _cfg()
    assert tk.resolve_tree_batch(8, cfg, 1000) == 8


def test_resolve_tree_batch_off(monkeypatch):
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "off")
    assert tk.resolve_tree_batch(8, _cfg(), 1000) == 1


def test_resolve_tree_batch_pinned_clamps_to_divisor(monkeypatch):
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "3")
    # 3 does not divide 8 -> largest divisor <= 3 is 2
    assert tk.resolve_tree_batch(8, _cfg(), 1000) == 2
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "4")
    assert tk.resolve_tree_batch(8, _cfg(), 1000) == 4
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "100")
    assert tk.resolve_tree_batch(8, _cfg(), 1000) == 8


def test_resolve_tree_batch_hbm_gate(monkeypatch):
    """auto shrinks the batch when per-tree residents exceed the budget;
    a tiny budget forces sequential."""
    monkeypatch.setenv("TPUML_RF_TREE_BATCH_BUDGET", "1")
    assert tk.resolve_tree_batch(8, _cfg(), 10_000_000) == 1
    # generous budget -> full group
    monkeypatch.setenv("TPUML_RF_TREE_BATCH_BUDGET", "1e12")
    assert tk.resolve_tree_batch(8, _cfg(), 1000) == 8


@pytest.mark.parametrize("bad", ["nonsense", "-2", "0", "1.5"])
def test_resolve_tree_batch_invalid(monkeypatch, bad):
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", bad)
    with pytest.raises(EnvSpecError):
        tk.resolve_tree_batch(8, _cfg(), 1000)


# ---------------------------------------------------------------------------
# estimator level: defaults inert (auto batched == off sequential == HEAD)
# ---------------------------------------------------------------------------


def test_estimator_outputs_bit_identical_batched_vs_off(monkeypatch):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(500, 12)).astype(np.float32)
    y = ((X[:, 1] - X[:, 7]) > 0).astype(np.float32)
    df = DataFrame({"features": X, "label": y})
    kw = dict(numTrees=6, maxDepth=4, seed=11, featureSubsetStrategy="sqrt")

    m_auto = RandomForestClassifier(**kw).fit(df)  # default: auto (batched)
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "off")
    m_off = RandomForestClassifier(**kw).fit(df)

    np.testing.assert_array_equal(m_auto._features_arr, m_off._features_arr)
    np.testing.assert_array_equal(
        m_auto._thresholds_arr, m_off._thresholds_arr
    )
    np.testing.assert_array_equal(
        m_auto._leaf_stats_arr, m_off._leaf_stats_arr
    )


def test_estimator_regressor_bit_identical_batched_vs_off(monkeypatch):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(400, 10)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 5]).astype(np.float32)
    df = DataFrame({"features": X, "label": y})
    kw = dict(numTrees=4, maxDepth=4, seed=2)

    m_auto = RandomForestRegressor(**kw).fit(df)
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "off")
    m_off = RandomForestRegressor(**kw).fit(df)

    np.testing.assert_array_equal(m_auto._features_arr, m_off._features_arr)
    np.testing.assert_array_equal(
        m_auto._thresholds_arr, m_off._thresholds_arr
    )
    np.testing.assert_array_equal(
        m_auto._leaf_stats_arr, m_off._leaf_stats_arr
    )


def test_return_rows_leaf_assignment():
    """return_rows=True hands back each row's final node id — must agree
    with a fresh descent through the fitted tree tables."""
    bins, valid, cls_stats, _ = _data()
    cfg = _cfg(hist_strategy="scatter", bootstrap=False)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    kk = jax.lax.map(jax.random.split, keys)
    sw = cls_stats[None] * jnp.ones((2, 1, 1), jnp.float32)
    out = tk._grow_trees_batched(bins, sw, kk[:, 1], cfg, return_rows=True)
    node = np.asarray(out["node"])                       # (2, n)
    feat = np.asarray(out["feature"])
    thrb = np.asarray(out["threshold_bin"])
    bins_np = np.asarray(tk._unpack_bins(bins))
    for t in range(2):
        cur = np.zeros(bins_np.shape[0], np.int64)
        for _ in range(cfg.max_depth):
            f = feat[t][cur]
            split = f >= 0
            b = bins_np[np.arange(len(cur)), np.clip(f, 0, None)].astype(int)
            go_right = b > thrb[t][cur]
            cur = np.where(split, 2 * cur + 1 + go_right, cur)
        np.testing.assert_array_equal(node[t], cur)
