"""A forest fit's bins four to an int32 word (``ops/tree_kernels._pack_bins``):
the planar layout (byte ``p`` of word ``w`` holds column ``p * d_pad / 4 +
w``) packs and unpacks to the bit at the widths the fits take, with the zero
pad columns and the subset's sentinel ids; the contraction gather and the
element gather read a column out of its word as ``take_along_axis`` reads it
out of the bytes; ``binize`` writes the words itself through both of its
passes; the classifier through the fused selection and the regressor through
the wide selection (interpret mode) grow the tables recorded from the parent
commit, which held its bins a byte each; and the fit's spans say how the
bins were held."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.rf_pallas as rfp
import spark_rapids_ml_tpu.ops.tree_kernels as tk
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIDTHS = (4, 128, 1024, 3072)
TABLES = ("feature", "threshold_bin", "leaf_stats", "gain")


def _bins(d_pad, seed=0, n=37):
    """uint8 bins of ``d_pad`` columns, the last few a zero pad (a frame of
    ``d`` < ``d_pad`` columns, as ``binize`` fills them), every byte value
    among the rest."""
    rng = np.random.default_rng(seed + d_pad)
    d = max(1, d_pad - 3)
    bins = np.zeros((n, d_pad), np.uint8)
    bins[:, :d] = rng.integers(0, 256, (n, d))
    bins[0, :d] = 255
    return bins, d


@pytest.mark.parametrize("d_pad", WIDTHS)
def test_pack_and_unpack_round_trip_in_the_planar_layout(d_pad):
    bins, d = _bins(d_pad)
    words = d_pad // 4
    packed = tk._pack_bins(jnp.asarray(bins))
    assert packed.shape == (bins.shape[0], words) and packed.dtype == jnp.int32
    p = np.asarray(packed).view(np.uint32)
    for plane in range(4):   # byte p of word w is column p * W + w
        np.testing.assert_array_equal((p >> (8 * plane)) & 0xFF, bins[:, plane * words:(plane + 1) * words])
    np.testing.assert_array_equal(np.asarray(tk._unpack_bins(packed)), bins.astype(np.int32))
    assert not np.asarray(tk._unpack_bins(packed))[:, d:].any()          # the pad columns read 0
    unpack = jax.jit(tk._unpack_bins, static_argnums=2)    # c0 traced, as a feature chunk's
    for width in sorted({1, words, 2 * words, 4 * words} | ({words // 2} if words > 1 else set())):
        for c0 in range(0, d_pad, width):
            np.testing.assert_array_equal(
                np.asarray(unpack(packed, c0, width)),
                bins[:, c0:c0 + width].astype(np.int32), err_msg=f"{c0}+{width}",
            )


@pytest.mark.parametrize("d_pad", WIDTHS)
def test_gathers_read_a_column_out_of_its_word(d_pad):
    """``_contract_gather`` and ``_bins_at`` against ``take_along_axis`` of the
    bytes: real ids, the pad columns, and the sentinels a padded subset
    carries — ``n_features`` (a pad column, bin 0) and ``d_pad`` itself (past
    every column: the contraction reads 0, the element gather clips it to the
    last column, as the byte gathers did)."""
    bins, d = _bins(d_pad, seed=1)
    n = bins.shape[0]
    rng = np.random.default_rng(d_pad)
    idx = rng.integers(0, d_pad, (n, 9)).astype(np.int32)
    idx[:, 0], idx[:, 1], idx[:, 2] = d, d_pad, d_pad - 1
    packed = tk._pack_bins(jnp.asarray(bins))
    plain = np.take_along_axis(bins, np.clip(idx, 0, d_pad - 1), axis=1).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(tk._contract_gather(packed, jnp.asarray(idx))), np.where(idx < d_pad, plain, 0))
    np.testing.assert_array_equal(np.asarray(tk._bins_at(packed, None, jnp.asarray(idx))), plain)
    rows = rng.permutation(n)[:20].astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(tk._bins_at(packed, jnp.asarray(rows), jnp.asarray(idx[rows, 3]))), plain[rows, 3]
    )
    np.testing.assert_array_equal(
        np.asarray(tk._bins_at(packed, jnp.asarray(rows)[:, None], jnp.asarray(idx[rows]))), plain[rows]
    )


@pytest.mark.parametrize("path", ["xla", "pallas_row_major", "pallas_rows_minor"])
@pytest.mark.parametrize("cols,d_pad", [(128, 128), (200, 256), (1100, 1536), (3000, 3072)])
def test_binize_writes_the_words(cols, d_pad, path, monkeypatch):
    """The fit's ``binize`` gives the packed bins itself — XLA's pass packs
    its counts in the one program, the kernel ORs four byte planes' counts
    into each word it writes — equal to the packing of the byte bins it gives
    a transform batch, bit for bit, at 32, 64, 384 and 768 words a row."""
    rng = np.random.default_rng(cols)
    X = (rng.standard_normal((1000, cols)) * rng.uniform(0.1, 10.0, cols)).astype(np.float32)
    X[::7, 3] = np.nan
    edges = tk.make_bin_edges(X, 128)
    want = np.asarray(tk._pack_bins(tk.binize(jnp.asarray(X), jnp.asarray(edges), d_pad=d_pad)))
    if path == "xla":
        plan = tk.binize_plan(X.shape, jnp.float32, edges.shape[1], d_pad, packed=True)
        assert plan.kernel == "xla" and plan.packed
    else:
        monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
        assert rfp.rf_binize_declined(1000, cols, 127, jnp.float32, words=d_pad // 4) == ""
        rows, words, _ = rfp.binize_tile(1000, d_pad // 4)
        plan = tk.BinizePlan("pallas", f"{rows}x{words}", "", path == "pallas_rows_minor", True)
    got = tk.binize(jnp.asarray(X), jnp.asarray(edges), d_pad=d_pad, plan=plan)
    assert got.shape == (1000, d_pad // 4) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_binize_gate_wants_whole_tiles_of_words(monkeypatch):
    """The kernel writes a lane tile of words a step, or every word of a
    narrow row in whole sublane tiles (rows minor on a TPU): 200 words a row
    (past a lane tile, not a multiple of one) and 12 (not a multiple of 8)
    are neither. (The gate's other terms as interpret mode answers them.)"""
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    for words in (200, 12):
        assert rfp.rf_binize_declined(4096, 3000, 127, jnp.float32, words=words) == "words"
    for words in (64, 96, 768):
        assert rfp.rf_binize_declined(4096, 3000, 127, jnp.float32, words=words) == ""


def _case(kind):
    """The data the tables were recorded on: 1,024 x 1,100 (columns 700-1099
    repeat 0-399, so tied gains occur), 32 bins, 1,536 bin columns; a
    classifier drawing 34 features a node (the fused selection) or a
    regressor drawing 367 (the wide selection), depth 4."""
    rng = np.random.default_rng(44)
    n, d, nb, d_pad = 1024, 1100, 32, 1536
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 700:] = X[:, :400]
    common = dict(max_depth=4, n_bins=nb, n_features=d, min_samples_leaf=1, min_info_gain=0.0,
                  min_samples_split=2, bootstrap=True)
    if kind == "classifier":
        y = rng.integers(0, 2, n)
        X[:, :30] += np.where(y[:, None] > 0, 0.4, -0.4).astype(np.float32)
        stats = jax.nn.one_hot(jnp.asarray(y), 2, dtype=jnp.float32)
        cfg = tk.ForestConfig(n_stats=2, impurity="gini", k_features=34, **common)
    else:
        coef = np.array([3.0, -2.0, 1.5, 1.0, -1.0], np.float32)
        y = jnp.asarray((X[:, :5] @ coef + 0.3 * rng.normal(size=n)).astype(np.float32) * 100)
        stats = jnp.stack([jnp.ones(n), y, y * y], axis=1)
        cfg = tk.ForestConfig(n_stats=3, impurity="variance", k_features=367, **common)
    edges = tk.make_bin_edges(X, nb)
    plan = tk.binize_plan(X.shape, jnp.float32, edges.shape[1], d_pad, packed=True)
    return tk.binize(jnp.asarray(X), jnp.asarray(edges), d_pad=d_pad, plan=plan), stats, cfg


@pytest.mark.parametrize("builder", ["sequential", "batched"])
@pytest.mark.parametrize("kind,strategy", [("classifier", "pallas_sel"), ("regressor", "pallas_sel_wide")])
def test_forest_tables_are_the_parents(monkeypatch, kind, strategy, builder):
    """Two trees through each builder, the kernels in interpret mode, a level
    in chunks of one kernel block: every table (the gains too) is the one the
    parent commit (2d1e104, bins a byte each) grew on this data, to the bit
    (``tests/data/rf_tables_byte_bins.npz``)."""
    recorded = np.load(os.path.join(ROOT, "tests", "data", "rf_tables_byte_bins.npz"))
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 512)
    jax.clear_caches()
    try:
        bins, stats, cfg = _case(kind)
        n = bins.shape[0]
        assert bins.dtype == jnp.int32 and bins.shape[1] == 1536 // 4
        assert {tk.level_plan(n, 1536, lv, cfg).strategy for lv in range(cfg.max_depth)} == {strategy}
        keys = jax.random.split(jax.random.PRNGKey(4), 2)
        if builder == "sequential":
            build = jax.jit(tk._build_tree, static_argnums=(4,))
            trees = [build(bins, stats, jnp.ones(n), key, cfg) for key in keys]
            got = {t: np.stack([np.asarray(tree[t]) for tree in trees]) for t in TABLES}
        else:
            got = tk._build_trees_batched(bins, stats, jnp.ones(n), keys, cfg)
        for t in TABLES:
            assert np.array_equal(np.asarray(got[t]), recorded[f"{kind}_{builder}_{t}"]), (kind, builder, t)
        assert (np.asarray(got["feature"]) >= 0).sum() >= 20     # the trees split
    finally:
        jax.clear_caches()


def test_the_fits_spans_say_the_bins_are_packed(monkeypatch):
    """``bins_layout`` on ``forest.binize`` and on every ``forest.grow_group``
    of a fit reads ``packed32``; anything else says its dtype."""
    monkeypatch.setenv("TPUML_RF_APPLY", "bins")
    rng = np.random.default_rng(7)
    X = rng.standard_normal((1200, 21)).astype(np.float32)
    y = (X[:, 0] + X[:, 5] > 0).astype(np.float32)
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        model = RandomForestClassifier(numTrees=3, maxDepth=3, seed=2, num_workers=1).fit(
            DataFrame({"features": X, "label": y})
        )
        pred = np.asarray(model.transform(DataFrame({"features": X})).column("prediction"))
    finally:
        telemetry.remove_span_sink(sink)
    assert (pred == y).mean() > 0.7
    by = lambda name: [s["args"] for s in spans if s["name"] == name]  # noqa: E731
    assert [a["bins_layout"] for a in by("forest.binize")] == ["packed32"]
    assert by("forest.grow_group") and {a["bins_layout"] for a in by("forest.grow_group")} == {"packed32"}
    assert tk.bins_layout(jnp.zeros((2, 8), jnp.uint8)) == "dtype=uint8"
