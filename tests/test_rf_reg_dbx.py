"""``rf_reg_dbx``: the regression forest through its normal path against the
benchmark's plain reference (``chipbench/references/rf_reg_dbx.py``) at a
small size — sound forests are correct with every weighted count exact, on
the CPU's scatter path and through the WIDE fused selection
(``rf_pallas.subblock_hist_sel_wide``, interpret mode) at a subset whose
one-hot is past the fused kernel's cap; both controls and each fault of the
reference's own fit are not; the plan of the cell's own shape names its
strategy and why the fused selection was not taken; the grow group's span
says what the histogram read and how wide the selection's windows were; the
wide kernel's sums are float64's in the draw's slot order; and a
classifier's and a regressor's forest are still the parent's, table for
table."""
import copy
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.rf_pallas as rfp
from chipbench.data import gen_data
from chipbench.references import rf_reg_dbx as ref
from chipbench.traffic import closed_loop
from spark_rapids_ml_tpu.ops import linalg, tree_kernels as tk
from spark_rapids_ml_tpu.regression import RandomForestRegressor
from spark_rapids_ml_tpu.runtime import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = {"steps": ["fit", "transform"]}


_BUILD_TREE = jax.jit(tk._build_tree, static_argnums=(4,))


def _config(cols, trees=4, depth=6, bins=32):
    with open(os.path.join(ROOT, "chipbench", "configs", "rf_reg_dbx.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["cols"] = cols
    config["estimator"]["params"].update(numTrees=trees, maxDepth=depth, maxBins=bins)
    return config


def _ok(config, numbers):
    return {name: value <= config["limits"][name] for name, value in numbers}


@pytest.fixture(scope="module")
def frame():
    return gen_data.make(7, 4096, 300, {"kind": "regression"})


@pytest.fixture
def wide(monkeypatch):
    """The gates as a TPU answers them, the kernels in interpret mode, a level
    in chunks of two kernel blocks."""
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 1024)
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time
    yield
    jax.clear_caches()


def _grow_spans(config, columns):
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        runner = closed_loop.Runner(config, MIX, columns, RandomForestRegressor, 1)
        jobs = [runner.run_job(), runner.run_job()]
    finally:
        telemetry.remove_span_sink(sink)
    return jobs, [s["args"] for s in spans if s["name"] == "forest.grow_group"]


def test_sound_forest_is_correct_with_exact_counts_on_the_scatter_path(frame):
    config = _config(300)
    jobs, groups = _grow_spans(config, frame)
    numbers = ref.check(config, frame, jobs)
    assert all(_ok(config, numbers).values()), numbers
    got = dict(numbers)
    assert got["count_err"] == 0.0 and got["repeat_err"] == 0.0 and got["struct_err"] == 0.0
    # 100 of 300 features a node in 128 slots; the CPU takes XLA's scatter: no kernel call
    assert all(g["hist_cols"] == 128 and g["hist_calls"] == 0 for g in groups) and groups
    assert all(g["sel_cols"] == 0 for g in groups)


def test_sound_forest_through_the_wide_fused_selection(wide):
    """2,048 x 1,100, 32 bins: 367 features a node in 512 slots, a one-hot
    16,384 lanes wide — past the fused selection's 8,192 — so every level
    takes the selection tiled over slots, the route ``rf_reg_dbx.job`` runs."""
    config = _config(1100, trees=2, depth=5)
    columns = gen_data.make(11, 2048, 1100, {"kind": "regression"})
    jobs, groups = _grow_spans(config, columns)
    assert groups and all(g["strategy"] == ",".join(["pallas_sel_wide"] * 5) for g in groups)
    assert all(g["hist_cols"] == 512 and g["hist_calls"] == 1 and g["levels_declined"] == 5 for g in groups)
    assert all("width<=8192" in g["declined"] for g in groups)
    numbers = ref.check(config, columns, jobs)
    assert all(_ok(config, numbers).values()), numbers
    got = dict(numbers)
    assert got["count_err"] == 0.0 and got["repeat_err"] == 0.0 and got["struct_err"] == 0.0
    # the bootstrap's 63.2% on every level: a continuous target never goes pure
    for g in groups:
        assert all(0.55 * 2048 < rows < 0.70 * 2048 for rows in g["live_rows_by_level"])
    # a node's 367 ids ascending: a slot tile's 128 read ~3 of the 9 column tiles
    assert all(128 <= g["sel_cols"] < 0.5 * 1152 for g in groups)


@pytest.mark.parametrize(
    "fault,caught_by",
    [
        ({"control": True}, ("count_err",)),
        ({"stat_control": True}, ("mean_err", "var_err")),
        ({"fit_rows": 2048}, ("count_err",)),
        ({"bootstrap": False}, ("count_err",)),
        ({"cut_depth": 4}, ("split_excess",)),
        ({"runner_up": True}, ("split_excess",)),
        ({"alter_row": 17}, ("out_err",)),
    ],
    ids=["bf16_frame_control", "bf16_statistics_control", "half_of_the_rows", "bootstrap_off", "cut_at_depth_4",
         "runner_up_split", "one_row_altered"],
)
def test_controls_and_faults_are_not_correct(frame, fault, caught_by):
    config = _config(300, trees=2)
    ok = _ok(config, ref.check(config, frame, [ref.reference_job(config, frame, **fault)]))
    assert not any(ok[name] for name in caught_by), ok


def test_the_reference_in_the_programs_place_is_correct(frame):
    config = _config(300, trees=2)
    numbers = ref.check(config, frame, [ref.reference_job(config, frame)])
    assert all(_ok(config, numbers).values()), numbers


def test_the_cells_plan_names_its_strategy_and_why(monkeypatch):
    """``level_plan(500000, 3072, level, cfg)`` for the cell's own shape, the
    gates steered as a TPU answers them: the fused selection declines on its
    one-hot width and its VMEM, the selection tiled over slots is taken, one
    kernel call a chunk over 1024 slots; the tree batch stays 1 (the per-tree
    builder), and a budget the residents do not fit says ``hbm``; at 256
    bins, or twice the slots, every level still takes the tiled selection."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(linalg, "probe_pallas_lowering", lambda cache, key, fn, name: True)
    monkeypatch.delenv("TPUML_RF_TREE_BATCH", raising=False)
    cfg = tk.ForestConfig(
        max_depth=6, n_bins=128, n_features=3000, n_stats=3, impurity="variance", k_features=1000, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True, held_bytes=500_000 * 3000 * 4,
    )
    for level in range(6):
        plan = tk.level_plan(500_000, 3072, level, cfg)
        assert plan.strategy == "pallas_sel_wide" and plan.declined == "sel:width<=8192,vmem"
        assert (plan.r_sub, plan.hist_cols, plan.hist_calls) == (rfp.WIDE_BLOCK_ROWS, 1024, 1)
    assert tk.plan_reads(500_000, 3072, cfg) == (1024, 1)
    # the kernel's VMEM does not grow with the slots: maxBins 256, or twice the slots, still takes it
    for wider in (cfg._replace(n_bins=256), cfg._replace(k_features=2000)):
        assert {tk.level_plan(500_000, 3072, lv, wider).strategy for lv in range(6)} == {"pallas_sel_wide"}
    assert tk.resolve_tree_batch(5, cfg, 500_000, 3072) == 1
    tight = tk.level_plan(500_000, 3072, 5, cfg._replace(held_bytes=12_000_000_000))
    assert tight.strategy == "pallas" and "wide:hbm" in tight.declined and "hbm" in tight.declined.split(";")[0]
    # the classifier's cell keeps its own kernel, one call a chunk over its 64 slots
    gini = cfg._replace(max_depth=13, n_stats=2, impurity="gini", k_features=55)
    assert tk.plan_levels(500_000, 3072, gini) == (",".join(["pallas_sel"] * 13), {})
    assert tk.plan_reads(500_000, 3072, gini) == (64, 1)


def test_the_statistics_split_is_exact():
    rng = np.random.default_rng(3)
    sw = np.concatenate([rng.normal(size=(2, 4096)) * 10.0 ** rng.integers(-3, 8, (2, 4096)), rng.poisson(1.0, (1, 4096))]).astype(np.float32)
    parts = np.asarray(rfp.split_f32_exact(jnp.asarray(sw)).astype(jnp.float32)).astype(np.float64)
    assert parts.shape == (rfp.WIDE_STAT_ROWS, 4096) and not parts[9:].any()
    assert np.array_equal(parts[:9].reshape(3, 3, 4096).sum(axis=0), sw.astype(np.float64))


def test_a_classifiers_forest_is_the_parents(monkeypatch):
    """One tree of a classifier (1,500 x 1,100, 34 of 1100 features a node, 32
    bins, depth 5, a fixed key) through the fused selection in interpret mode
    and through the CPU's scatter: the tables recorded from the parent commit
    (17e2e49, ``tests/data/rf_classifier_tables_parent_pr39.npz``)."""
    recorded = np.load(os.path.join(ROOT, "tests", "data", "rf_classifier_tables_parent_pr39.npz"))
    rng = np.random.default_rng(5)
    n, d, nb, d_pad = 1500, 1100, 32, 1152
    y = rng.integers(0, 2, n)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, :40] += np.where(y[:, None] > 0, 0.35, -0.35).astype(np.float32)
    bins = tk._pack_bins(tk.binize(jnp.asarray(X), jnp.asarray(tk.make_bin_edges(X, nb)), d_pad=d_pad))
    stats = jax.nn.one_hot(jnp.asarray(y), 2, dtype=jnp.float32)
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 1024)
    key = jax.random.PRNGKey(9)

    def tree_of(strategy):
        cfg = tk.ForestConfig(
            max_depth=5, n_bins=nb, n_features=d, n_stats=2, impurity="gini", k_features=34, min_samples_leaf=1,
            min_info_gain=0.0, min_samples_split=2, bootstrap=True, hist_strategy=strategy,
        )
        plans = {tk.level_plan(n, d_pad, lv, cfg).strategy for lv in range(5)}
        return plans, _BUILD_TREE(bins, stats, jnp.ones(n), key, cfg)

    try:
        for name, interpret, strategy in (("pallas", True, "auto"), ("scatter", False, "scatter")):
            monkeypatch.setattr(rfp, "FORCE_INTERPRET", interpret)
            plans, tree = tree_of(strategy)
            assert plans == {"pallas_sel" if interpret else "scatter"}
            for table in ("feature", "threshold_bin", "leaf_stats"):
                assert np.array_equal(np.asarray(tree[table]), recorded[f"{name}_{table}"]), (name, table)
            np.testing.assert_allclose(np.asarray(tree["gain"]), recorded[f"{name}_gain"], rtol=1e-6, atol=1e-9)
            jax.clear_caches()
    finally:
        jax.clear_caches()


def _wide_case():
    """Three calls on one level's running sums, 4 nodes of 256 slots over
    1536 columns (1100 of them the frame's), 32 bins, 512-row blocks: call 1 holds nodes 0, 0, 1, 2;
    call 2 nodes 2, 2, 3 and a dead block (3 live), so node 2 spans both
    calls; call 3 no live block at all. Each node's draw of ids; node 1's
    last 40 slots are the sentinel 1100, and node 3 drew 0 and 1099 first."""
    rng = np.random.default_rng(21)
    d, d_pad, k, nb = 1100, 1536, 256, 32
    draw = np.stack([rng.permutation(d)[:k] for _ in range(4)]).astype(np.int32)
    draw[1, -40:] = d
    draw[3, :2] = [0, d - 1]
    bins = rng.integers(0, nb, (8 * 512, d_pad)).astype(np.float32)
    bins[:, d:] = 0
    sw = (rng.normal(size=(3, 8 * 512)) * 10.0 ** rng.integers(-2, 5, (3, 8 * 512))).astype(np.float32)
    sw[:, 7 * 512 :] = 0.0
    calls = [([0, 0, 1, 2], slice(0, 2048), 4), ([2, 2, 3, 3], slice(2048, 4096), 3), ([0, 0, 0, 0], slice(0, 2048), 0)]
    return draw, bins, sw, calls, nb


@pytest.mark.parametrize("order", ["ascending", "drawn", "ascending_but_node_3"])
def test_the_wide_kernels_sums_against_float64(order):
    """The kernel in interpret mode — several nodes in a call, a node across
    two calls, a call with no live block, sentinel slots — against float64
    on the host, in the draw's slot order. ``ascending``: each node's ids
    sorted, as the level hands them (narrow windows), and ``wide_hist_nodes``
    puts the slots back in the draw's order; ``drawn``: the ids as drawn (a
    tile's window about the whole width); ``ascending_but_node_3``: node 3's
    drawn ids, whose first tile spans 0 ... 1099, beside sorted ones."""
    draw, bins, sw, calls, nb = _wide_case()
    k, d_pad = draw.shape[1], bins.shape[1]
    fed = np.sort(draw, axis=1) if order != "drawn" else draw.copy()
    if order == "ascending_but_node_3":
        fed[3] = draw[3]
    acc = jnp.zeros((4, rfp.WIDE_STAT_ROWS, k * nb), jnp.float32)
    for nodes, rows, live in calls:
        acc = rfp.subblock_hist_sel_wide(
            tk._pack_bins(jnp.asarray(bins[rows], jnp.uint8)), jnp.asarray(fed[nodes]),
            rfp.split_f32_exact(jnp.asarray(sw[:, rows])),
            jnp.asarray(nodes, jnp.int32), jnp.asarray([live], jnp.int32), acc, n_bins=nb, interpret=True,
        )
    got = np.asarray(rfp.wide_hist_nodes(acc, 3, k, nb, draw=None if order == "drawn" else jnp.asarray(draw)), np.float64)
    if order == "ascending_but_node_3":   # node 3 went in as drawn: its slots are in the draw's order already
        got[3] = np.asarray(rfp.wide_hist_nodes(acc, 3, k, nb), np.float64)[3]
    want = np.zeros_like(got)
    for nodes, rows, live in calls:
        for b in range(live):
            r = np.arange(rows.start + 512 * b, rows.start + 512 * (b + 1))
            for j, f in enumerate(draw[nodes[b]]):
                col = bins[r, min(f, d_pad - 1)].astype(np.int64)
                for s in range(3):
                    want[nodes[b], s, j] += np.bincount(col, weights=sw[s, r].astype(np.float64), minlength=nb)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6 * np.abs(want).max())
    lo, cnt = (np.asarray(a) for a in rfp.wide_windows(jnp.asarray(fed), d_pad))
    if order == "drawn":
        assert cnt.min() >= 8                       # a drawn tile's window: about the whole width
    else:
        assert cnt[:3].max() <= 6                   # a sorted tile's: narrow
    if order == "ascending_but_node_3":
        assert (lo[3, 0], cnt[3, 0]) == (0, 9)      # and the tile that spans every column


def test_sel_cols_on_the_per_tree_builders_span(wide, monkeypatch):
    """The per-tree builder (a tree batch of 1, the cell's) at the wide shape
    counts its windows too: a slot tile's selection read fewer than half of
    the 1152 columns it would read at full width."""
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "off")
    config = _config(1100, trees=1, depth=3)
    columns = gen_data.make(11, 2048, 1100, {"kind": "regression"})
    jobs, groups = _grow_spans(config, columns)
    assert [g["tree_batch"] for g in groups] == [1, 1]
    assert all(g["strategy"] == ",".join(["pallas_sel_wide"] * 3) and 128 <= g["sel_cols"] < 0.5 * 1152 for g in groups)


@pytest.mark.parametrize("builder", ["sequential", "batched"])
def test_a_regressors_forest_is_the_parents(monkeypatch, builder):
    """Two trees of a regressor (2,048 x 1,100, every column twice — columns
    550-1099 repeat 0-549, so a node that drew both twins of its best feature
    has two equal gains and the tie order decides — 367 features a node in
    512 slots, 32 bins, depth 5) through the wide selection in interpret mode,
    in chunks of two blocks: the tables recorded from the parent commit
    (7f8a1c5, ``tests/data/rf_regressor_tables_parent_pr40.npz``), to the
    bit. The kernel takes a node's ids ascending; their slots go back to the
    draw's order before the gain search, which breaks ties as before."""
    recorded = np.load(os.path.join(ROOT, "tests", "data", "rf_regressor_tables_parent_pr40.npz"))
    rng = np.random.default_rng(11)
    n, d, nb, d_pad = 2048, 1100, 32, 1536
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 550:] = X[:, :550]
    coef = np.array([3.0, -2.0, 1.5, 1.0, -1.0, 0.5], np.float32)
    y = jnp.asarray((X[:, :6] @ coef + 0.3 * rng.normal(size=n)).astype(np.float32) * 100)
    bins = tk._pack_bins(tk.binize(jnp.asarray(X), jnp.asarray(tk.make_bin_edges(X, nb)), d_pad=d_pad))
    stats = jnp.stack([jnp.ones(n), y, y * y], axis=1)
    cfg = tk.ForestConfig(
        max_depth=5, n_bins=nb, n_features=d, n_stats=3, impurity="variance", k_features=367, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True,
    )
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 1024)
    jax.clear_caches()
    try:
        assert {tk.level_plan(n, d_pad, lv, cfg).strategy for lv in range(5)} == {"pallas_sel_wide"}
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        tables = ("feature", "threshold_bin", "leaf_stats", "gain")
        if builder == "sequential":
            trees = [_BUILD_TREE(bins, stats, jnp.ones(n), key, cfg) for key in keys]
            got = {t: np.stack([np.asarray(tree[t]) for tree in trees]) for t in tables}
        else:
            got = tk._build_trees_batched(bins, stats, jnp.ones(n), keys, cfg)
        for t in tables:
            assert np.array_equal(np.asarray(got[t]), recorded[f"{builder}_{t}"]), (builder, t)
    finally:
        jax.clear_caches()
