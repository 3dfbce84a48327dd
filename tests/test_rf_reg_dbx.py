"""``rf_reg_dbx``: the regression forest through its normal path against the
benchmark's plain reference (``chipbench/references/rf_reg_dbx.py``) at a
small size — sound forests are correct with every weighted count exact, on
the CPU's scatter path and through the WIDE fused selection
(``rf_pallas.subblock_hist_sel_wide``, interpret mode) at a subset whose
one-hot is past the fused kernel's cap; both controls and each fault of the
reference's own fit are not; the plan of the cell's own shape names its
strategy and why the fused selection was not taken; the grow group's span
says what the histogram read; and a classifier's forest is still the
parent's, table for table."""
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
    builder), and a budget the residents do not fit says ``hbm``."""
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
    bins = tk.binize(jnp.asarray(X), jnp.asarray(tk.make_bin_edges(X, nb)), d_pad=d_pad)
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
