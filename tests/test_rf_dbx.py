"""``rf_dbx``: the estimator through its normal path against the benchmark's
plain reference (``chipbench/references/rf_dbx.py``) at a small size — sound
forests are correct with every class count exact, the bf16 control and each
fault of the reference's own fit are not; a tree count that is no multiple of
the dispatch group still builds one program size; the tree batch counts what
the fused-selection kernel keeps; the device sketch's runs give even bins on
a sorted frame."""
import copy
import json
import os

import numpy as np
import pytest

import jax

from chipbench.data import gen_data
from chipbench.references import rf_dbx as ref
from chipbench.traffic import closed_loop
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.ops import tree_kernels as tk
from spark_rapids_ml_tpu.runtime import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = {"steps": ["fit", "transform"]}


def _config(cols, trees=4, depth=6, bins=32):
    with open(os.path.join(ROOT, "chipbench", "configs", "rf_dbx.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["cols"] = cols
    config["estimator"]["params"].update(numTrees=trees, maxDepth=depth, maxBins=bins)
    return config


def _ok(config, numbers):
    return {name: value <= config["limits"][name] for name, value in numbers}


@pytest.fixture(scope="module")
def frame():
    return gen_data.make(7, 4096, 300, {"kind": "classification"})


@pytest.mark.parametrize("rows,cols", [(4096, 300), (2048, 1100)], ids=["4096x300", "2048x1100_lane_multiple_bins"])
def test_sound_forest_is_correct_with_exact_counts(rows, cols):
    config = _config(cols)
    columns = gen_data.make(11, rows, cols, {"kind": "classification"})
    runner = closed_loop.Runner(config, MIX, columns, RandomForestClassifier, 1)
    numbers = ref.check(config, columns, [runner.run_job(), runner.run_job()])
    assert all(_ok(config, numbers).values()), numbers
    got = dict(numbers)
    assert got["count_err"] == 0.0 and got["repeat_err"] == 0.0 and got["struct_err"] == 0.0


@pytest.mark.parametrize(
    "fault,caught_by",
    [
        ({"control": True}, ("count_err", "out_err")),
        ({"fit_rows": 2048}, ("count_err",)),
        ({"bootstrap": False}, ("count_err",)),
        ({"cut_depth": 5}, ("split_excess",)),
        ({"runner_up": True}, ("split_excess",)),
        ({"alter_row": 17}, ("out_err",)),
    ],
    ids=["bf16_control", "half_of_the_rows", "bootstrap_off", "cut_at_depth_5", "runner_up_split", "one_row_altered"],
)
def test_control_and_faults_are_not_correct(frame, fault, caught_by):
    config = _config(300)
    ok = _ok(config, ref.check(config, frame, [ref.reference_job(config, frame, **fault)]))
    assert not any(ok[name] for name in caught_by), ok


def test_the_reference_in_the_programs_place_is_correct(frame):
    config = _config(300)
    numbers = ref.check(config, frame, [ref.reference_job(config, frame)])
    assert all(_ok(config, numbers).values()), numbers


def test_tree_count_off_the_group_builds_one_program_size(frame):
    """11 trees: two dispatches of 8, one compiled size, the five that fill
    the last group dropped — and tree t still has the key split(key, 11)[t]."""
    config = _config(300, trees=11, depth=4)
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        before = tk.build_forest._cache_size()
        runner = closed_loop.Runner(config, MIX, frame, RandomForestClassifier, 1)
        job = runner.run_job()
        built = tk.build_forest._cache_size() - before
    finally:
        telemetry.remove_span_sink(sink)
    groups = [s["args"] for s in spans if s["name"] == "forest.grow_group"]
    assert [g["trees"] for g in groups] == [8, 8] and built == 1
    assert all("strategy" in g and "levels_declined" in g for g in groups)
    # the group's live rows (PR 36): a level works on the rows of positive
    # bootstrap weight that sit in one of its nodes — 63% of them at the root,
    # fewer below, and their share of trees x levels x rows in one number
    for g in groups:
        by_level = g["live_rows_by_level"]
        assert len(by_level) == 4 and 0.55 * 4096 < by_level[0] < 0.70 * 4096
        assert all(a >= b for a, b in zip(by_level, by_level[1:]))
        assert abs(g["live_share"] - sum(by_level) / (4 * 4096)) < 1e-9
    fetches = [s["args"] for s in spans if s["name"] == "forest.fetch_group"]
    assert [f["parent_id"] for f in fetches] == [g["span_id"] for g in groups]
    assert job["model"]["features"].shape[0] == 11
    numbers = ref.check(config, frame, [job])
    assert all(_ok(config, numbers).values()), numbers


def test_closed_at_birth_is_on_the_grow_group_span(frame, monkeypatch):
    """The per-tree builder (the one the benchmark's shape takes: a tree batch
    of 1) closes a node when it makes it, and says how many on its span: the
    children of the served splits whose class counts are pure or under
    min_samples_split = 2, the group's mean a tree. Their counts are handed
    down, their rows left the levels' work — and every node's count is still
    the reference's."""
    monkeypatch.setenv("TPUML_RF_TREE_BATCH", "off")
    config = _config(300, trees=4, depth=4)
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        job = closed_loop.Runner(config, MIX, frame, RandomForestClassifier, 1).run_job()
    finally:
        telemetry.remove_span_sink(sink)
    (grow,) = [s["args"] for s in spans if s["name"] == "forest.grow_group"]
    assert grow["tree_batch"] == 1 and grow["trees"] == 4
    feat, leaf = job["model"]["features"], job["model"]["leaf_stats"]
    made = np.repeat(feat[:, :15] >= 0, 2, axis=1)             # a split at heap slot i makes slots 2i+1, 2i+2
    held = leaf[:, 1:31][made]
    shut = ((held > 0).sum(axis=1) <= 1) | (held.sum(axis=1) < 2)
    assert grow["closed_at_birth"] == shut.sum() / 4 > 0
    numbers = dict(ref.check(config, frame, [job]))
    assert numbers["count_err"] == 0.0 and numbers["struct_err"] == 0.0, numbers


@pytest.mark.parametrize("trees,group", [(1, 1), (8, 8), (16, 8), (50, 5), (20, 5), (53, 8), (12, 6)])
def test_dispatch_group_is_one_size(trees, group):
    from spark_rapids_ml_tpu.models.tree import _dispatch_group

    assert _dispatch_group(trees) == group


def test_tree_batch_counts_the_fused_kernels_residents(monkeypatch):
    """At rf_dbx's shape the fused-selection kernel keeps a node-sorted copy
    of the full bins rows (1.6 GB) and its partials (0.5 GB) a tree: eight
    trees a batch would ask for 17 GB, so the budget admits one; the plan
    says every level takes the kernel with X counted, and why not once the
    residents pass the budget."""
    from spark_rapids_ml_tpu.ops import linalg

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(linalg, "probe_pallas_lowering", lambda cache, key, fn, name: True)
    cfg = tk.ForestConfig(max_depth=13, n_bins=128, n_features=3000, n_stats=2, impurity="gini", k_features=55,
                          min_samples_leaf=1, min_info_gain=0.0, min_samples_split=2, bootstrap=True, held_bytes=6_000_000_000)
    assert tk.resolve_tree_batch(8, cfg, 500_000, 3072) == 1
    strategies, declined = tk.plan_levels(500_000, 3072, cfg)
    assert strategies == ",".join(["pallas_sel"] * 13) and declined == {}
    strategies, declined = tk.plan_levels(500_000, 3072, cfg._replace(held_bytes=12_000_000_000))
    assert strategies == ",".join(["pallas"] * 13) and len(declined) == 13 and all("hbm" in why for why in declined.values())


def test_sketch_of_a_sorted_frame_gives_even_bins():
    """150,000 rows sorted by their first column: the sketch's 1024 runs of
    128 consecutive rows still put the fullest bin of that column under the
    configuration's ``bin_skew`` limit; 64 runs of 2048 would not."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((150_000, 4)).astype(np.float32)
    X = X[np.argsort(X[:, 0])]
    edges, finite = tk.quantile_edges(jax.numpy.asarray(X), jax.numpy.ones(len(X), np.float32), n_bins=128)
    assert bool(finite)
    edges = np.asarray(edges)
    bins = (X[:, :, None] >= edges[None, :, :]).sum(axis=2)
    fullest = max(np.bincount(bins[:, c], minlength=128).max() for c in range(4)) * 128 / len(X)
    assert fullest <= _config(4)["limits"]["bin_skew"], fullest


def test_sketch_matches_numpy_quantiles_and_skips_masked_rows():
    rng = np.random.default_rng(5)
    X = rng.standard_normal((1000, 7)).astype(np.float32)
    mask = np.ones(1000, np.float32)
    mask[900:] = 0.0
    X[950] = np.nan                              # a padding row may hold anything
    edges, finite = tk.quantile_edges(jax.numpy.asarray(X), jax.numpy.asarray(mask), n_bins=32)
    want = np.quantile(X[:900].astype(np.float64), np.linspace(0, 1, 33)[1:-1], axis=0).T
    assert bool(finite) and np.abs(np.asarray(edges) - want).max() < 1e-5
    X[3, 2] = np.inf
    assert not bool(tk.quantile_edges(jax.numpy.asarray(X), jax.numpy.asarray(mask), n_bins=32)[1])
