"""The live frontier of a forest's level (``ops/tree_kernels._hist_compact``,
``_route_live``, ``_level_seg``; ``ops/rf_pallas`` live-block prefetch): a
level works on the rows that sit in one of its OPEN nodes with a positive
bootstrap weight, and on nothing else — and returns the tables it returned
when every level ran at full size. A node is closed when it is made where
its class counts, handed down by the split that makes it, already say it
cannot split (pure, or under ``min_samples_split``): its rows stay at the
parent and its statistics are the handed-down ones. Variance statistics
hand nothing down.

Pallas kernels run in interpret mode, which fills an output block the
kernel never wrote with NaN: a partial past the live blocks that were read
would poison the tables below.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.rf_pallas as rfp
import spark_rapids_ml_tpu.ops.tree_kernels as tk

TABLES = ("feature", "threshold_bin", "leaf_stats", "gain")
D, NB = 128, 32


def _data(labels: str, seed=0, n=600):
    """``separable``: two classes eight sigma apart on every column (the
    benchmark's data, more so), a tree is pure after one split and most
    levels are empty. ``noise``: the label is a coin, nodes stay impure and
    rows stay live to the last level."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D)).astype(np.float32)
    y = rng.integers(0, 2, n)
    if labels == "separable":
        X += np.where(y[:, None] > 0, 4.0, -4.0).astype(np.float32)
        yr = np.where(y > 0, 1.0, -1.0)
    else:
        yr = rng.normal(size=n)
    bins = tk._pack_bins(tk.binize(jnp.asarray(X), jnp.asarray(tk.make_bin_edges(X, NB)), d_pad=D))
    yr = jnp.asarray(yr, jnp.float32)
    counts = jax.nn.one_hot(jnp.asarray(y), 2, dtype=jnp.float32)
    return bins, {
        "gini": counts,
        "entropy": counts,
        "variance": jnp.stack([jnp.ones(n), yr, yr * yr], axis=1),
        "y": y,
    }


def _cfg(impurity, **kw):
    base = dict(
        max_depth=5, n_bins=NB, n_features=D, n_stats=3 if impurity == "variance" else 2, impurity=impurity,
        k_features=11, min_samples_leaf=1, min_info_gain=0.0, min_samples_split=2, bootstrap=True,
        hist_strategy="compact",
    )
    base.update(kw)
    return tk.ForestConfig(**base)


# jitted builders by (strategy, impurity, bootstrap): a program is traced once
# (Pallas in interpret mode compiles for ~10 s here) and every data set and
# row mask of the cases below runs through it
_PROGRAMS: dict = {}


@pytest.fixture(scope="module", autouse=True)
def _drop_programs():
    yield
    _PROGRAMS.clear()
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time


def _programs(monkeypatch, strategy, impurity, bootstrap):
    """(cfg, the live-frontier builder, the tree-batched builder, the
    scatter builder), traced under the gates that give ``strategy``."""
    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 512)   # 600 rows are two or three chunks
    if strategy == "pallas_sel":
        monkeypatch.setattr(tk, "_SEL_MIN_DPAD", 0)
    key = (strategy, impurity, bootstrap)
    if key not in _PROGRAMS:
        cfg = _cfg(impurity, bootstrap=bootstrap, hist_strategy="scatter" if strategy == "scatter" else "compact")
        scatter = cfg._replace(hist_strategy="scatter")
        _PROGRAMS[key] = (
            cfg,
            jax.jit(lambda b, s, v, k: tk._build_tree(b, s, v, k, cfg)),
            jax.jit(lambda b, s, v, ks: tk._build_trees_batched(b, s, v, ks, cfg)),
            jax.jit(lambda b, s, v, k: tk._build_tree(b, s, v, k, scatter)),
        )
    return _PROGRAMS[key]


def _weights(valid, key, cfg):
    """The tree's own draws: a row's bootstrap weight under its mask."""
    n = valid.shape[0]
    kb, _ = jax.random.split(key)
    if not cfg.bootstrap:
        return np.asarray(valid)
    logical = np.clip(np.cumsum(np.asarray(valid).astype(np.int64)) - 1, 0, n - 1)
    return np.asarray(jax.random.poisson(kb, 1.0, (n,)))[logical] * np.asarray(valid)


def _step_down(tree, bins, node, moving):
    """One level down the served splits: the rows that move, and where to."""
    feat, thr, b = np.asarray(tree["feature"]), np.asarray(tree["threshold_bin"]), np.asarray(tk._unpack_bins(bins))
    f = feat[node]
    moving = moving & (f >= 0)
    right = b[np.arange(len(node)), np.clip(f, 0, None)] > thr[node]
    return np.where(moving, 2 * node + 1 + right, node), moving


def _host_walk(bins, valid, key, cfg, tree, y=None):
    """Rows a level has to work on, from the served tables and the tree's own
    draws: a row of positive weight, walked down the served splits, reaches a
    level's node — and is live there unless the node was closed when it was
    made: with class labels ``y``, a child whose weighted class counts are
    pure or under ``min_samples_split`` (no ``y``: nothing closes). Per split
    level the live rows and the weight of the rows that reached it, and the
    nodes closed at birth."""
    n = bins.shape[0]
    w = _weights(valid, key, cfg)
    node, reached = np.zeros(n, np.int64), w > 0
    live = reached
    counts, weights, closed = [], [], 0
    for _ in range(cfg.max_depth):
        counts.append(int(live.sum()))
        weights.append(float(w[reached].sum()))
        node, reached = _step_down(tree, bins, node, reached)
        live = reached
        for child in (np.unique(node[reached]) if y is not None else ()):
            rows = reached & (node == child)
            held = np.bincount(y[rows], weights=w[rows], minlength=2)
            if (held > 0).sum() <= 1 or held.sum() < cfg.min_samples_split:
                closed += 1
                live = live & ~rows
    return counts, weights, closed


@pytest.mark.parametrize("masked", [False, True], ids=["all_rows", "masked_rows"])
@pytest.mark.parametrize("labels", ["separable", "noise"])
@pytest.mark.parametrize(
    "strategy,impurity,bootstrap",
    [
        ("pallas_sel", "gini", True),
        ("pallas_sel", "gini", False),
        ("pallas_sel", "variance", True),
        ("pallas", "gini", True),
        ("pallas", "variance", True),
        ("pallas", "variance", False),
        ("pallas_sel", "entropy", True),
        ("pallas", "entropy", False),
        ("scatter", "gini", True),
        ("scatter", "entropy", False),
        ("scatter", "variance", True),
    ],
)
def test_live_frontier_grows_the_full_size_forest(monkeypatch, strategy, impurity, bootstrap, labels, masked):
    """(a) The tables of the live-frontier path are the full-size path's bit
    for bit: the tree-batched builder's, whose row work stays at full size
    over the same ``_level_seg`` and which closes no node when it makes it
    (every table, the gains too). Integer statistics (gini, entropy) are
    exact under every grouping, so there the scatter strategy — no
    sub-blocks — grows the same forest: equal tables, the gains to the 4 ulp
    that XLA's CPU backend contracts two programs apart
    (``tests/test_tree_batch.py``). (b) ``live_rows`` is what the served
    tables and the tree's own draws say — the rows of positive weight in a
    node that was neither pure nor under ``min_samples_split`` when it was
    made — ``closed_at_birth`` is the walk's count of the others, and both
    are 0 below a tree's last split. Variance statistics hand nothing down:
    ``live_rows`` is the batched builder's, ``closed_at_birth`` 0. A forest
    on coin labels keeps its rows live to the last level and its tables."""
    cfg, live_fn, batched_fn, scatter_fn = _programs(monkeypatch, strategy, impurity, bootstrap)
    bins, stats = _data(labels)
    n = bins.shape[0]
    valid = jnp.ones((n,), jnp.float32)
    if masked:
        valid = valid.at[n - 70 :].set(0.0).at[3].set(0.0)
    assert {tk.level_plan(n, D, lv, cfg).strategy for lv in range(cfg.max_depth)} == {strategy}
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    both = batched_fn(bins, stats[impurity], valid, keys)
    assert not np.asarray(both["closed_at_birth"]).any()
    for i, key in enumerate(keys):
        got = live_fn(bins, stats[impurity], valid, key)
        assert np.isfinite(np.asarray(got["leaf_stats"])).all()
        for f in TABLES:
            np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(both[f][i]), err_msg=f)
        if impurity != "variance" and strategy != "scatter":
            want = scatter_fn(bins, stats[impurity], valid, key)
            for f in TABLES[:3]:
                np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(want[f]), err_msg=f)
            np.testing.assert_array_max_ulp(np.asarray(got["gain"]), np.asarray(want["gain"]), maxulp=4)
        live, full = np.asarray(got["live_rows"]), np.asarray(both["live_rows"][i])
        counts, weights, closed = _host_walk(
            bins, valid, key, cfg, got, None if impurity == "variance" else stats["y"]
        )
        assert live.tolist() == counts and int(got["closed_at_birth"]) == closed
        if impurity == "variance":
            assert live.tolist() == full.tolist() and closed == 0
        else:
            # every death on separable data is a death by purity: a level
            # works on what the level below it worked on before
            assert (live <= full).all() and live[0] == full[0]
            if labels == "separable":
                assert closed > 0 and live[1:].tolist() == full[2:].tolist() + [0]
        leaf = np.asarray(got["leaf_stats"])
        for lv in range(cfg.max_depth):
            level = leaf[(1 << lv) - 1 : (2 << lv) - 1]
            held = level[:, 0].sum() if impurity == "variance" else level.sum()
            assert held == weights[lv]                       # small integers: exact in f32
        split_levels = [lv for lv in range(cfg.max_depth) if (np.asarray(got["feature"])[(1 << lv) - 1 : (2 << lv) - 1] >= 0).any()]
        last = max(split_levels, default=-1)
        assert not live[last + 2 :].any()
        if labels == "noise":
            assert live[-1] > 0                              # rows stay live to the last level
            assert live.sum() >= 0.95 * full.sum()           # and hardly a node is closed when made
        else:
            assert last <= 1 and not live[3:].any()          # pure after a split or two: most levels are empty


def _plain_hist(bins, seg, sw, feats, n_nodes):
    """(F, n_nodes, nb, S) by a loop over the rows, in float64."""
    F, S = feats.shape[1], sw.shape[1]
    hist = np.zeros((F, n_nodes, NB, S))
    for r in np.flatnonzero(seg < n_nodes):
        for j, f in enumerate(feats[seg[r]]):
            hist[j, seg[r], bins[r, f]] += sw[r]
    return hist


@pytest.mark.parametrize(
    "live", [0, 1, 512, 513, 1100],
    ids=["no_live_row", "one_row", "one_chunk", "one_row_past_a_chunk", "every_row"],
)
@pytest.mark.parametrize("sel", [True, False], ids=["pallas_sel", "pallas"])
def test_level_follows_its_live_prefix(monkeypatch, sel, live):
    """(c) One level, ``live`` rows in two nodes and the rest dead: the
    histogram is the plain one whatever the prefix — empty, a row past a
    chunk boundary (a chunk is one 512-row block here), the whole level —
    and the routing moves the live rows of the node that split, and no
    other entry of ``node``; with one of its children closed, the rows bound
    for the other alone."""
    monkeypatch.setattr(tk, "_LIVE_CHUNK", 512)
    n, n_nodes, r_sub, n_pad, F = 1100, 2, 64, 1536, 16
    rng = np.random.default_rng(live)
    bins = rng.integers(0, NB, (n, D)).astype(np.uint8)
    seg = np.full(n, n_nodes, np.int32)
    rows = rng.permutation(n)[:live]
    # one sub-block's worth in node 0, the rest in node 1: P = 64 + the rest
    # rounded up to 64 (512 live rows fill one chunk, 513 spill into the next)
    seg[rows] = 1
    seg[rows[:64]] = 0
    sw = rng.integers(0, 3, (n, 2)).astype(np.float32)
    feats = np.stack([rng.permutation(D)[:F] for _ in range(n_nodes)]).astype(np.int32)
    jb, jf = jnp.asarray(bins), jnp.asarray(feats)
    hist, parent, frontier = tk._hist_compact(
        None if sel else (lambda r, nd: jb[r[:, None], jf[nd]]), jnp.asarray(seg), jnp.asarray(sw),
        n_nodes=n_nodes, n_slots=F, nb=NB, r_sub=r_sub, n_pad=n_pad, f_chunk=F, variance=False,
        full_bins=tk._pack_bins(jb) if sel else None, feats=jf if sel else None, interpret=True,
    )
    assert frontier.chunk == 512
    p_live = sum(-(-int((seg == g).sum()) // r_sub) * r_sub for g in range(n_nodes))
    assert int(frontier.trips) == -(-p_live // 512)
    want = _plain_hist(bins, seg, sw, feats, n_nodes)
    np.testing.assert_array_equal(np.asarray(hist), want)
    np.testing.assert_array_equal(np.asarray(parent), want[0].sum(axis=1))
    got_rows = np.asarray(frontier.rows)
    assert sorted(got_rows[got_rows < n].tolist()) == sorted(np.flatnonzero(seg < n_nodes).tolist())
    # node 1 splits on its first feature at bin 15, node 0 does not
    offset = 7
    node = np.where(seg < n_nodes, offset + seg, 99).astype(np.int32)
    route = lambda opens: np.asarray(tk._route_live(  # noqa: E731
        jnp.asarray(node), frontier, lambda r, f: jb[r, f].astype(jnp.int32),
        jnp.asarray(opens), jf[:, 0], jnp.asarray([15, 15], jnp.int32), offset=offset,
    ))
    moved = seg == 1
    right = bins[np.arange(n), feats[1, 0]] > 15
    child = 2 * (offset + 1) + 1 + right
    np.testing.assert_array_equal(route([[False, False], [True, True]]), np.where(moved, child, node))
    # its right child closed when made: the rows bound for it stay put
    np.testing.assert_array_equal(route([[False, False], [True, False]]), np.where(moved & ~right, child, node))


@pytest.mark.parametrize("live", [0, 1, 3])
def test_kernels_skip_blocks_past_the_live_count(live):
    """(d) Both kernels under a live-block count of 0, 1 and every block: the
    partials of the live blocks are the unskipped kernel's, bit for bit."""
    R, r_sub, S, k = rfp.BLOCK_ROWS, 64, 2, 11
    n = 3 * R
    rng = np.random.default_rng(live)
    swT = jnp.asarray(rng.integers(0, 3, (S, n)), jnp.float32)
    count = jnp.asarray([live], jnp.int32)
    kept = live * (R // r_sub)
    bq = tk._pack_bins(jnp.asarray(rng.integers(0, NB, (n, D)), jnp.uint8))
    fq = jnp.asarray(rng.integers(0, D, (n // r_sub, k)), jnp.int32)
    sel = lambda *a: rfp.subblock_hist_sel(bq, fq, swT, *a, n_bins=NB, r_sub=r_sub, interpret=True)
    np.testing.assert_array_equal(np.asarray(sel(count))[:kept], np.asarray(sel())[:kept])
    binq = jnp.asarray(rng.integers(0, NB, (n, 16)), jnp.int32)
    pre = lambda *a: rfp.subblock_hist(binq, swT, *a, n_bins=NB, r_sub=r_sub, interpret=True, transposed_sw=True)
    np.testing.assert_array_equal(np.asarray(pre(count))[:kept], np.asarray(pre())[:kept])


@pytest.mark.parametrize("impurity", ["gini", "entropy"])
@pytest.mark.parametrize("depth", [0, 1, 6], ids=["root_only", "depth_1", "split_to_the_last_level"])
def test_last_level_is_handed_down(impurity, depth):
    """(e) Where statistics are handed down the leaf level runs no
    ``segment_sum`` over the rows: its ``leaf_stats`` are its parents' left
    and right sums — the batched builder's ``segment_sum``, and the weighted
    class counts of the rows a walk down the served splits brings there."""
    bins, stats = _data("noise")
    n = bins.shape[0]
    valid = jnp.ones((n,), jnp.float32).at[::7].set(0.0)
    cfg = _cfg(impurity, max_depth=depth, hist_strategy="scatter")
    key = jax.random.PRNGKey(5)
    got = tk._build_tree(bins, stats[impurity], valid, key, cfg)
    want = tk._build_trees_batched(bins, stats[impurity], valid, key[None], cfg)
    for f in TABLES:
        np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(want[f][0]), err_msg=f)
    w = _weights(valid, key, cfg)
    node, moving = np.zeros(n, np.int64), w > 0
    for _ in range(depth):
        node, moving = _step_down(got, bins, node, moving)
    last = np.zeros((1 << depth, 2))
    np.add.at(last, (node[moving] - ((1 << depth) - 1), stats["y"][moving]), w[moving])
    np.testing.assert_array_equal(np.asarray(got["leaf_stats"])[(1 << depth) - 1 :], last)
    assert last.sum() > 0                                    # the walk came down to the last level


@pytest.mark.parametrize("builder", ["per_tree", "batched"])
def test_a_pure_node_does_not_split_on_rounding_noise(monkeypatch, builder):
    """A TPU's float32 division is not exact: c / c reads 1 - 6e-8 for some
    c, a pure node's gini 1e-7, and its "gain" cleared the 1e-9 floor — the
    chip split pure nodes into children of the same one class, level after
    level (PERF.md section 6, PR 38). The CPU divides exactly, so the noise
    is put in by hand here: with it neither builder splits a node that holds
    one class (``_can_split``), and the two still grow the same tables."""
    real = tk._impurity
    monkeypatch.setattr(
        tk, "_impurity",
        lambda stats, impurity: real(stats, impurity) + 1.2e-7 * (tk._count(stats, impurity) % 2),
    )
    bins, stats = _data("separable")
    valid = jnp.ones((bins.shape[0],), jnp.float32)
    cfg = _cfg("gini", hist_strategy="scatter")
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    both = tk._build_trees_batched(bins, stats["gini"], valid, keys, cfg)
    for i, key in enumerate(keys):
        got = tk._build_tree(bins, stats["gini"], valid, key, cfg) if builder == "per_tree" else {f: both[f][i] for f in TABLES}
        split = np.asarray(got["feature"]) >= 0
        assert split.any() and ((np.asarray(got["leaf_stats"])[split] > 0).sum(axis=1) == 2).all()
        for f in TABLES:
            np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(both[f][i]), err_msg=f)


def test_return_rows_keeps_zero_weight_rows_live():
    """The liveness rule reads what the caller asked for: a caller that wants
    every row's final node (GBT's rounds) gets it for a row of weight 0."""
    bins, stats = _data("separable")
    n = bins.shape[0]
    w = jnp.ones((n,), jnp.float32).at[::3].set(0.0)
    cfg = _cfg("gini", hist_strategy="scatter", bootstrap=False, k_features=D)
    kf = jax.random.split(jax.random.PRNGKey(2), 1)
    sw = (stats["gini"] * w[:, None])[None]
    walked = tk._grow_trees_batched(bins, sw, kf, cfg, return_rows=True)
    counted = tk._grow_trees_batched(bins, sw, kf, cfg)
    for f in TABLES:
        np.testing.assert_array_equal(np.asarray(walked[f]), np.asarray(counted[f]))
    assert int(walked["live_rows"][0, 0]) == n and int(counted["live_rows"][0, 0]) == int((w > 0).sum())
    assert (np.asarray(walked["node"])[0, ::3] > 0).all()    # the root split, and they went down with it
