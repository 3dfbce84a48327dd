"""Plain reference of ``rf_reg_dbx``: a regression forest as Spark states it
(variance impurity, a third of the features a node), judged tree by tree.

As ``rf_dbx``'s reference (beside this file; its module text says why a forest
is VERIFIED along its own splits and not fitted again): the frame is binned by
the served ``bin_edges`` from the reference's own float32 copy, each checked
served tree is walked down its own splits with the copied draws, and at every
node that can split (two weighted rows or more: a continuous target never
goes pure) the histogram over the node's OWN sampled features is built on the
host — the weighted count in int64, the sums of w*y and w*y^2 in float64,
``numpy.bincount`` a feature slot — and asked whether the served split is the
admissible split of greatest variance gain there. The same walk taking its
own best split is a fit (:func:`reference_job`): the controls and the faults
are that fit put in the program's place.

Imports nothing of the program. What one forest reference shares with the
other is imported from ``rf_dbx.py``, not written twice: the copied draw
contract (tree keys, Poisson(1) weights, the uniforms a level ranks its
features by, the k largest with ties), the frame in blocks (binning, the
raw-space descent), the host's column gather, the reference's own edges, the
choice of the walked trees.

Two controls, each the nearest precision below what the configuration states:
``control`` rounds X to bfloat16 before it is binned and before the descent
(``rf_dbx``'s); ``stat_control`` rounds every row's w*y and w*y^2 to bfloat16
before they are summed — a histogram whose statistics went through ONE bf16
MXU pass, the precision the program's exact three-way split (or
``Precision.HIGHEST``) is there for.
"""

from __future__ import annotations

import math
import sys

import jax.numpy as jnp
import numpy as np

from . import rf_dbx as forest
from ._blocks import f64

NAMES = ("count_err", "mean_err", "var_err", "split_excess", "gain_err", "out_err", "bin_skew", "repeat_err", "struct_err")
_SLOT_CHUNK = 128      # feature slots whose bins are taken from a node's rows at once


def _log(*a) -> None:
    print("chipbench: reference:", *a, file=sys.stderr, flush=True)


def features_per_node(d: int) -> int:
    """Spark's ``featureSubsetStrategy=auto`` for a regressor: a third, rounded up."""
    return max(1, min(d, math.ceil(d / 3.0)))


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16, as float64."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32), np.float64)


def _variance(stats: np.ndarray) -> np.ndarray:
    """Impurity of (count, sum w*y, sum w*y^2) in float64; 0 where no row."""
    n = stats[..., 0]
    safe = np.where(n > 0, n, 1.0)
    mean = stats[..., 1] / safe
    return np.where(n > 0, np.maximum(stats[..., 2] / safe - mean * mean, 0.0), 0.0)


def _gains(hist: np.ndarray, parent: np.ndarray, valid: np.ndarray, min_leaf: float) -> np.ndarray:
    """Variance gain of every (feature slot, threshold bin) of ONE node:
    ``hist`` is (slots, bins, 3), a threshold bin b sends bins <= b left.
    -inf where a child would hold fewer than ``min_leaf`` weighted rows or
    the slot is none of the node's features."""
    left = np.cumsum(hist, axis=1)[:, :-1]
    right = parent[None, None, :] - left
    nl, nr = left[..., 0], right[..., 0]
    gain = _variance(parent) - (nl * _variance(left) + nr * _variance(right)) / max(parent[0], 1e-300)
    ok = (nl >= min_leaf) & (nr >= min_leaf) & valid[:, None]
    return np.where(ok, gain, -np.inf)


class Walk:
    """One tree over the binned frame: every row's weight and weighted
    statistics, its current node, and per level the statistics of every node
    and the gains of the nodes that can split."""

    def __init__(self, bins: np.ndarray, y: np.ndarray, w: np.ndarray, key, d: int, params: dict):
        self.bins, self.key, self.d = bins, key, d
        self.w = w.astype(np.int64)
        wy, wy2 = self.w * y, self.w * y * y
        if params.get("stat_control"):
            wy, wy2 = _bf16(wy), _bf16(wy2)
        self.stats = (self.w.astype(np.float64), wy, wy2)
        self.n_bins, self.k = int(params["n_bins"]), int(params["k"])
        self.min_leaf = float(params.get("min_leaf", 1))
        self.node = np.zeros(len(y), np.int64)
        self.live = self.w > 0

    def sums(self, level: int) -> np.ndarray:
        """(2**level, 3) float64: weighted count (a whole number), sum w*y, sum w*y^2."""
        off, nodes = (1 << level) - 1, 1 << level
        at = self.live & (self.node >= off)
        local = self.node[at] - off
        out = np.stack([np.bincount(local, weights=s[at], minlength=nodes) for s in self.stats], axis=1)
        out[:, 0] = np.rint(out[:, 0])
        return out

    def gains(self, level: int, sums: np.ndarray):
        """``(nodes, idx, gain)``: the level's nodes of two weighted rows or
        more, their sampled features (nodes, slots; -1 where a slot is none)
        and the gain of every (slot, threshold bin) of each."""
        off, nb = (1 << level) - 1, self.n_bins
        held = np.flatnonzero(sums[:, 0] >= 2)
        if not len(held):
            return held, np.zeros((0, 1), np.int32), np.zeros((0, 1, nb - 1))
        idx, valid = forest.sampled_features(forest.level_draws(self.key, level, self.d)[held], self.k)
        rows = np.flatnonzero(self.live & (self.node >= off))
        order = np.argsort(self.node[rows], kind="stable")
        rows = rows[order]
        bounds = np.searchsorted(self.node[rows], off + np.arange((1 << level) + 1))
        slots = idx.shape[1]
        gain = np.empty((len(held), slots, nb - 1))
        for i, g in enumerate(held):
            mine = rows[bounds[g]:bounds[g + 1]]
            whole = self.bins[mine]                                   # the node's rows, every column
            stats = [s[mine] for s in self.stats]
            hist = np.empty((slots, nb, 3))
            for lo in range(0, slots, _SLOT_CHUNK):
                cols = np.ascontiguousarray(whole[:, idx[i, lo:lo + _SLOT_CHUNK]].T)   # (slots, rows)
                for j, col in enumerate(cols):
                    for s in range(3):
                        hist[lo + j, :, s] = np.bincount(col, weights=stats[s], minlength=nb)
            gain[i] = _gains(hist, sums[g], valid[i], self.min_leaf)
        return held, np.where(valid, idx, -1), gain

    route = forest.Walk.route      # rows of a split node go to a child: bin > threshold bin goes right


def _tree_params(config: dict, d: int, stat_control: bool = False) -> dict:
    return {"n_bins": int(config["estimator"]["params"]["maxBins"]), "k": features_per_node(d), "min_leaf": 1,
            "stat_control": stat_control}


def verify_tree(bins, y, w, key, d, params, depth, feat, thr_bin, leaf_stats, served_gains) -> dict:
    """The served tree walked along its own splits. ``count_err``: largest
    difference of a node's weighted count to the served one. ``mean_err`` /
    ``var_err``: a node's served mean and variance (from its served float32
    sums) against float64, over the root's standard deviation / variance.
    ``split_excess``: largest (best admissible gain - gain of the served
    split) x the node's weight share / the root's variance; a served leaf is
    a split of gain 0. ``gain_err``: served gain against the reference's gain
    of the served split, over the root's variance."""
    walk = Walk(bins, y, w, key, d, params)
    out = dict.fromkeys(("count_err", "mean_err", "var_err", "split_excess", "gain_err"), 0.0)
    held_nodes, deepest, root_w, root_var = 0, -1, 1.0, 1.0
    for level in range(depth + 1):
        off, nodes = (1 << level) - 1, 1 << level
        sums, served = walk.sums(level), f64(leaf_stats[off:off + nodes])
        if level == 0:
            root_w, root_var = float(sums[0, 0]), max(float(_variance(sums)[0]), 1e-300)
        weight = sums[:, 0]
        out["count_err"] = max(out["count_err"], float(np.abs(weight - served[:, 0]).max()))
        has = weight > 0
        held_nodes += int(has.sum())
        if has.any():
            mean, smean = sums[has, 1] / weight[has], served[has, 1] / np.maximum(served[has, 0], 1e-300)
            out["mean_err"] = max(out["mean_err"], float(np.abs(mean - smean).max() / math.sqrt(root_var)))
            out["var_err"] = max(out["var_err"], float(np.abs(_variance(sums[has]) - _variance(served[has])).max() / root_var))
        if level > 0:
            parents = np.flatnonzero(feat[off - (nodes >> 1):off] >= 0)
            if (np.minimum(weight[2 * parents], weight[2 * parents + 1]) < walk.min_leaf).any():
                out["split_excess"] = float(np.finfo(np.float64).max)      # an inadmissible served split
        if level == depth or not has.any():
            break
        if ((feat[off:off + nodes] >= 0) & has).any():
            deepest = level
        held, idx, gain = walk.gains(level, sums)
        best = np.maximum(gain.reshape(len(held), -1).max(axis=1, initial=-np.inf), 0.0)   # a leaf is admissible, at gain 0
        f, b = feat[off + held], thr_bin[off + held]
        hit = idx == f[:, None]
        g_served = np.where(f < 0, 0.0, np.where(hit.any(axis=1), gain[np.arange(len(held)), hit.argmax(axis=1), np.clip(b, 0, gain.shape[2] - 1)], -np.inf))
        with np.errstate(invalid="ignore"):
            excess = (best - g_served) * (weight[held] / root_w) / root_var
        out["split_excess"] = max(out["split_excess"], float(np.nan_to_num(excess, nan=np.inf).max(initial=0.0)))
        lone = (feat[off:off + nodes] >= 0) & (weight < 2)              # a served split of a node that cannot split
        if lone.any():
            out["split_excess"] = float(np.finfo(np.float64).max)
        if (f >= 0).any():
            err = np.abs(f64(served_gains[off + held])[f >= 0] - g_served[f >= 0]) / root_var
            out["gain_err"] = max(out["gain_err"], float(np.nan_to_num(err, nan=np.inf, posinf=np.finfo(np.float64).max).max()))
        walk.route(level, feat[off:off + nodes], thr_bin[off:off + nodes])
    return dict(out, nodes=held_nodes, deepest_split=deepest)


def grow_tree(bins, y, w, key, d, params, depth, edges, cut_depth=None, runner_up=False) -> dict:
    """The same walk taking its own best split: a fit. ``cut_depth`` stops the
    growth there; ``runner_up`` gives the root the best split of its
    second-best feature."""
    M = (1 << (depth + 1)) - 1
    feat, thr_bin = np.full(M, -1, np.int32), np.zeros(M, np.int32)
    leaf, gains = np.zeros((M, 3), np.float32), np.zeros(M, np.float32)
    walk = Walk(bins, y, w, key, d, params)
    for level in range(depth + 1):
        off, nodes = (1 << level) - 1, 1 << level
        sums = walk.sums(level)
        leaf[off:off + nodes] = sums
        if level == depth or (cut_depth is not None and level >= cut_depth) or not sums[:, 0].any():
            if level < depth:
                walk.route(level, np.full(nodes, -1, np.int32), np.zeros(nodes, np.int32))
            continue
        held, idx, gain = walk.gains(level, sums)
        if len(held):
            flat = gain.reshape(len(held), -1)
            if runner_up and level == 0:
                per_slot = gain.max(axis=2)
                per_slot[0, per_slot[0].argmax()] = -np.inf
                flat = np.where((np.arange(gain.shape[1]) == per_slot[0].argmax())[None, :, None], gain, -np.inf).reshape(1, -1)
            pick = flat.argmax(axis=1)
            g = flat[np.arange(len(held)), pick]
            split = g > 0
            slot, b = pick // gain.shape[2], pick % gain.shape[2]
            feat[off + held] = np.where(split, idx[np.arange(len(held)), slot], -1)
            thr_bin[off + held] = np.where(split, b, 0)
            gains[off + held] = np.where(split, g, 0.0)
        walk.route(level, feat[off:off + nodes], thr_bin[off:off + nodes])
    thr = np.where(feat >= 0, edges[np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, edges.shape[1] - 1)], 0.0).astype(np.float32)
    return {"features": feat, "threshold_bins": thr_bin, "thresholds": thr, "leaf_stats": leaf, "gains": gains}


def _served(job: dict, d: int, n_bins: int, depth: int):
    """The served tables in ``rf_dbx``'s layout, with three statistics a node."""
    tables = forest._served(job, d, n_bins, depth)
    return tables if tables is not None and tables[3].shape[2] == 3 else None


def _struct_err(feat, thr_bin, thr, leaf, edges, d: int, n_bins: int, n_trees: int) -> float:
    """Faults of the layout, counted: a tree count that is not the
    configuration's, a feature outside [0, d), a split on the last level, a
    threshold bin outside [0, n_bins - 2], a threshold that is not the served
    edge of its bin, bin edges that fall, a weighted count that is no whole
    number or negative, a sum of squares below zero, a child that holds rows
    under a leaf."""
    split = feat >= 0
    inner = (feat.shape[1] - 1) // 2
    bad = int(feat.shape[0] != n_trees) + int((feat >= d).sum()) + int((split[:, inner:]).sum())
    bad += int(((thr_bin < 0) | (thr_bin > n_bins - 2))[split].sum())
    bad += int((thr[split] != edges[np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, n_bins - 2)][split]).sum())
    count = leaf[:, :, 0]
    bad += int((np.diff(edges, axis=1) < 0).sum()) + int((count != np.rint(count)).sum()) + int((count < 0).sum()) + int((leaf[:, :, 2] < 0).sum())
    parents = (np.arange(1, feat.shape[1]) - 1) // 2
    bad += int(((count > 0)[:, 1:] & ~split[:, parents]).sum())
    return float(bad)


def _predict(frame, feat, thr, leaf, n_trees: int) -> np.ndarray:
    """Mean over the trees of the leaf mean where the raw-space descent
    (x >= threshold goes right) ends, float64, all rows."""
    leaves = frame.leaves(feat, thr)
    means = f64(leaf[:, :, 1]) / np.maximum(f64(leaf[:, :, 0]), 1e-300)
    total = np.zeros(leaves.shape[1])
    for t in range(n_trees):
        total += means[t][leaves[t]]
    return total / n_trees


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    ``out_err``: widest gap of the served ``prediction`` to :func:`_predict`
    of the served forest, all rows and trees, over the label's standard
    deviation. ``count_err``, ``mean_err``, ``var_err``, ``split_excess``,
    ``gain_err``: :func:`verify_tree`, worst over ``trees_checked`` trees.
    ``bin_skew``, ``repeat_err``, ``struct_err``: as ``rf_dbx``'s. Jobs whose
    model and outputs are bit-identical are judged once."""
    X, y = columns["features"], f64(columns["label"])
    p = config["estimator"]["params"]
    n, d = X.shape
    depth, n_bins, n_trees = int(p["maxDepth"]), int(p["maxBins"]), int(p["numTrees"])
    bad = [(name, float("inf")) for name in NAMES]
    served = [_served(job, d, n_bins, depth) for job in jobs]
    if any(s is None for s in served):
        return bad
    params = _tree_params(config, d)
    y_std = max(float(y.std()), 1e-300)
    frame = forest.Frame(X)
    worst = dict.fromkeys(NAMES, 0.0)
    judged: dict = {}
    binned: dict = {}
    keys = forest.tree_keys(int(p["seed"]), n_trees)
    first = served[0]
    for job, (feat, thr_bin, thr, leaf, gains, edges) in zip(jobs, served):
        pred = np.asarray(job["outputs"].get(config["outputs"]["prediction"], ()))
        if pred.shape != (n,) or feat.shape[0] != n_trees or not np.isfinite(pred).all():
            return bad
        key = tuple(a.tobytes() for a in (feat, thr_bin, thr, leaf, gains, edges, pred))
        if key not in judged:
            res = {"struct_err": _struct_err(feat, thr_bin, thr, leaf, edges, d, n_bins, n_trees)}
            ref_pred = _predict(frame, feat, thr, leaf, n_trees)
            res["out_err"] = float(np.abs(pred - ref_pred).max() / y_std)
            rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
            ekey = edges.tobytes()
            if ekey not in binned:
                binned.clear()
                binned[ekey], binned["skew"] = frame.bins(edges)
            res["bin_skew"] = binned["skew"]
            trees = forest._checked_trees(X, n_trees, int(config.get("trees_checked", 2)))
            per_tree = [
                verify_tree(binned[ekey], y, forest.bootstrap_weights(keys[t], n), keys[t], d, params, depth,
                            feat[t], thr_bin[t], leaf[t], gains[t])
                for t in trees
            ]
            for name in ("count_err", "mean_err", "var_err", "split_excess", "gain_err"):
                res[name] = max(r[name] for r in per_tree)
            _log(f"rmse {rmse:.6g} (label std {y_std:.6g}), trees walked {trees}, nodes that hold rows a tree "
                 f"{[r['nodes'] for r in per_tree]}, deepest level with a split {[r['deepest_split'] for r in per_tree]}, "
                 f"splits a tree {float((feat >= 0).sum()) / n_trees:.1f}")
            judged[key] = res
        for name, value in judged[key].items():
            worst[name] = max(worst[name], value)
        worst["repeat_err"] = max(worst["repeat_err"], *(
            float(np.abs(f64(a) - f64(b)).max()) if a.shape == b.shape else float("inf")
            for a, b in zip((feat, thr_bin, thr, leaf, edges), (first[0], first[1], first[2], first[3], first[5]))
        ))
    return [(name, worst[name]) for name in NAMES]


def reference_job(config: dict, columns: dict, control: bool = False, stat_control: bool = False, fit_rows=None,
                  bootstrap: bool = True, cut_depth=None, runner_up: bool = False, alter_row=None) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, the output column), made by the reference alone.
    ``control``: X is rounded to bfloat16 before the binning and the descent.
    ``stat_control``: every row's w*y and w*y^2 are rounded to bfloat16
    before they are summed, in the histograms and the nodes' sums alike.
    ``fit_rows``: the trees see only the first rows. ``bootstrap=False``:
    every row weighs 1. ``cut_depth``: trees stop there. ``runner_up``: every
    root takes the best split of its second-best feature. ``alter_row``:
    that row's prediction is another's."""
    X, y = columns["features"], f64(columns["label"])
    p = config["estimator"]["params"]
    n, d = X.shape
    depth, n_bins, n_trees = int(p["maxDepth"]), int(p["maxBins"]), int(p["numTrees"])
    params = _tree_params(config, d, stat_control)
    edges = forest.sketch_edges(X, n_bins)
    frame = forest.Frame(X, control=control)
    bins, _ = frame.bins(edges)
    m = int(fit_rows or n)
    keys = forest.tree_keys(int(p["seed"]), n_trees)
    trees = []
    for t in range(n_trees):
        w = np.zeros(n, np.int64)
        w[:m] = forest.bootstrap_weights(keys[t], m) if bootstrap else 1
        trees.append(grow_tree(bins, y, w, keys[t], d, params, depth, edges, cut_depth=cut_depth, runner_up=runner_up))
    del bins
    model = {k: np.stack([t[k] for t in trees]) for k in trees[0]}
    pred = _predict(frame, model["features"], model["thresholds"], model["leaf_stats"], n_trees).astype(np.float32)
    if alter_row is not None:
        pred[alter_row] = pred[(alter_row + 1) % n] + np.float32(y.std())
    model.update(bin_edges=edges, n_classes=np.asarray(0), num_features=np.asarray(d))
    return {"model": model, "outputs": {config["outputs"]["prediction"]: pred}}
