"""Plain reference of ``rf_dbx``: a random forest as Spark states it, judged
tree by tree.

A forest cannot be fitted a second time and compared: which split wins a
near-tie turns on the last bit of a gain. So the reference VERIFIES each served
tree along its own path, as ``kmeans_dbx`` verifies a fixed point: it bins the
frame by the served ``bin_edges`` (bin = #{edges <= x}) from its own float32
copy, walks the rows down the served splits with the bootstrap weights, and at
every node that holds rows of more than one class (a pure node's every split
has gain 0) builds the node's histogram over the node's own sampled features
and asks whether the served split is the admissible split of greatest gini
gain there. The same walk, taking its own best split instead of
the served one, is a fit (:func:`reference_job`): the control and the faults
are that fit put in the program's place.

The reference imports nothing of the program and takes nothing it made. **One
thing is taken from the program's contract, copied here and not imported**
(the configuration's ``guarantees`` state it): the draws. Tree *t* of *T* has
the key ``jax.random.split(PRNGKey(seed), T)[t]``; ``kb, kf = split(key)``;
its bootstrap weights are ``jax.random.poisson(kb, 1.0, (n,))`` by row; the
nodes of level *L* sample the ``k`` largest of ``jax.random.uniform(
fold_in(kf, L), (2**L, d))`` each (a tie at the k-th value admits both).
Without them no two forests could be compared at all.

Plain ``jax.numpy`` in row blocks (``_blocks.py``) for the binning and the
descent; the bins then come to the host once (rows x cols uint8) and the
per-row column gathers are numpy's (a uint8 element gather runs at 3e6 a
second on the chip, ten times that on its host); every sum (class counts,
histograms, gains, the vote) is int64 / float64 on the host.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import _blocks
from ._blocks import f64

NAMES = ("count_err", "split_excess", "gain_err", "out_err", "bin_skew", "repeat_err", "struct_err")
_COL_CHUNK = 512       # columns binned at once: bounds the (rows, chunk, edges) compare
_NODE_CHUNK = 256      # nodes whose gains are searched at once on the host


def _log(*a) -> None:
    print("chipbench: reference:", *a, file=sys.stderr, flush=True)


# ---- the copied draw contract ---------------------------------------------

def tree_keys(seed: int, n_trees: int) -> np.ndarray:
    return np.asarray(jax.random.split(jax.random.PRNGKey(int(seed)), int(n_trees)))


def bootstrap_weights(key, n: int) -> np.ndarray:
    kb, _ = jax.random.split(jnp.asarray(key))
    return np.asarray(jax.random.poisson(kb, 1.0, (int(n),))).astype(np.int64)


def level_draws(key, level: int, d: int) -> np.ndarray:
    """The uniforms that level ``level``'s nodes rank their features by: (2**level, d)."""
    _, kf = jax.random.split(jnp.asarray(key))
    return np.asarray(jax.random.uniform(jax.random.fold_in(kf, level), (1 << level, int(d))))


def sampled_features(draws: np.ndarray, k: int):
    """Per node the columns whose draw is among the ``k`` largest (ties at the
    k-th value included): ``(idx, valid)``, both (nodes, kmax), largest first."""
    d = draws.shape[1]
    if k >= d:
        idx = np.broadcast_to(np.arange(d, dtype=np.int32), draws.shape)
        return idx, np.ones(draws.shape, bool)
    kth = np.partition(draws, d - k, axis=1)[:, d - k]
    sel = draws >= kth[:, None]
    kmax = int(sel.sum(axis=1).max())
    order = np.argsort(-draws, axis=1, kind="stable")[:, :kmax].astype(np.int32)
    return order, np.take_along_axis(sel, order, axis=1)


def features_per_node(d: int) -> int:
    """Spark's ``featureSubsetStrategy=auto`` for a classifier: ceil(sqrt(d))."""
    return max(1, min(d, math.ceil(math.sqrt(d))))


# ---- the frame on the device ------------------------------------------------

@jax.jit
def _bin_block(xb, edges):
    """bin = #{edges <= x}: (rows, d) uint8, in column chunks."""
    x = xb.astype(jnp.float32)
    parts = []
    for c0 in range(0, x.shape[1], _COL_CHUNK):
        cnt = (x[:, c0:c0 + _COL_CHUNK, None] >= edges[None, c0:c0 + _COL_CHUNK, :]).sum(axis=2, dtype=jnp.int32)
        parts.append(cnt.astype(jnp.uint8))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


@functools.partial(jax.jit, static_argnames=("n_bins",))
def _cell_counts(bb, n_bins: int):
    """Rows of the block in every (feature, bin) cell: (d, n_bins) int32."""
    return (bb[:, :, None] == jnp.arange(n_bins, dtype=jnp.uint8)[None, None, :]).sum(axis=0, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("depth",))
def _descend_block(xb, feat, thr, depth: int):
    """Leaf of every row in every tree, in raw feature space: x >= threshold
    goes right, a node without a split keeps the row. (T, rows) int32."""
    x = xb.astype(jnp.float32)
    d = x.shape[1]

    def one(tree):
        f, t = tree

        def body(_, node):
            nf = f[node]
            xv = jnp.take_along_axis(x, jnp.clip(nf, 0, d - 1)[:, None], axis=1)[:, 0]
            return jnp.where(nf < 0, node, 2 * node + 1 + (xv >= t[node]).astype(jnp.int32))

        return jax.lax.fori_loop(0, depth, body, jnp.zeros((x.shape[0],), jnp.int32))

    return jax.lax.map(one, (feat, thr))


class Frame:
    """The frame on the device in blocks; ``control`` rounds it to bfloat16 on
    its way up (what the binning and the descent then see)."""

    def __init__(self, X: np.ndarray, control: bool = False):
        self.n, self.d = X.shape
        self.blocks = _blocks.place(X, control)

    def bins(self, edges: np.ndarray):
        """``(bins, skew)``: the frame binned by ``edges`` (bin = #{edges <= x})
        on the host, (n, d) uint8; and the fullest (feature, bin) cell over all
        rows, times the bins over the rows: 1 where every feature's bins hold
        the same number of rows."""
        e = jnp.asarray(edges, jnp.float32)
        n_bins = edges.shape[1] + 1
        out = np.empty((self.n, self.d), np.uint8)
        cells = np.zeros((self.d, n_bins), np.int64)
        lo = 0
        for xb in self.blocks:
            bb = _bin_block(xb, e)
            cells += np.asarray(_cell_counts(bb, n_bins)).astype(np.int64)
            out[lo:lo + len(xb)] = np.asarray(bb)
            lo += len(xb)
        return out, float(cells.max()) * n_bins / self.n

    def leaves(self, feat: np.ndarray, thr: np.ndarray) -> np.ndarray:
        """(T, n) leaf node of every row in every tree."""
        depth = int(math.log2(feat.shape[1] + 1)) - 1
        f, t = jnp.asarray(feat, jnp.int32), jnp.asarray(thr, jnp.float32)
        return np.concatenate([np.asarray(_descend_block(xb, f, t, depth)) for xb in self.blocks], axis=1)


def gather(bins: np.ndarray, rows: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """bins[rows[r], idx[r, j]]: the plain per-row column gather, in pieces
    that keep its index arrays small."""
    out = np.empty(idx.shape, np.uint8)
    for lo in range(0, len(rows), 1 << 16):
        out[lo:lo + (1 << 16)] = bins[rows[lo:lo + (1 << 16), None], idx[lo:lo + (1 << 16)]]
    return out


# ---- gini on the host, float64 ----------------------------------------------

def _gini(stats: np.ndarray) -> np.ndarray:
    n = stats.sum(axis=-1)
    p = stats / np.maximum(n, 1e-300)[..., None]
    return np.where(n > 0, 1.0 - (p * p).sum(axis=-1), 0.0)


def _gains(hist: np.ndarray, valid: np.ndarray, min_leaf: float) -> np.ndarray:
    """Gain of every (feature slot, threshold bin) of every node: hist is
    (nodes, slots, bins, classes) weighted counts; a threshold bin b sends
    bins <= b left. -inf where a child would hold fewer than ``min_leaf``
    (weighted) rows or the slot is none of the node's features."""
    parent = hist[:, 0].sum(axis=1)                          # (nodes, C): any slot's bins sum to the node
    left = np.cumsum(hist, axis=2)[:, :, :-1]
    right = parent[:, None, None, :] - left
    nl, nr = left.sum(axis=-1), right.sum(axis=-1)
    pcount = np.maximum(parent.sum(axis=-1), 1e-300)
    gain = _gini(parent)[:, None, None] - (nl * _gini(left) + nr * _gini(right)) / pcount[:, None, None]
    ok = (nl >= min_leaf) & (nr >= min_leaf) & valid[:, :, None]
    return np.where(ok, gain, -np.inf)


# ---- one tree, level by level -----------------------------------------------

class Walk:
    """One tree over the binned frame: the rows' weights and labels, the
    current node of every row, and per level the class counts of every node,
    the histograms of the nodes that hold rows and their gains."""

    def __init__(self, bins: np.ndarray, y: np.ndarray, w: np.ndarray, key, d: int, params: dict):
        self.bins, self.y, self.w, self.key, self.d = bins, y.astype(np.int64), w.astype(np.int64), key, d
        self.n_bins, self.C, self.k = int(params["n_bins"]), int(params["n_classes"]), int(params["k"])
        self.min_leaf = float(params.get("min_leaf", 1))
        self.node = np.zeros(len(y), np.int64)               # heap index of the row's node
        self.live = self.w > 0                               # rows of no weight add to no count

    def counts(self, level: int) -> np.ndarray:
        """(2**level, C) weighted class counts of the level's nodes."""
        off, nodes = (1 << level) - 1, 1 << level
        at = self.live & (self.node >= off)
        flat = np.bincount((self.node[at] - off) * self.C + self.y[at], weights=self.w[at], minlength=nodes * self.C)
        return np.rint(flat).astype(np.int64).reshape(nodes, self.C)

    def gains(self, level: int, counts: np.ndarray):
        """``(nodes, idx, gain)``: the level's nodes that hold rows of more
        than one class (every split of a pure node has gain 0, so it needs no
        histogram: on this data most rows sit in pure nodes after two levels),
        their sampled features (nodes, slots; -1 where a slot is none) and the
        gain of every (slot, threshold bin) of each."""
        off = (1 << level) - 1
        held = np.flatnonzero((counts > 0).sum(axis=1) >= 2)
        if not len(held):
            return held, np.zeros((0, 1), np.int32), np.zeros((0, 1, self.n_bins - 1))
        idx, valid = sampled_features(level_draws(self.key, level, self.d)[held], self.k)
        rank = np.full(1 << level, -1, np.int64)
        rank[held] = np.arange(len(held))
        rows = np.flatnonzero(self.live & (self.node >= off))
        rows = rows[rank[self.node[rows] - off] >= 0]
        r_rank = rank[self.node[rows] - off]
        b = gather(self.bins, rows, idx[r_rank]).astype(np.int64)   # (rows, slots)
        slots, nb, C = idx.shape[1], self.n_bins, self.C
        gain = np.empty((len(held), slots, nb - 1))
        for lo in range(0, len(held), _NODE_CHUNK):
            m = (r_rank >= lo) & (r_rank < lo + _NODE_CHUNK)
            size = min(_NODE_CHUNK, len(held) - lo)
            key = (((r_rank[m] - lo)[:, None] * slots + np.arange(slots)[None, :]) * nb + b[m]) * C + self.y[rows[m]][:, None]
            hist = np.bincount(key.ravel(), weights=np.repeat(self.w[rows[m]], slots), minlength=size * slots * nb * C)
            gain[lo:lo + size] = _gains(hist.reshape(size, slots, nb, C), valid[lo:lo + size], self.min_leaf)
        return held, np.where(valid, idx, -1), gain

    def route(self, level: int, feat: np.ndarray, thr_bin: np.ndarray) -> None:
        """Rows of the level's split nodes go to a child: bin > threshold bin
        goes right. ``feat`` < 0 is a leaf and keeps its rows."""
        off = (1 << level) - 1
        rows = np.flatnonzero(self.node >= off)
        f = feat[self.node[rows] - off]
        moving = rows[f >= 0]
        f, local = f[f >= 0], self.node[moving] - off
        b = gather(self.bins, moving, f.astype(np.int32)[:, None])[:, 0].astype(np.int64)
        self.node[rows] = -1                                  # a row at a leaf has left the walk
        self.node[moving] = 2 * (local + off) + 1 + (b > thr_bin[local])


def _tree_params(config: dict, d: int, n_classes: int) -> dict:
    p = config["estimator"]["params"]
    return {"n_bins": int(p["maxBins"]), "n_classes": n_classes, "k": features_per_node(d), "min_leaf": 1}


def verify_tree(bins, y, w, key, d, params, depth, feat, thr_bin, leaf_stats, served_gains) -> dict:
    """The served tree walked along its own splits: ``count_err`` (largest
    difference of a node's class count to the served one), ``split_excess``
    (largest g* − g(served split), over the root's impurity, times the node's
    share of the root's weight; a served leaf is a split of gain 0),
    ``gain_err`` (served gain against the reference's gain of the served
    split), nodes that hold rows, the deepest level with a split."""
    walk = Walk(bins, y, w, key, d, params)
    count_err = split_excess = gain_err = 0.0
    held_nodes, deepest, root_w, root_imp = 0, -1, 1.0, 1.0
    for level in range(depth + 1):
        off, nodes = (1 << level) - 1, 1 << level
        counts = walk.counts(level)
        count_err = max(count_err, float(np.abs(counts - f64(leaf_stats[off:off + nodes])).max()))
        if level == 0:
            root_w, root_imp = float(counts.sum()), float(_gini(f64(counts))[0])
        weight = counts.sum(axis=1)
        held_nodes += int((weight > 0).sum())
        if level > 0:
            # a served split is admissible only if both children hold min_leaf weighted rows
            parents = np.flatnonzero(feat[off - (nodes >> 1):off] >= 0)
            if (np.minimum(weight[2 * parents], weight[2 * parents + 1]) < walk.min_leaf).any():
                split_excess = float(np.finfo(np.float64).max)
        if level == depth or not counts.any():
            break
        served = feat[off:off + nodes]
        if ((served >= 0) & (weight > 0)).any():
            deepest = level
        held, idx, gain = walk.gains(level, counts)                     # the impure nodes; a pure node's every split has gain 0
        pure_splits = (served >= 0) & (weight > 0)
        pure_splits[held] = False
        if pure_splits.any():
            gain_err = max(gain_err, float(np.abs(f64(served_gains[off:off + nodes])[pure_splits]).max()))
        best = gain.reshape(len(held), gain.shape[1] * gain.shape[2]).max(axis=1, initial=-np.inf)
        can_split = weight[held] >= 2
        best = np.where(can_split, np.maximum(best, 0.0), 0.0)          # a leaf is always admissible, at gain 0
        f, b = feat[off + held], thr_bin[off + held]
        slot = (idx == f[:, None]).argmax(axis=1)
        in_set = (idx == f[:, None]).any(axis=1)
        g_served = np.where(f < 0, 0.0, np.where(in_set, gain[np.arange(len(held)), slot, np.clip(b, 0, gain.shape[2] - 1)], -np.inf))
        with np.errstate(invalid="ignore"):
            excess = (best - g_served) * (weight[held] / root_w) / max(root_imp, 1e-300)
        split_excess = max(split_excess, float(np.nan_to_num(excess, nan=np.inf).max(initial=0.0)))
        if (f >= 0).any():
            gain_err = max(gain_err, float(np.nan_to_num(np.abs(f64(served_gains[off + held])[f >= 0] - g_served[f >= 0])).max()))
        walk.route(level, feat[off:off + nodes], thr_bin[off:off + nodes])
    return {"count_err": count_err, "split_excess": split_excess, "gain_err": gain_err, "nodes": held_nodes, "deepest_split": deepest}


def grow_tree(bins, y, w, key, d, params, depth, edges, cut_depth=None, runner_up=False) -> dict:
    """The same walk taking its own best split: a fit. ``cut_depth`` stops the
    growth there (the tree keeps the layout of ``depth``); ``runner_up`` gives
    the root the best split of its second-best feature."""
    M = (1 << (depth + 1)) - 1
    feat, thr_bin = np.full(M, -1, np.int32), np.zeros(M, np.int32)
    leaf, gains = np.zeros((M, params["n_classes"]), np.float32), np.zeros(M, np.float32)
    walk = Walk(bins, y, w, key, d, params)
    for level in range(depth + 1):
        off, nodes = (1 << level) - 1, 1 << level
        counts = walk.counts(level)
        leaf[off:off + nodes] = counts
        if level == depth or (cut_depth is not None and level >= cut_depth) or not counts.any():
            if level < depth:
                walk.route(level, np.full(nodes, -1, np.int32), np.zeros(nodes, np.int32))
            continue
        held, idx, gain = walk.gains(level, counts)
        if not len(held):                       # every node that holds rows is pure: all leaves
            walk.route(level, feat[off:off + nodes], thr_bin[off:off + nodes])
            continue
        flat = gain.reshape(len(held), -1)
        if runner_up and level == 0:
            per_slot = gain.max(axis=2)
            per_slot[0, per_slot[0].argmax()] = -np.inf
            flat = np.where((np.arange(gain.shape[1]) == per_slot[0].argmax())[None, :, None], gain, -np.inf).reshape(1, -1)
        pick = flat.argmax(axis=1)
        g = flat[np.arange(len(held)), pick]
        split = (g > 0) & (counts[held].sum(axis=1) >= 2)
        slot, b = pick // gain.shape[2], pick % gain.shape[2]
        feat[off + held] = np.where(split, idx[np.arange(len(held)), slot], -1)
        thr_bin[off + held] = np.where(split, b, 0)
        gains[off + held] = np.where(split, g, 0.0)
        walk.route(level, feat[off:off + nodes], thr_bin[off:off + nodes])
    thr = np.where(feat >= 0, edges[np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, edges.shape[1] - 1)], 0.0).astype(np.float32)
    return {"features": feat, "threshold_bins": thr_bin, "thresholds": thr, "leaf_stats": leaf, "gains": gains}


# ---- what the timed jobs returned -------------------------------------------

def _served(job: dict, d: int, n_bins: int, depth: int):
    m = job["model"]
    try:
        feat, thr_bin = np.asarray(m["features"]), np.asarray(m["threshold_bins"])
        thr, leaf, gains, edges = (np.asarray(m[k]) for k in ("thresholds", "leaf_stats", "gains", "bin_edges"))
    except KeyError:
        return None
    M = (1 << (depth + 1)) - 1
    T = feat.shape[0] if feat.ndim == 2 else -1
    ok = (feat.shape == (T, M) and thr_bin.shape == (T, M) and thr.shape == (T, M) and gains.shape == (T, M)
          and leaf.ndim == 3 and leaf.shape[:2] == (T, M) and edges.shape == (d, n_bins - 1)
          and all(np.isfinite(a).all() for a in (thr, leaf, gains, edges)))
    return (feat.astype(np.int64), thr_bin.astype(np.int64), thr, leaf, gains, edges) if ok else None


def _struct_err(feat, thr_bin, thr, leaf, edges, d: int, n_bins: int, n_trees: int) -> float:
    """Faults of the layout, counted: a tree count that is not the
    configuration's, a feature outside [0, d), a threshold bin outside
    [0, n_bins - 2], a threshold that is not the served edge of its bin, bin
    edges that fall, a child under a leaf that holds rows, a class count that
    is no whole number."""
    split = feat >= 0
    inner = (feat.shape[1] - 1) // 2
    bad = int(feat.shape[0] != n_trees) + int((feat >= d).sum()) + int((split[:, inner:]).sum())
    bad += int(((thr_bin < 0) | (thr_bin > n_bins - 2))[split].sum())
    bad += int((thr[split] != edges[np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, n_bins - 2)][split]).sum())
    bad += int((np.diff(edges, axis=1) < 0).sum()) + int((leaf != np.rint(leaf)).sum()) + int((leaf < 0).sum())
    held = leaf.sum(axis=2) > 0
    parents = (np.arange(1, feat.shape[1]) - 1) // 2
    bad += int((held[:, 1:] & ~split[:, parents]).sum())
    return float(bad)


def _checked_trees(X: np.ndarray, n_trees: int, want: int) -> list:
    """Which trees are walked: drawn from the frame's first values, so from ``--seed``."""
    rng = np.random.default_rng(int(np.ascontiguousarray(X[0, :4]).view(np.uint32).sum()))
    return sorted(int(t) for t in rng.choice(n_trees, min(want, n_trees), replace=False))


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    ``out_err``: the larger of (a) the widest gap of the served
    ``probability`` (and of ``rawPrediction`` over the tree count) to the mean
    of the served trees' leaf class distributions where the reference's
    descent in raw feature space (x >= threshold goes right) ends, all rows
    and all trees, and (b) the NUMBER of rows whose ``prediction`` is not that
    vote's argmax, a row not counted where its two largest probabilities lie
    within ``tie_margin`` (one such row reads 1).
    ``count_err``, ``split_excess``, ``gain_err``: :func:`verify_tree`, worst
    over ``trees_checked`` trees. ``bin_skew``: :meth:`Frame.bins` under the
    served edges. ``repeat_err``: largest difference between any two jobs'
    forests (0: the same seed gives the same forest). ``struct_err``:
    :func:`_struct_err`. Jobs whose model and outputs are bit-identical are
    judged once."""
    X, y = columns["features"], np.asarray(columns["label"]).astype(np.int64)
    p = config["estimator"]["params"]
    n, d = X.shape
    depth, n_bins, n_trees = int(p["maxDepth"]), int(p["maxBins"]), int(p["numTrees"])
    outs = config["outputs"]
    bad = [(name, float("inf")) for name in NAMES]
    served = [_served(job, d, n_bins, depth) for job in jobs]
    if any(s is None for s in served):
        return bad
    n_classes = served[0][3].shape[2]
    params = _tree_params(config, d, n_classes)
    margin = float(config.get("tie_margin", 0.0))
    frame = Frame(X)
    worst = dict.fromkeys(NAMES, 0.0)
    judged: dict = {}
    binned: dict = {}
    keys = tree_keys(int(p["seed"]), n_trees)
    first = served[0]
    for job, (feat, thr_bin, thr, leaf, gains, edges) in zip(jobs, served):
        try:
            prob, raw, pred = (np.asarray(job["outputs"][outs[k]]) for k in ("probability", "raw", "prediction"))
        except KeyError:
            return bad
        if prob.shape != (n, n_classes) or raw.shape != (n, n_classes) or pred.shape != (n,) or feat.shape[0] != n_trees:
            return bad
        key = tuple(a.tobytes() for a in (feat, thr_bin, thr, leaf, gains, edges, prob, raw, pred))
        if key not in judged:
            res = {"struct_err": _struct_err(feat, thr_bin, thr, leaf, edges, d, n_bins, n_trees)}
            leaves = frame.leaves(feat, thr)
            dist = f64(leaf) / np.maximum(f64(leaf).sum(axis=2, keepdims=True), 1e-300)
            vote = np.zeros((n, n_classes))
            for t in range(n_trees):
                vote += dist[t][leaves[t]]
            ref_prob = vote / n_trees
            top = np.sort(ref_prob, axis=1)
            wrong = int(((pred != ref_prob.argmax(axis=1)) & (top[:, -1] - top[:, -2] >= margin)).sum())
            res["out_err"] = float(max(np.abs(prob - ref_prob).max(), np.abs(raw - vote).max() / n_trees, wrong))
            accuracy = float((pred == y).mean())
            ekey = edges.tobytes()
            if ekey not in binned:
                binned.clear()
                binned[ekey], binned["skew"] = frame.bins(edges)
            res["bin_skew"] = binned["skew"]
            trees = _checked_trees(X, n_trees, int(config.get("trees_checked", 4)))
            per_tree = [
                verify_tree(binned[ekey], y, bootstrap_weights(keys[t], n), keys[t], d, params, depth,
                            feat[t], thr_bin[t], leaf[t], gains[t])
                for t in trees
            ]
            for name in ("count_err", "split_excess", "gain_err"):
                res[name] = max(r[name] for r in per_tree)
            _log(f"accuracy {accuracy:.6f}, trees walked {trees}, nodes that hold rows a tree "
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


def sketch_edges(X: np.ndarray, n_bins: int, rows: int = 32768) -> np.ndarray:
    """The reference's own quantile edges: (d, n_bins - 1) float32 from about
    ``rows`` rows taken evenly over the frame, in float64."""
    step = max(1, len(X) // rows)
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.ascontiguousarray(np.quantile(np.asarray(X[::step], np.float64), qs, axis=0).T.astype(np.float32))


def reference_job(config: dict, columns: dict, control: bool = False, fit_rows=None, bootstrap: bool = True,
                  cut_depth=None, runner_up: bool = False, alter_row=None) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, output columns), made by the reference alone.
    ``control``: X is rounded to bfloat16 before the binning and before the
    descent. ``fit_rows``: the trees see only the first rows (weights drawn
    for that many). ``bootstrap=False``: every row weighs 1. ``cut_depth``:
    trees stop there. ``runner_up``: every root takes the best split of its
    second-best feature. ``alter_row``: that row's output is another's."""
    X, y = columns["features"], np.asarray(columns["label"]).astype(np.int64)
    p = config["estimator"]["params"]
    n, d = X.shape
    depth, n_bins, n_trees = int(p["maxDepth"]), int(p["maxBins"]), int(p["numTrees"])
    n_classes = max(int(y.max()) + 1, 2)
    params = _tree_params(config, d, n_classes)
    edges = sketch_edges(X, n_bins)
    frame = Frame(X, control=control)
    bins, _ = frame.bins(edges)
    m = int(fit_rows or n)
    keys = tree_keys(int(p["seed"]), n_trees)
    trees = []
    for t in range(n_trees):
        w = np.zeros(n, np.int64)
        w[:m] = bootstrap_weights(keys[t], m) if bootstrap else 1
        trees.append(grow_tree(bins, y, w, keys[t], d, params, depth, edges, cut_depth=cut_depth, runner_up=runner_up))
    del bins
    model = {k: np.stack([t[k] for t in trees]) for k in trees[0]}
    leaves = frame.leaves(model["features"], model["thresholds"])
    dist = f64(model["leaf_stats"]) / np.maximum(f64(model["leaf_stats"]).sum(axis=2, keepdims=True), 1e-300)
    vote = np.zeros((n, n_classes))
    for t in range(n_trees):
        vote += dist[t][leaves[t]]
    prob = (vote / n_trees).astype(np.float32)
    if alter_row is not None:
        prob[alter_row] = prob[alter_row][::-1] if prob[alter_row][0] != prob[alter_row][-1] else 1.0 - prob[alter_row] * 0.5
    raw = (prob.astype(np.float64) * n_trees).astype(np.float32) if alter_row is not None else vote.astype(np.float32)
    outs = config["outputs"]
    model.update(bin_edges=edges, n_classes=np.asarray(n_classes), num_features=np.asarray(d))
    return {
        "model": model,
        "outputs": {outs["prediction"]: prob.argmax(axis=1).astype(np.float32), outs["probability"]: prob, outs["raw"]: raw},
    }
