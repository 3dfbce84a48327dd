"""In-situ attribution of RF build costs: time FULL 13-level tree builds
with individual stages knocked out (semantically wrong, cost-indicative).

Variants:
  full      — unmodified _build_tree
  nofeats   — per-node subsets replaced by one fixed subset (skips top_k)
  noroute   — rows never move (skips routing gathers + child update)
  nogain    — split search replaced by slot-0/bin-median constants
  nosubset  — histogram fed bins[:, :16] directly (skips contract gather)
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops import tree_kernels as tk
from spark_rapids_ml_tpu.ops.rf_pallas import BLOCK_ROWS

N = 131072
D = 256
K = 16
NB = 128
S = 2
DEPTH = 13


def build_tree_variant(bins, stats, valid, key, cfg, *, knock=None):
    n, d_pad = bins.shape
    S = cfg.n_stats
    nb = cfg.n_bins
    M = tk.max_nodes(cfg.max_depth)
    dt = stats.dtype
    kb, kf = jax.random.split(jnp.asarray(key))
    w = valid.astype(dt)
    sw = stats * w[:, None]
    feat = jnp.full((M,), -1, jnp.int32)
    thr_bin = jnp.zeros((M,), jnp.int32)
    leaf = jnp.zeros((M, S), dt)
    node = jnp.zeros((n,), jnp.int32)
    packed = tk._pack_bins(bins)

    for level in range(cfg.max_depth + 1):
        offset = (1 << level) - 1
        n_nodes = 1 << level
        local = node - offset
        in_level = (local >= 0) & (local < n_nodes)
        seg = jnp.where(in_level, local, n_nodes).astype(jnp.int32)
        if level == cfg.max_depth:
            parent = jax.ops.segment_sum(sw, seg, num_segments=n_nodes + 1)[:n_nodes]
            leaf = leaf.at[offset:offset + n_nodes].set(parent)
            break

        if knock == "nofeats":
            base = jnp.arange(K, dtype=jnp.int32) * (D // K)
            feats = jnp.broadcast_to(base[None, :], (n_nodes, K))
        else:
            r = jax.random.uniform(
                jax.random.fold_in(kf, level), (n_nodes, cfg.n_features))
            feats = lax.top_k(r, cfg.k_features)[1].astype(jnp.int32)

        lc0 = jnp.clip(local, 0, n_nodes - 1)
        if knock == "nosubset":
            hist_src = bins[:, :K].astype(jnp.int32)
        else:
            row_feats = feats[lc0]
            hist_src = tk._contract_gather(packed, row_feats)

        r_sub = tk._compact_r_sub(n, n_nodes, BLOCK_ROWS, S)
        n_pad_c = -(-(n + (n_nodes + 1) * r_sub) // BLOCK_ROWS) * BLOCK_ROWS
        hist_full, parent, _ = tk._hist_compact(
            lambda r, _nd, h=hist_src: h[r], seg, sw, n_nodes=n_nodes,
            n_slots=K, nb=nb, r_sub=r_sub, n_pad=n_pad_c, f_chunk=K,
            variance=False)
        leaf = leaf.at[offset:offset + n_nodes].set(parent)
        pcount = tk._count(parent, cfg.impurity)
        pimp = tk._impurity(parent, cfg.impurity)

        if knock == "nogain":
            bg = jnp.ones((n_nodes,), dt) + hist_full.sum() * 1e-30
            bf = jnp.broadcast_to(jnp.int32(0), (n_nodes,))
            bb = jnp.full((n_nodes,), NB // 2, jnp.int32)
        else:
            g, f, b, _ = tk._best_splits_from_hist(
                hist_full, parent, pcount, pimp, feats.T, nb, cfg)
            bg, bf, bb = g, f, b

        do_split = jnp.isfinite(bg) & (bg >= 1e-9) & (pcount >= cfg.min_samples_split)
        feat = feat.at[offset:offset + n_nodes].set(jnp.where(do_split, bf, -1))
        thr_bin = thr_bin.at[offset:offset + n_nodes].set(bb)

        if knock == "noroute":
            # rows stay at node 0's subtree spine: wrong but cheap
            node = jnp.where(in_level, 2 * node + 1 + (bb[lc0] // NB), node)
        else:
            row_feat = bf[lc0]
            row_bin = tk._contract_gather(packed, row_feat[:, None])[:, 0]
            go_right = (row_bin > bb[lc0]).astype(jnp.int32)
            child = 2 * node + 1 + go_right
            moves = in_level & do_split[lc0]
            node = jnp.where(moves, child, node)

    return {"feature": feat, "threshold_bin": thr_bin, "leaf_stats": leaf}


def main():
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, NB, size=(N, D), dtype=np.uint8))
    yc = rng.integers(0, 2, size=N)
    stats = jnp.asarray(np.eye(2, dtype=np.float32)[yc])
    valid = jnp.ones((N,), jnp.float32)
    cfg = tk.ForestConfig(
        max_depth=DEPTH, n_bins=NB, n_features=D, n_stats=S, impurity="gini",
        k_features=K, min_samples_leaf=1, min_info_gain=0.0,
        min_samples_split=2, bootstrap=False)

    # pre-staged perturbed copies: a per-rep host->device push of 33 MB
    # inside the timed loop would swamp the build time
    bins_reps = [
        jax.block_until_ready(
            jnp.asarray((np.asarray(bins) + (r + 1)) % NB, jnp.uint8))
        for r in range(3)
    ]
    for knock in [None, "nofeats", "nosubset", "nogain", "noroute"]:
        # each knockout variant IS a distinct program; compiled once per
        # variant and reused across the timed reps  # tpuml: ignore[TPU003]
        fn = jax.jit(lambda b, st, v, k, kn=knock: build_tree_variant(
            b, st, v, k, cfg, knock=kn))
        # fixed key on purpose: all variants must see identical splits
        # tpuml: ignore[TPU004]
        out = fn(bins, stats, valid, jax.random.PRNGKey(1))
        jax.block_until_ready(out)
        best = 1e30
        for r in range(3):
            t0 = time.perf_counter()
            # same fixed key as the warm call  # tpuml: ignore[TPU004]
            out = fn(bins_reps[r], stats, valid, jax.random.PRNGKey(1))
            np.asarray(out["feature"])
            best = min(best, time.perf_counter() - t0)
        print(f"{str(knock):10s}: {best*1e3:7.1f} ms/tree")


if __name__ == "__main__":
    main()
