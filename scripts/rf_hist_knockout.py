"""In-situ knockout attribution INSIDE _hist_compact: full 13-level tree
builds with pieces of the compact histogram path stubbed out (wrong
results, cost-indicative).

  full     — real _hist_compact
  nosort   — identity permutation (skips lax.sort)
  noglue   — fake uniform node runs (skips searchsorted/table machinery)
  nogather — kernel fed the first n_pad rows unsorted (skips swq/binq gathers)
  nokernel — zero partials (skips the Pallas kernel)
  nosegsum — partials summed flat (skips the wide per-node segment_sum)
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
from spark_rapids_ml_tpu.ops.rf_pallas import BLOCK_ROWS, subblock_hist

N, D, K, NB, S, DEPTH = 131072, 256, 16, 128, 2, 13


def hist_compact_knock(hist_src, seg, sw, *, n_nodes, nb, r_sub, n_pad,
                       f_chunk, knock):
    n, F = hist_src.shape
    S = sw.shape[1]
    n_sb = n_pad // r_sub
    iota = jnp.arange(n, dtype=jnp.int32)
    if knock == "nosort":
        keys_s, perm = seg, iota
    else:
        keys_s, perm = lax.sort((seg, iota), num_keys=1)
    if knock == "noglue":
        # fake uniform runs: node i owns rows [i*n//n_nodes, ...)
        per = n_pad // n_sb
        seg_sb = jnp.minimum(
            jnp.arange(n_sb, dtype=jnp.int32) * n_nodes // n_sb, n_nodes - 1)
        src2 = perm[jnp.minimum(jnp.arange(n_pad) % n, n - 1)]
        pvalid = jnp.arange(n_pad) < n
        seg_red = seg_sb
    else:
        starts = jnp.searchsorted(
            keys_s, jnp.arange(n_nodes + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        lens = starts[1:] - starts[:-1]
        plen = -(-lens // r_sub) * r_sub
        pstart = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(plen)])
        sb_pos = jnp.arange(n_sb, dtype=jnp.int32) * r_sub
        seg_sb = jnp.searchsorted(pstart[1:], sb_pos, side="right").astype(jnp.int32)
        sbc = jnp.clip(seg_sb, 0, n_nodes - 1)
        tbl = jnp.stack([starts[:-1], pstart[:-1], lens], axis=1)
        tbl_rows = jnp.broadcast_to(tbl[sbc][:, None, :], (n_sb, r_sub, 3)).reshape(n_pad, 3)
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        off = pos - tbl_rows[:, 1]
        src = tbl_rows[:, 0] + off
        pvalid = (off < tbl_rows[:, 2]) & (
            jnp.broadcast_to(seg_sb[:, None], (n_sb, r_sub)).reshape(n_pad) < n_nodes)
        src2 = perm[jnp.clip(src, 0, n - 1)]
        seg_red = jnp.where(seg_sb < n_nodes, seg_sb, n_nodes)
    if knock == "nogather":
        swq = jnp.broadcast_to(sw[:1], (n_pad, S)) * pvalid[:, None]
        binq = jnp.broadcast_to(hist_src[:1].astype(jnp.int32), (n_pad, F))
    else:
        swq = sw[src2] * pvalid[:, None].astype(sw.dtype)
        binq = hist_src[src2].astype(jnp.int32)
    if knock == "nokernel":
        partials = jnp.zeros((n_sb, S, F * nb), jnp.float32) + swq.sum() * 1e-30 + binq.sum() * 1e-30
    else:
        partials = subblock_hist(binq, swq, n_bins=nb, r_sub=r_sub,
                                 variance=False)
    if knock == "nosegsum":
        tot = partials.sum(axis=0, keepdims=True)
        hist_nodes = jnp.broadcast_to(tot, (n_nodes, S, F * nb)).reshape(
            n_nodes, S, F, nb) + seg_red[0] * 1e-30
    else:
        hist_nodes = jax.ops.segment_sum(
            partials.reshape(n_sb, S * F * nb), seg_red,
            num_segments=n_nodes + 1)[:n_nodes].reshape(n_nodes, S, F, nb)
    parent = hist_nodes[:, :, 0, :].sum(axis=-1)
    return hist_nodes.transpose(2, 0, 3, 1), parent


def build_tree(bins, stats, valid, key, cfg, knock):
    n, d_pad = bins.shape
    S, nb = cfg.n_stats, cfg.n_bins
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
        r = jax.random.uniform(jax.random.fold_in(kf, level), (n_nodes, D))
        feats = lax.top_k(r, K)[1].astype(jnp.int32)
        lc0 = jnp.clip(local, 0, n_nodes - 1)
        hist_src = tk._contract_gather(packed, feats[lc0])
        r_sub = tk._compact_r_sub(n, n_nodes, BLOCK_ROWS, S)
        n_pad_c = -(-(n + (n_nodes + 1) * r_sub) // BLOCK_ROWS) * BLOCK_ROWS
        hist_full, parent = hist_compact_knock(
            hist_src, seg, sw, n_nodes=n_nodes, nb=nb, r_sub=r_sub,
            n_pad=n_pad_c, f_chunk=K, knock=knock)
        leaf = leaf.at[offset:offset + n_nodes].set(parent)
        pcount = tk._count(parent, cfg.impurity)
        pimp = tk._impurity(parent, cfg.impurity)
        bg, bf, bb, _ = tk._best_splits_from_hist(
            hist_full, parent, pcount, pimp, feats.T, nb, cfg)
        do_split = jnp.isfinite(bg) & (bg >= 1e-9) & (pcount >= cfg.min_samples_split)
        feat = feat.at[offset:offset + n_nodes].set(jnp.where(do_split, bf, -1))
        thr_bin = thr_bin.at[offset:offset + n_nodes].set(bb)
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
    cfg = tk.ForestConfig(max_depth=DEPTH, n_bins=NB, n_features=D, n_stats=S,
        impurity="gini", k_features=K, min_samples_leaf=1, min_info_gain=0.0,
        min_samples_split=2, bootstrap=False)
    bins_reps = [jax.block_until_ready(jnp.asarray((np.asarray(bins)+(r+1)) % NB, jnp.uint8)) for r in range(3)]
    for knock in ["full", "nosort", "noglue", "nogather", "nokernel", "nosegsum"]:
        # each knockout variant IS a distinct program; compiled once per
        # variant and reused across the timed reps  # tpuml: ignore[TPU003]
        fn = jax.jit(lambda b, kn=knock: build_tree(
            b, stats, valid, jax.random.PRNGKey(1), cfg, kn))
        jax.block_until_ready(fn(bins))
        best = 1e30
        for rr in range(3):
            t0 = time.perf_counter()
            out = fn(bins_reps[rr])
            np.asarray(out["feature"])
            best = min(best, time.perf_counter() - t0)
        print(f"{knock:9s}: {best*1e3:7.1f} ms/tree")


if __name__ == "__main__":
    main()
