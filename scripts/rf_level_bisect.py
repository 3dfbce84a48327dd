"""Bisect the per-level cost of the RF compact-strategy build at the bench
shape (131k x 256, k=16, nb=128, S=2) on the real chip.

Stages timed at a steady-state deep level (default n_nodes=1024):
  full level  — histogram + gain + routing, as _build_tree runs it
  sort        — the per-level stable lax.sort((seg, iota))
  glue        — searchsorted/table/row-index machinery after the sort
  gathers     — sw[src2] + hist_src[src2] row gathers
  kernel      — subblock_hist + wide segment_sum
  gain        — _best_splits_from_hist over the full histogram
  subset_gather — make_hist_src (contraction gather) cost
  routing     — best-feature bin lookup + child computation

All timings amortize RTT with ITERS in-jit repeats carrying a non-foldable
dependence, with per-rep salted inputs (no rep repeats an earlier one exactly).
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

N = 131072
D = 256
K = 16
NB = 128
S = 2
N_NODES = int(os.environ.get("RF_BISECT_NODES", 1024))
ITERS = 32


def timed(fn, *args, reps=3):
    jitted = jax.jit(fn)
    float(jitted(jnp.float32(0), *args))
    best = 1e30
    for r in range(reps):
        salt = jnp.float32(1e-22 * (r + 1))
        t0 = time.perf_counter()
        float(jitted(salt, *args))
        best = min(best, time.perf_counter() - t0)
    return best / ITERS


def loop(body):
    def fn(salt, *args):
        def step(i, c):
            out = body(c, i, *args)
            return c + jnp.sum(out).astype(jnp.float32) * 1e-30
        return lax.fori_loop(0, ITERS, step, salt)
    return fn


def dep(ix, c):
    return jnp.where(c >= jnp.float32(-1e30), ix, 0)


def main():
    rng = np.random.default_rng(0)
    bins_np = rng.integers(0, NB, size=(N, D), dtype=np.uint8)
    bins = jnp.asarray(bins_np)
    sw = jnp.asarray(rng.random((N, S)).astype(np.float32))
    # realistic skewed node occupancy at a deep level
    node_p = rng.dirichlet(np.full(N_NODES, 0.5))
    seg_np = rng.choice(N_NODES, size=N, p=node_p).astype(np.int32)
    seg = jnp.asarray(seg_np)
    feats = jnp.asarray(
        np.stack([rng.choice(D, size=K, replace=False) for _ in range(N_NODES)])
        .astype(np.int32)
    )
    packed = tk._pack_bins(bins)
    hist_src = tk._contract_gather(packed, feats[jnp.clip(seg, 0, N_NODES-1)])

    r_sub = tk._compact_r_sub(N, N_NODES, BLOCK_ROWS, S)
    n_pad = -(-(N + (N_NODES + 1) * r_sub) // BLOCK_ROWS) * BLOCK_ROWS
    n_sb = n_pad // r_sub
    print(f"n_nodes={N_NODES} r_sub={r_sub} n_pad={n_pad} n_sb={n_sb}")

    # --- sort
    def f_sort(c, i, seg):
        iota = jnp.arange(N, dtype=jnp.int32)
        _, perm = lax.sort((dep(seg, c), iota), num_keys=1)
        return perm
    print(f"sort            : {timed(loop(f_sort), seg)*1e3:6.2f} ms")

    # --- glue (post-sort index machinery)
    def glue(keys_s, perm):
        starts = jnp.searchsorted(
            keys_s, jnp.arange(N_NODES + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        lens = starts[1:] - starts[:-1]
        plen = -(-lens // r_sub) * r_sub
        pstart = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(plen)])
        sb_pos = jnp.arange(n_sb, dtype=jnp.int32) * r_sub
        seg_sb = jnp.searchsorted(pstart[1:], sb_pos, side="right").astype(jnp.int32)
        sbc = jnp.clip(seg_sb, 0, N_NODES - 1)
        tbl = jnp.stack([starts[:-1], pstart[:-1], lens], axis=1)
        tbl_rows = jnp.broadcast_to(tbl[sbc][:, None, :], (n_sb, r_sub, 3)).reshape(n_pad, 3)
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        off = pos - tbl_rows[:, 1]
        src = tbl_rows[:, 0] + off
        pvalid = (off < tbl_rows[:, 2]) & (
            jnp.broadcast_to(seg_sb[:, None], (n_sb, r_sub)).reshape(n_pad) < N_NODES)
        src2 = perm[jnp.clip(src, 0, N - 1)]
        seg_red = jnp.where(seg_sb < N_NODES, seg_sb, N_NODES)
        return src2, pvalid, seg_red

    def f_glue(c, i, seg):
        iota = jnp.arange(N, dtype=jnp.int32)
        keys_s, perm = lax.sort((dep(seg, c), iota), num_keys=1)
        src2, pvalid, seg_red = glue(keys_s, perm)
        return src2 + pvalid + seg_red[:1]
    print(f"sort+glue       : {timed(loop(f_glue), seg)*1e3:6.2f} ms")

    # --- + gathers
    def f_gath(c, i, seg, sw, hist_src):
        iota = jnp.arange(N, dtype=jnp.int32)
        keys_s, perm = lax.sort((dep(seg, c), iota), num_keys=1)
        src2, pvalid, seg_red = glue(keys_s, perm)
        swq = sw[src2] * pvalid[:, None].astype(sw.dtype)
        binq = hist_src[src2].astype(jnp.int32)
        return swq.sum() + binq.sum()
    print(f"sort+glue+gather: {timed(loop(f_gath), seg, sw, hist_src)*1e3:6.2f} ms")

    # --- full _hist_compact
    def f_hist(c, i, seg, sw, hist_src):
        src = jnp.where(c >= jnp.float32(-1e30), hist_src, 0)
        h, p, _ = tk._hist_compact(
            lambda r, _nd: src[r], seg, sw,
            n_nodes=N_NODES, n_slots=K, nb=NB, r_sub=r_sub, n_pad=n_pad,
            f_chunk=K, variance=False)
        return h.sum() + p.sum()
    t_hist = timed(loop(f_hist), seg, sw, hist_src)
    print(f"hist_compact    : {t_hist*1e3:6.2f} ms")

    # --- gain search
    hist_full, parent, _ = tk._hist_compact(
        lambda r, _nd: hist_src[r], seg, sw, n_nodes=N_NODES, n_slots=K,
        nb=NB, r_sub=r_sub, n_pad=n_pad, f_chunk=K, variance=False)
    cfg = tk.ForestConfig(
        max_depth=13, n_bins=NB, n_features=D, n_stats=S, impurity="gini",
        k_features=K, min_samples_leaf=1, min_info_gain=0.0,
        min_samples_split=2, bootstrap=True)
    pcount = tk._count(parent, "gini")
    pimp = tk._impurity(parent, "gini")
    realf = feats.T

    def f_gain(c, i, hist_full, parent, pcount, pimp, realf):
        g, f, b, _ = tk._best_splits_from_hist(
            jnp.where(c >= jnp.float32(-1e30), hist_full, 0.0),
            parent, pcount, pimp, realf, NB, cfg)
        return g.sum() + f.sum() + b.sum()
    print(f"gain search     : {timed(loop(f_gain), hist_full, parent, pcount, pimp, realf)*1e3:6.2f} ms")

    # --- subset gather (contraction)
    def f_subset(c, i, packed, seg):
        rf = feats[jnp.clip(dep(seg, c), 0, N_NODES - 1)]
        return tk._contract_gather(packed, rf)
    print(f"subset extract  : {timed(loop(f_subset), packed, seg)*1e3:6.2f} ms")

    # --- routing
    bf = jnp.asarray(rng.integers(0, D, size=(N_NODES,)).astype(np.int32))
    bb = jnp.asarray(rng.integers(0, NB, size=(N_NODES,)).astype(np.int32))

    def f_route(c, i, packed, seg, bf, bb):
        lc = jnp.clip(dep(seg, c), 0, N_NODES - 1)
        row_feat = bf[lc]
        row_bin = tk._contract_gather(packed, row_feat[:, None])[:, 0]
        go_right = (row_bin > bb[lc]).astype(jnp.int32)
        return 2 * seg + 1 + go_right
    print(f"routing         : {timed(loop(f_route), packed, seg, bf, bb)*1e3:6.2f} ms")

    # --- feats top_k
    def f_feats(c, i, key):
        r = jax.random.uniform(jax.random.fold_in(key, i + c.astype(jnp.int32)), (N_NODES, D))
        return lax.top_k(r, K)[1]
    print(f"feats top_k     : {timed(loop(f_feats), jax.random.PRNGKey(0))*1e3:6.2f} ms")


if __name__ == "__main__":
    main()
