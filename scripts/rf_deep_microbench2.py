"""Follow-up RF measurements: unfoldable scatter dependence + full-tree
ground truth + bf16 Pallas variant.

The first microbench's scatter-level loop dependence (`+ c % 1`) was
constant-foldable, letting XLA hoist the scatter out of the timing loop.
This run uses a data-dependent select XLA cannot fold.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

ITERS = 16

N = 131072
K = 16
NB = 128
S = 2
N_NODES = 4096


def timeit_looped(jitted, *args, reps=3, warmup=1, iters=ITERS):
    for _ in range(warmup):
        np.asarray(jnp.ravel(jitted(*args))[:1])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jnp.ravel(jitted(*args))[:1])
        ts.append(time.perf_counter() - t0)
    return min(ts) / iters


def main():
    print("devices:", jax.devices())
    rng = np.random.default_rng(0)
    binc = jnp.asarray(rng.integers(0, NB, size=(N, K)), jnp.int32)
    sw = jnp.asarray(rng.random((N, S)), jnp.float32)
    local = jnp.asarray(rng.integers(0, N_NODES, size=(N,)), jnp.int32)

    # 1. scatter level with unfoldable dependence: where(c >= 0, binc, 0)
    #    costs one select pass (~0.1 ms) but cannot be hoisted.
    @jax.jit
    def hist_scatter_loop(binc, local, sw):
        def body(_, c):
            b2 = jnp.where(c >= 0.0, binc, 0)
            ids = local[:, None] * NB + b2
            hist = jnp.stack(
                [
                    jax.vmap(
                        lambda col, cc=sw[:, s]: jax.ops.segment_sum(
                            cc, col, num_segments=N_NODES * NB + 1
                        ),
                        in_axes=1,
                    )(ids)
                    for s in range(S)
                ],
                axis=-1,
            )
            return hist[:, : N_NODES * NB, :].sum()

        return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

    t = timeit_looped(hist_scatter_loop, binc, local, sw)
    print(f"1. scatter level UNFOLDABLE (n={N}, k={K}): {t*1e3:.2f} ms "
          f"({N*K*S/t/1e8:.2f}e8 upd/s)")

    # 1b. same at shallow width (n_nodes=8): is scatter node-count-flat?
    local8 = jnp.asarray(rng.integers(0, 8, size=(N,)), jnp.int32)

    @jax.jit
    def hist_scatter8(binc, local, sw):
        def body(_, c):
            b2 = jnp.where(c >= 0.0, binc, 0)
            ids = local[:, None] * NB + b2
            hist = jnp.stack(
                [
                    jax.vmap(
                        lambda col, cc=sw[:, s]: jax.ops.segment_sum(
                            cc, col, num_segments=8 * NB + 1
                        ),
                        in_axes=1,
                    )(ids)
                    for s in range(S)
                ],
                axis=-1,
            )
            return hist[:, : 8 * NB, :].sum()

        return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

    t = timeit_looped(hist_scatter8, binc, local8, sw)
    print(f"1b. scatter level n_nodes=8: {t*1e3:.2f} ms "
          f"({N*K*S/t/1e8:.2f}e8 upd/s)")

    # 2. full current-code tree build at bench shape (ground truth)
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        ForestConfig, _build_tree, _pack_bins, next_pow2,
    )

    d = 256
    bins = _pack_bins(jnp.asarray(rng.integers(0, NB, size=(N, d)), jnp.uint8))
    stats = jnp.asarray(
        np.stack([rng.random(N), rng.random(N)], axis=1), jnp.float32
    )
    valid = jnp.ones((N,), jnp.float32)
    cfg = ForestConfig(
        max_depth=13, n_bins=NB, n_features=d, n_stats=S,
        impurity="gini", k_features=16, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True,
        hist_strategy="auto", contract_gather="auto",
    )

    @jax.jit
    def one_tree(bins, stats, valid, key):
        out = _build_tree(bins, stats, valid, key, cfg)
        return out["leaf_stats"].sum() + out["gain"].sum()

    key = jax.random.PRNGKey(0)
    t = timeit_looped(one_tree, bins, stats, valid, key, iters=1, reps=3)
    print(f"2. full _build_tree depth13 (current code): {t*1e3:.1f} ms")

    # 3. Pallas kernel bf16 variant comparison is deferred; re-measure f32
    #    with the select-guard to match methodology
    from spark_rapids_ml_tpu.ops.rf_pallas import subblock_hist

    binq = jnp.asarray(rng.integers(0, NB, size=(N, K)), jnp.int32)
    swq = jnp.asarray(rng.random((N, S)), jnp.float32)

    for r_sub in (8, 16):
        @jax.jit
        def phist_loop(binq, swq):
            def body(_, c):
                b2 = jnp.where(c >= 0.0, binq, 0)
                h = subblock_hist(b2, swq, n_bins=NB, r_sub=r_sub)
                return h.sum()

            return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t = timeit_looped(phist_loop, binq, swq)
        print(f"3. pallas subblock hist guarded (r_sub={r_sub}): {t*1e3:.2f} ms")


if __name__ == "__main__":
    main()
