"""Primitive measurements for the deep-level RF histogram redesign (round 4).

Round 3 established the XLA envelope: every histogram formulation XLA can
see bottoms out at ~1.2e8 scatter updates/s (docs/rf_performance.md).
The round-4 candidate bypasses XLA's one-hot-dot->scatter rewrite with a
Pallas kernel over node-contiguous rows. Its viability hinges on numbers
this script measures on the real chip:

  1. the status-quo per-level scatter cost (re-confirm the wall)
  2. row-permute gather X[perm] throughput (the compaction's per-level
     data movement)
  3. multi-operand lax.sort cost (fallback permutation application)
  4. wide-row scatter at histogram width (candidate final reduce)
  5. big-2D cumsum cost (candidate final reduce, cumsum-diff form)
  6. the Pallas sub-block histogram kernel itself

Timing methodology: a dispatch+fetch round trip costs far more than a
single op, swamping single-op timings. Every measurement therefore
runs the op ITERS times inside one jitted fori_loop with a data
dependence through the carry (so XLA cannot hoist or CSE the body), and
divides out the loop count. A scalar fetch proves completion.
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

ITERS = 32


def timeit_looped(jitted, *args, reps=3, warmup=1):
    """Time `jitted` (which runs its op ITERS times internally); returns
    seconds per op iteration."""
    for _ in range(warmup):
        np.asarray(jnp.ravel(jitted(*args))[:1])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jnp.ravel(jitted(*args))[:1])
        ts.append(time.perf_counter() - t0)
    return min(ts) / ITERS


# bench shape
N = 131072
K = 16          # k_pad (feature subset)
NB = 128
S = 2
N_NODES = 4096  # level 12


def main():
    print("devices:", jax.devices())
    rng = np.random.default_rng(0)
    binc = jnp.asarray(rng.integers(0, NB, size=(N, K)), jnp.int32)
    sw = jnp.asarray(rng.random((N, S)), jnp.float32)
    local = jnp.asarray(rng.integers(0, N_NODES, size=(N,)), jnp.int32)

    # 0. RTT floor
    @jax.jit
    def nop(x):
        return x.sum()

    for _ in range(2):
        np.asarray(nop(sw))
    t0 = time.perf_counter()
    np.asarray(nop(sw))
    print(f"0. dispatch+fetch floor: {(time.perf_counter()-t0)*1e3:.1f} ms")

    # 1. status-quo scatter level
    @jax.jit
    def hist_scatter_loop(binc, local, sw):
        def body(_, c):
            ids = local[:, None] * NB + binc + (c.astype(jnp.int32) % 1)
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
    print(f"1. scatter level (n={N}, k={K}, S={S}): {t*1e3:.2f} ms "
          f"({N*K*S/t/1e8:.2f}e8 upd/s)")

    # 2. row-permute gather: carry the gathered matrix (serializes reps)
    perm = jnp.asarray(rng.permutation(N), jnp.int32)
    for W in (1, 8, 16):
        X = jnp.asarray(rng.integers(0, 1 << 30, size=(N, W)), jnp.int32)

        @jax.jit
        def rowperm_loop(X, perm):
            def body(_, Xc):
                return Xc[perm]

            return lax.fori_loop(0, ITERS, body, X).sum()

        t = timeit_looped(rowperm_loop, X, perm)
        print(f"2. row-permute gather (n={N}, w={W}): {t*1e3:.2f} ms "
              f"({N*W/t/1e9:.2f}e9 elem/s)")

    # 3. lax.sort key + payloads (key re-derived from carry each iter)
    key0 = jnp.asarray(rng.integers(0, N_NODES * 2, size=(N,)), jnp.int32)
    for n_payload in (1, 4):
        pls = [
            jnp.asarray(rng.integers(0, 1 << 30, size=(N,)), jnp.int32)
            for _ in range(n_payload)
        ]

        @jax.jit
        def sort_loop(key0, *pls):
            def body(_, k):
                out = lax.sort((k,) + pls, num_keys=1)
                return out[0] ^ 1  # depend on result, change key bits

            return lax.fori_loop(0, ITERS, body, key0).sum()

        t = timeit_looped(sort_loop, key0, *pls)
        print(f"3. lax.sort key+{n_payload} payloads: {t*1e3:.2f} ms")

    # 4. wide-row scatter: n_sb rows of width K*NB*S into N_NODES slots
    for n_sb in (8192, 20480):
        Wd = K * NB * S
        rows = jnp.asarray(rng.random((n_sb, Wd)), jnp.float32)
        seg = jnp.asarray(np.sort(rng.integers(0, N_NODES, size=(n_sb,))), jnp.int32)

        @jax.jit
        def wscatter_loop(rows, seg):
            def body(_, c):
                h = jax.ops.segment_sum(rows + c, seg, num_segments=N_NODES)
                return h.sum()

            return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t = timeit_looped(wscatter_loop, rows, seg)
        print(f"4. wide-row scatter ({n_sb} x {Wd}): {t*1e3:.2f} ms "
              f"({n_sb/t/1e6:.2f}e6 rows/s)")

    # 5. cumsum-diff segment reduce on (n_sb, W)
    for n_sb in (8192, 20480):
        Wd = K * NB * S
        rows = jnp.asarray(rng.random((n_sb, Wd)), jnp.float32)
        ends = jnp.asarray(
            np.sort(rng.choice(n_sb, N_NODES, replace=False)), jnp.int32
        )

        @jax.jit
        def cumdiff_loop(rows, ends):
            def body(_, c):
                cm = jnp.cumsum(rows + c, axis=0)
                seg_end = cm[ends]
                return (seg_end[1:] - seg_end[:-1]).sum()

            return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t = timeit_looped(cumdiff_loop, rows, ends)
        print(f"5. cumsum-diff reduce ({n_sb} x {Wd}): {t*1e3:.2f} ms")

    # 6. Pallas sub-block histogram kernel
    from spark_rapids_ml_tpu.ops.rf_pallas import subblock_hist, rf_hist_pallas_ok

    for r_sub in (8, 16, 32):
        n_pad = N
        if not rf_hist_pallas_ok(n_pad, K, NB, S, r_sub):
            print(f"6. pallas subblock hist r_sub={r_sub}: not eligible")
            continue
        binq = jnp.asarray(rng.integers(0, NB, size=(n_pad, K)), jnp.int32)
        swq = jnp.asarray(rng.random((n_pad, S)), jnp.float32)

        @jax.jit
        def phist_loop(binq, swq):
            def body(_, c):
                h = subblock_hist(
                    binq, swq + c, n_bins=NB, r_sub=r_sub
                )
                return h.sum()

            return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t = timeit_looped(phist_loop, binq, swq)
        print(f"6. pallas subblock hist (n={n_pad}, r_sub={r_sub}): {t*1e3:.2f} ms "
              f"({n_pad*K*S/t/1e8:.2f}e8 upd/s-equiv)")

    # 7. cumsum-diff at Pallas output granularity (n_sb, S, W)
    for r_sub in (8, 16):
        n_sb = N // r_sub + N_NODES
        Wd = K * NB
        rows = jnp.asarray(rng.random((n_sb, S * Wd)), jnp.float32)
        ends = jnp.asarray(
            np.sort(rng.choice(n_sb, N_NODES, replace=False)), jnp.int32
        )

        @jax.jit
        def cumdiff2_loop(rows, ends):
            def body(_, c):
                cm = jnp.cumsum(rows + c, axis=0)
                seg_end = cm[ends]
                return (seg_end[1:] - seg_end[:-1]).sum()

            return lax.fori_loop(0, ITERS, body, jnp.float32(0.0))

        t = timeit_looped(cumdiff2_loop, rows, ends)
        print(f"7. cumsum-diff ({n_sb} x {S*Wd}) [r_sub={r_sub}]: {t*1e3:.2f} ms")


if __name__ == "__main__":
    main()
