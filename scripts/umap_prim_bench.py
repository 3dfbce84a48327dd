"""Micro-bench the primitive ops that bound the UMAP SGD epoch on this chip.

All timings amortize the dispatch+fetch round trip with a 16-iter fori_loop
whose body depends non-foldably on the carry.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

N = 65536
M = 1_769_472  # bench edge count padded
ITERS = 16


def timed(fn, *args, reps=3):
    jitted = jax.jit(fn)
    out = float(jitted(jnp.float32(0.0), *args))
    best = 1e30
    for r in range(reps):
        # fresh salt per rep: no rep repeats an earlier (executable,
        # buffers) pair exactly, which a remote backend may memoize
        salt = jnp.float32(1e-22 * (r + 1))
        t0 = time.perf_counter()
        float(jitted(salt, *args))  # scalar fetch forces completion
        best = min(best, time.perf_counter() - t0)
    print(f"  [raw best {best*1e3:.1f} ms for {ITERS} iters]")
    return best / ITERS, out


def loop(body):
    """fori_loop wrapper: body(carry_scalar, i) -> array; carries a scalar
    checksum so nothing folds."""
    def fn(salt, *args):
        def step(i, c):
            out = body(c, i, *args)
            # consume the FULL output or XLA dead-code-eliminates the op
            return c + out.sum()
        return lax.fori_loop(0, ITERS, step, salt)
    return fn


def main():
    rng = np.random.default_rng(0)
    emb2 = jnp.asarray(rng.normal(size=(N, 2)).astype(np.float32))
    emb128 = jnp.asarray(rng.normal(size=(N, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, size=(M,)).astype(np.int32))
    idx_s = jnp.sort(idx)
    grads2 = jnp.asarray(rng.normal(size=(M, 2)).astype(np.float32))

    def dep(c, x):
        # non-foldable carry dependence on the whole array
        return jnp.where(c >= jnp.float32(-1e30), x, 0.0)

    # 1) gather (M,2) from (N,2)
    t, _ = timed(loop(lambda c, i, e, ix: e[dep_idx(ix, c)][:, :2]), emb2, idx)
    print(f"gather (M,2)<-({N},2): {t*1e3:.1f} ms -> {M/t/1e6:.0f}M rows/s")

    # 2) gather (M,128) from (N,128)
    t, _ = timed(loop(lambda c, i, e, ix: e[dep_idx(ix, c)]), emb128, idx)
    print(f"gather (M,128)<-({N},128): {t*1e3:.1f} ms -> {M*512/t/1e9:.0f} GB/s, {M/t/1e6:.0f}M rows/s")

    # 2b) sorted-idx gather (M,2)
    t, _ = timed(loop(lambda c, i, e, ix: e[dep_idx(ix, c)]), emb2, idx_s)
    print(f"gather sorted (M,2): {t*1e3:.1f} ms -> {M/t/1e6:.0f}M rows/s")

    # 3) segment_sum (M,2) -> (N,2)
    def seg(c, i, g, ix):
        return jax.ops.segment_sum(dep(c, g), ix, num_segments=N)
    t, _ = timed(loop(seg), grads2, idx)
    print(f"segment_sum (M,2)->({N},2): {t*1e3:.1f} ms -> {M/t/1e6:.0f}M rows/s")

    # 3b) segment_sum sorted ids with indices_are_sorted
    def seg_s(c, i, g, ix):
        return jax.ops.segment_sum(dep(c, g), ix, num_segments=N,
                                   indices_are_sorted=True)
    t, _ = timed(loop(seg_s), grads2, idx_s)
    print(f"segment_sum sorted: {t*1e3:.1f} ms -> {M/t/1e6:.0f}M rows/s")

    # 4) random permutation of N
    def perm(c, i, k):
        kk = jax.random.fold_in(k, i + c.astype(jnp.int32))
        return jax.random.permutation(kk, N).astype(jnp.float32)
    t, _ = timed(loop(perm), jax.random.PRNGKey(0))
    print(f"permutation({N}): {t*1e3:.2f} ms")

    # 5) uniform ints (M,5) generation (current neg sampling cost, no gather)
    def ri(c, i, k):
        kk = jax.random.fold_in(k, i + c.astype(jnp.int32))
        return jax.random.randint(kk, (M, 5), 0, N).astype(jnp.float32)
    t, _ = timed(loop(ri), jax.random.PRNGKey(0))
    print(f"randint (M,5): {t*1e3:.2f} ms")

    # 6) gather (M,5,2) negatives from (N,2)  [current formulation]
    idx5 = jnp.asarray(rng.integers(0, N, size=(M, 5)).astype(np.int32))
    def negg(c, i, e, ix):
        return e[dep_idx(ix.reshape(-1), c)].reshape(M, 5, 2)
    t, _ = timed(loop(negg), emb2, idx5)
    print(f"gather (M*5,2) negs: {t*1e3:.1f} ms -> {5*M/t/1e6:.0f}M rows/s")

    # 7) one-hot matmul gather: emb(N,128) gathered for M rows via blocked
    #    dot against one-hot built from iota — XLA (not pallas), block 8192
    B = 8192
    nb = M // B
    def oh(c, i, e, ix):
        ixb = dep_idx(ix[:B], c)
        oneh = (ixb[:, None] == jnp.arange(N)[None, :]).astype(jnp.bfloat16)
        return (oneh @ e.astype(jnp.bfloat16)).astype(jnp.float32)
    t, _ = timed(loop(oh), emb128, idx)
    print(f"one-hot dot gather block {B} from ({N},128): {t*1e3:.2f} ms/block -> full M: {t*nb*1e3:.0f} ms")


def dep_idx(ix, c):
    # non-foldable carry dependence (memory note: c*0 gets folded+hoisted)
    return jnp.where(c >= jnp.float32(-1e30), ix, 0)


if __name__ == "__main__":
    main()
