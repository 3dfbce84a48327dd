"""Per-op timing of the RF level loop at the bench shape (131072 x 256,
nb=128, k=16) — attributes the ~30 ms/level fixed cost the depth sweep
exposed (fit time is linear in depth with a level-width-independent
constant, so histogram arithmetic is NOT the bound).

Each candidate op is timed as ONE jitted call that runs the op R times in a
``lax.scan`` whose carry feeds back into the op's inputs — the chain defeats
loop-invariant hoisting, and the single dispatch amortizes the
dispatch+fetch round trip.

Usage: python scripts/rf_microbench.py  (expects a reachable TPU; falls
back to whatever jax.default_backend() is and says so).
"""

import time
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

N, D, NB, K, S = 131072, 256, 128, 16, 2
R = 30


def timed_op(name, build):
    """build(key) -> (init_carry, scan_body). Times R chained iterations."""
    carry0, body = build(jax.random.key(0))

    @jax.jit
    def run(carry0):
        c, _ = lax.scan(body, carry0, jnp.arange(R))
        return jax.tree.map(
            lambda l: jnp.asarray(l, jnp.float32).sum() if l.size > 64 else l, c
        )

    out = jax.block_until_ready(run(carry0))  # compile
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(carry0))
    dt = (time.perf_counter() - t0) / R
    print(f"{name:34s} {dt*1e3:8.2f} ms/op")
    return dt


def main():
    print("backend:", jax.default_backend(), jax.devices()[:1])
    kx, kb, kf, kn = jax.random.split(jax.random.key(1), 4)
    bins = jax.random.randint(kb, (N, D), 0, NB, jnp.uint8)
    node = jax.random.randint(kn, (N,), 0, 4096, jnp.int32)
    feats = jax.random.randint(kf, (4096, K), 0, D, jnp.int32)
    sw = jax.random.uniform(kx, (N, S), jnp.float32)
    jax.block_until_ready((bins, node, feats, sw))

    def dep_idx(c):
        # data-dependent 0/1 the compiler cannot fold
        return (jnp.float32(c).astype(jnp.int32) & 1).astype(jnp.int32)

    # A: per-row k-column gather from the big bin matrix (hist_src build)
    def build_a(_):
        def body(c, i):
            rf = jnp.clip(feats[jnp.clip(node + dep_idx(c), 0, 4095)], 0, D - 1)
            g = jnp.take_along_axis(bins, rf, axis=1)  # (N, K)
            return jnp.float32(g.sum()), None
        return jnp.float32(0), body

    # B: node -> feature-row table lookup only (small table)
    def build_b(_):
        def body(c, i):
            rf = feats[jnp.clip(node + dep_idx(c), 0, 4095)]
            return jnp.float32(rf.sum()), None
        return jnp.float32(0), body

    # C: single-column per-row gather (row routing read)
    def build_c(_):
        def body(c, i):
            col = jnp.clip(node + dep_idx(c), 0, D - 1)[:, None]
            g = jnp.take_along_axis(bins, col, axis=1)[:, 0]
            return jnp.float32(g.sum()), None
        return jnp.float32(0), body

    # D: parent segment_sum (N, S) -> 4096 nodes
    def build_d(_):
        def body(c, i):
            seg = jnp.clip(node + dep_idx(c), 0, 4096)
            p = jax.ops.segment_sum(sw, seg, num_segments=4097)
            return jnp.float32(p.sum()), None
        return jnp.float32(0), body

    # E: per-node top_k feature draw (deepest level: 4096 nodes)
    def build_e(k):
        def body(c, i):
            r = jax.random.uniform(jax.random.fold_in(k, i), (4096, D))
            t = lax.top_k(r + c * 0.0, K)[1]
            return jnp.float32(t.sum()), None
        return jnp.float32(0), body

    # F: one matmul-path histogram level at n_nodes=1024, d_hist=16
    def build_f(_):
        n_nodes, F = 1024, 16
        Cc = 8192
        binc = jax.random.randint(kb, (N, F), 0, NB, jnp.uint8).astype(jnp.int32)
        loc = jnp.clip(node, 0, n_nodes - 1)
        node_ar = jnp.arange(n_nodes, dtype=jnp.int32)
        bin_ar = jnp.arange(NB, dtype=jnp.int32)

        def body(c, i):
            def row_body(ri, acc):
                start = ri * Cc
                bc = lax.dynamic_slice(binc, (start, 0), (Cc, F))
                lo = lax.dynamic_slice(loc, (start,), (Cc,)) + dep_idx(c) * 0
                swc = lax.dynamic_slice(sw, (start, 0), (Cc, S))
                Noh = (lo[:, None] == node_ar[None, :]).astype(jnp.float32)
                Boh = (bc[:, :, None] == bin_ar[None, None, :]).astype(
                    jnp.float32
                ).reshape(Cc, F * NB)
                return acc + jnp.stack(
                    [jnp.matmul((Noh * swc[:, s][:, None]).T, Boh) for s in range(S)],
                    axis=-1,
                )
            acc = lax.fori_loop(
                0, N // Cc, row_body, jnp.zeros((n_nodes, F * NB, S), jnp.float32)
            )
            return jnp.float32(acc.sum()), None
        return jnp.float32(0), body

    # G: one scatter-path histogram level at n_nodes=2048, d_hist=16
    def build_g(_):
        n_nodes, F = 2048, 16
        binc = jax.random.randint(kb, (N, F), 0, NB, jnp.int32)
        loc = jnp.clip(node, 0, n_nodes - 1)

        def body(c, i):
            ids = loc[:, None] * NB + binc + dep_idx(c) * 0
            hist = jnp.stack(
                [
                    jax.vmap(
                        lambda col, cc=sw[:, s]: jax.ops.segment_sum(
                            cc, col, num_segments=n_nodes * NB + 1
                        ),
                        in_axes=1,
                    )(ids)
                    for s in range(S)
                ],
                axis=-1,
            )
            return jnp.float32(hist.sum()), None
        return jnp.float32(0), body

    timed_op("A  hist_src row-gather (N,K)<-D", build_a)
    timed_op("B  node->feats table lookup", build_b)
    timed_op("C  single-col row gather", build_c)
    timed_op("D  parent segment_sum", build_d)
    timed_op("E  top_k feature draw @4096", build_e)
    timed_op("F  matmul hist level n_nodes=1024", build_f)
    timed_op("G  scatter hist level n_nodes=2048", build_g)


if __name__ == "__main__":
    main()
