"""Fused Pallas distance + exact top-k pass for the kNN ring.

The XLA ring step materializes every (qc, ic) distance tile in HBM and
runs ``lax.top_k`` over it; measured on v5e at the bench shape the tile
matmul+epilogue costs ~9 ms and the top_k read adds ~21 ms at an effective
51 GB/s — the selection, not the math, dominates (12 s of a 13.3 s
kneighbors call). This kernel keeps the whole tile VMEM-resident and
replaces the sort with a tau-gated extraction loop:

* score = ||xi||^2 - 2 xq.xi (the row-constant ||xq||^2 cannot change a
  row's ordering; it is added back once, outside, like the Lloyd kernel);
  masked/padded items ride in with score +inf via their ||xi||^2;
* a ``lax.while_loop`` extracts the block's best candidate and inserts it
  into the running (k)-slot state, repeating only while some row still has
  a candidate better than its current k-th best (tau). Once tau tightens
  (a few ring blocks in), most blocks run ZERO iterations — the loop
  condition is the only full-tile read, and it fuses with the matmul.
* Exactness: each iteration inserts the globally best remaining candidate
  of the block; k iterations bound the loop because a block's (k+1)-th
  best can never enter the top-k alongside its k better neighbours.
  The distance product itself runs at ``Precision.HIGHEST``: Mosaic's
  default for f32 operands is a reduced product, under which the
  selection is exact over inexact scores (``chip_smoke.py`` holds the
  result against an f64 brute force).

Reference role: replaces the fused distance+select kernels cuML's
``NearestNeighborsMG.kneighbors`` runs per partition pair
(``/root/reference/python/src/spark_rapids_ml/knn.py:553-564``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax

# Test hook (mirrors ops.kmeans_pallas.FORCE_INTERPRET).
FORCE_INTERPRET = False

# Block sizes trade grid overhead + item-matrix re-reads against VMEM:
# the item shard is swept once per QUERY block, so HBM traffic scales as
# (nq/_QB) * ni * d * 4 — at the bench shape (131k x 1M x 256) the
# original 256-row query blocks cost 512 GB of Xi re-reads (and a ~1M
# step grid); 2048-row blocks cut that to 64 GB / 62k steps. The
# (QB, IB) f32 score tile and its while-carry copies stay ~8 MB each,
# well inside the 100 MB budget.
_QB = 2048  # query rows per block
_IB = 1024  # item cols per block


# Hardware-lowering probe results per (d, k); the probe policy lives in
# ops.linalg.probe_pallas_lowering.
_LOWERING_OK: dict = {}


def _probe_lowering(d: int, k: int) -> bool:
    from .linalg import probe_pallas_lowering

    def compile_fn():
        args = (
            jax.ShapeDtypeStruct((_QB, d), jnp.float32),
            jax.ShapeDtypeStruct((_IB, d), jnp.float32),
            jax.ShapeDtypeStruct((1, _IB), jnp.float32),
            jax.ShapeDtypeStruct((1, _IB), jnp.int32),
            jax.ShapeDtypeStruct((_QB, k), jnp.float32),
            jax.ShapeDtypeStruct((_QB, k), jnp.int32),
        )
        knn_pallas_pass.lower(*args).compile()

    return probe_pallas_lowering(_LOWERING_OK, (d, k), compile_fn, "fused kNN")


def knn_pallas_ok(nq: int, ni: int, d: int, k: int, dtype) -> bool:
    """Trace-time gate: TPU, f32, lane-aligned d, block-aligned shapes,
    and k small enough that the (QB, k) state stays trivial."""
    ok = (
        (jax.default_backend() == "tpu" or FORCE_INTERPRET)
        and dtype == jnp.float32
        and d % 128 == 0
        and nq % _QB == 0
        and ni % _IB == 0
        and 1 <= k <= 128
    )
    if ok and not FORCE_INTERPRET:
        ok = _probe_lowering(d, k)
    return ok


@functools.partial(jax.jit, static_argnames=("interpret",))
def knn_pallas_pass(
    Xq: jax.Array,       # (nq, d) f32
    Xi: jax.Array,       # (ni, d) f32 — current ring shard
    csq_eff: jax.Array,  # (1, ni) f32: ||xi||^2, +inf for masked items
    ids: jax.Array,      # (1, ni) int32 global item ids
    topd: jax.Array,     # (nq, k) f32 running scores (NO ||xq||^2 term)
    topi: jax.Array,     # (nq, k) int32 running global ids
    *,
    interpret: bool | None = None,
):
    """One full (nq x ni) pass folding every item of the shard into the
    running top-k state. Returns (topd, topi) updated."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    nq, d = Xq.shape
    ni = Xi.shape[0]
    k = topd.shape[1]

    def kern(xq_ref, xi_ref, csq_ref, ids_ref, td_in, ti_in, td_ref, ti_ref):
        ii = pl.program_id(1)

        @pl.when(ii == 0)
        def _():
            td_ref[:] = td_in[:]
            ti_ref[:] = ti_in[:]

        xq = xq_ref[:]                    # (QB, d)
        xi = xi_ref[:]                    # (IB, d)
        # HIGHEST: Mosaic's default for f32 operands is a reduced product,
        # which on v5e put 11% of queries on a wrong neighbour set (PR 22)
        xc = jax.lax.dot_general(
            xq, xi, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                 # (QB, IB)
        score0 = csq_ref[:] - 2.0 * xc    # (1, IB) broadcasts; +inf = masked
        lane_k = jax.lax.broadcasted_iota(jnp.int32, (_QB, k), 1)
        lane_ib = jax.lax.broadcasted_iota(jnp.int32, (_QB, _IB), 1)
        ids_b = ids_ref[:]                # (1, IB)

        def cond(carry):
            j, score, td, ti = carry
            tau = jnp.max(td, axis=1, keepdims=True)
            m = jnp.min(score, axis=1, keepdims=True)
            return jnp.logical_and(j < k, jnp.any(m < tau))

        def body(carry):
            j, score, td, ti = carry
            tau = jnp.max(td, axis=1, keepdims=True)
            m = jnp.min(score, axis=1, keepdims=True)        # (QB, 1)
            am = jnp.argmin(score, axis=1, keepdims=True)    # first-min lane
            firstm = (lane_ib == am) & (m < tau)             # (QB, IB)
            sel = jnp.sum(
                jnp.where(firstm, jnp.broadcast_to(ids_b, firstm.shape), 0),
                axis=1, keepdims=True,
            )                                                # (QB, 1)
            worst = jnp.argmax(td, axis=1, keepdims=True)
            repl = (lane_k == worst) & (m < tau)
            td = jnp.where(repl, jnp.broadcast_to(m, td.shape), td)
            ti = jnp.where(repl, jnp.broadcast_to(sel, ti.shape), ti)
            score = jnp.where(firstm, jnp.inf, score)
            return (j + 1, score, td, ti)

        _, _, td, ti = lax.while_loop(
            cond, body, (jnp.int32(0), score0, td_ref[:], ti_ref[:])
        )
        td_ref[:] = td
        ti_ref[:] = ti

    return pl.pallas_call(
        kern,
        grid=(nq // _QB, ni // _IB),
        in_specs=[
            pl.BlockSpec((_QB, d), lambda qi, ii: (qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_IB, d), lambda qi, ii: (ii, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _IB), lambda qi, ii: (0, ii),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _IB), lambda qi, ii: (0, ii),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_QB, k), lambda qi, ii: (qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_QB, k), lambda qi, ii: (qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((_QB, k), lambda qi, ii: (qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_QB, k), lambda qi, ii: (qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(Xq, Xi, csq_eff, ids, topd, topi)
