"""Fused Pallas Lloyd step: assignment + centroid stats in ONE data pass.

The XLA chunked step (``kmeans_kernels._chunk_stats``) materializes two
(csize, k) intermediates per chunk in HBM — the distance tile consumed by
argmin and the assignment one-hot consumed by the stats contraction and
the counts reduction (~268 MB each at csize=65536, k=1024, f32). Measured
effect on v5e at 12M x 256 / k=1024: the iteration runs at ~103 ms where
the two MXU contractions alone price at ~64 ms (bf16) — and switching the
contractions to bf16 does not move the time, the signature of an
HBM-intermediate-bound loop, not an MXU-bound one.

This kernel streams row tiles HBM->VMEM once and keeps EVERYTHING else
VMEM-resident: distances (computed as ``c_sq - 2 x.c``; ``x_sq`` joins
only for the cost, it cannot change the argmin), the one-hot, and the
(k, d) sums / (k,) counts / cost accumulators. HBM traffic per iteration
drops to one read of X.

Numerics match the XLA step: f32 accumulation everywhere;
``matmul_dtype=bfloat16`` rounds only the two contraction operands (the
one-hot is exact in bf16; x rounds at ~1e-3 relative, washed out by the
per-cluster mean) — the same contract as ``kmeans_kernels.stats_dot``.

Reference role: this replaces the fused distance+update kernels cuML's
KMeans runs per minibatch (``/root/reference/python/src/spark_rapids_ml/
clustering.py`` drives cuml.cluster.KMeans_mg whose CUDA kernels fuse
pairwise distances with the assignment reduction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# Test hook (mirrors ops.linalg.FORCE_INTERPRET): run the kernel through
# the Pallas interpreter on CPU so tests cover the real kernel body.
FORCE_INTERPRET = False

# rows per VMEM tile: (tile, k) f32 distance block is the big resident —
# 8 MB at tile=2048, k=1024 — plus the (k, d) f32 sums accumulator (1 MB
# at k=1024, d=256). Both double-buffered operands stay well inside the
# 100 MB budget.
_TILE = 2048


# Hardware-lowering probe results keyed by (d, k_pad, matmul_dtype); the
# policy lives in ops.linalg.probe_pallas_lowering. (n does not affect
# lowering — it only changes the grid length — so one tile suffices.)
_LOWERING_OK: dict = {}


def _probe_lowering(d: int, k: int, matmul_dtype, exact: bool = False) -> bool:
    from .linalg import probe_pallas_lowering

    key = (
        d, -(-k // 128) * 128,
        jnp.dtype(matmul_dtype).name if matmul_dtype else None, exact,
    )

    def compile_fn():
        # avals only — the probe may run while an outer fit is tracing,
        # so no device buffers and nothing the outer trace could capture
        x = jax.ShapeDtypeStruct((_TILE, d), jnp.float32)
        m = jax.ShapeDtypeStruct((_TILE,), jnp.float32)
        c = jax.ShapeDtypeStruct((k, d), jnp.float32)
        lloyd_step_pallas.lower(
            x, m, c, matmul_dtype=matmul_dtype, exact=exact
        ).compile()

    return probe_pallas_lowering(_LOWERING_OK, key, compile_fn, "fused Lloyd")


def kmeans_pallas_ok(
    n_local: int, d: int, k: int, dtype, matmul_dtype=None, exact: bool = False
) -> bool:
    """Trace-time gate: TPU, f32 input, lane-aligned d (KMeans ingestion
    pads features to 128, so the reference d=3000 shape qualifies), local
    rows divisible by the tile (the shard_rows csize invariant makes the
    padded count a 65536-multiple in practice), and a (tile, k_pad)
    distance block + (k_pad, d) accumulator that fit the VMEM budget."""
    k_pad = -(-k // 128) * 128
    # residents: double-buffered (tile, k_pad) distance/one-hot temporaries,
    # the centers INPUT and the sums OUTPUT (both (k_pad, d) f32), and
    # double-buffered (tile, d) row blocks
    vmem = (
        _TILE * k_pad * 4 * 2
        + 2 * k_pad * d * 4
        + _TILE * d * 4 * 2
    )
    ok = (
        (jax.default_backend() == "tpu" or FORCE_INTERPRET)
        and dtype == jnp.float32
        and d % 128 == 0
        and n_local % _TILE == 0
        and vmem < 90 * 1024 * 1024
    )
    if ok and not FORCE_INTERPRET:
        ok = _probe_lowering(d, k, matmul_dtype, exact)
    return ok


@functools.partial(
    jax.jit, static_argnames=("matmul_dtype", "exact", "interpret")
)
def lloyd_step_pallas(
    Xl: jax.Array,       # (n_local, d) f32 — padded rows carry mask 0
    ml: jax.Array,       # (n_local,) f32 row validity
    centers: jax.Array,  # (k, d) f32
    *,
    matmul_dtype=None,
    exact: bool = False,
    interpret: bool | None = None,
):
    """One Lloyd accumulation pass over local rows.

    Returns (sums (k, d) f32, counts (k,) int32, cost () f32) — the same
    triple as ``kmeans_kernels._chunk_stats``, before the cross-device
    psum. ``exact`` runs the distance contraction at
    ``Precision.HIGHEST`` (Mosaic's default for f32 operands is a reduced
    product): the pass whose cost is reported asks for it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n, d = Xl.shape
    k = centers.shape[0]
    k_pad = -(-k // 128) * 128
    if k_pad > k:
        # padded centers must never win the argmin: +inf squared norm
        centers = jnp.pad(centers, ((0, k_pad - k), (0, 0)))
        c_sq = jnp.concatenate(
            [
                (centers[:k] * centers[:k]).sum(axis=1),
                jnp.full((k_pad - k,), jnp.inf, jnp.float32),
            ]
        )
    else:
        c_sq = (centers * centers).sum(axis=1)
    cd = centers.astype(matmul_dtype) if matmul_dtype is not None else centers

    def kern(x_ref, m_ref, c_ref, csq_ref, sums_ref, counts_ref, cost_ref):
        # Everything stays 2-D (keepdims): Mosaic rejects both scalar VMEM
        # stores and 1-D full reductions ("Offset change" on
        # vector<1x2048> -> vector<1>) — both discovered only on hardware.
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            sums_ref[:] = jnp.zeros_like(sums_ref)
            counts_ref[:] = jnp.zeros_like(counts_ref)
            cost_ref[:] = jnp.zeros_like(cost_ref)

        x = x_ref[:]                       # (tile, d) f32
        # mask loads 1-D ((tile,) linear layout: a (n, 1) operand would be
        # tile-padded T(8,128) = 128x HBM expansion + a full copy) and is
        # expanded to (tile, 1) in-register for the 2-D ops below
        m = m_ref[:][:, None]              # (tile, 1) f32
        xd = x.astype(cd.dtype)
        xc = jax.lax.dot_general(
            xd, c_ref[:], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if exact else None,
            preferred_element_type=jnp.float32,
        )                                  # (tile, k_pad)
        # x_sq is row-constant: it joins for the cost only, never the argmin
        part = csq_ref[:] - 2.0 * xc       # (1, k_pad) - : broadcasts
        a = jnp.argmin(part, axis=1, keepdims=True)   # (tile, 1)
        best = jnp.min(part, axis=1, keepdims=True)   # (tile, 1)
        x_sq = (x * x).sum(axis=1, keepdims=True)     # (tile, 1)
        contrib = jnp.maximum(best + x_sq, 0.0) * m   # (tile, 1)
        cost_ref[:, :] += jnp.sum(contrib, axis=0, keepdims=True)
        onehot = (
            a == jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
        )                                  # (tile, k_pad) bool
        counts_ref[:] += jnp.sum(
            onehot & (m > 0), axis=0, keepdims=True
        ).astype(jnp.int32)
        oh = onehot.astype(cd.dtype) * m.astype(cd.dtype)
        sums_ref[:] += jax.lax.dot_general(
            oh, xd, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                  # (k_pad, d)

    sums, counts, cost = pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, _TILE),),
        in_specs=[
            pl.BlockSpec((_TILE, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((k_pad, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((k_pad, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(Xl, ml, cd, c_sq.reshape(1, k_pad))
    return sums[:k], counts[0, :k], cost[0, 0]
