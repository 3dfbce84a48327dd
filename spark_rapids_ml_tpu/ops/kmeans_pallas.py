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
drops to one read of X. The same body without the one-hot and the sums is
the pass that computes the REPORTED cost (``lloyd_cost_pallas``), at
``Precision.HIGHEST``; the row tile of either follows from the shape
(``lloyd_tile``).

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

# The LARGEST row tile; the tile a shape runs at follows from the shape
# (:func:`lloyd_tile` halves from here until every VMEM resident fits). At
# d=256, k=1024 that is 2048: 31 MB counted, the three (tile, k_pad) f32
# temporaries of 8.4 MB the big residents beside 1 MB each of centres and
# sums. At the reference's padded width, d=3072 and k_pad=1024, every
# (k_pad, d) resident is 12.6 MB and the row tile gives way to 1024
# (PERF.md §6, PR 29, has what the compiler and the chip showed per tile).
_TILE = 2048
# The smallest: the mask rides in as a 1-D (tile,) block, and XLA lays a 1-D
# f32 operand out in tiles of 1024, which Mosaic wants the block to match
# ("XLA layout {0:T(1024)} does not match Mosaic layout {0:T(512)}").
_MIN_TILE = 1024
# lanes the cost is accumulated over (one vreg row)
_COST_LANES = 128
# Handed to Mosaic as its limit, and what the counted residents must fit.
_VMEM_LIMIT = 100 * 1024 * 1024


def _k_pad(k: int) -> int:
    return -(-k // 128) * 128


def lloyd_vmem_bytes(
    tile: int, d: int, k_pad: int, matmul_dtype=None, exact: bool = False,
    stats: bool = True,
) -> int:
    """VMEM the kernel holds at one row tile, every resident counted:

    * the (tile, d) f32 row block and the (k_pad, d) centres in the operand
      dtype, two buffers each (the pipeline's), and the mask;
    * with ``stats`` the (k_pad, d) f32 sums the pass accumulates into;
    * three (tile, k_pad) f32 temporaries (``xc``, ``part`` and the one-hot;
      the cost pass keeps a third one live too, by the compiler's own count);
    * a narrow operand's copy of the row tile (``x.astype``);
    * at ``exact`` the bf16 pieces ``Precision.HIGHEST`` splits both f32
      operands into inside Mosaic, three each.

    Against the compiler (v5e, d=3072, k_pad=1024, tile 2048, exact): 162.0
    MiB counted with ``stats`` where Mosaic reports 162.47 MiB used, 150.0
    MiB without where it reports 146.0-158.0 MiB."""
    item = jnp.dtype(matmul_dtype).itemsize if matmul_dtype is not None else 4
    need = 2 * tile * d * 4 + 2 * tile * 4 + 2 * k_pad * d * item
    if stats:
        need += k_pad * d * 4
    need += 3 * tile * k_pad * 4
    if item < 4:
        need += tile * d * item
    if exact:
        need += 3 * 2 * (tile + k_pad) * d
    return need


def lloyd_tile(
    d: int, k: int, matmul_dtype=None, exact: bool = False,
    stats: bool = True,
) -> tuple:
    """``(tile, vmem bytes)``: the largest power-of-two row tile, from
    ``_TILE`` down, whose residents (:func:`lloyd_vmem_bytes`) fit
    ``_VMEM_LIMIT``; ``(0, bytes at the smallest tile)`` where none does.
    The ONE rule the gate, the lowering probe and the kernel read: a
    function of the width, k (padded to 128 lanes), the operand dtype and
    what the pass computes, nothing else."""
    def need(tile):
        return lloyd_vmem_bytes(tile, d, _k_pad(k), matmul_dtype, exact, stats)

    tile = _TILE
    while need(tile) > _VMEM_LIMIT and tile // 2 >= _MIN_TILE:
        tile //= 2
    return (tile if need(tile) <= _VMEM_LIMIT else 0), need(tile)


# Hardware-lowering probe results keyed by (tile, d, k_pad, matmul_dtype,
# exact, stats); the policy lives in ops.linalg.probe_pallas_lowering. (n
# does not affect lowering — it only changes the grid length — so one tile
# of rows suffices.)
_LOWERING_OK: dict = {}


def _probe_lowering(
    d: int, k: int, matmul_dtype, exact: bool, stats: bool
) -> bool:
    from .linalg import probe_pallas_lowering

    tile, _ = lloyd_tile(d, k, matmul_dtype, exact, stats)
    key = (
        tile, d, _k_pad(k),
        jnp.dtype(matmul_dtype).name if matmul_dtype else None, exact, stats,
    )

    def compile_fn():
        # avals only — the probe may run while an outer fit is tracing,
        # so no device buffers and nothing the outer trace could capture
        x = jax.ShapeDtypeStruct((tile, d), jnp.float32)
        m = jax.ShapeDtypeStruct((tile,), jnp.float32)
        c = jax.ShapeDtypeStruct((k, d), jnp.float32)
        if stats:
            lloyd_step_pallas.lower(
                x, m, c, matmul_dtype=matmul_dtype, exact=exact
            ).compile()
        else:
            lloyd_cost_pallas.lower(x, m, c).compile()

    return probe_pallas_lowering(_LOWERING_OK, key, compile_fn, "fused Lloyd")


def kmeans_pallas_declined(
    n_local: int, d: int, k: int, dtype, matmul_dtype=None,
    exact: bool = False, stats: bool = True,
) -> str:
    """The terms of the fused kernel's gate that fail, comma-joined (empty:
    the kernel is admitted): TPU, f32 input, lane-aligned d (KMeans
    ingestion pads features to 128: the reference's d=3000 arrives as
    3072), a row tile (:func:`lloyd_tile`) whose residents fit VMEM, and
    at least one tile of local rows. The rows need not divide by the tile:
    the kernel zeroes what the last block holds past them. A pure function
    of its arguments and the backend: the estimator evaluates it again on
    the host to say on its ``solver.launch`` span which step a fit ran, and
    why."""
    tile, _ = lloyd_tile(d, k, matmul_dtype, exact, stats)
    terms = (
        ("backend", jax.default_backend() == "tpu" or FORCE_INTERPRET),
        ("dtype", dtype == jnp.float32),
        ("d%128", d % 128 == 0),
        ("tile", tile > 0),
        ("rows>=tile", n_local >= tile),
    )
    return ",".join(name for name, ok in terms if not ok)


def kmeans_pallas_ok(
    n_local: int, d: int, k: int, dtype, matmul_dtype=None,
    exact: bool = False, stats: bool = True,
) -> bool:
    """Trace-time gate (:func:`kmeans_pallas_declined`); on hardware a shape
    it admits is compiled once before first use, and a refusal raises."""
    ok = not kmeans_pallas_declined(
        n_local, d, k, dtype, matmul_dtype, exact, stats
    )
    if ok and not FORCE_INTERPRET:
        ok = _probe_lowering(d, k, matmul_dtype, exact, stats)
    return ok


def _lloyd_pass(Xl, ml, centers, *, matmul_dtype, exact, stats, interpret):
    """The pallas_call behind both passes: with ``stats`` the Lloyd
    accumulation (sums, counts, cost), without it the cost alone — no
    (k_pad, d) accumulator, no one-hot, no second contraction."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n, d = Xl.shape
    k = centers.shape[0]
    k_pad = _k_pad(k)
    tile, _ = lloyd_tile(d, k, matmul_dtype, exact, stats)
    if not tile:
        raise ValueError(
            f"fused Lloyd: no row tile fits n={n}, d={d}, k={k} "
            "(kmeans_pallas_ok gates this)"
        )
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

    def kern(x_ref, m_ref, c_ref, csq_ref, *out_refs):
        # Everything stays 2-D (keepdims): Mosaic rejects both scalar VMEM
        # stores and 1-D full reductions ("Offset change" on
        # vector<1x2048> -> vector<1>) — both discovered only on hardware.
        i = pl.program_id(0)
        cost_ref = out_refs[-1]

        @pl.when(i == 0)
        def _():
            for ref in out_refs:
                ref[:] = jnp.zeros_like(ref)

        x = x_ref[:]                       # (tile, d) f32
        # mask loads 1-D ((tile,) linear layout: a (n, 1) operand would be
        # tile-padded T(8,128) = 128x HBM expansion + a full copy) and is
        # expanded to (tile, 1) in-register for the 2-D ops below
        m = m_ref[:][:, None]              # (tile, 1) f32
        if n % tile:
            # the last block reaches past the rows: what it holds there is
            # unspecified, so those rows are zeroed with mask 0
            row = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            x = jnp.where(row < n, x, 0.0)
            m = jnp.where(row < n, m, 0.0)
        xd = x.astype(cd.dtype)
        xc = jax.lax.dot_general(
            xd, c_ref[:], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST if exact else None,
            preferred_element_type=jnp.float32,
        )                                  # (tile, k_pad)
        # x_sq is row-constant: it joins for the cost only, never the argmin
        part = csq_ref[:] - 2.0 * xc       # (1, k_pad) - : broadcasts
        best = jnp.min(part, axis=1, keepdims=True)   # (tile, 1)
        x_sq = (x * x).sum(axis=1, keepdims=True)     # (tile, 1)
        contrib = jnp.maximum(best + x_sq, 0.0) * m   # (tile, 1)
        # tile i adds its cost to lane i % 128: 128 short running sums where
        # one long f32 sum over all tiles lost ~1e-6 of the total at 489 tiles
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _COST_LANES), 1)
        cost_ref[:] += jnp.where(
            lane == i % _COST_LANES, jnp.sum(contrib, axis=0, keepdims=True), 0.0
        )
        if not stats:
            return
        sums_ref, counts_ref, _ = out_refs
        a = jnp.argmin(part, axis=1, keepdims=True)   # (tile, 1)
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

    def resident(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)

    outs = [((1, _COST_LANES), jnp.float32)]
    if stats:
        outs = [((k_pad, d), jnp.float32), ((1, k_pad), jnp.int32)] + outs
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            resident((k_pad, d)),
            resident((1, k_pad)),
        ],
        out_specs=[resident(shape) for shape, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, dt) for shape, dt in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(Xl, ml, cd, c_sq.reshape(1, k_pad))


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
    product)."""
    k = centers.shape[0]
    sums, counts, cost = _lloyd_pass(
        Xl, ml, centers, matmul_dtype=matmul_dtype, exact=exact, stats=True,
        interpret=interpret,
    )
    return sums[:k], counts[0, :k], cost.sum()


@functools.partial(jax.jit, static_argnames=("interpret",))
def lloyd_cost_pallas(
    Xl: jax.Array, ml: jax.Array, centers: jax.Array, *,
    interpret: bool | None = None,
):
    """The cost at ``centers`` alone, f32 operands at ``Precision.HIGHEST``:
    the pass whose result is reported (``kmeans_kernels._chunk_cost``)."""
    (cost,) = _lloyd_pass(
        Xl, ml, centers, matmul_dtype=None, exact=True, stats=False,
        interpret=interpret,
    )
    return cost.sum()
