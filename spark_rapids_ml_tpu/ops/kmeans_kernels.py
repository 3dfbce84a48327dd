"""KMeans device kernels: Lloyd iterations + k-means|| seeding support.

TPU-native replacement for cuML's ``KMeansMG.fit`` (reference
``/root/reference/python/src/spark_rapids_ml/clustering.py:340-378``; cuML
does NCCL allreduce of centroid partials per iteration). Here:

* rows are dp-sharded; each device walks its rows in fixed-size chunks
  (``fori_loop`` + in-place ``dynamic_slice`` — see ``ops.linalg.row_chunk``)
  so the (chunk, k) distance tile and the one-hot accumulation matmuls stay
  MXU-shaped and HBM-bounded regardless of n;
* per-iteration partials (sums (k,d), counts (k,), cost) are combined with
  ``lax.psum`` over the dp axis — the explicit ICI collective;
* the Lloyd loop is a ``lax.while_loop`` (movement < tol or maxIter), so
  the whole fit is ONE compiled program; no host round-trips per iteration.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from jax import shard_map

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS
from .linalg import check_row_chunking, row_chunk


def pairwise_sq_dists(
    x: jax.Array,
    centers: jax.Array,
    c_sq: jax.Array | None = None,
    *,
    matmul_dtype=None,
    precision=None,
) -> jax.Array:
    """(rows, k) squared euclidean distances: ||x||² - 2 x·c + ||c||², ≥ 0.

    The single distance formula shared by Lloyd, seeding, transform and
    single-row predict — the x@centers.T contraction is the MXU hot loop.
    ``matmul_dtype=bfloat16`` runs that contraction with bf16 operands and
    f32 accumulation (~2x MXU rate; ||x||²/||c||² stay f32): assignment
    flips only on near-ties, which Lloyd's local search absorbs.

    ``precision``: on a TPU an f32 contraction at the default precision is
    NOT an f32 product (measured on v5e, PR 22: the expansion then loses
    ~1e-3 of a distance where ||x||² ≫ d²). Callers whose RESULT is the
    distance — exact kNN, the reported KMeans cost — pass
    ``lax.Precision.HIGHEST``; callers that only take an argmin over
    well-separated centers keep the default. No effect on the CPU.
    """
    if c_sq is None:
        c_sq = (centers * centers).sum(axis=1)
    x_sq = (x * x).sum(axis=1)
    if matmul_dtype is not None:
        xc = jnp.dot(
            x.astype(matmul_dtype),
            centers.T.astype(matmul_dtype),
            preferred_element_type=x.dtype,
        )
    else:
        xc = jnp.dot(x, centers.T, precision=precision)
    d2 = x_sq[:, None] - 2.0 * xc + c_sq[None, :]
    return jnp.maximum(d2, 0.0)


def stats_dot(onehot: jax.Array, x: jax.Array, matmul_dtype=None) -> jax.Array:
    """onehot.T @ x with optional bf16 operands / f32 accumulation — the
    assignment-stats contraction shared by the resident and streamed Lloyd
    steps (keep the two numerically identical: change it HERE only)."""
    if matmul_dtype is None:
        return onehot.T @ x
    return jnp.dot(
        onehot.T.astype(matmul_dtype),
        x.astype(matmul_dtype),
        preferred_element_type=x.dtype,
    )


def _chunk_stats(X_local, mask_local, centers, csize: int, matmul_dtype=None):
    """Chunked pass over local rows; returns (sums (k,d), counts int32 (k,),
    cost).

    On TPU at qualifying shapes the pass runs as ONE fused Pallas kernel
    (``ops.kmeans_pallas``): distances, argmin, one-hot and both
    contractions stay VMEM-resident, so HBM sees a single read of X per
    iteration instead of the two (csize, k) intermediates this XLA path
    materializes per chunk.

    Chunks are read with :func:`ops.linalg.row_chunk` (NOT a lax.scan over
    a reshaped X — see its docstring for the layout-repack hazard).
    ``matmul_dtype=bfloat16`` also runs the one-hot stats contraction with
    bf16 operands (one-hots are exact; x rounds at ~1e-3 relative, washed
    out by the per-cluster mean). The distances run at the default
    precision — an argmin over centers needs no more; the cost that is
    REPORTED comes from :func:`_chunk_cost`, which carries no statistics."""
    from .kmeans_pallas import kmeans_pallas_ok, lloyd_step_pallas

    k = centers.shape[0]
    d = X_local.shape[1]
    if kmeans_pallas_ok(X_local.shape[0], d, k, X_local.dtype, matmul_dtype):
        return lloyd_step_pallas(
            X_local, mask_local, centers, matmul_dtype=matmul_dtype
        )
    n_chunks = check_row_chunking(X_local.shape[0], csize)
    c_sq = (centers * centers).sum(axis=1)  # (k,)

    def body(i, carry):
        sums, counts, cost = carry
        x, m = row_chunk(i, csize, X_local, mask_local)
        d2 = pairwise_sq_dists(x, centers, c_sq, matmul_dtype=matmul_dtype)
        assign = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=x.dtype) * m[:, None]
        sums = sums + stats_dot(onehot, x, matmul_dtype)
        # counts in int32: float accumulation drops +1 increments once a
        # cluster's count passes 2^24 (realistic at ~1e8 rows/device)
        counts = counts + onehot.sum(axis=0).astype(jnp.int32)
        cost = cost + (jnp.min(d2, axis=1) * m).sum()
        return (sums, counts, cost)

    init = (
        jnp.zeros((k, d), dtype=X_local.dtype),
        jnp.zeros((k,), dtype=jnp.int32),
        jnp.zeros((), dtype=X_local.dtype),
    )
    return lax.fori_loop(0, n_chunks, body, init)


def _chunk_cost(X_local, mask_local, centers, csize: int):
    """The cost at ``centers`` over local rows, alone: f32 operands at
    ``Precision.HIGHEST``, no (k, d) sums and no one-hot contraction — the
    pass whose result is reported. Same two routes as :func:`_chunk_stats`:
    the fused kernel where its gate admits the shape, else XLA chunks."""
    from .kmeans_pallas import kmeans_pallas_ok, lloyd_cost_pallas

    if kmeans_pallas_ok(
        X_local.shape[0], X_local.shape[1], centers.shape[0], X_local.dtype,
        None, True, False,
    ):
        return lloyd_cost_pallas(X_local, mask_local, centers)
    n_chunks = check_row_chunking(X_local.shape[0], csize)
    c_sq = (centers * centers).sum(axis=1)  # (k,)

    def body(i, cost):
        x, m = row_chunk(i, csize, X_local, mask_local)
        d2 = pairwise_sq_dists(
            x, centers, c_sq, precision=lax.Precision.HIGHEST
        )
        return cost + (jnp.min(d2, axis=1) * m).sum()

    return lax.fori_loop(
        0, n_chunks, body, jnp.zeros((), dtype=X_local.dtype)
    )


def _lloyd_shift(new_centers, centers, before):
    """Largest squared move of a center in one iteration — and 0 where the
    new centers are exactly those of two iterations before. At reduced
    precision one near-tied row can go back and forth between two centers
    for ever (measured on a v5e, PR 29: one seed in six of the reference's
    KMeans run, iterations 12 to 30 alternating between two states); the
    pair is as converged as the product allows, so the loop ends there as
    it does at a fixed point. Float32 products cannot cycle."""
    shift = ((new_centers - centers) ** 2).sum(axis=1).max()
    return jnp.where((new_centers == before).all(), 0.0, shift)


def _lloyd_state(centers, dtype):
    """(centers, centers of the iteration before: none yet, last shift, it)."""
    return (
        centers, jnp.full_like(centers, jnp.nan),
        jnp.asarray(jnp.inf, dtype), jnp.asarray(0),
    )


def mp_kmeans_shards(mesh, k: int) -> int:
    """Resolved model-axis degree for centroid-sharded Lloyd: the mesh's mp
    extent when ``TPUML_MP_KMEANS`` is on and there are at least mp
    centroids, else 1. Reads the env OUTSIDE jit."""
    from ..runtime import envspec

    from ..parallel.mesh import MP_AXIS

    n_mp = int(mesh.shape.get(MP_AXIS, 1))
    if n_mp <= 1 or k < n_mp:
        return 1
    if str(envspec.get("TPUML_MP_KMEANS")) == "off":
        return 1
    return n_mp


# Sentinel coordinate for k-padding rows on the centroid-sharded path:
# large enough that a padded center can never win an argmin against any
# real center, small enough that ||c||² = d·1e30 stays finite in f32
# (jnp.inf would poison the centroid-shift reduction with inf-inf=NaN).
_PAD_CENTER = 1e15


def kmeans_lloyd(
    X: jax.Array,
    mask: jax.Array,
    centers0: jax.Array,
    *,
    mesh: Mesh,
    csize: int,
    max_iter: int,
    tol: float,
    matmul_dtype=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Run Lloyd to convergence. Returns (centers, cost, n_iters).

    Dispatching wrapper: resolves the centroid-sharding gate (env read —
    must stay outside jit) and routes to the replicated-table kernel or the
    mp-sharded one. With ``TPUML_MESH_MP`` unset the mesh has no model axis
    and this is exactly the historical 1-D program."""
    k = centers0.shape[0]
    n_mp = mp_kmeans_shards(mesh, k)
    if n_mp == 1:
        return _kmeans_lloyd_1d(
            X, mask, centers0, mesh=mesh, csize=csize, max_iter=max_iter,
            tol=tol, matmul_dtype=matmul_dtype,
        )
    kb = -(-k // n_mp)
    k_pad = kb * n_mp
    if k_pad != k:
        pad = jnp.full(
            (k_pad - k, centers0.shape[1]), _PAD_CENTER, centers0.dtype
        )
        centers0 = jnp.concatenate([centers0, pad], axis=0)
    centers, cost, it = _kmeans_lloyd_mp(
        X, mask, centers0, mesh=mesh, csize=csize, max_iter=max_iter,
        tol=tol, matmul_dtype=matmul_dtype, n_mp=n_mp,
    )
    return centers[:k], cost, it


@functools.partial(
    jax.jit, static_argnames=("mesh", "csize", "max_iter", "matmul_dtype")
)
def _kmeans_lloyd_1d(
    X: jax.Array,
    mask: jax.Array,
    centers0: jax.Array,
    *,
    mesh: Mesh,
    csize: int,
    max_iter: int,
    tol: float,
    matmul_dtype=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Replicated-centroid-table Lloyd (the historical kernel)."""

    def per_device(X_local, mask_local, centers):
        def cond(state):
            _, _, prev_shift, it = state
            return jnp.logical_and(it < max_iter, prev_shift > tol * tol)

        def body(state):
            centers, before, _, it = state
            with jax.named_scope("lloyd.iter"):
                sums, counts, _ = _chunk_stats(
                    X_local, mask_local, centers, csize, matmul_dtype
                )
            sums = lax.psum(sums, DP_AXIS)
            counts = lax.psum(counts, DP_AXIS)
            # empty cluster keeps its previous center (Spark behavior)
            countsf = counts.astype(sums.dtype)
            safe = jnp.maximum(countsf, 1.0)
            new_centers = jnp.where(
                counts[:, None] > 0, sums / safe[:, None], centers
            )
            shift = _lloyd_shift(new_centers, centers, before)
            return (new_centers, centers, shift, it + 1)

        state = _lloyd_state(centers, X_local.dtype)
        centers, _, _, it = lax.while_loop(cond, body, state)
        # final pass: cost at converged centers. NOTE: reading X after the
        # while loop makes XLA's buffer analysis insert a defensive copy of
        # the matrix at lane-unaligned d — but that copy is inserted even
        # when all reads are folded inside the loop (measured: a terminal
        # no-update phase still copies AND costs ~4% per iteration), so the
        # straight-line form is kept; the unaligned-d memory note lives in
        # COVERAGE.md.
        #
        # The final cost pass ALWAYS runs f32 operands at HIGHEST
        # precision: the ||x||²-2x·c+||c||² expansion cancels
        # catastrophically at bf16 precision when rows sit near their
        # centroid (intra-cluster distance² ~ |x|²·2⁻⁸ rounding), which
        # corrupts the reported cost even though iteration ARGMIN
        # assignments only need inter-center contrast. On the MXU an f32
        # dot at DEFAULT precision is such a reduced product (measured on
        # v5e, PR 22: reported cost 1.7e-3 off a plain f32 Lloyd).
        with jax.named_scope("lloyd.cost"):
            cost = _chunk_cost(X_local, mask_local, centers, csize)
        cost = lax.psum(cost, DP_AXIS)
        return centers, cost, it

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated()),
        out_specs=(LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated()),
        check_vma=False,
    )(X, mask, centers0)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "csize", "max_iter", "matmul_dtype", "n_mp"),
)
def _kmeans_lloyd_mp(
    X: jax.Array,
    mask: jax.Array,
    centers0: jax.Array,
    *,
    mesh: Mesh,
    csize: int,
    max_iter: int,
    tol: float,
    matmul_dtype=None,
    n_mp: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Centroid-sharded Lloyd: the k axis is partitioned over mp.

    Each device computes distance tiles against only its OWN (k/mp, d)
    centroid block — the (chunk, k) distance tile and the one-hot stats
    contraction, the two structures that bound k on a chip, shrink by
    1/mp. Per chunk the per-shard (min, argmin) pairs are all-gathered
    over mp (2 floats + int per row per shard — O(mp·chunk), not O(k·d))
    and reduced to the global assignment; cross-shard ties resolve to the
    LOWEST shard index, which together with argmin's first-occurrence
    within a block reproduces ``jnp.argmin``'s tie-break over the full
    row, so assignments are identical to the 1-D kernel up to matmul
    reduction-order rounding (docs/mesh.md tolerance contract). Stats
    accumulate for the own block only, psum over dp, and the updated
    blocks all-gather over mp into the replicated table the next
    iteration slices.

    ``centers0`` must be k-padded to a multiple of ``n_mp`` with
    ``_PAD_CENTER`` sentinel rows (the :func:`kmeans_lloyd` wrapper does
    this); sentinel centers never win an argmin, keep zero counts, and so
    persist unchanged through every update.
    """
    from ..parallel.mesh import MP_AXIS

    k_pad = centers0.shape[0]
    kb = k_pad // n_mp

    def per_device(X_local, mask_local, centers):
        s = lax.axis_index(MP_AXIS)
        nc = check_row_chunking(X_local.shape[0], csize)

        def assign_rows(x, m, block, c_sq_b, mm_dtype):
            """Global (assign, best-d²) for one chunk from the own-block
            distances + the (mp, chunk) all-gathered partial argmins."""
            d2 = pairwise_sq_dists(x, block, c_sq_b, matmul_dtype=mm_dtype)
            lmin = d2.min(axis=1)
            larg = d2.argmin(axis=1) + s * kb
            gmin = lax.all_gather(lmin, MP_AXIS)     # (mp, chunk)
            garg = lax.all_gather(larg, MP_AXIS)     # (mp, chunk)
            win = jnp.argmin(gmin, axis=0)           # ties -> lowest shard
            cols = jnp.arange(x.shape[0])
            return garg[win, cols], gmin[win, cols]

        def iter_stats(centers, mm_dtype):
            block = lax.dynamic_slice_in_dim(centers, s * kb, kb, 0)
            c_sq_b = (block * block).sum(axis=1)

            def body(i, carry):
                sums, counts, cost = carry
                x, m = row_chunk(i, csize, X_local, mask_local)
                assign, best = assign_rows(x, m, block, c_sq_b, mm_dtype)
                # one-hot over the OWN block only: rows assigned elsewhere
                # contribute nothing here (their owner accumulates them)
                local = assign - s * kb
                own = (local >= 0) & (local < kb)
                onehot = (
                    jax.nn.one_hot(jnp.where(own, local, 0), kb, dtype=x.dtype)
                    * (own & (m > 0))[:, None]
                )
                sums = sums + stats_dot(onehot, x, mm_dtype)
                counts = counts + onehot.sum(axis=0).astype(jnp.int32)
                cost = cost + (best * m).sum()
                return (sums, counts, cost)

            init = (
                jnp.zeros((kb, X_local.shape[1]), X_local.dtype),
                jnp.zeros((kb,), jnp.int32),
                jnp.zeros((), X_local.dtype),
            )
            return block, lax.fori_loop(0, nc, body, init)

        def cond(state):
            _, _, prev_shift, it = state
            return jnp.logical_and(it < max_iter, prev_shift > tol * tol)

        def body(state):
            centers, before, _, it = state
            with jax.named_scope("lloyd.iter"):
                block, (sums, counts, _) = iter_stats(centers, matmul_dtype)
            sums = lax.psum(sums, DP_AXIS)
            counts = lax.psum(counts, DP_AXIS)
            countsf = counts.astype(sums.dtype)
            safe = jnp.maximum(countsf, 1.0)
            # empty cluster keeps its previous center (Spark behavior);
            # sentinel pad rows always fall here (zero counts, unchanged)
            new_block = jnp.where(
                counts[:, None] > 0, sums / safe[:, None], block
            )
            new_centers = lax.all_gather(
                new_block, MP_AXIS, tiled=True
            )  # (k_pad, d), shard-order = global centroid order
            shift = _lloyd_shift(new_centers, centers, before)
            return (new_centers, centers, shift, it + 1)

        state = _lloyd_state(centers, X_local.dtype)
        centers, _, _, it = lax.while_loop(cond, body, state)
        # final cost pass at converged centers, always f32 operands (see
        # the 1-D kernel's cancellation note)
        with jax.named_scope("lloyd.cost"):
            _, (_, _, cost) = iter_stats(centers, None)
        cost = lax.psum(cost, DP_AXIS)
        return centers, cost, it

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated()),
        out_specs=(LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated()),
        check_vma=False,
    )(X, mask, centers0)


@functools.partial(jax.jit, static_argnames=("mesh", "csize"))
def min_sq_dists(
    X: jax.Array, mask: jax.Array, centers: jax.Array, *, mesh: Mesh, csize: int
) -> jax.Array:
    """Per-row min squared distance to any center (padding rows -> 0).

    Used by k-means|| seeding (sampling probabilities l*d^2/sum d^2).
    """

    def per_device(X_local, mask_local, centers):
        c_sq = (centers * centers).sum(axis=1)
        n_chunks = check_row_chunking(X_local.shape[0], csize)

        def body(_, i):
            (x,) = row_chunk(i, csize, X_local)
            return None, pairwise_sq_dists(x, centers, c_sq).min(axis=1)

        _, md = lax.scan(body, None, jnp.arange(n_chunks))
        return md.reshape(-1) * mask_local

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated()),
        out_specs=LAYOUT.rows(),
        check_vma=False,
    )(X, mask, centers)


@functools.partial(jax.jit, static_argnames=("mesh", "csize"))
def count_closest(
    X: jax.Array, mask: jax.Array, centers: jax.Array, *, mesh: Mesh, csize: int
) -> jax.Array:
    """How many rows are closest to each center — k-means|| candidate weights."""

    def per_device(X_local, mask_local, centers):
        sums, counts, _ = _chunk_stats(X_local, mask_local, centers, csize)
        return lax.psum(counts, DP_AXIS)

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated()),
        out_specs=LAYOUT.replicated(),
        check_vma=False,
    )(X, mask, centers)
