"""Exact kNN device kernel: ppermute ring + running top-k merge.

TPU-native replacement for cuML ``NearestNeighborsMG.kneighbors`` (reference
``/root/reference/python/src/spark_rapids_ml/knn.py:553-564``), which
exchanges index/query partitions over UCX endpoints and merges per-rank
top-k results. The ring formulation maps that p2p exchange onto ICI:

* queries stay resident on their device; item shards rotate around the dp
  ring with ``lax.ppermute`` (n_dev steps);
* each step computes one (nq_local, ni_local) distance tile — an MXU matmul
  via the ||x||^2 - 2 x.y + ||y||^2 expansion — and folds it into the
  running (distances, ids) top-k with one ``lax.top_k`` over the
  concatenated candidates;
* after a full rotation every query has seen every item exactly once; no
  host round-trips, one compiled program.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS
from .kmeans_kernels import pairwise_sq_dists

# chunk sizes inside a ring step: the live distance tile is bounded to
# (_Q_CHUNK x _I_CHUNK) regardless of shard sizes — without the item
# chunking a single-device "ring" against a 1M-item shard would
# materialize an (nq, 1M) f32 tile (32.7 GB at nq=8192, observed OOM on a
# 16 GB v5e)
_Q_CHUNK = 8192
_I_CHUNK = 32768


def resolve_knn_topk() -> str:
    """Validated tile top-k implementation from TPUML_KNN_TOPK. The three
    values select three distinct paths on TPU: "auto" = fused Pallas
    distance+top-k kernel when eligible, else the partial-reduce tile
    path; "partial" = force the XLA tile path with ``lax.approx_max_k``
    (routes AROUND the fused kernel — the debugging escape hatch for the
    Pallas path specifically); "sort" = force the XLA tile path with full
    ``lax.top_k`` (no PartialReduce at all). Resolved by CALLERS outside
    jit and passed as a static arg — an env read inside the traced
    function would be silently ignored on jit cache hits."""
    from ..runtime import envspec

    return str(envspec.get("TPUML_KNN_TOPK"))


def _tile_top_k(neg_d2: jax.Array, k: int, topk_impl: str):
    """Top-k over a wide distance tile.

    On TPU ("auto"/"partial") this routes through ``lax.approx_max_k``
    with ``recall_target=1.0`` — the hardware PartialReduce op. At recall
    1.0 the partial-reduce shrink is disabled, making the result EXACT
    (the approximation bound collapses; verified on-chip: full distance +
    id agreement with ``lax.top_k`` at the bench shape, where recall 0.95
    measurably is not exact).
    """
    use_partial = (
        topk_impl == "partial"
        or (topk_impl == "auto" and jax.default_backend() == "tpu")
    )
    if use_partial:
        return lax.approx_max_k(neg_d2, k, recall_target=1.0)
    return lax.top_k(neg_d2, k)


@functools.partial(jax.jit, static_argnames=("mesh", "k", "topk_impl"))
def ring_knn(
    Xq: jax.Array,     # (Nq_pad, d) queries, dp-sharded
    Xi: jax.Array,     # (Ni_pad, d) items, dp-sharded
    mi: jax.Array,     # (Ni_pad,) item validity mask, dp-sharded
    ids_i: jax.Array,  # (Ni_pad,) int32 global item row ids, dp-sharded
    *,
    mesh: Mesh,
    k: int,
    topk_impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (distances (Nq_pad, k) ascending squared-euclidean,
    indices (Nq_pad, k) global item row ids). ``topk_impl`` should come
    from :func:`resolve_knn_topk` (static: participates in the jit key)."""
    n_dev = mesh.shape[DP_AXIS]
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def _rotate(Xi_cur, mi_cur, idi_cur):
        """One ring rotation — the single definition both the Pallas and
        XLA branches use, so permutation semantics cannot diverge."""
        return (
            lax.ppermute(Xi_cur, DP_AXIS, perm),
            lax.ppermute(mi_cur, DP_AXIS, perm),
            lax.ppermute(idi_cur, DP_AXIS, perm),
        )

    def per_device(Xq_l, Xi_l, mi_l, idi_l):
        from .knn_pallas import _QB, _IB, knn_pallas_ok, knn_pallas_pass

        nq = Xq_l.shape[0]
        ni = Xi_l.shape[0]
        d = Xq_l.shape[1]

        # fused Pallas path: pad shapes to the kernel's block multiples
        # (padded queries are sliced off; padded items ride with +inf
        # score via csq_eff and can never be selected). Only "auto"
        # engages the fused kernel: "sort" and "partial" are the validated
        # escape hatches that force the XLA tile paths (full top_k /
        # approx_max_k respectively), so each env value names a distinct
        # implementation.
        from .knn_pallas import FORCE_INTERPRET as _KNN_INTERPRET

        nq_p = -(-nq // _QB) * _QB
        ni_p = -(-ni // _IB) * _IB
        if topk_impl == "auto" and knn_pallas_ok(
            nq_p, ni_p, d, k, Xq_l.dtype
        ):
            Xq_p = jnp.pad(Xq_l, ((0, nq_p - nq), (0, 0)))
            Xi_p = jnp.pad(Xi_l, ((0, ni_p - ni), (0, 0)))
            mi_p = jnp.pad(mi_l, ((0, ni_p - ni),))
            idi_p = jnp.pad(idi_l, ((0, ni_p - ni),))
            x_sq = (Xq_p * Xq_p).sum(axis=1)
            # ||xi||^2 with the mask folded in, computed ONCE: the small
            # (ni,) vector rotates with the shard instead of re-reading
            # the (ni, d) matrix every ring step
            csq0 = jnp.where(
                mi_p > 0, (Xi_p * Xi_p).sum(axis=1), jnp.inf
            )

            def pstep(state, _):
                Xi_cur, csq_cur, idi_cur, td, ti = state
                td, ti = knn_pallas_pass(
                    Xq_p, Xi_cur, csq_cur[None, :], idi_cur[None, :],
                    td, ti, interpret=_KNN_INTERPRET or None,
                )
                Xi_cur, csq_cur, idi_cur = _rotate(Xi_cur, csq_cur, idi_cur)
                return (Xi_cur, csq_cur, idi_cur, td, ti), None

            td0 = jnp.full((nq_p, k), jnp.inf, Xq_l.dtype)
            ti0 = jnp.full((nq_p, k), -1, jnp.int32)
            (_, _, _, td, ti), _ = lax.scan(
                pstep, (Xi_p, csq0, idi_p, td0, ti0), None, length=n_dev
            )
            # restore the row-constant ||xq||^2 term and emit ascending
            d2 = jnp.maximum(td + x_sq[:, None], 0.0)
            negd, order = lax.top_k(-d2, k)
            return (
                (-negd)[:nq],
                jnp.take_along_axis(ti, order, axis=1)[:nq],
            )
        # pad the local query shard to a chunk multiple so the scan below
        # always engages; padded query rows are sliced off at the end
        # (their results are garbage but harmless)
        qc = min(_Q_CHUNK, nq)
        q_pad = (-nq) % qc
        Xq_p = jnp.pad(Xq_l, ((0, q_pad), (0, 0)))
        nc = (nq + q_pad) // qc
        bd0 = jnp.full((nc, qc, k), jnp.inf, Xq_l.dtype)
        bi0 = jnp.full((nc, qc, k), -1, jnp.int32)
        Xq_c = Xq_p.reshape(nc, qc, -1)
        # pad the item shard to a chunk multiple too: padded rows carry
        # mask 0 -> +inf distance, never selected. The padding travels the
        # ring (every device pads identically, so permuted shapes agree).
        ic = min(_I_CHUNK, ni)
        i_pad = (-ni) % ic
        Xi_l = jnp.pad(Xi_l, ((0, i_pad), (0, 0)))
        mi_l = jnp.pad(mi_l, ((0, i_pad),))
        idi_l = jnp.pad(idi_l, ((0, i_pad),))
        nic = (ni + i_pad) // ic

        def step(state, _):
            Xi_cur, mi_cur, idi_cur, bd, bi = state

            def body(_, ch):
                xq, bd_c, bi_c = ch

                def iblock(carry, blk):
                    bd_c, bi_c = carry
                    xi, mi_b, idi_b = blk
                    d2 = pairwise_sq_dists(
                        xq, xi, precision=lax.Precision.HIGHEST
                    )
                    d2 = jnp.where(mi_b[None, :] > 0, d2, jnp.inf)
                    # top-k the raw tile, THEN merge with the carry at
                    # width 2k. Concatenating the (qc, ic) tile with the
                    # carry first costs two extra full-tile HBM
                    # materializations per block (the cat_d copy and the
                    # broadcast ids plane) — at 131k x 1M that is ~1 TB of
                    # avoidable traffic per kneighbors call.
                    w = d2.shape[1]
                    if w < k:
                        # shard narrower than k (tiny item sets over many
                        # devices): pad with +inf/-1 so top_k stays legal
                        # and unfilled slots keep the inf/-1 convention
                        d2 = jnp.pad(
                            d2, ((0, 0), (0, k - w)),
                            constant_values=jnp.inf,
                        )
                        idi_b = jnp.pad(
                            idi_b, (0, k - w), constant_values=-1
                        )
                    negd, sel = _tile_top_k(-d2, k, topk_impl)  # (qc, k)
                    blk_ids = idi_b[sel]                     # (qc, k) global
                    cat_d = jnp.concatenate([bd_c, -negd], axis=1)
                    cat_i = jnp.concatenate([bi_c, blk_ids], axis=1)
                    negm, selm = lax.top_k(-cat_d, k)
                    return (
                        -negm,
                        jnp.take_along_axis(cat_i, selm, axis=1),
                    ), None

                (bd_c, bi_c), _ = lax.scan(
                    iblock,
                    (bd_c, bi_c),
                    (
                        Xi_cur.reshape(nic, ic, -1),
                        mi_cur.reshape(nic, ic),
                        idi_cur.reshape(nic, ic),
                    ),
                )
                return None, (bd_c, bi_c)

            _, (bd, bi) = lax.scan(body, None, (Xq_c, bd, bi))
            Xi_cur, mi_cur, idi_cur = _rotate(Xi_cur, mi_cur, idi_cur)
            return (Xi_cur, mi_cur, idi_cur, bd, bi), None

        (_, _, _, bd, bi), _ = lax.scan(
            step, (Xi_l, mi_l, idi_l, bd0, bi0), None, length=n_dev
        )
        return bd.reshape(-1, k)[:nq], bi.reshape(-1, k)[:nq]

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows()),
        out_specs=(LAYOUT.rows(), LAYOUT.rows()),
        check_vma=False,
    )(Xq, Xi, mi, ids_i)
