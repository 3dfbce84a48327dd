"""IVF-Flat approximate kNN: MXU coarse quantization + probe-list scan.

TPU-native analog of the reference's IVF-Flat ``ApproximateNearestNeighbors``
backend (cuML ``NearestNeighborsMG`` with ``algorithm="ivfflat"``). The
index partitions the item set into ``nlist`` Voronoi cells of a k-means
coarse quantizer; a query scans only its ``nprobe`` closest cells instead
of every row. Both hot steps are MXU-shaped tall-skinny matmuls — the
regime the TPU linear-algebra literature targets (see PAPERS.md):

* **coarse quantization** (build + search): one ``pairwise_sq_dists``
  contraction against the (nlist, d) centroid table;
* **probe scan** (search): per-probe candidate gather + a batched
  query-row x candidate-block contraction, folded into a running top-k
  through the same ``_tile_top_k`` (PartialReduce) machinery as the exact
  ring — so ``TPUML_KNN_TOPK`` applies here unchanged.

Index layout: rows are cluster-sorted (CSR ``offsets``/``lens`` kept as
metadata) and then scattered into a *capacity-padded* layout — list ``l``
owns slots ``[l*cap, (l+1)*cap)`` with padding slots carrying ``+inf``
squared norm / id ``-1``. The pad makes every per-probe gather a static
``(qc, cap)`` window (no ragged CSR arithmetic inside jit); ``cap`` is
the observed max list length under a *loosely* balanced assignment —
rows spill to their second-closest list only above a hard
``3 * n / nlist`` bound, so pathological skew cannot blow up the padded
scan while routine cell-size variation keeps its nearest centroid
(a tight 1.25x bound was measured to spill ~20% of rows and cap recall
at ~0.93 regardless of nprobe).

A fused Pallas scan-and-top-k kernel was evaluated and deliberately NOT
built: the probe scan's item operand is a per-query HBM gather (each query
row addresses a different candidate window), so there is no shared
VMEM-resident item block for a kernel to exploit — unlike the dense
distance tile ``knn_pallas.py`` fuses. See ``docs/ann_performance.md``.

Distribution: queries are dp-sharded exactly like ``ring_knn``'s query
side; the (replicated) index arrays ride ``LAYOUT.replicated()`` specs. Rotating index
shards around the ring — the exact path's layout — would multiply the
sparse gather passes by ``n_dev`` without reducing per-device work, since
a probe touches O(nprobe * cap) rows wherever they live.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding

from jax import shard_map
from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS, MP_AXIS
from .kmeans_kernels import kmeans_lloyd, pairwise_sq_dists
from .knn_kernels import _tile_top_k

_LOGGER = logging.getLogger("spark_rapids_ml_tpu.umap")

# balanced-assignment HARD capacity: ceil(_BALANCE_HARD * n / nlist). Rows
# only spill to their second-closest list above this bound, so the spill
# is reserved for pathological skew (a hot cluster soaking up the dataset)
# instead of routine cell-size variation. A tight bound (1.25x was
# measured) spills ~20% of rows on blob data and caps recall at ~0.93
# regardless of nprobe — a spilled row lives in a list its queries rank
# ~30th of 63; at 2.0x the plateau is still visible (0.985). The padded
# slot count of a healthy index is the OBSERVED max list length
# (data-dependent but host-static), not this bound.
_BALANCE_HARD = 3.0
_CAP_MULTIPLE = 8

# coarse-quantizer training: Lloyd on a bounded sample (IVF quality needs
# cell shapes, not converged centroids — 10 iterations on <=2^18 rows is
# the standard recipe) chunked at _TRAIN_CSIZE rows per device pass.
_TRAIN_SAMPLE = 1 << 18
_TRAIN_ITERS = 10
_TRAIN_CSIZE = 4096

# assignment pass chunk (build): (chunk, nlist) distance tile.
_ASSIGN_CHUNK = 16384

# search-time gathered tile budget, in f32 elements: the (qc, cap, d)
# per-probe candidate gather is the live intermediate; qc adapts so it
# stays ~256 MB regardless of cap * d.
_GATHER_BUDGET_ELEMS = 64 * 1024 * 1024

# hard feasibility floor: below this the index build (sample + Lloyd +
# balance) costs more than the exact sweep it displaces.
_MIN_IVF_ROWS = 256
# every list must expect at least this many rows or the quantizer is
# fragmenting the data (empty/singleton cells -> recall collapse).
_MIN_ROWS_PER_LIST = 4


# --------------------------------------------------------------------------
# env resolution + parameter heuristics (resolved OUTSIDE jit; the values
# participate in static args / host control flow only)
# --------------------------------------------------------------------------


def resolve_umap_graph() -> str:
    """Validated ``TPUML_UMAP_GRAPH`` (auto | exact | ivf)."""
    from ..runtime import envspec

    return str(envspec.get("TPUML_UMAP_GRAPH"))


def mp_ivf_shards(mesh, nlist: int) -> int:
    """Resolved model-axis degree for list-sharded IVF search: the mesh's
    mp extent when ``TPUML_MP_IVF`` is on and there are at least mp lists,
    else 1. Reads the env OUTSIDE jit."""
    from ..runtime import envspec

    from ..parallel.mesh import MP_AXIS

    n_mp = int(mesh.shape.get(MP_AXIS, 1))
    if n_mp <= 1 or nlist < n_mp:
        return 1
    if str(envspec.get("TPUML_MP_IVF")) == "off":
        return 1
    return n_mp


def resolve_ann_gate_rows() -> int:
    """Validated ``TPUML_ANN_GATE_ROWS`` — the auto-dispatch row floor."""
    from ..runtime import envspec

    return int(envspec.get("TPUML_ANN_GATE_ROWS"))


def default_nlist(n_rows: int) -> int:
    """sqrt(n)-scaled list count — the standard IVF sizing (cells of
    ~sqrt(n) rows balance quantization cost against scan cost)."""
    return max(2, min(int(round(math.sqrt(max(n_rows, 4)))), n_rows // 2))


def default_nprobe(nlist: int) -> int:
    """nlist/8 probes (~12.5% of lists), floored at 6 — the measured
    recall>=0.95 operating point on clustered data at the default nlist
    (see docs/ann_performance.md for the trade-off table). The floor only
    binds below nlist=48, where a tiny quantizer slices clusters finely
    enough that a fixed list fraction misses boundary neighbors — and
    where scanning a few extra (small) lists costs almost nothing."""
    return min(nlist, max(6, -(-nlist // 8)))


def hard_capacity(n_rows: int, nlist: int) -> int:
    """The enforced per-list row bound (spill threshold)."""
    cap = -(-int(_BALANCE_HARD * n_rows) // nlist)
    return -(-max(cap, 1) // _CAP_MULTIPLE) * _CAP_MULTIPLE


def resolve_ann_params(
    n_rows: int,
    nlist: Optional[int] = None,
    nprobe: Optional[int] = None,
) -> Tuple[int, int]:
    """Resolve + validate (nlist, nprobe) for an ``n_rows``-item index.

    Explicit arguments (estimator ``algoParams``) win over the
    ``TPUML_ANN_NLIST`` / ``TPUML_ANN_NPROBE`` env overrides, which win
    over the heuristics. Raises ``ValueError`` on out-of-domain values —
    the estimator surfaces these verbatim.
    """
    from ..runtime import autotune, envspec

    tuned = None
    if (nlist is None or nprobe is None) and autotune.active():
        # tuned winners (bench probe or kneighbors' in-situ recall-gated
        # search) fill only the slots neither algoParams nor env pinned
        tuned = autotune.consult(
            "ann_params", autotune.shape_key(n=n_rows)
        )
        if not (
            isinstance(tuned, (list, tuple))
            and len(tuned) == 2
            and all(isinstance(v, int) for v in tuned)
        ):
            tuned = None
    if nlist is None:
        nlist = envspec.get("TPUML_ANN_NLIST")
    if nlist is None and tuned is not None and 2 <= tuned[0] <= max(n_rows, 1):
        nlist = tuned[0]
    if nlist is None:
        nlist = default_nlist(n_rows)
    nlist = int(nlist)
    if nlist < 2:
        raise ValueError(f"ivfflat nlist={nlist} must be >= 2")
    if nlist > max(n_rows, 1):
        raise ValueError(
            f"ivfflat nlist={nlist} must be <= number of index rows {n_rows}"
        )
    if nprobe is None:
        nprobe = envspec.get("TPUML_ANN_NPROBE")
    if nprobe is None and tuned is not None and tuned[0] == nlist:
        # a tuned nprobe is only meaningful at the nlist it was measured
        # against — a stale pair from another nlist falls through
        if 1 <= tuned[1] <= nlist:
            nprobe = tuned[1]
    if nprobe is None:
        nprobe = default_nprobe(nlist)
    nprobe = int(nprobe)
    if nprobe < 1:
        raise ValueError(f"ivfflat nprobe={nprobe} must be >= 1")
    if nprobe > nlist:
        raise ValueError(
            f"ivfflat nprobe={nprobe} must be <= nlist={nlist}"
        )
    return nlist, nprobe


def ivf_feasible(n_rows: int, k: int, nlist: int, nprobe: int) -> bool:
    """Shape gate: can an (nlist, nprobe) index answer k-NN on n_rows
    sanely? False when the build would cost more than it saves, when the
    cells would fragment, or when the probed candidate pool cannot even
    hold k rows."""
    if n_rows < _MIN_IVF_ROWS or k >= n_rows:
        return False
    if nlist < 2 or n_rows < _MIN_ROWS_PER_LIST * nlist:
        return False
    # conservative candidate-pool floor: probed lists must plausibly hold
    # k real rows (padding slots carry +inf and never fill a slot). Cell
    # sizes vary, so budget each probed list at 1/4 of the mean.
    min_per_list = n_rows // int(_BALANCE_HARD * nlist) or 1
    return nprobe * min_per_list >= k


def select_graph_engine(
    n_rows: int,
    k: int,
    *,
    nlist: Optional[int] = None,
    nprobe: Optional[int] = None,
) -> str:
    """Resolve ``TPUML_UMAP_GRAPH`` against the feasibility gate: returns
    ``"ivf"`` or ``"exact"``. An explicit ``ivf`` that the gate rejects
    warns and falls back — the fit must not crash on a shape the index
    cannot serve (same clean-fallback contract as ``select_sgd_engine``).
    ``auto`` additionally requires ``n_rows >= TPUML_ANN_GATE_ROWS`` so
    unconfigured fits keep the exact graph bit-identically."""
    mode = resolve_umap_graph()
    if mode == "exact":
        return "exact"
    try:
        nl, npb = resolve_ann_params(n_rows, nlist=nlist, nprobe=nprobe)
        feasible = ivf_feasible(n_rows, k, nl, npb)
        reason = "below the IVF feasibility gate"
    except ValueError as e:  # env/param combo invalid for this shape
        feasible = False
        reason = str(e)
    if mode == "ivf":
        if feasible:
            return "ivf"
        _LOGGER.warning(
            "TPUML_UMAP_GRAPH=ivf but the IVF graph engine is unavailable "
            "for config (n_rows=%d, k=%d): %s; falling back to the exact "
            "brute-force graph",
            n_rows, k, reason,
        )
        return "exact"
    if feasible and n_rows >= resolve_ann_gate_rows():
        return "ivf"
    return "exact"


# --------------------------------------------------------------------------
# index build
# --------------------------------------------------------------------------


class IvfIndex(NamedTuple):
    """Built index: device arrays + host CSR metadata.

    ``grouped_*`` use the capacity-padded cluster-grouped layout (list
    ``l`` at slots ``[l*cap, (l+1)*cap)``); ``offsets``/``lens`` are the
    CSR description of the underlying cluster-sorted ordering.
    """

    centroids: jax.Array    # (nlist, d) f32 coarse quantizer
    grouped_x: jax.Array    # (nlist*cap, d) f32, zero-filled padding
    grouped_sq: jax.Array   # (nlist*cap,) f32 ||x||^2, +inf on padding
    grouped_ids: jax.Array  # (nlist*cap,) int32 source row ids, -1 padding
    offsets: np.ndarray     # (nlist+1,) int64 CSR starts (compact order)
    lens: np.ndarray        # (nlist,) int32 valid rows per list
    cap: int                # static padded list length
    nlist: int
    n_rows: int


@functools.partial(jax.jit, static_argnames=("chunk",))
def _assign_top2(
    X: jax.Array, centers: jax.Array, *, chunk: int
) -> Tuple[jax.Array, jax.Array]:
    """Two closest centroids per row: (d2 (n, 2) ascending, idx (n, 2)).

    The second choice is the balancer's spill target; its distance gap is
    the spill cost. Chunked so the (chunk, nlist) tile bounds HBM.
    """
    n = X.shape[0]
    pad = (-n) % chunk
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    c_sq = (centers * centers).sum(axis=1)

    def body(_, xc):
        d2 = pairwise_sq_dists(xc, centers, c_sq)
        negd, idx = lax.top_k(-d2, 2)
        return None, (-negd, idx)

    _, (d2, idx) = lax.scan(body, None, Xp.reshape(-1, chunk, X.shape[1]))
    return d2.reshape(-1, 2)[:n], idx.reshape(-1, 2)[:n]


def _balanced_assign(
    d2_2: np.ndarray, idx_2: np.ndarray, nlist: int, cap: int
) -> np.ndarray:
    """Capacity-balanced list assignment (host): start from the nearest
    centroid, then spill each overfull list's cheapest-to-move rows
    (smallest second-choice distance gap) to their second choice; a rare
    final pass routes any still-overfull remainder to the least-loaded
    lists. Total capacity ``nlist*cap > n`` guarantees termination."""
    first = idx_2[:, 0].astype(np.int64)
    counts = np.bincount(first, minlength=nlist)
    if counts.max() <= cap:
        return first
    assign = first.copy()
    margin = d2_2[:, 1] - d2_2[:, 0]
    for l in np.flatnonzero(counts > cap):
        rows = np.flatnonzero(first == l)
        spill = rows[
            np.argsort(margin[rows], kind="stable")[: counts[l] - cap]
        ]
        assign[spill] = idx_2[spill, 1]
    counts = np.bincount(assign, minlength=nlist)
    while counts.max() > cap:
        for l in np.flatnonzero(counts > cap):
            rows = np.flatnonzero(assign == l)
            spill = rows[
                np.argsort(margin[rows], kind="stable")[: counts[l] - cap]
            ]
            for r in spill:
                tgt = int(np.argmin(counts))
                assign[r] = tgt
                counts[tgt] += 1
                counts[l] -= 1
    return assign


def build_ivf_index(
    X: np.ndarray,
    *,
    nlist: int,
    seed: int,
    mesh: Optional[Mesh] = None,
    max_iter: int = _TRAIN_ITERS,
) -> IvfIndex:
    """Train the coarse quantizer and lay out the cluster-grouped index.

    Deterministic for a given (X, nlist, seed): the sample draw, seeding
    and balancer are all host numpy under ``default_rng(seed)``, and the
    Lloyd/assignment device passes are plain f32 XLA.
    """
    from ..parallel.mesh import make_mesh, shard_rows

    X = np.ascontiguousarray(np.asarray(X, dtype=np.float32))
    n, d = X.shape
    rng = np.random.default_rng(seed)

    # 1) coarse quantizer: Lloyd on a bounded sample, reusing the shared
    # kmeans machinery (chunked stats + psum; Pallas fused step when
    # eligible). An IVF quantizer needs cell shapes, not convergence.
    if n > _TRAIN_SAMPLE:
        sample = X[rng.choice(n, _TRAIN_SAMPLE, replace=False)]
    else:
        sample = X
    centers0 = sample[rng.choice(sample.shape[0], nlist, replace=False)]
    if mesh is None:
        mesh = make_mesh()
    Xs_d, ms_d = shard_rows(sample, mesh, row_multiple=_TRAIN_CSIZE)
    centers, _, _ = kmeans_lloyd(
        Xs_d,
        ms_d,
        jnp.asarray(centers0),
        mesh=mesh,
        csize=_TRAIN_CSIZE,
        max_iter=int(max_iter),
        tol=1e-4,
    )

    # 2) two-choice assignment of every row (device); host balance only
    # spills rows above the loose hard bound — routine cell-size variation
    # stays on the nearest centroid (see _BALANCE_HARD), the padded slot
    # count then follows the OBSERVED max list length
    d2_2, idx_2 = _assign_top2(
        jnp.asarray(X), centers, chunk=min(_ASSIGN_CHUNK, max(n, 1))
    )
    assign = _balanced_assign(
        np.asarray(d2_2), np.asarray(idx_2), nlist, hard_capacity(n, nlist)
    )
    max_len = int(np.bincount(assign, minlength=nlist).max())
    cap = -(-max(max_len, 1) // _CAP_MULTIPLE) * _CAP_MULTIPLE

    # 3) cluster-sorted CSR ordering, then scatter into the padded layout
    order = np.argsort(assign, kind="stable")
    lens = np.bincount(assign, minlength=nlist).astype(np.int32)
    offsets = np.zeros(nlist + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    sorted_assign = assign[order]
    pos_in_list = np.arange(n, dtype=np.int64) - offsets[sorted_assign]
    slots = sorted_assign * cap + pos_in_list
    grouped_x = np.zeros((nlist * cap, d), dtype=np.float32)
    grouped_x[slots] = X[order]
    grouped_sq = np.full((nlist * cap,), np.inf, dtype=np.float32)
    grouped_sq[slots] = (X[order] * X[order]).sum(axis=1)
    grouped_ids = np.full((nlist * cap,), -1, dtype=np.int32)
    grouped_ids[slots] = order.astype(np.int32)

    return IvfIndex(
        # host round-trip decommits the Lloyd output from the BUILD mesh so
        # the search-time mesh (possibly a different worker count) is free
        # to place every index array itself
        centroids=jnp.asarray(np.asarray(centers)),
        grouped_x=jnp.asarray(grouped_x),
        grouped_sq=jnp.asarray(grouped_sq),
        grouped_ids=jnp.asarray(grouped_ids),
        offsets=offsets,
        lens=lens,
        cap=cap,
        nlist=nlist,
        n_rows=n,
    )


# --------------------------------------------------------------------------
# probe search
# --------------------------------------------------------------------------


def _search_qchunk(cap: int, d: int) -> int:
    """Query chunk size bounding the (qc, cap, d) gathered candidate tile
    to ``_GATHER_BUDGET_ELEMS`` f32 elements (sublane-multiple)."""
    qc = _GATHER_BUDGET_ELEMS // max(cap * d, 1)
    qc = max(8, min(1024, qc))
    return max(8, (qc // 8) * 8)


def _probe_scan(
    Xq_l: jax.Array,
    cents: jax.Array,
    gx: jax.Array,
    gsq: jax.Array,
    gids: jax.Array,
    *,
    k: int,
    nprobe: int,
    cap: int,
    topk_impl: str,
    qchunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Per-device IVF search body: coarse top-nprobe, then a probe-major
    scan folding each (qc, cap) candidate window into a running top-k —
    the same raw-tile-then-2k-merge discipline as the exact ring's
    ``iblock`` (concatenating full tiles first costs an extra HBM
    materialization per probe). Lists are disjoint, so candidates never
    repeat across probes."""
    nq = Xq_l.shape[0]
    qc = min(qchunk, nq)
    pad = (-nq) % qc
    Xq_p = jnp.pad(Xq_l, ((0, pad), (0, 0)))
    c_sq = (cents * cents).sum(axis=1)
    cap_ar = jnp.arange(cap, dtype=jnp.int32)

    def qbody(_, xq):
        x_sq = (xq * xq).sum(axis=1)
        dc = pairwise_sq_dists(xq, cents, c_sq)  # (qc, nlist) MXU
        _, probes = lax.top_k(-dc, nprobe)       # (qc, nprobe)
        bd0 = jnp.full((qc, k), jnp.inf, Xq_l.dtype)
        bi0 = jnp.full((qc, k), -1, jnp.int32)

        def pstep(carry, pj):
            bd, bi = carry
            cand = pj[:, None] * cap + cap_ar[None, :]   # (qc, cap)
            xi = gx[cand]                                # (qc, cap, d)
            csq = gsq[cand]
            ids = gids[cand]
            dots = jnp.einsum("qd,qcd->qc", xq, xi)
            d2 = jnp.maximum(x_sq[:, None] - 2.0 * dots + csq, 0.0)
            if cap < k:
                # candidate window narrower than k: pad with +inf/-1 so
                # top_k stays legal and unfilled slots keep the convention
                d2 = jnp.pad(
                    d2, ((0, 0), (0, k - cap)), constant_values=jnp.inf
                )
                ids = jnp.pad(
                    ids, ((0, 0), (0, k - cap)), constant_values=-1
                )
            negd, sel = _tile_top_k(-d2, k, topk_impl)
            blk_ids = jnp.take_along_axis(ids, sel, axis=1)
            cat_d = jnp.concatenate([bd, -negd], axis=1)
            cat_i = jnp.concatenate([bi, blk_ids], axis=1)
            negm, selm = lax.top_k(-cat_d, k)
            return (-negm, jnp.take_along_axis(cat_i, selm, axis=1)), None

        (bd, bi), _ = lax.scan(
            pstep, (bd0, bi0), jnp.transpose(probes)  # (nprobe, qc)
        )
        return None, (bd, bi)

    _, (bd, bi) = lax.scan(
        qbody, None, Xq_p.reshape(-1, qc, Xq_l.shape[1])
    )
    return bd.reshape(-1, k)[:nq], bi.reshape(-1, k)[:nq]


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "k", "nprobe", "cap", "topk_impl", "qchunk"),
)
def _ivf_search_sharded(
    Xq: jax.Array,
    cents: jax.Array,
    gx: jax.Array,
    gsq: jax.Array,
    gids: jax.Array,
    *,
    mesh: Mesh,
    k: int,
    nprobe: int,
    cap: int,
    topk_impl: str,
    qchunk: int,
) -> Tuple[jax.Array, jax.Array]:
    body = functools.partial(
        _probe_scan,
        k=k, nprobe=nprobe, cap=cap, topk_impl=topk_impl, qchunk=qchunk,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated()),
        out_specs=(LAYOUT.rows(), LAYOUT.rows()),
        check_vma=False,
    )(Xq, cents, gx, gsq, gids)


def _probe_scan_mp(
    Xq_l: jax.Array,
    cents: jax.Array,
    gx_l: jax.Array,
    gsq_l: jax.Array,
    gids_l: jax.Array,
    *,
    k: int,
    nprobe: int,
    cap: int,
    topk_impl: str,
    qchunk: int,
    n_local: int,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`_probe_scan` with the grouped index LIST-SHARDED over mp.

    Each device holds only its own ``n_local = nlist_pad/mp`` lists
    (``LAYOUT.list_blocks()`` on dim 0 of the grouped arrays) — the index
    residency that bounds corpus size on a chip shrinks by 1/mp. The
    coarse quantizer stays replicated (it is (nlist, d) — small), so
    every mp peer ranks the same probe sequence; per probe only the
    OWNING shard gathers real candidates, the rest fold masked +inf/-1
    rows (a no-op on their running top-k). One all-gather of the per-shard
    (k) finalists per query chunk — O(mp·k) per row, never the candidate
    tiles — and a (qc, mp·k) top-k merge produce the global result.
    Probed lists are disjoint across shards, so the merged pool equals the
    replicated path's candidate pool exactly: recall is identical at equal
    nprobe (docs/mesh.md tolerance contract)."""
    from ..parallel.mesh import MP_AXIS

    nq = Xq_l.shape[0]
    qc = min(qchunk, nq)
    pad = (-nq) % qc
    Xq_p = jnp.pad(Xq_l, ((0, pad), (0, 0)))
    c_sq = (cents * cents).sum(axis=1)
    cap_ar = jnp.arange(cap, dtype=jnp.int32)
    l0 = lax.axis_index(MP_AXIS) * n_local     # first OWNED global list id

    def qbody(_, xq):
        x_sq = (xq * xq).sum(axis=1)
        dc = pairwise_sq_dists(xq, cents, c_sq)  # (qc, nlist) MXU
        _, probes = lax.top_k(-dc, nprobe)       # (qc, nprobe) global ids
        bd0 = jnp.full((qc, k), jnp.inf, Xq_l.dtype)
        bi0 = jnp.full((qc, k), -1, jnp.int32)

        def pstep(carry, pj):
            bd, bi = carry
            local = pj - l0                          # (qc,)
            own = (local >= 0) & (local < n_local)
            lc = jnp.clip(local, 0, n_local - 1)     # clamped: gather legal
            cand = lc[:, None] * cap + cap_ar[None, :]
            xi = gx_l[cand]                          # (qc, cap, d)
            csq = gsq_l[cand]
            ids = gids_l[cand]
            dots = jnp.einsum("qd,qcd->qc", xq, xi)
            d2 = jnp.maximum(x_sq[:, None] - 2.0 * dots + csq, 0.0)
            d2 = jnp.where(own[:, None], d2, jnp.inf)
            ids = jnp.where(own[:, None], ids, -1)
            if cap < k:
                d2 = jnp.pad(
                    d2, ((0, 0), (0, k - cap)), constant_values=jnp.inf
                )
                ids = jnp.pad(
                    ids, ((0, 0), (0, k - cap)), constant_values=-1
                )
            negd, sel = _tile_top_k(-d2, k, topk_impl)
            blk_ids = jnp.take_along_axis(ids, sel, axis=1)
            cat_d = jnp.concatenate([bd, -negd], axis=1)
            cat_i = jnp.concatenate([bi, blk_ids], axis=1)
            negm, selm = lax.top_k(-cat_d, k)
            return (-negm, jnp.take_along_axis(cat_i, selm, axis=1)), None

        (bd, bi), _ = lax.scan(
            pstep, (bd0, bi0), jnp.transpose(probes)
        )
        # 2k-style shard merge: every peer's k finalists, one all-gather
        abd = lax.all_gather(bd, MP_AXIS)            # (mp, qc, k)
        abi = lax.all_gather(bi, MP_AXIS)
        cat_d = jnp.moveaxis(abd, 0, 1).reshape(qc, -1)
        cat_i = jnp.moveaxis(abi, 0, 1).reshape(qc, -1)
        negm, selm = lax.top_k(-cat_d, k)
        return None, (-negm, jnp.take_along_axis(cat_i, selm, axis=1))

    _, (bd, bi) = lax.scan(
        qbody, None, Xq_p.reshape(-1, qc, Xq_l.shape[1])
    )
    return bd.reshape(-1, k)[:nq], bi.reshape(-1, k)[:nq]


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "k", "nprobe", "cap", "topk_impl", "qchunk", "n_local"
    ),
)
def _ivf_search_sharded_mp(
    Xq: jax.Array,
    cents: jax.Array,
    gx: jax.Array,
    gsq: jax.Array,
    gids: jax.Array,
    *,
    mesh: Mesh,
    k: int,
    nprobe: int,
    cap: int,
    topk_impl: str,
    qchunk: int,
    n_local: int,
) -> Tuple[jax.Array, jax.Array]:
    from ..parallel.mesh import MP_AXIS

    body = functools.partial(
        _probe_scan_mp,
        k=k, nprobe=nprobe, cap=cap, topk_impl=topk_impl, qchunk=qchunk,
        n_local=n_local,
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.replicated(), LAYOUT.list_blocks(), LAYOUT.list_blocks(), LAYOUT.list_blocks()),
        out_specs=(LAYOUT.rows(), LAYOUT.rows()),
        check_vma=False,
    )(Xq, cents, gx, gsq, gids)


@functools.partial(
    jax.jit, static_argnames=("k", "nprobe", "cap", "topk_impl", "qchunk")
)
def _ivf_search_local(
    Xq: jax.Array,
    cents: jax.Array,
    gx: jax.Array,
    gsq: jax.Array,
    gids: jax.Array,
    *,
    k: int,
    nprobe: int,
    cap: int,
    topk_impl: str,
    qchunk: int,
) -> Tuple[jax.Array, jax.Array]:
    return _probe_scan(
        Xq, cents, gx, gsq, gids,
        k=k, nprobe=nprobe, cap=cap, topk_impl=topk_impl, qchunk=qchunk,
    )


# provenance of the most recent ivf_search dispatch (mirrors
# ops.streaming.last_ingest_report): callers read it AFTER the search to
# surface mp_degree / measured per-shard index bytes without threading a
# side channel through the return contract.
_LAST_SEARCH_REPORT: dict = {}


def last_search_report() -> dict:
    """Copy of the most recent :func:`ivf_search` dispatch provenance.
    Empty dict when the last search ran the replicated (1-D) layout."""
    return dict(_LAST_SEARCH_REPORT)


def ivf_search(
    Xq: jax.Array,
    index: IvfIndex,
    *,
    k: int,
    nprobe: int,
    topk_impl: str = "auto",
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate k-NN against a built index.

    Returns ``(d2 (nq, k) ascending SQUARED distances, ids (nq, k) int32
    source-row ids)`` — the exact ring's output contract, so callers'
    sqrt/id-mapping plumbing is shared. With ``mesh`` the queries must be
    dp-sharded (``shard_rows``) and come back dp-sharded; without it the
    whole search runs on the default device (the single-host UMAP graph
    path, mirroring ``knn_brute``). ``topk_impl`` comes from
    ``resolve_knn_topk()`` — resolved by the caller outside jit.

    On a mesh with a model axis (and ``TPUML_MP_IVF`` on) the grouped
    index arrays are list-sharded over mp — lists padded to a multiple of
    mp with never-probed empty slots — and the probe scan runs
    :func:`_probe_scan_mp`; :func:`last_search_report` then carries
    ``mp_degree`` and the measured per-shard index bytes.
    """
    global _LAST_SEARCH_REPORT
    _LAST_SEARCH_REPORT = {}
    qchunk = _search_qchunk(index.cap, index.grouped_x.shape[1])
    if mesh is None:
        return _ivf_search_local(
            Xq, index.centroids, index.grouped_x, index.grouped_sq,
            index.grouped_ids,
            k=k, nprobe=nprobe, cap=index.cap, topk_impl=topk_impl,
            qchunk=qchunk,
        )
    n_mp = mp_ivf_shards(mesh, index.nlist)
    if n_mp > 1:
        cap = index.cap
        n_local = -(-index.nlist // n_mp)
        nlist_pad = n_local * n_mp
        pad_rows = (nlist_pad - index.nlist) * cap
        gx, gsq, gids = index.grouped_x, index.grouped_sq, index.grouped_ids
        if pad_rows:
            # empty pad lists: +inf ||x||² / -1 ids keep the slot
            # convention; their global list ids exceed nlist-1 so the
            # coarse quantizer can never rank them into a probe set
            gx = jnp.concatenate(
                [gx, jnp.zeros((pad_rows, gx.shape[1]), gx.dtype)]
            )
            gsq = jnp.concatenate(
                [gsq, jnp.full((pad_rows,), jnp.inf, gsq.dtype)]
            )
            gids = jnp.concatenate(
                [gids, jnp.full((pad_rows,), -1, gids.dtype)]
            )
        rep = NamedSharding(mesh, LAYOUT.replicated())
        blocks = NamedSharding(mesh, LAYOUT.list_blocks())
        cents = jax.device_put(index.centroids, rep)
        gx = jax.device_put(gx, blocks)
        gsq = jax.device_put(gsq, blocks)
        gids = jax.device_put(gids, blocks)
        _LAST_SEARCH_REPORT = {
            "mp_degree": n_mp,
            "index_shard_bytes": int(
                gx.addressable_shards[0].data.nbytes
                + gsq.addressable_shards[0].data.nbytes
                + gids.addressable_shards[0].data.nbytes
            ),
        }
        return _ivf_search_sharded_mp(
            Xq, cents, gx, gsq, gids,
            mesh=mesh, k=k, nprobe=nprobe, cap=cap, topk_impl=topk_impl,
            qchunk=qchunk, n_local=n_local,
        )
    # pin the (replicated) index operands to the SEARCH mesh: the build may
    # have committed them elsewhere, and jit refuses mixed device sets
    rep = NamedSharding(mesh, LAYOUT.replicated())
    cents, gx, gsq, gids = (
        jax.device_put(a, rep)
        for a in (
            index.centroids, index.grouped_x, index.grouped_sq,
            index.grouped_ids,
        )
    )
    return _ivf_search_sharded(
        Xq, cents, gx, gsq, gids,
        mesh=mesh, k=k, nprobe=nprobe, cap=index.cap, topk_impl=topk_impl,
        qchunk=qchunk,
    )
