"""RandomForest device kernels: histogram tree building + batched inference.

TPU-native replacement for the CUDA decision-tree builder the reference
drives through cuML (``/root/reference/python/src/spark_rapids_ml/tree.py:269-402``
trains a local ``cuml.RandomForest*`` per worker; the builder itself lives in
libcuml). A translation is impossible and undesirable — instead this is an
XGBoost-style **histogram** builder designed for XLA:

* features are quantized once to ``n_bins`` buckets (a byte each; a fit
  keeps them four to an int32 word, ``_pack_bins``), so every split
  decision becomes dense integer work with static shapes;
* trees grow **level-wise**: one ``segment_sum`` per feature-chunk builds the
  (node, feature, bin, stat) histogram, a cumulative-sum scan turns it into
  left/right sufficient statistics for every candidate threshold, and an
  argmax picks the best split — no per-node recursion, no dynamic shapes;
* the per-level feature chunk size adapts to keep the histogram tile inside
  a fixed HBM budget, so depth-13 × 3000-feature forests (the reference
  benchmark config, ``databricks/run_benchmark.sh:95-112``) fit;
* trees are embarrassingly parallel: each device builds its share of the
  forest on its local row shard (exactly the reference's
  ``_estimators_per_worker`` split, ``tree.py:256-267``) inside one
  ``shard_map`` — zero collectives during growth, matching
  ``_require_nccl_ucx() -> (False, False)`` (``tree.py:416-417``).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS
from ..runtime import autotune, envspec, telemetry

# elements per (F, nodes, bins, stats) histogram tile; bounds peak HBM of the
# deepest level (tile is float32: 1<<22 elems = 16 MiB)
_HIST_BUDGET = 1 << 22

# Histogram strategy cost model. A scatter-add (segment_sum) update costs a
# roughly constant time on TPU (~1e8 updates/s measured — the round-2
# builder's 8.5 s/tree at 131k x 256 x depth 13 is exactly 13 levels of
# n*d*S updates at that rate), while the one-hot-matmul formulation costs
# 2*n_nodes*n_bins MXU flops per update (~5e13 flop/s). The matmul path
# therefore wins while 2*n_nodes*n_bins is below ~5e5 "scatter-equivalent
# flops" — i.e. every level until n_nodes*n_bins ~ 2.5e5 — by up to two
# orders of magnitude at shallow levels. Overridable for re-tuning on other
# chip generations.
_SCATTER_EQ_FLOPS = float(envspec.get("TPUML_RF_SCATTER_EQ_FLOPS"))

# HBM budget for the fused-selection path's residents. Resolved ONCE at
# import (the _SCATTER_EQ_FLOPS pattern — a per-trace env read would be
# silently ignored on jit cache hits): env override, else 3/4 of the
# device's reported memory (of a nominal 16 GB on the CPU backend, which
# reports none). Device memory is process-stable, so deriving it at first
# use cannot go stale.
_SEL_HBM_BUDGET_ENV = envspec.get("TPUML_RF_SEL_HBM_BUDGET")


def _sel_hbm_budget() -> float:
    if _SEL_HBM_BUDGET_ENV:
        return float(_SEL_HBM_BUDGET_ENV)
    from ..parallel.mesh import device_bytes_limit

    return 0.75 * device_bytes_limit(16e9)


# minimum feature width for the fused-selection histogram kernel: below
# this the word-packed contraction gather is already cheap (~1.6 ms per
# level) and the fused kernel's full-row reads + lane padding cost more
# than they save (measured either way on v5e, round 4). Tests lower it
# to exercise the fused path at interpret-friendly sizes.
_SEL_MIN_DPAD = 1024
def resolve_contract_gather() -> str:
    """Validated subset-extraction strategy from TPUML_RF_CONTRACT_GATHER:
    "auto" (TPU at moderate widths), "on", or "off". Rides the static
    ForestConfig so it participates in the jit cache key — a module flag
    read at trace time would be silently ignored on cache hits."""
    return str(envspec.get("TPUML_RF_CONTRACT_GATHER"))
# rows per matmul accumulation chunk: bounds the (C, n_nodes) node-onehot
# and (C, F*nb) bin-onehot intermediates (C=8192, level 12, F*nb=512:
# 8192*4096*4 = 128 MB node-onehot is the largest, still < HBM noise)
_ROW_CHUNK = 1 << 13


def resolve_hist_strategy() -> str:
    """Validated histogram strategy from the TPUML_RF_FORCE_STRATEGY env
    var (typos must error, not silently fall back to the heuristic).

    "compact" forces the node-contiguous Pallas path on every level where
    its lowering is eligible (TPU, f32 stats, lane-aligned widths) and
    falls back to scatter on levels where it is not — the fused-kernel
    analog of knn's "auto", kept as its own name so "auto" can keep
    meaning "per-level cost model" as strategies evolve."""
    return str(envspec.get("TPUML_RF_FORCE_STRATEGY"))


def _largest_divisor_leq(t: int, b: int) -> int:
    for d in range(min(t, b), 0, -1):
        if t % d == 0:
            return d
    return 1


def resolve_tree_batch(
    t_group: int, cfg: "ForestConfig", n_rows: int, d_pad: int = 0
) -> int:
    """Trees advanced per batched level dispatch (1 = sequential builder).

    ``TPUML_RF_TREE_BATCH``: ``off`` pins the sequential per-tree builder,
    an integer pins a batch width, ``auto`` targets the whole dispatch
    group. The result is clamped to (a) a divisor of ``t_group`` — the
    group reshapes to (G, B, 2) key batches — and (b) the widest batch
    whose per-level residents fit the HBM budget: the histogram tile, its
    gain-chain copies, and the per-tree row state (stat weights, routing
    ids, subset-gathered bins — and, where the fused-selection kernel
    engages at ``d_pad`` bins a row, its node-sorted copy of the FULL bins
    rows and its partials: 1.6 + 0.5 GB a tree at 500,000 x 3072, which
    this budget once left out, so that eight trees asked for 17 GB) all
    scale xT, while the per-level strategy
    gates deliberately stay per-tree so batched and sequential builds
    select identical strategies — a precondition of their bit-identity
    (see docs/rf_performance.md).
    """
    raw = str(envspec.get("TPUML_RF_TREE_BATCH")).strip().lower()
    if raw == "off":
        return 1
    tune_key = None
    if raw == "auto":
        want = t_group
        if autotune.active():
            tune_key = autotune.shape_key(
                n=n_rows,
                d=cfg.n_features,
                k=cfg.n_stats,
                dtype="uint8",
                depth=cfg.max_depth,
                group=t_group,
            )
            tuned = autotune.consult("rf_tree_batch", tune_key)
            # a tuned width only applies where it still divides the
            # group — a stale entry from a different tree count falls
            # through to the heuristic rather than breaking the reshape
            if (
                isinstance(tuned, int)
                and 1 <= tuned <= t_group
                and t_group % tuned == 0
            ):
                want = tuned
                tune_key = None  # provenance already filed by consult
    else:
        try:
            want = int(raw)
        except ValueError:
            raise envspec.EnvSpecError(
                f"TPUML_RF_TREE_BATCH={raw!r}: expected 'auto', 'off', or "
                "a positive integer"
            ) from None
        if want < 1:
            raise envspec.EnvSpecError(
                f"TPUML_RF_TREE_BATCH={want}: batch width must be >= 1"
            )
    budget = envspec.get("TPUML_RF_TREE_BATCH_BUDGET")
    budget = float(budget) if budget else _sel_hbm_budget() / 4.0
    subset = cfg.k_features < cfg.n_features
    d_hist = next_pow2(cfg.k_features if subset else max(1, cfg.n_features))
    n_nodes_max = 1 << max(0, cfg.max_depth - 1)
    tile = min(_HIST_BUDGET, n_nodes_max * cfg.n_bins * cfg.n_stats * d_hist)
    plan = (
        level_plan(n_rows, d_pad, max(0, cfg.max_depth - 1), cfg)
        if d_pad else None
    )
    deepest = plan.strategy if plan else ""
    # the selection tiled over slots gathers no subset: the kernel selects
    gathered = d_hist if subset and deepest != "pallas_sel_wide" else 0
    per_tree = 4 * n_rows * (cfg.n_stats + 4 + gathered) + 16 * tile
    if deepest == "pallas_sel":
        per_tree += plan.n_pad * d_pad + (
            plan.n_pad // plan.r_sub
        ) * cfg.n_stats * d_hist * cfg.n_bins * 4
    elif deepest == "pallas_sel_wide":
        # the kernel's running sums, the histogram and its transpose, and a
        # gain chain over the compact path's 4 * _HIST_BUDGET cells
        from .rf_pallas import WIDE_STAT_ROWS

        cells = n_nodes_max * d_hist * cfg.n_bins
        per_tree += 4 * cells * (WIDE_STAT_ROWS + 2 * cfg.n_stats) + 64 * min(
            4 * _HIST_BUDGET, cells * cfg.n_stats
        )
    fit = max(1, int(budget // max(1, per_tree)))
    batch = _largest_divisor_leq(t_group, min(want, fit))
    if tune_key is not None:
        autotune.record_heuristic("rf_tree_batch", tune_key, batch)
    telemetry.record_hbm_estimate("tree_batch", float(per_tree) * batch)
    return batch


class ForestConfig(NamedTuple):
    """Static (compile-time) build configuration."""

    max_depth: int
    n_bins: int
    n_features: int        # real (unpadded) feature count
    n_stats: int           # classification: n_classes; regression: 3
    impurity: str          # "gini" | "entropy" | "variance"
    k_features: int        # features sampled per node (featureSubsetStrategy)
    min_samples_leaf: int  # Spark minInstancesPerNode
    min_info_gain: float   # Spark minInfoGain
    min_samples_split: int
    bootstrap: bool
    # histogram strategy: "auto" (TPU: per-level cost model; CPU: scatter),
    # "matmul", or "scatter". Part of the static config so it participates
    # in the jit cache key (an env var read inside the traced function
    # would be silently ignored on cache hits).
    hist_strategy: str = "auto"
    # subset-extraction strategy: "auto" | "on" | "off" (see
    # resolve_contract_gather); static for the same cache-key reason
    contract_gather: str = "auto"
    # bytes the caller keeps on the device through the growth besides the
    # bins (the estimator's X): counted among the fused-selection kernel's
    # residents, which has no fallback on a runtime OOM
    held_bytes: int = 0


def max_nodes(max_depth: int) -> int:
    """Full binary tree layout: node i's children are 2i+1 / 2i+2."""
    return (1 << (max_depth + 1)) - 1


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


def make_bin_edges(
    X: np.ndarray, n_bins: int, max_sample: int = 131072, seed: int = 0
) -> np.ndarray:
    """Per-feature quantile bin edges (host, on a row subsample).

    Approximate quantile sketching is the standard histogram-GBM approach;
    cuML similarly computes per-feature quantiles on device. Returns
    ``(d, n_bins - 1)`` float32; row x falls in bin ``#{edges <= x}``.
    """
    n = X.shape[0]
    if n > max_sample:
        idx = np.random.default_rng(seed).choice(n, max_sample, replace=False)
        Xs = X[idx]
    else:
        Xs = X
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = np.quantile(np.asarray(Xs, dtype=np.float64), qs, axis=0)
    return np.ascontiguousarray(edges.T.astype(np.float32))  # (d, nb-1)


# rows of the quantile sketch, taken as runs of one lane tile of consecutive
# rows spread evenly over the frame
_SKETCH_ROWS = 131072
_SKETCH_RUN = 128


@functools.partial(jax.jit, static_argnames=("n_bins",))
def quantile_edges(X: jax.Array, mask: jax.Array, *, n_bins: int):
    """Per-feature quantile bin edges ON THE DEVICE: ``((d, n_bins - 1)
    edges, all sampled values finite)``. Only the edges cross to the host
    (1.5 MB at 3000 x 127), where the strided sample of old brought every
    third row of the frame (2.0 GB at 500,000 x 3000) by a row gather that
    a rows-minor frame answers with a relaid copy of itself.

    A frame of more than ``_SKETCH_ROWS`` rows is sampled as 1024 runs of
    128 consecutive rows spread evenly from its first row to its last: a run
    of whole lane tiles is a slice of a rows-minor frame, a stride is a
    gather. On data SORTED by a column a run holds 128 neighbouring ranks
    and the next run starts n/1024 rows on, so an edge of that column can
    miss its rank by at most n/1024 - 128 rows (0.07% of the rows at
    500,000, a tenth of a 128-quantile bin); 64 runs of 2048 would miss by
    1.2%, more than a bin. Rows the mask leaves out sort past every valid
    value (+inf) and the quantile positions are taken among the valid ones
    (``numpy.quantile``'s linear interpolation, in the frame's dtype).
    """
    n, d = X.shape
    if n > _SKETCH_ROWS:
        runs = _SKETCH_ROWS // _SKETCH_RUN
        los = (np.arange(runs) * (n - _SKETCH_RUN)) // (runs - 1)
        los = jnp.asarray(los // _SKETCH_RUN * _SKETCH_RUN, jnp.int32)
        sample = lax.map(
            lambda lo: lax.dynamic_slice(X, (lo, 0), (_SKETCH_RUN, d)), los
        ).reshape(runs * _SKETCH_RUN, d)
        valid = lax.map(
            lambda lo: lax.dynamic_slice(mask, (lo,), (_SKETCH_RUN,)), los
        ).reshape(runs * _SKETCH_RUN) > 0
    else:
        sample, valid = X, mask > 0
    finite = jnp.all(jnp.isfinite(sample) | ~valid[:, None])
    ordered = jnp.sort(
        jnp.where(valid[:, None], sample, jnp.inf), axis=0, stable=False
    )
    m = jnp.maximum(valid.sum(), 1)
    qs = jnp.linspace(0.0, 1.0, n_bins + 1)[1:-1].astype(X.dtype)
    pos = qs * (m - 1).astype(X.dtype)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, m - 1)
    a, b = ordered[lo], ordered[hi]
    edges = a + (b - a) * (pos - lo.astype(X.dtype))[:, None]
    return edges.T, finite


class BinizePlan(NamedTuple):
    """Which pass :func:`binize` runs at a shape, and why: ``kernel``
    ``"pallas"`` (``rf_pallas.rf_binize_pass``) or ``"xla"``; ``tile`` the
    kernel's rows x columns a grid step reads, or XLA's chunk of columns;
    ``declined`` the kernel gate's failing terms; ``rows_minor_out`` whether
    the device keeps the bins with their rows minor (the kernel writes them
    so); ``packed`` whether the bins come four to an int32 word
    (:func:`_pack_bins`: a fit's) or a byte each (a transform batch's)."""

    kernel: str
    tile: str
    declined: str
    rows_minor_out: bool
    packed: bool = False

    def span_attrs(self) -> dict:
        return {"kernel": self.kernel, "tile": self.tile, "declined": self.declined}


def bins_layout(bins: jax.Array) -> str:
    """How a fit holds its bins, for its spans: ``packed32`` (four to an
    int32 word, :func:`_pack_bins`), else their dtype — the reason they are
    not."""
    return "packed32" if bins.dtype == jnp.int32 else f"dtype={bins.dtype}"


def _binize_chunk(n: int, d: int) -> int:
    """Columns of one compare-count chunk of XLA's pass: the (n, chunk, nb)
    compare held near 4M elements, in whole 8-column sublane tiles (a chunk
    of 39 put every later chunk of a rows-minor batch mid-tile)."""
    return min(d, max(8, (1 << 22) // max(n, 1) // 8 * 8))


def binize_plan(
    shape, dtype, n_edges: int, d_pad: int, devices: int = 1, device=None, packed: bool = False
) -> BinizePlan:
    """The :class:`BinizePlan` of an ``(n, d)`` frame on ``devices`` devices
    (the first of them ``device``) at ``d_pad`` bin columns, a byte each or
    (``packed``) four to an int32 word."""
    from .linalg import rows_minor
    from .rf_pallas import binize_tile, rf_binize_declined

    n, d = shape
    words = d_pad // 4 if packed else 0
    declined = rf_binize_declined(n, d, n_edges, dtype, devices, device, words)
    if declined:
        return BinizePlan("xla", str(_binize_chunk(n, d)), declined, False, packed)
    rows, cols, _ = binize_tile(n, words)
    out_minor = rows_minor(
        device or jax.devices()[0], n, words or d_pad, jnp.int32 if packed else jnp.uint8
    )
    return BinizePlan("pallas", f"{rows}x{cols}", "", out_minor, packed)


@functools.partial(jax.jit, static_argnames=("d_pad", "plan"))
def binize(X: jax.Array, edges: jax.Array, *, d_pad: int, plan: BinizePlan | None = None) -> jax.Array:
    """Quantize rows to bins: (n, d) x (d, nb-1) -> (n, d_pad) uint8, or
    where ``plan.packed`` (a fit's) the same bins four to an int32 word,
    (n, d_pad / 4) in :func:`_pack_bins`' layout, written so by the pass.

    bin = #{edges <= x}. ``plan`` (:func:`binize_plan`; asked of the shape
    on the default device where None) says which pass counts it. On a TPU,
    a rows-minor f32 frame takes ``rf_pallas.rf_binize_pass``: one read of
    the frame as its transpose, a search of each column's sorted edges.
    Elsewhere XLA's broadcast compare-count in column chunks
    (:func:`_binize_chunk`) — the searchsorted formulation lowers to a
    per-element binary search (~n*d*log(nb) serialized gathers, seconds at
    131k x 256) while the compare-count is a fused VPU reduction (n*d*nb
    compare-adds, ~ms). Elementwise along rows, so XLA keeps the dp row
    sharding. Both give the same bins, bit for bit. Padding features
    (d..d_pad) get bin 0 and are masked out of split search.

    Input contract — FINITE values only. NaN compares false against every
    edge, so a NaN lands in bin 0 (the leftmost child everywhere below),
    where numpy's searchsorted would route it PAST the last edge into the
    rightmost bin. This routing is intentional and fixed (fit and
    transform quantize through this same function, so training and
    serving agree), but it is a semantics choice, not an accident — the
    estimator boundary enforces/documents the finite-input contract
    (``models/tree.py``, ``TPUML_RF_CHECK_FINITE``) rather than paying a
    per-element isnan pass here on the hot path.
    """
    n, d = X.shape
    if plan is None:
        plan = binize_plan(X.shape, X.dtype, edges.shape[1], d_pad)
    if plan.kernel == "pallas":
        from .rf_pallas import FORCE_INTERPRET, rf_binize_pass

        return rf_binize_pass(
            X, edges, d_pad=d_pad, rows_minor_out=plan.rows_minor_out, packed=plan.packed,
            interpret=FORCE_INTERPRET,
        )
    Fc = int(plan.tile)
    parts = []
    for c0 in range(0, d, Fc):
        xc = X[:, c0 : c0 + Fc]                       # (n, fc)
        ec = edges[c0 : c0 + Fc]                      # (fc, nb-1)
        cnt = (xc[:, :, None] >= ec[None, :, :]).sum(
            axis=2, dtype=jnp.int32
        )
        parts.append(cnt.astype(jnp.uint8))
    bins = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if d_pad > d:
        bins = jnp.pad(bins, ((0, 0), (0, d_pad - d)))
    return _pack_bins(bins) if plan.packed else bins


# ---------------------------------------------------------------------------
# impurity
# ---------------------------------------------------------------------------


def _count(stats: jax.Array, impurity: str) -> jax.Array:
    """Row weight in a stats vector: class-count sum, or the weight slot."""
    if impurity == "variance":
        return stats[..., 0]
    return stats.sum(axis=-1)


def _impurity(stats: jax.Array, impurity: str) -> jax.Array:
    n = _count(stats, impurity)
    safe = jnp.maximum(n, 1e-12)
    if impurity == "variance":
        mean = stats[..., 1] / safe
        return jnp.maximum(stats[..., 2] / safe - mean * mean, 0.0)
    p = stats / safe[..., None]
    if impurity == "gini":
        return 1.0 - (p * p).sum(axis=-1)
    if impurity == "entropy":
        return -(jnp.where(p > 0.0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0)).sum(
            axis=-1
        )
    raise ValueError(f"unknown impurity {impurity!r}")


def _can_split(stats: jax.Array, cfg: "ForestConfig") -> jax.Array:
    """Whether a node holding ``stats`` may split at all, whatever its
    histogram: it holds ``min_samples_split`` and, for class counts, more
    than one class. In exact arithmetic a pure node's impurity and every
    gain of it are 0, under the 1e-9 floor; a TPU's float32 division is not
    exact (on a v5e c / c reads 1 - 6e-8 or 1 + 1.2e-7 for a quarter of the
    counts below 200,000), so there a pure node's gini is +-2.4e-7 and its
    "gain" cleared the floor: 27% of a forest's splits on the benchmark's
    data were splits of a pure node on rounding noise, into children of the
    same one class, down to three levels on (PERF.md section 6, PR 38). Said
    once, here, for both builders' gain search and for the closing of a
    child when it is made."""
    ok = _count(stats, cfg.impurity) >= cfg.min_samples_split
    if cfg.impurity != "variance":
        ok = ok & ((stats > 0).sum(axis=-1) > 1)
    return ok


def _chunk_features(
    d_pad: int, n_nodes: int, n_bins: int, n_stats: int, budget: int = _HIST_BUDGET
) -> int:
    """Largest power-of-two feature-chunk keeping the histogram tile in
    budget; d_pad is a power of two, so the chunk always divides it."""
    per_feat = max(1, n_nodes * n_bins * n_stats)
    f = max(1, budget // per_feat)
    f = 1 << (f.bit_length() - 1)
    return min(f, d_pad)


# ---------------------------------------------------------------------------
# contraction gather (TPU): per-row feature-subset bin extraction
# ---------------------------------------------------------------------------


def _pack_bins(bins: jax.Array) -> jax.Array:
    """(n, d) uint8 bins -> (n, d/4) int32 words, four bins to a word
    (d % 4 == 0). PLANAR: byte ``p`` of word ``w`` holds column
    ``p * (d/4) + w``, so byte plane ``p`` is the columns ``[p*d/4,
    (p+1)*d/4)`` in order — a lane tile of a plane is a lane tile of
    columns, and a kernel reads 128 columns as 128 words and a shift
    (``rf_pallas``). THE layout of a fit's bins from ``binize`` to the last
    level, and of every word-packed bins here."""
    w = bins.shape[1] // 4
    b32 = bins.astype(jnp.int32)
    return (
        b32[:, :w]
        | (b32[:, w : 2 * w] << 8)
        | (b32[:, 2 * w : 3 * w] << 16)
        | (b32[:, 3 * w :] << 24)
    )


def _unpack_bins(packed: jax.Array, c0=0, width: int | None = None) -> jax.Array:
    """Columns ``[c0, c0 + width)`` of packed bins (all of them by default)
    as (n, width) int32. ``c0`` may be traced; it is a multiple of
    ``width``, which divides the words a row (a slice of one byte plane) or
    is a multiple of them (whole planes)."""
    words = packed.shape[1]
    width = 4 * words if width is None else width
    if width <= words:
        assert words % width == 0, (words, width)
        w = lax.dynamic_slice_in_dim(packed, c0 % words, width, axis=1)
        return (w >> (8 * (c0 // words))) & 0xFF
    assert width % words == 0, (words, width)
    return jnp.concatenate(
        [(packed >> (8 * (c0 // words + p))) & 0xFF for p in range(width // words)],
        axis=1,
    )


def _bins_at(packed: jax.Array, rows, cols: jax.Array) -> jax.Array:
    """``bins[rows, cols]`` of packed bins as int32: one element gather of a
    word and a shift. ``rows`` broadcasts against ``cols``; None takes row
    ``r`` for ``cols[r]`` (``take_along_axis``). A column id past the width
    (a padded subset's sentinel) is clipped to the last column, as the
    byte gathers clipped it."""
    words = packed.shape[1]
    c = jnp.clip(cols, 0, 4 * words - 1)
    if rows is None:
        w = jnp.take_along_axis(packed, c % words, axis=1)
    else:
        w = packed[rows, c % words]
    return (w >> (8 * (c // words))) & 0xFF


def _contract_gather(packed: jax.Array, idx: jax.Array) -> jax.Array:
    """bins[r, idx[r, j]] as a dense compare-select-reduce: (n, k) int32.

    TPU gathers run at ~1e8 elem/s, making ``take_along_axis`` of the
    per-node sampled columns the single dominant cost of an RF level
    (measured 25.5 ms of a ~33 ms level at 131k x 256, k=16). Expressed as
    a word-packed one-hot contraction the same extraction streams on the
    VPU at ~1.6 ms: compare the column's word (``idx % W``) against the W
    word lanes, reduce, and shift its byte (``idx // W``) out. Feature-count
    sentinels yield bin 0 (see the sentinel invariant note below this
    function), and the gain search masks those slots exactly like the old
    clipped-gather path."""
    words = packed.shape[1]
    ar_w = jnp.arange(words, dtype=jnp.int32)
    sel = (idx % words)[:, :, None] == ar_w[None, None, :]
    w = jnp.where(sel, packed[:, None, :], 0).sum(-1)  # (n, k)
    return jnp.where(idx < 4 * words, (w >> ((idx // words) * 8)) & 0xFF, 0)


# Sentinel invariant for _contract_gather: a feature-count sentinel
# (idx == n_features) either lies past every column (n_features == d_pad)
# and yields 0, or lands in a zero-filled padding column (n_features <
# d_pad; binize pads bins with 0) and yields bin 0 — the same value the old
# clipped take_along_axis produced. Both cases rely on binize's zero fill
# of columns >= n_features, and the gain search additionally masks every
# sentinel slot via realf < n_features.


# ---------------------------------------------------------------------------
# compact histogram strategy (TPU): node-contiguous Pallas sub-blocks
# ---------------------------------------------------------------------------


def _compact_r_sub(n: int, n_nodes: int, R: int, S: int) -> int:
    """Per-level sub-block size: ~half the average node width, so the
    alignment padding stays ~+50% worst-case while sub-block count (and
    with it the final segment reduce) stays small at shallow levels.
    Capped so the kernel's (L*S, W) output block keeps a sublane dim
    that is a multiple of 8 (L = R // r_sub; Mosaic block rule)."""
    import math

    r = min(512, max(8, next_pow2(max(1, n // (n_nodes * 2)))))
    # (L*S) % 8 == 0 needs L a multiple of 8/gcd(S, 8); the fused-
    # selection kernel additionally needs L >= 8 for its feature-id
    # block, so cap at R/8 (costs a few extra sub-blocks per level at
    # shallow depths — sub-ms in the segment reduce)
    cap = min(R // (8 // math.gcd(S, 8)), R // 8)
    return max(1, min(r, cap, R))


def _sorted_block_reduce(partials2d, pstart, r_sub, n_nodes):
    """Per-node reduction of node-sorted sub-block partials via cumulative
    sums + boundary differences instead of a segment_sum scatter: the
    sub-blocks are already contiguous per node, so node g's histogram is
    ``C[pstart[g+1]/r_sub] - C[pstart[g]/r_sub]`` with C the zero-prefixed
    cumsum. Wide-row segment_sum measures ~3e6 rows/s; the cumsum runs at
    bandwidth and the boundary gather touches only n_nodes+1 rows.

    EXACT for integer stats while the GLOBAL per-column prefix stays
    < 2^24 (every f32 running sum is then an exactly-representable
    integer — note this bounds the whole column's cumsum, a stronger
    requirement than per-node sums, so callers gate on total row count);
    callers keep the scatter path for variance stats where cumsum
    reassociation would round, and for row counts where a concentrated
    bin could push a column prefix past 2^24."""
    C = jnp.concatenate(
        [jnp.zeros((1, partials2d.shape[1]), partials2d.dtype),
         jnp.cumsum(partials2d, axis=0)]
    )
    bounds = C[pstart[: n_nodes + 1] // r_sub]
    return bounds[1:] - bounds[:-1]


# rows of the node-sorted order that one trip of a level's loops works on: 32
# kernel blocks, 50 MB of whole rows at 3072 bins a row. ONE size for every
# level of every tree, so a tree's thirteen histogram calls are one Mosaic
# shape; what follows the live rows is the trip count.
_LIVE_CHUNK = 16384


def _level_seg(node: jax.Array, level: int, weight=None):
    """``(local, in_level, seg)`` of ``level``: the level-local node id of
    every row, whether the row sits in a node of the level, and the id the
    histograms group by — ``n_nodes`` (the dump slot) for a dead row.

    THE one place that decides liveness, for both builders (their
    bit-identity on variance statistics rests on equal groupings of the f32
    sums, so they must form the same sub-blocks). A row is live iff it sits
    in a node of the level and, where ``weight`` is given, its weight is
    positive: a row the bootstrap drew zero times (36.8% of a tree's rows)
    or the mask leaves out adds 0 to every histogram cell, to the parents'
    and to the leaves' statistics, and is no output of the fit — it sorts
    past the live rows and is not gathered, histogrammed, reduced or routed.
    A row bound for a child that was closed when it was made
    (``_build_tree``) was never moved there: it sits at its parent's id, in
    no node of any later level, and is dead here without a word about it.
    A caller that needs EVERY row's final node (``return_rows``) passes no
    weight and keeps such rows live."""
    n_nodes = 1 << level
    local = node - (n_nodes - 1)
    in_level = (local >= 0) & (local < n_nodes)
    live = in_level if weight is None else in_level & (weight > 0)
    return local, in_level, jnp.where(live, local, n_nodes).astype(jnp.int32)


class _Frontier(NamedTuple):
    """A level's live rows in node-sorted order, for the routing."""

    rows: jax.Array     # (n_ceil,) original row id at a sorted position; n = padding
    sb_node: jax.Array  # (n_ceil // r_sub,) level-local node of a sub-block (clipped)
    trips: jax.Array    # () chunks that hold a live row: ceil(P / chunk)
    chunk: int          # rows a chunk (a BLOCK_ROWS multiple)
    r_sub: int
    # the wide selection's reads over the level's live blocks: (column tiles
    # its products read, slot tiles it ran), int32; zeros on another strategy
    sel_tiles: jax.Array


def _hist_compact(
    row_bins,             # (row ids (m,), their level-local nodes (m,)) ->
                          # (m, F) int bins; None with full_bins
    seg: jax.Array,       # (n,) int32 level-local node id; n_nodes = dead
    sw: jax.Array,        # (n, S) f32 stats*weight
    *,
    n_nodes: int,
    n_slots: int,         # F: the histogram's feature slots
    nb: int,
    r_sub: int,
    n_pad: int,           # from the caller's eligibility gate: the SAME
                          # block-aligned padded row count it validated
    f_chunk: int,         # feature-chunk width (gate-validated, divides F)
    variance: bool,
    full_bins=None,       # (n, d_pad/4) int32 packed + feats => fused-selection
    feats=None,           # (n_nodes, F) int32 per-node feature ids
    wide: bool = False,   # the fused selection tiled over slots (r_sub = its block)
    interpret=None,
):
    """(F, n_nodes, nb, S) histogram, (n_nodes, S) parent stats and the
    level's :class:`_Frontier` via the node-contiguous Pallas path
    (``ops/rf_pallas.py``).

    One stable sort groups rows by node; every node's run is padded to an
    ``r_sub`` multiple so each aligned sub-block is node-pure; the Pallas
    kernel turns each sub-block into a (S, F*nb) histogram with a bin-only
    one-hot (NO node dimension — the whole point); and a segment-sum over
    the node-sorted sub-blocks finishes the per-node histograms. Parent
    stats fall out of the histogram (bin-sum of the first subset slot —
    slot 0 is always a real feature), saving the per-level parent scatter
    the other strategies pay.

    **The live frontier.** Dead rows sort to the end, so the live rows are
    the prefix ``[0, P)`` of the padded order, ``P = pstart[n_nodes]``, a
    device scalar. Every shape stays static; what follows ``P`` is a trip
    count. The level is ONE loop over chunks of ``_LIVE_CHUNK`` sorted
    positions, ``ceil(P / chunk)`` trips: a trip gathers its rows' ids and
    weights and the rows themselves (whole rows of packed words for the
    fused selection, which the kernel unpacks in VMEM: a row of 3072 bins
    is 6 lane tiles of words where its bytes were 24 sub-word tiles, and
    the gather's rate goes by the tile), hands that chunk to the
    kernel, and adds the chunk's partials to the per-node sums in sub-block
    order, as ``segment_sum`` adds them (the batched builder's grouping of
    the f32 sums). No node-sorted copy of a level is held (1.5-2.3 GB at
    500,000 x 3072, and its partials 0.5-0.8 GB): a chunk and its partials
    are 70 MB. In the last trip the kernel takes the number of its
    blocks that hold a live row by scalar prefetch and neither fetches nor
    computes the others; their partials are unwritten and are dropped
    unread (an out-of-range segment id). A chunk past ``P`` is not touched.

    Measured v5e at 131k x 16 x 128 x 2 (level 12): ~41 ms for the
    scatter strategy's histogram vs ~1 ms kernel + ~4 ms glue here
    (scripts/rf_deep_microbench*.py).

    ``wide`` (``level_plan``'s ``pallas_sel_wide``): the same loop, the
    chunk's whole rows of words handed to ``subblock_hist_sel_wide`` with the running
    per-node sums, which the kernel adds to in place — no partials, no
    scatter-add; a sub-block is one kernel block of one node. The kernel
    takes each node's ids ascending, so a slot tile's selection reads a
    narrow window of columns (the frontier's ``sel_tiles`` says how narrow);
    ``wide_hist_nodes`` puts the slots back in the draw's order.
    """
    from .rf_pallas import (
        BLOCK_ROWS,
        WIDE_SLOTS,
        WIDE_STAT_ROWS,
        split_f32_exact,
        subblock_hist,
        subblock_hist_sel,
        subblock_hist_sel_wide,
        wide_hist_nodes,
        wide_windows,
    )

    n = seg.shape[0]
    F, Fc = n_slots, f_chunk
    S = sw.shape[1]
    chunk = min(n_pad, _LIVE_CHUNK)
    chunk_sb = chunk // r_sub
    # the padded order, rounded up to whole chunks (index arithmetic only)
    n_ceil = -(-n_pad // chunk) * chunk
    n_sb = n_ceil // r_sub

    # stable sort of row ids by node: perm[j] = original row at sorted pos j
    iota = jnp.arange(n, dtype=jnp.int32)
    keys_s, perm = lax.sort((seg, iota), num_keys=1)
    # per-node source runs and r_sub-aligned destination runs
    starts = jnp.searchsorted(
        keys_s, jnp.arange(n_nodes + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)                                     # (n_nodes+1,)
    lens = starts[1:] - starts[:-1]                         # (n_nodes,)
    plen = -(-lens // r_sub) * r_sub
    pstart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(plen)]
    )                                                       # (n_nodes+1,)
    p_live = pstart[n_nodes]                                # P
    trips = -(-p_live // chunk)
    # node of each sub-block (sub-blocks are node-pure by construction;
    # positions past the data resolve to the n_nodes dump slot)
    sb_pos = jnp.arange(n_sb, dtype=jnp.int32) * r_sub
    seg_sb = jnp.searchsorted(pstart[1:], sb_pos, side="right").astype(
        jnp.int32
    )                                                       # (n_sb,)
    # per-row source index: ONE small-table row gather at sub-block
    # granularity (n_sb rows), broadcast to rows — per-row gathers from
    # the (n_nodes,) tables would cost ~1 ms each at the elementwise
    # gather wall. Index arithmetic only: it stays at full size.
    sbc = jnp.clip(seg_sb, 0, n_nodes - 1)
    tbl = jnp.stack([starts[:-1], pstart[:-1], lens], axis=1)
    tbl_rows = jnp.broadcast_to(
        tbl[sbc][:, None, :], (n_sb, r_sub, 3)
    ).reshape(n_ceil, 3)
    pos = jnp.arange(n_ceil, dtype=jnp.int32)
    off = pos - tbl_rows[:, 1]
    src = jnp.clip(tbl_rows[:, 0] + off, 0, n - 1)
    pvalid = (off < tbl_rows[:, 2]) & (
        jnp.broadcast_to(seg_sb[:, None], (n_sb, r_sub)).reshape(n_ceil)
        < n_nodes
    )
    # a stat at a time, from (n,) vectors: a gather of (n, S) rows makes
    # the device pad S to a lane tile first, 256 MB at 500,000 x 2. (Sorting
    # the stats along with the ids and reading both as one r_sub-wide window
    # a sub-block was measured and is SLOWER: PERF.md section 6, PR 36.)
    sw_cols = [sw[:, s] for s in range(S)]
    sel_tiles = jnp.zeros((2,), jnp.int32)
    if wide:
        # a node's ids ascending: a slot tile's are then a run of them, whose
        # window of columns is narrow; its blocks each run every slot tile
        ids_up = jnp.sort(feats, axis=1)
        featsq = ids_up[sbc]                                # (n_sb, F)
        blocks = plen // r_sub
        sel_tiles = jnp.stack([
            (blocks * wide_windows(ids_up, 4 * full_bins.shape[1])[1].sum(axis=1)).sum(),
            blocks.sum() * (F // WIDE_SLOTS),
        ]).astype(jnp.int32)
    elif full_bins is not None:
        featsq = feats[sbc]                                 # (n_sb, F)
    ar_sb = jnp.arange(chunk_sb, dtype=jnp.int32)

    def level_chunk(c, carry):
        accs, live_ids = carry
        lo = c * chunk
        lo_sb = c * chunk_sb
        ok = lax.dynamic_slice(pvalid, (lo,), (chunk,))
        ids = perm[lax.dynamic_slice(src, (lo,), (chunk,))]
        swT = jnp.stack([col[ids] for col in sw_cols]) * ok[None, :].astype(
            sw.dtype
        )                                                   # (S, chunk)
        # blocks of this chunk that hold a live row
        live_blocks = jnp.clip(
            -(-(p_live - lo) // BLOCK_ROWS), 0, chunk // BLOCK_ROWS
        ).reshape(1)
        if wide:
            with jax.named_scope("forest.wide_rows"):
                # whole rows of words (the kernel unpacks a column tile of
                # bins at a time) and the statistics' three-way split
                rows = full_bins[ids]
                parts = split_f32_exact(swT)
            # the kernel adds a node's blocks to its running sums in place
            accs = (
                subblock_hist_sel_wide(
                    rows,
                    lax.dynamic_slice(featsq, (lo_sb, 0), (chunk_sb, F)),
                    parts, lax.dynamic_slice(sbc, (lo_sb,), (chunk_sb,)),
                    live_blocks, accs[0], n_bins=nb, interpret=interpret,
                ),
            )
        else:
            if full_bins is not None:
                # whole rows of words; the kernel selects each node's k
                # columns with an MXU one-hot dot, replacing the per-row k-column gather
                # that costs ~780 ms per level at the reference 1M x 3000
                # shape. Dump sub-blocks of a live block get garbage feature
                # rows but zero weights — they contribute nothing.
                parts = [
                    subblock_hist_sel(
                        full_bins[ids],
                        lax.dynamic_slice(featsq, (lo_sb, 0), (chunk_sb, F)),
                        swT, live_blocks, n_bins=nb, r_sub=r_sub,
                        variance=variance, interpret=interpret,
                    )
                ]                                           # (chunk_sb, S, F*nb)
            else:
                nodes = jnp.broadcast_to(
                    lax.dynamic_slice(sbc, (lo_sb,), (chunk_sb,))[:, None],
                    (chunk_sb, r_sub),
                ).reshape(chunk)
                # int32 bins always: the kernel — and its lowering probe —
                # see exactly one input dtype. Feature-chunked: the kernel's
                # one-hot is at most 8192 lanes wide
                binq = row_bins(ids, nodes).astype(jnp.int32)   # (chunk, F)
                parts = [
                    subblock_hist(
                        binq[:, c0 : c0 + Fc], swT, live_blocks, n_bins=nb,
                        r_sub=r_sub, variance=variance, interpret=interpret,
                        transposed_sw=True,
                    )
                    for c0 in range(0, F, Fc)
                ]                                           # (chunk_sb, S, Fc*nb)
            # a sub-block past P keeps its unwritten partial out of every
            # sum (dropped unread)
            seg_c = jnp.where(
                (lo_sb + ar_sb) * r_sub < p_live,
                lax.dynamic_slice(seg_sb, (lo_sb,), (chunk_sb,)),
                n_nodes,
            )
            accs = tuple(
                acc.at[seg_c].add(part.reshape(chunk_sb, -1), mode="drop")
                for acc, part in zip(accs, parts)
            )
        live_ids = lax.dynamic_update_slice(live_ids, jnp.where(ok, ids, n), (lo,))
        return accs, live_ids

    width = F if full_bins is not None else Fc
    if wide:
        accs0 = (jnp.zeros((n_nodes, WIDE_STAT_ROWS, F * nb), jnp.float32),)
    else:
        accs0 = tuple(
            jnp.zeros((n_nodes, S * width * nb), sw.dtype)
            for _ in range(F // width)
        )
    accs, rows = lax.fori_loop(
        0, trips, level_chunk, (accs0, jnp.full((n_ceil,), n, jnp.int32))
    )
    if wide:
        hist_nodes = wide_hist_nodes(accs[0], S, F, nb, draw=feats).astype(
            sw.dtype
        )
    else:
        hist_nodes = jnp.concatenate(
            [acc.reshape(n_nodes, S, width, nb) for acc in accs], axis=2
        )                                                   # (n_nodes, S, F, nb)
    parent = hist_nodes[:, :, 0, :].sum(axis=-1)            # (n_nodes, S)
    hist = hist_nodes.transpose(2, 0, 3, 1)                 # (F, n_nodes, nb, S)
    return hist, parent, _Frontier(rows, sbc, trips, chunk, r_sub, sel_tiles)


def _route_live(node, frontier, row_bin, opens, bf, bb, *, offset: int):
    """Send the live rows of a level to their children: ``node`` with the
    entries of the frontier's rows bound for an OPEN child set to that child
    (``opens`` (n_nodes, 2): the node split and its left / right child was
    not closed when made). The rows' ids are the frontier's, their nodes are
    known by sub-block, so a chunk costs one element gather
    (``row_bin(row ids, features)``) and one scatter — over the live prefix,
    not over n. Rows in a node that became a leaf, and rows bound for a
    closed child, stay put: at their parent's id they are outside every
    later level's range, so ``_level_seg`` needs no word about them. Dead
    rows are never touched."""
    n = node.shape[0]
    chunk, r_sub = frontier.chunk, frontier.r_sub
    chunk_sb = chunk // r_sub
    # per sub-block: whether its node's children are open, the split's
    # feature and threshold (n_sb-scale)
    tbl = jnp.stack(
        [
            *opens.astype(jnp.int32).T, bf, bb,
            jnp.arange(bf.shape[0], dtype=jnp.int32),
        ],
        axis=1,
    )[frontier.sb_node]                                     # (n_sb, 5)

    def body(c, node):
        ids = lax.dynamic_slice(frontier.rows, (c * chunk,), (chunk,))
        t = jnp.broadcast_to(
            lax.dynamic_slice(tbl, (c * chunk_sb, 0), (chunk_sb, 5))[:, None],
            (chunk_sb, r_sub, 5),
        ).reshape(chunk, 5)
        right = row_bin(jnp.minimum(ids, n - 1), t[:, 2]) > t[:, 3]
        child = 2 * (offset + t[:, 4]) + 1 + right.astype(jnp.int32)
        moves = jnp.where(right, t[:, 1], t[:, 0]) > 0
        return node.at[jnp.where(moves, ids, n)].set(child, mode="drop")

    return lax.fori_loop(0, frontier.trips, body, node)


def _best_splits_from_hist(hist, parent, pcount, pimp, realf, nb, cfg):
    """Best (gain, feature, bin) per node from a histogram block, and the
    statistics that split sends left: ``cum[f*, node, b*]``, (n_nodes, S) —
    what the left child will hold (the right one holds ``parent`` less it),
    a node-scale gather from the sums the search has formed anyway.

    ``hist`` is (F, n_nodes, nb, S); ``realf`` (F, n_nodes) maps block
    slots to real feature ids (sentinel = cfg.n_features, masked out).
    Shared by the chunked matmul/scatter strategies and the compact path.
    """
    cum = jnp.cumsum(hist, axis=2)
    left = cum[:, :, :-1, :]                 # threshold = bin b goes left
    right = parent[None, :, None, :] - left
    nl = _count(left, cfg.impurity)
    nr = _count(right, cfg.impurity)
    il = _impurity(left, cfg.impurity)
    ir = _impurity(right, cfg.impurity)
    denom = jnp.maximum(pcount, 1e-12)[None, :, None]
    gain = pimp[None, :, None] - (nl * il + nr * ir) / denom
    ok = (nl >= cfg.min_samples_leaf) & (nr >= cfg.min_samples_leaf)
    ok = ok & (realf < cfg.n_features)[:, :, None]
    gain = jnp.where(ok, gain, -jnp.inf)
    # per-(feature, node) best bin with CENTERED tie-breaking: equal
    # gains form a run across the empty-bin gap between the two row
    # populations; picking the middle edge approximates the midpoint
    # threshold exact tree builders use (robust for unseen rows near
    # the gap, where the first tied edge would hug the left side)
    m = gain.max(axis=2)                                # (F, n_nodes)
    tie = gain == m[:, :, None]
    first = jnp.argmax(tie, axis=2)
    last = (nb - 2) - jnp.argmax(tie[:, :, ::-1], axis=2)
    mid = (first + last + 1) // 2
    midg = jnp.take_along_axis(gain, mid[:, :, None], axis=2)[:, :, 0]
    bbin = jnp.where(midg == m, mid, first)             # (F, n_nodes)
    fi = jnp.argmax(m, axis=0)                          # (n_nodes,)
    g = jnp.take_along_axis(m, fi[None, :], axis=0)[0]
    f = jnp.take_along_axis(realf, fi[None, :], axis=0)[0]
    b = jnp.take_along_axis(bbin, fi[None, :], axis=0)[0].astype(jnp.int32)
    return g, f, b, cum[fi, jnp.arange(fi.shape[0]), b]


# ---------------------------------------------------------------------------
# per-level histogram plan (static): which strategy a level takes, and why
# ---------------------------------------------------------------------------


class LevelPlan(NamedTuple):
    """What one level's histogram takes, from static shapes alone."""

    r_sub: int       # sub-block rows of the node-sorted copy
    n_pad: int       # its block-aligned padded row count
    f_chunk: int     # feature chunk of the pre-gathered Pallas kernel
    strategy: str    # "pallas_sel" | "pallas_sel_wide" | "pallas" | "matmul" | "scatter"
    declined: str    # why the shape's own Pallas kernel was not taken ("" where it was)
    hist_cols: int = 0   # bins columns the histogram reads a live row
    hist_calls: int = 0  # kernel calls a chunk of live rows (0: no kernel)


def level_plan(
    n: int, d_pad: int, level: int, cfg: ForestConfig, dt=jnp.float32
) -> LevelPlan:
    """The histogram strategy of ``level`` for ``n`` rows of ``d_pad`` bins.

    Static per level, so both builders and the estimator's span evaluate the
    SAME expressions: the sequential and the tree-batched builder pick the
    same strategy (a precondition of their bit-identity), and a level that
    falls to the XLA scatter (~1e8 updates/s, 8x slower) says why.

    compact strategy (TPU): node-contiguous rows + the Pallas sub-block
    kernel (ops/rf_pallas.py). Eligibility: f32 stats, lane-aligned one-hot
    width, a full-level histogram tile that fits HBM comfortably, and a
    probed lowering. The fused-selection variant selects each node's k
    columns in-kernel over node-sorted FULL bins rows and skips the per-row
    subset gather entirely (the single dominant cost at wide d). It is
    single-shot (no feature chunking), so its transients are gated against
    an HBM budget: the probe compiles a tiny instance and cannot see HBM
    pressure, and a runtime OOM here has no fallback. Residents counted:
    what the caller holds (``cfg.held_bytes``), bins + the row-gathered copy
    (both a byte a bin: four to an int32 word), partials, and two histogram
    tiles.
    """
    from .rf_pallas import (
        BLOCK_ROWS,
        WIDE_BLOCK_ROWS,
        WIDE_STAT_ROWS,
        rf_hist_pallas_declined,
        rf_hist_pallas_ok,
        rf_hist_sel_declined,
        rf_hist_sel_ok,
        rf_hist_wide_declined,
        rf_hist_wide_ok,
    )

    S, nb = cfg.n_stats, cfg.n_bins
    n_nodes = 1 << level
    subset = cfg.k_features < cfg.n_features
    d_hist = next_pow2(cfg.k_features) if subset else d_pad
    variance = cfg.impurity == "variance"
    r_sub = _compact_r_sub(n, n_nodes, BLOCK_ROWS, S)
    # Pad with the DEEPEST split level's node count when that waste is small
    # relative to n: r_sub converges to its cap at scale, so one padded row
    # count then serves every level and the Pallas kernels have ONE shape per
    # tree config instead of one per level. At small n the uniform pad would
    # triple the kernel's row count, so fall back to per-level padding there.
    n_nodes_max = 1 << max(0, cfg.max_depth - 1)
    pad_nodes = n_nodes_max if (n_nodes_max + 1) * r_sub * 3 <= n else n_nodes
    n_pad_c = -(-(n + (pad_nodes + 1) * r_sub) // BLOCK_ROWS) * BLOCK_ROWS
    # what a level holds at once is a CHUNK of its live rows and that chunk's
    # partials (_hist_compact; the batched builder's level-wide copy and
    # partials are resolve_tree_batch's to count, a batch at a time)
    chunk = min(n_pad_c, _LIVE_CHUNK)
    chunk_sb = chunk // r_sub
    # feature chunk: largest power of two satisfying the kernel's one-hot
    # width cap (Fc*nb <= 8192) AND a ~256 MB partials transient budget; must
    # divide d_hist
    Fc = 1 << max(0, min(d_hist, 8192 // nb).bit_length() - 1)
    while Fc > 1 and (
        d_hist % Fc != 0 or chunk_sb * S * Fc * nb * 4 > (256 << 20)
    ):
        Fc //= 2
    shape_terms = (
        ("strategy", cfg.hist_strategy in ("auto", "compact")),
        ("dtype", dt == jnp.float32),
        ("chunk", d_hist % Fc == 0),
        ("tile", n_nodes * d_hist * nb * S <= (1 << 28)),
    )
    shape_declined = ",".join(name for name, ok in shape_terms if not ok)
    sel_resident = (
        cfg.held_bytes
        + n * d_pad                      # bins (a byte each, packed)
        + chunk * d_pad                  # a chunk's gathered whole rows
        + chunk_sb * S * d_hist * nb * 4  # its partials (f32)
        + 2 * n_nodes * S * d_hist * nb * 4  # hist + transpose
    )
    sel_terms = (
        ("subset", subset),
        # only where the per-row subset gather is the dominant cost (see
        # _SEL_MIN_DPAD)
        ("d_pad", d_pad > _SEL_MIN_DPAD),
        ("hbm", sel_resident <= _sel_hbm_budget()),
    )
    sel_declined = ",".join(
        t for t in (
            shape_declined,
            ",".join(name for name, ok in sel_terms if not ok),
            rf_hist_sel_declined(n_pad_c, d_pad, d_hist, nb, S, r_sub),
        ) if t
    )
    if not sel_declined and rf_hist_sel_ok(
        n_pad_c, d_pad, d_hist, nb, S, r_sub, variance=variance
    ):
        return LevelPlan(r_sub, n_pad_c, Fc, "pallas_sel", "", d_hist, 1)
    # the fused selection TILED over feature slots (rf_pallas.
    # subblock_hist_sel_wide): where the shape is the fused kernel's (a
    # subset of a wide frame) and only its one-hot width, its VMEM or the
    # residents that scale with that width decline it. One node a block of
    # WIDE_BLOCK_ROWS rows; the per-node sums are the kernel's own, in place.
    wanted_sel = subset and d_pad > _SEL_MIN_DPAD
    n_pad_w = (
        -(-(n + (pad_nodes + 1) * WIDE_BLOCK_ROWS) // WIDE_BLOCK_ROWS)
        * WIDE_BLOCK_ROWS
    )
    wide_resident = (
        cfg.held_bytes
        + n * d_pad                                  # bins (a byte each, packed)
        + min(n_pad_w, _LIVE_CHUNK) * d_pad          # a chunk's rows of words
        + n_nodes * WIDE_STAT_ROWS * d_hist * nb * 4   # the running sums
        + 6 * n_nodes * S * d_hist * nb * 4          # hist and the gain chain
    )
    wide_terms = (
        ("hbm", wide_resident <= _sel_hbm_budget()),
    )
    wide_declined = ",".join(
        t for t in (
            shape_declined,
            ",".join(name for name, ok in wide_terms if not ok),
            rf_hist_wide_declined(n_pad_w, d_pad, d_hist, nb, S),
        ) if t
    )
    if wanted_sel and not wide_declined and rf_hist_wide_ok(
        n_pad_w, d_pad, d_hist, nb, S
    ):
        return LevelPlan(
            WIDE_BLOCK_ROWS, n_pad_w, Fc, "pallas_sel_wide",
            f"sel:{sel_declined}", d_hist, 1,
        )
    compact_declined = ",".join(
        t for t in (
            shape_declined,
            rf_hist_pallas_declined(n_pad_c, Fc, nb, S, r_sub),
        ) if t
    )
    if not compact_declined and rf_hist_pallas_ok(
        n_pad_c, Fc, nb, S, r_sub, variance=variance
    ):
        # the pre-gathered kernel where the fused one was the shape's own
        # (a per-row subset gather of ~780 ms a level at 1M x 3000) says so
        return LevelPlan(
            r_sub, n_pad_c, Fc, "pallas",
            f"sel:{sel_declined};wide:{wide_declined}" if wanted_sel else "",
            d_hist, d_hist // Fc,
        )
    # strategy per level (static). Subset path: the gathered operand is only
    # k_pad wide, and measured v5e scatter on it is ~2.2 ms/level FLAT in
    # n_nodes while the one-hot matmul grows past 8 ms — scatter always
    # wins. No-subset path: one-hot matmuls on the MXU until the
    # 2*n_nodes*nb waste factor exceeds a scatter-add update's cost. "auto"
    # is TPU-only: the trade inverts on CPU, where scatter-adds are cheap
    # and dense one-hot matmuls are pure waste. Forced-compact levels that
    # fail the eligibility gate take scatter, as resolve_hist_strategy
    # documents — matmul would silently change variance-stat numerics.
    if cfg.hist_strategy == "matmul":
        use_matmul = True
    elif cfg.hist_strategy in ("scatter", "compact") or subset:
        use_matmul = False
    else:
        use_matmul = (
            jax.default_backend() == "tpu"
            and (2.0 * n_nodes * nb) < _SCATTER_EQ_FLOPS
        )
    return LevelPlan(
        r_sub, n_pad_c, Fc, "matmul" if use_matmul else "scatter",
        f"sel:{sel_declined};compact:{compact_declined}", d_hist, 0,
    )


def _level_plans(n: int, d_pad: int, cfg: ForestConfig, dt):
    return [level_plan(n, d_pad, lv, cfg, dt) for lv in range(cfg.max_depth)]


def plan_levels(n: int, d_pad: int, cfg: ForestConfig, dt=jnp.float32):
    """:func:`level_plan` of every split level, for a span: the strategies
    comma-joined in level order, the levels that did not take their Pallas
    kernel as ``{level: declined}``."""
    plans = _level_plans(n, d_pad, cfg, dt)
    return (
        ",".join(p.strategy for p in plans),
        {lv: p.declined for lv, p in enumerate(plans) if p.declined},
    )


def plan_reads(n: int, d_pad: int, cfg: ForestConfig, dt=jnp.float32):
    """``(hist_cols, hist_calls)`` for the same span: the most bins columns a
    level's histogram reads a live row, and the most kernel calls a level
    makes a chunk of live rows — the two numbers that say a later change
    flipped the histogram's path."""
    plans = _level_plans(n, d_pad, cfg, dt)
    return (
        max((p.hist_cols for p in plans), default=0),
        max((p.hist_calls for p in plans), default=0),
    )


# ---------------------------------------------------------------------------
# single-tree level-wise builder
# ---------------------------------------------------------------------------


def _build_tree(
    packed: jax.Array,  # (n, d_pad/4) int32: the bins four to a word (_pack_bins)
    stats: jax.Array,   # (n, S) float
    valid: jax.Array,   # (n,) float row mask
    key: jax.Array,
    cfg: ForestConfig,
) -> Dict[str, jax.Array]:
    """One tree, level by level, as heap-ordered tables over ``max_nodes``:
    ``feature`` (-1 = leaf), ``threshold_bin`` (bin(x) > b goes right; **0 at
    every node that does not split** — a don't-care no reader looks at: the
    descents and ``thresholds`` read it where ``feature >= 0`` only),
    ``leaf_stats`` (the node's weighted statistics, 0 where no row came),
    ``gain`` (0 at a leaf); and three counts for the caller's span:
    ``live_rows`` (a split level: rows of positive weight in an OPEN node of
    it, the rows the level worked on), ``closed_at_birth`` (nodes closed
    when they were made: always 0 for variance statistics) and
    ``sel_tiles`` (the wide selection's column tiles read and slot tiles
    run, over its levels: ``_Frontier.sel_tiles``)."""
    n, words = packed.shape
    d_pad = 4 * words
    S = cfg.n_stats
    nb = cfg.n_bins
    M = max_nodes(cfg.max_depth)
    dt = stats.dtype

    kb, kf = jax.random.split(jnp.asarray(key))
    if cfg.bootstrap:
        # Poisson(1) bootstrap ~ sampling-with-replacement. Draws are
        # indexed by LOGICAL row position (cumsum of the validity mask),
        # not padded position: multi-process layouts interleave padding
        # per-process block, and logical indexing makes the same dataset
        # produce the same weights — and therefore bit-identical
        # integer-stat trees — under any process/padding layout.
        logical = jnp.clip(
            jnp.cumsum(valid.astype(jnp.int32)) - 1, 0, n - 1
        )
        draws = jax.random.poisson(kb, 1.0, (n,)).astype(dt)
        w = draws[logical] * valid
    else:
        w = valid.astype(dt)
    sw = stats * w[:, None]

    feat = jnp.full((M,), -1, jnp.int32)
    thr_bin = jnp.zeros((M,), jnp.int32)
    leaf = jnp.zeros((M, S), dt)
    gains = jnp.zeros((M,), dt)
    node = jnp.zeros((n,), jnp.int32)
    # a row the bootstrap drew zero times (or the mask leaves out) is dead
    # from the root on: nothing the fit returns can depend on it
    row_w = _count(sw, cfg.impurity)
    live_rows = []    # per split level: rows the level worked on
    sel_tiles = jnp.zeros((2,), jnp.int32)  # the wide selection's reads
    # A child's statistics and fate are handed down by the split that makes
    # it: its class counts are the winner's left sums (or the parent's less
    # them), and where they are pure or under min_samples_split the next
    # level's gain search could not split it whatever its histogram (a pure
    # node's impurity is 0, so every gain is <= 0 < 1e-9). Such a child is
    # CLOSED AT BIRTH: its rows stay at the parent's id, out of every later
    # level's range, and the next level writes the handed-down counts for it.
    # Weighted class counts are integers in f32 (exact below 2^24 a cell), so
    # handed-down and own-histogram values are equal to the bit and the
    # forest is the same forest. Variance statistics are f32 sums in another
    # grouping: there nothing is handed down and a level runs as it did.
    hands_down = cfg.impurity != "variance"
    closed = jnp.zeros((1,), bool)      # this level's nodes, closed when made
    handed = jnp.zeros((1, S), dt)      # and what their parents' splits gave them
    closed_at_birth = jnp.zeros((), jnp.int32)

    # The contraction gather over the words (TPU: per-row gathers run at
    # ~1e8 elem/s, making take_along_axis ~16x slower than the dense
    # formulation at d_pad=256; CPU keeps take_along_axis). The contraction
    # does d_pad/4 word-ops per extracted element (~8.6e10 word-ops/s
    # measured), so its advantage erodes linearly with width — "auto" caps
    # it at d_pad<=1024 (4x the measured shape), past which the predicted
    # win thins and the un-fused intermediate risk grows.
    if cfg.contract_gather == "on":
        use_contract = True
    elif cfg.contract_gather == "off":
        use_contract = False
    else:
        use_contract = (
            jax.default_backend() == "tpu"
            and d_pad <= 1024
        )

    # levels are a static python loop: each level has its own (static) node
    # count and feature-chunk size, so XLA compiles tight fixed-shape kernels
    for level in range(cfg.max_depth + 1):
        offset = (1 << level) - 1
        n_nodes = 1 << level
        local, in_level, seg = _level_seg(node, level, row_w)
        if level == cfg.max_depth:
            # final level: leaf stats only. Every node of it was made by a
            # split (or holds nothing), so where statistics are handed down
            # they are the leaves; else the one remaining per-level parent
            # scatter (the compact path below derives parent from its
            # histogram on every split level)
            if hands_down and level > 0:
                parent = handed
            else:
                parent = jax.ops.segment_sum(
                    sw, seg, num_segments=n_nodes + 1
                )[:n_nodes]
            leaf = leaf.at[offset : offset + n_nodes].set(parent)
            break
        live_rows.append((seg < n_nodes).sum(dtype=jnp.int32))

        # Per-node feature subsampling (cuML max_features semantics): the
        # k_features highest of a per-(node, feature) uniform draw. The
        # subset is EXPLOITED, not just masked: each row gathers its
        # node's k selected feature bins and the histogram covers only
        # those k virtual features — n*k*S updates per level instead of
        # n*d*S. At the reference's own semantics (featureSubsetStrategy
        # "auto" -> sqrt(d) for classification) that is a 16x cut at
        # d=256 and ~55x at the 1M x 3000 benchmark shape, which is what
        # makes the reference forest config fit a single-chip build.
        subset = cfg.k_features < cfg.n_features
        if subset:
            r = jax.random.uniform(
                jax.random.fold_in(kf, level), (n_nodes, cfg.n_features)
            )
            if jax.default_backend() == "tpu":
                # indices of the k largest uniforms are a uniform random
                # k-subset either way; PartialReduce at recall 1.0 is exact
                # and ~4x cheaper than full-sort top_k at (4096, 256)
                feats = lax.approx_max_k(
                    r, cfg.k_features, recall_target=1.0
                )[1].astype(jnp.int32)
            else:
                feats = lax.top_k(r, cfg.k_features)[1].astype(jnp.int32)
            k_pad = next_pow2(cfg.k_features)
            if k_pad > cfg.k_features:
                # sentinel n_features: invalid (masked out of gain search)
                feats = jnp.pad(
                    feats,
                    ((0, 0), (0, k_pad - cfg.k_features)),
                    constant_values=cfg.n_features,
                )
            d_hist = k_pad
        else:
            feats = None
            d_hist = d_pad

        def row_bins(rows, nodes, feats=feats):
            """(m, d_hist) bins of the rows ``rows`` that sit in the nodes
            ``nodes``: their nodes' sampled columns (the fused-selection
            kernel selects in-kernel and skips this entirely)."""
            if not subset:
                return _unpack_bins(packed[rows])
            row_feats = feats[nodes]  # (m, k_pad) real feature ids per row
            if use_contract:
                return _contract_gather(packed[rows], row_feats)   # i32
            return _bins_at(packed, rows[:, None], row_feats)

        def make_hist_src(feats=feats, local=local):
            """Per-row subset bin extraction at full size, for the
            strategies that take every row (the words themselves without
            a subset: a feature chunk unpacks its columns)."""
            if not subset:
                return packed
            lc0 = jnp.clip(local, 0, n_nodes - 1)
            row_feats = feats[lc0]  # (n, k_pad) real feature ids per row
            if use_contract:
                return _contract_gather(packed, row_feats)  # (n, k_pad) i32
            return _bins_at(packed, None, row_feats)  # (n, k_pad) i32

        plan = level_plan(n, d_pad, level, cfg, dt)
        r_sub, n_pad_c, Fc = plan.r_sub, plan.n_pad, plan.f_chunk
        use_wide = plan.strategy == "pallas_sel_wide"
        use_sel = use_wide or plan.strategy == "pallas_sel"
        use_compact = use_sel or plan.strategy == "pallas"
        if use_compact:
            hist_full, parent, frontier = _hist_compact(
                None if use_sel else row_bins, seg, sw, n_nodes=n_nodes,
                n_slots=d_hist, nb=nb, r_sub=r_sub, n_pad=n_pad_c,
                f_chunk=Fc, variance=(cfg.impurity == "variance"),
                full_bins=packed if use_sel else None,
                feats=feats if use_sel else None, wide=use_wide,
            )
            sel_tiles = sel_tiles + frontier.sel_tiles
        else:
            parent = jax.ops.segment_sum(sw, seg, num_segments=n_nodes + 1)[
                :n_nodes
            ]
        if hands_down:
            # a closed node has no live row and an empty histogram
            parent = jnp.where(closed[:, None], handed, parent)
        leaf = leaf.at[offset : offset + n_nodes].set(parent)
        pcount = _count(parent, cfg.impurity)
        pimp = _impurity(parent, cfg.impurity)

        if use_compact:
            if subset:
                realf_full = feats.T  # (k_pad, n_nodes) real feature ids
            else:
                realf_full = jnp.broadcast_to(
                    jnp.arange(d_hist, dtype=jnp.int32)[:, None],
                    (d_hist, n_nodes),
                )
            # gain search in feature-slot chunks: holding the full
            # (F, n_nodes, nb, S) histogram once is fine, but the
            # cumsum/left/right/gain chain materializes several copies of
            # the tile — ~1.5 GB of transients at the reference shape,
            # too much beside a near-HBM-sized X. Chunk merging uses
            # the same init and strict-> update as the chunk-scan path,
            # so results (including the (0, 0) feature/bin of no-gain
            # nodes and first-slot tie-breaking) stay bit-identical.
            Fc = d_hist
            while Fc > 1 and Fc * n_nodes * nb * S > 4 * _HIST_BUDGET:
                Fc //= 2
            bg = jnp.full((n_nodes,), -jnp.inf, dt)
            bf = jnp.zeros((n_nodes,), jnp.int32)
            bb = jnp.zeros((n_nodes,), jnp.int32)
            bl = jnp.zeros((n_nodes, S), dt)
            for c0 in range(0, d_hist, Fc):
                g, f, b, l = _best_splits_from_hist(
                    hist_full[c0 : c0 + Fc], parent, pcount, pimp,
                    realf_full[c0 : c0 + Fc], nb, cfg,
                )
                upd = g > bg
                bg = jnp.where(upd, g, bg)
                bf = jnp.where(upd, f, bf)
                bb = jnp.where(upd, b, bb)
                bl = jnp.where(upd[:, None], l, bl)
        else:
            use_matmul = plan.strategy == "matmul"

            # the narrow subset-scatter tile ((k_pad, n_nodes*nb, S): 67 MB at
            # k=16/depth-13) runs single-chunk under a raised budget — chunking
            # it only multiplied fixed scatter overheads
            hist_src = make_hist_src()
            budget = (1 << 25) if (subset and not use_matmul) else _HIST_BUDGET
            F = _chunk_features(d_hist, n_nodes, nb, S, budget)
            n_chunks = d_hist // F
            if use_matmul:
                # the (C, F*nb) bin one-hot is a materialized dot operand; the
                # histogram-tile budget alone lets F reach d_pad at shallow
                # levels (17 GB at d_pad=4096, C=8192, nb=128) — cap F so the
                # one-hot stays ~256 MB. Extra feature chunks cost nothing:
                # total matmul flops per level are F-invariant.
                C_lvl = min(_ROW_CHUNK, n)
                f_cap = max(1, (1 << 26) // (C_lvl * nb))
                f_cap = 1 << (f_cap.bit_length() - 1)
                F = min(F, f_cap)
                n_chunks = d_hist // F

            def _hist_scatter(binc, *, n_nodes, in_level, local, sw):
                """(F, n_nodes, nb, S) via segment_sum scatter-adds."""
                ids = jnp.where(
                    in_level[:, None], local[:, None] * nb + binc, n_nodes * nb
                )
                # Small S (regression stats, binary/few-class): one scalar
                # segment_sum per stat column — vmapping the (n, S) operand
                # broadcasts it to (F, n, S) with the tiny S minor dim
                # lane-padded S -> 128 on TPU, a 64x memory expansion at S=2
                # (16 GB observed at n=131k, F=256); per-stat 1-D operands
                # keep the broadcast at (F, n), lane-aligned. Wide S (many
                # classes): padding overhead fades (<= 8x at S >= 16) and S
                # unrolled scatters would dominate — keep one (n, S) scatter.
                F = binc.shape[1]
                if S <= 16:
                    hist = jnp.stack(
                        [
                            jax.vmap(
                                lambda col, c=sw[:, s]: jax.ops.segment_sum(
                                    c, col, num_segments=n_nodes * nb + 1
                                ),
                                in_axes=1,
                            )(ids)                       # (F, n_nodes*nb+1)
                            for s in range(S)
                        ],
                        axis=-1,
                    )                                    # (F, n_nodes*nb+1, S)
                else:
                    hist = jax.vmap(
                        lambda col: jax.ops.segment_sum(
                            sw, col, num_segments=n_nodes * nb + 1
                        ),
                        in_axes=1,
                    )(ids)                               # (F, n_nodes*nb+1, S)
                return hist[:, : n_nodes * nb, :].reshape(F, n_nodes, nb, S)

            def _hist_matmul(binc, *, n_nodes, in_level, local, sw):
                """(F, n_nodes, nb, S) via MXU one-hot contractions.

                hist[f,nd,b,s] = sum_r N[r,nd] * B[r,f*nb+b] * sw[r,s] with
                N the (row, node) one-hot (row weight/level mask folded in) and
                B the (row, feature-bin) one-hot — one (n_nodes, C) x (C, F*nb)
                matmul per stat per row chunk. Rows are accumulated in chunks
                so the one-hot intermediates stay bounded; the clamped last
                chunk masks re-read rows."""
                F = binc.shape[1]
                C = min(_ROW_CHUNK, n)
                nc = -(-n // C)
                node_ar = jnp.arange(n_nodes, dtype=jnp.int32)
                bin_ar = jnp.arange(nb, dtype=jnp.int32)

                def row_body(ri, acc):
                    start = jnp.minimum(ri * C, n - C)
                    bc = lax.dynamic_slice(binc, (start, 0), (C, F))
                    loc = lax.dynamic_slice(local, (start,), (C,))
                    lvl = lax.dynamic_slice(in_level, (start,), (C,))
                    swc = lax.dynamic_slice(sw, (start, 0), (C, S))
                    fresh = (start + jnp.arange(C)) >= ri * C  # clamp re-reads
                    Noh = (
                        (loc[:, None] == node_ar[None, :])
                        & lvl[:, None]
                        & fresh[:, None]
                    ).astype(dt)                              # (C, n_nodes)
                    Boh = (bc[:, :, None] == bin_ar[None, None, :]).astype(dt)
                    Boh = Boh.reshape(C, F * nb)              # (C, F*nb)
                    # TPU's default f32 matmul uses bf16 multiplies — exact for
                    # classification (one-hots and small-integer weights are
                    # bf16-representable; accumulation is f32) but NOT for
                    # variance stats carrying y/y^2, where rounding would flip
                    # near-tied splits vs the scatter path. Those pay the
                    # multi-pass HIGHEST f32 emulation.
                    prec = (
                        lax.Precision.HIGHEST
                        if cfg.impurity == "variance"
                        else None
                    )
                    return acc + jnp.stack(
                        [
                            jnp.matmul(
                                (Noh * swc[:, s][:, None]).T, Boh, precision=prec
                            )
                            for s in range(S)
                        ],
                        axis=-1,
                    )                                         # (n_nodes, F*nb, S)

                acc = lax.fori_loop(
                    0,
                    nc,
                    row_body,
                    jnp.zeros((n_nodes, F * nb, S), dt),
                )
                return acc.reshape(n_nodes, F, nb, S).transpose(1, 0, 2, 3)

            def chunk_body(carry, ci, *, n_nodes=n_nodes, parent=parent,
                           pcount=pcount, pimp=pimp, feats=feats, F=F,
                           in_level=in_level, local=local, sw=sw,
                           use_matmul=use_matmul, subset=subset,
                           hist_src=hist_src):
                bg, bf, bb, bl = carry
                if subset:
                    binc = lax.dynamic_slice(hist_src, (0, ci * F), (n, F))
                else:
                    binc = _unpack_bins(hist_src, ci * F, F)
                make = _hist_matmul if use_matmul else _hist_scatter
                hist = make(
                    binc, n_nodes=n_nodes, in_level=in_level, local=local, sw=sw
                )
                if subset:
                    # real feature id per (virtual feature, node), this chunk
                    realf = lax.dynamic_slice(
                        feats, (0, ci * F), (n_nodes, F)
                    ).T                                      # (F, n_nodes)
                else:
                    realf = jnp.broadcast_to(
                        (ci * F + jnp.arange(F, dtype=jnp.int32))[:, None],
                        (F, n_nodes),
                    )
                g, f, b, l = _best_splits_from_hist(
                    hist, parent, pcount, pimp, realf, nb, cfg
                )
                upd = g > bg
                return (
                    jnp.where(upd, g, bg),
                    jnp.where(upd, f, bf),
                    jnp.where(upd, b, bb),
                    jnp.where(upd[:, None], l, bl),
                ), None

            init = (
                jnp.full((n_nodes,), -jnp.inf, dt),
                jnp.zeros((n_nodes,), jnp.int32),
                jnp.zeros((n_nodes,), jnp.int32),
                jnp.zeros((n_nodes, S), dt),
            )
            (bg, bf, bb, bl), _ = lax.scan(
                chunk_body, init, jnp.arange(n_chunks)
            )

        do_split = (
            jnp.isfinite(bg)
            & (bg >= max(cfg.min_info_gain, 1e-9))
            & _can_split(parent, cfg)
        )
        feat = feat.at[offset : offset + n_nodes].set(jnp.where(do_split, bf, -1))
        thr_bin = thr_bin.at[offset : offset + n_nodes].set(
            jnp.where(do_split, bb, 0)
        )
        gains = gains.at[offset : offset + n_nodes].set(
            jnp.where(do_split, bg, jnp.zeros_like(bg))
        )

        # the children this level makes, (n_nodes, 2, ...) left and right
        made = jnp.broadcast_to(do_split[:, None], (n_nodes, 2))
        opens = made
        if hands_down:
            kids = jnp.where(
                made[:, :, None], jnp.stack([bl, parent - bl], axis=1), 0
            )
            shut = made & ~_can_split(kids, cfg)
            opens = made & ~shut
            handed = kids.reshape(2 * n_nodes, S)
            closed = shut.reshape(2 * n_nodes)
            closed_at_birth = closed_at_birth + shut.sum(dtype=jnp.int32)

        # route rows to children; rows whose node became a leaf, or whose
        # child was closed when made, stay put
        if use_compact:
            def row_bin(rows, row_feat):
                """bins[rows[i], row_feat[i]] as int32."""
                if use_contract:
                    return _contract_gather(
                        packed[rows], row_feat[:, None]
                    )[:, 0]
                return _bins_at(packed, rows, row_feat)

            node = _route_live(
                node, frontier, row_bin, opens, bf, bb, offset=offset
            )
        else:
            lc = jnp.clip(local, 0, n_nodes - 1)
            row_feat = bf[lc]
            if use_contract:
                row_bin = _contract_gather(packed, row_feat[:, None])[:, 0]
            else:
                row_bin = _bins_at(packed, None, row_feat[:, None])[:, 0]
            go_right = (row_bin > bb[lc]).astype(jnp.int32)
            child = 2 * node + 1 + go_right
            moves = in_level & opens[lc, go_right]
            node = jnp.where(moves, child, node)

    return {
        "feature": feat,
        "threshold_bin": thr_bin,
        "leaf_stats": leaf,
        "gain": gains,
        "live_rows": jnp.stack(live_rows)
        if live_rows
        else jnp.zeros((0,), jnp.int32),
        "closed_at_birth": closed_at_birth,
        "sel_tiles": sel_tiles,
    }


# ---------------------------------------------------------------------------
# tree-batched level-wise builder: T trees advance one level per dispatch
# ---------------------------------------------------------------------------


def _seg_sum_trees(vals, seg, num):
    """Per-tree segment sums fused into ONE global scatter.

    ``vals`` (T, n, ...) and ``seg`` (T, n) in [0, num) reduce to
    (T, num, ...) by offsetting tree t's segment ids by ``t * num`` —
    trees touch disjoint segment ranges and every tree's rows keep their
    original order, so each tree's accumulation sequence is exactly the
    per-tree ``segment_sum``'s (bitwise identical), while the device sees
    a single scatter over T*n rows instead of T small ones.
    """
    T, n = seg.shape
    gseg = seg + (num * jnp.arange(T, dtype=jnp.int32))[:, None]
    flat = vals.reshape((T * n,) + vals.shape[2:])
    out = jax.ops.segment_sum(flat, gseg.reshape(T * n), num_segments=T * num)
    return out.reshape((T, num) + vals.shape[2:])


def _hist_compact_batched(
    hist_src,             # (T, n, F) int bins, (n, d_pad/4) int32 packed words
                          # shared by the batch, or None with full_bins
    seg: jax.Array,       # (T, n) int32 level-local node id; n_nodes = dead
    sw: jax.Array,        # (T, n, S) f32 stats*weight
    *,
    n_nodes: int,
    nb: int,
    r_sub: int,
    n_pad: int,
    f_chunk: int,
    variance: bool,
    full_bins=None,       # (n, d_pad/4) int32 SHARED packed rows + feats => fused-sel
    feats=None,           # (T, n_nodes, F) int32 per-node feature ids
    interpret=None,
):
    """T-batched ``_hist_compact``: (T, F, n_nodes, nb, S) + (T, n_nodes, S).

    The per-tree sort/searchsorted bookkeeping is vmapped (cheap index
    math), but the Pallas kernel runs ONCE over the flattened
    (T*n_pad) rows: the kernel's grid blocks are ``BLOCK_ROWS``-aligned
    and ``n_pad % BLOCK_ROWS == 0`` (caller gate), so every block is
    tree-pure and the flattened call computes exactly the per-tree
    blocks back to back — bitwise identical to T separate calls.
    """
    from .rf_pallas import subblock_hist_batched, subblock_hist_sel_batched

    T = seg.shape[0]
    if full_bins is not None:
        n = full_bins.shape[0]
        F = feats.shape[-1]
    elif hist_src.ndim == 2:    # shared packed words (no subset)
        n, F = hist_src.shape[0], 4 * hist_src.shape[1]
    else:
        n, F = hist_src.shape[-2], hist_src.shape[-1]
    S = sw.shape[-1]
    n_sb = n_pad // r_sub
    iota = jnp.arange(n, dtype=jnp.int32)

    def prep(seg_t, sw_t):
        # mirror of _hist_compact's index math, one tree at a time
        keys_s, perm = lax.sort((seg_t, iota), num_keys=1)
        starts = jnp.searchsorted(
            keys_s, jnp.arange(n_nodes + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        lens = starts[1:] - starts[:-1]
        plen = -(-lens // r_sub) * r_sub
        pstart = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(plen)]
        )
        sb_pos = jnp.arange(n_sb, dtype=jnp.int32) * r_sub
        seg_sb = jnp.searchsorted(pstart[1:], sb_pos, side="right").astype(
            jnp.int32
        )
        sbc = jnp.clip(seg_sb, 0, n_nodes - 1)
        tbl = jnp.stack([starts[:-1], pstart[:-1], lens], axis=1)
        tbl_rows = jnp.broadcast_to(
            tbl[sbc][:, None, :], (n_sb, r_sub, 3)
        ).reshape(n_pad, 3)
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        off = pos - tbl_rows[:, 1]
        src = tbl_rows[:, 0] + off
        pvalid = (off < tbl_rows[:, 2]) & (
            jnp.broadcast_to(seg_sb[:, None], (n_sb, r_sub)).reshape(n_pad)
            < n_nodes
        )
        src2 = perm[jnp.clip(src, 0, n - 1)]
        swq = sw_t[src2] * pvalid[:, None].astype(sw_t.dtype)
        seg_red = jnp.where(seg_sb < n_nodes, seg_sb, n_nodes)
        return src2, swq, seg_red, pstart, sbc

    src2, swq, seg_red, pstart, sbc = jax.vmap(prep)(seg, sw)

    def _use_cumsum(width):
        return (not variance) and n <= (1 << 23) and width <= 8192

    def reduce_partials(p2d, width):  # (T, n_sb, width) -> (T, n_nodes, width)
        if _use_cumsum(width):
            # vmapped cumsum + boundary diff: per-tree scan order unchanged
            return jax.vmap(
                lambda p, ps: _sorted_block_reduce(p, ps, r_sub, n_nodes)
            )(p2d, pstart)
        return _seg_sum_trees(p2d, seg_red, n_nodes + 1)[:, :n_nodes]

    if full_bins is not None:
        bq = jax.vmap(lambda s2: full_bins[s2])(src2)       # (T, n_pad, d_pad/4)
        featsq = jax.vmap(lambda f, c: f[c])(feats, sbc)    # (T, n_sb, F)
        partials = subblock_hist_sel_batched(
            bq, featsq, swq.transpose(0, 2, 1), n_bins=nb, r_sub=r_sub,
            variance=variance, interpret=interpret,
        )                                                   # (T, n_sb, S, F*nb)
        hist_nodes = reduce_partials(
            partials.reshape(T, n_sb, S * F * nb), S * F * nb
        ).reshape(T, n_nodes, S, F, nb)
    else:
        if hist_src.ndim == 2:      # shared full bins (no subset)
            binq = jax.vmap(lambda s2: _unpack_bins(hist_src[s2]))(src2)
        else:                       # per-tree subset-gathered bins
            binq = jax.vmap(lambda h, s2: h[s2])(hist_src, src2).astype(
                jnp.int32
            )                                               # (T, n_pad, F)
        Fc = f_chunk
        hist_parts = []
        for c0 in range(0, F, Fc):
            partials = subblock_hist_batched(
                binq[:, :, c0 : c0 + Fc], swq, n_bins=nb, r_sub=r_sub,
                variance=variance, interpret=interpret,
            )                                               # (T, n_sb, S, Fc*nb)
            part = reduce_partials(
                partials.reshape(T, n_sb, S * Fc * nb), S * Fc * nb
            )
            hist_parts.append(part.reshape(T, n_nodes, S, Fc, nb))
        hist_nodes = (
            hist_parts[0]
            if len(hist_parts) == 1
            else jnp.concatenate(hist_parts, axis=3)
        )                                                   # (T, n_nodes, S, F, nb)
    parent = hist_nodes[:, :, :, 0, :].sum(axis=-1)         # (T, n_nodes, S)
    hist = hist_nodes.transpose(0, 3, 1, 4, 2)              # (T, F, n_nodes, nb, S)
    return hist, parent


def _grow_trees_batched(
    packed: jax.Array,  # (n, d_pad/4) int32 bins four to a word, shared across the tree batch
    sw: jax.Array,      # (T, n, S) float stats*weight per tree
    kf: jax.Array,      # (T, 2) per-tree feature-subset keys
    cfg: ForestConfig,
    *,
    axis_name=None,
    return_rows: bool = False,
) -> Dict[str, jax.Array]:
    """T-batched mirror of ``_build_tree``'s level loop.

    All T trees advance one level per dispatch: per-node histogram
    accumulations fuse into ONE (T*nodes)-segmented scatter / one
    tall-skinny (T*nodes, C) x (C, F*nb) one-hot matmul / one flattened
    Pallas sub-block kernel call, and the gain search vmaps over the tree
    axis. Every step either is a per-tree gather/elementwise op under
    vmap or preserves each tree's per-segment accumulation order (see
    _seg_sum_trees / _hist_compact_batched), and the per-level strategy
    gates are the SAME static expressions as the sequential builder —
    so fitted trees are bit-identical to ``_build_tree`` at the same
    keys (tests/test_tree_batch.py pins this per strategy).

    ``axis_name``: optional mesh axis to ``psum`` histograms and parent
    stats over — the data-parallel hook the GBT boosting loop uses to
    grow each round's trees on ALL rows while rows stay sharded. RF keeps
    it None (each tree trains on its device's shard by design).
    ``return_rows``: also return each row's final node id (T, n) —
    the boosting loop reads leaf assignments from it without a second
    descent.
    """
    n, words = packed.shape
    d_pad = 4 * words
    T = sw.shape[0]
    S = cfg.n_stats
    nb = cfg.n_bins
    M = max_nodes(cfg.max_depth)
    dt = sw.dtype

    def _allred(x):
        return lax.psum(x, axis_name) if axis_name is not None else x

    feat = jnp.full((T, M), -1, jnp.int32)
    thr_bin = jnp.zeros((T, M), jnp.int32)
    leaf = jnp.zeros((T, M, S), dt)
    gains = jnp.zeros((T, M), dt)
    node = jnp.zeros((T, n), jnp.int32)
    # the sequential builder's liveness (_level_seg), unless the caller asked
    # for every row's final node: then a zero-weight row walks the tree too
    row_w = None if return_rows else _count(sw, cfg.impurity)
    live_rows = []
    sel_tiles = jnp.zeros((T, 2), jnp.int32)

    if cfg.contract_gather == "on":
        use_contract = True
    elif cfg.contract_gather == "off":
        use_contract = False
    else:
        use_contract = (
            jax.default_backend() == "tpu"
            and d_pad <= 1024
        )

    for level in range(cfg.max_depth + 1):
        offset = (1 << level) - 1
        n_nodes = 1 << level
        local, in_level, seg = _level_seg(node, level, row_w)  # (T, n)
        if level == cfg.max_depth:
            parent = _allred(
                _seg_sum_trees(sw, seg, n_nodes + 1)[:, :n_nodes]
            )
            leaf = leaf.at[:, offset : offset + n_nodes].set(parent)
            break
        live_rows.append((seg < n_nodes).sum(axis=1, dtype=jnp.int32))

        subset = cfg.k_features < cfg.n_features
        if subset:
            # per-tree draws via lax.map of the sequential builder's exact
            # call — identical uniforms per (tree, level) by construction;
            # top-k rows are independent, so the (T*n_nodes)-row batch
            # selects identical subsets
            r = lax.map(
                lambda k: jax.random.uniform(
                    jax.random.fold_in(k, level),
                    (n_nodes, cfg.n_features),
                ),
                kf,
            ).reshape(T * n_nodes, cfg.n_features)
            if jax.default_backend() == "tpu":
                feats = lax.approx_max_k(
                    r, cfg.k_features, recall_target=1.0
                )[1].astype(jnp.int32)
            else:
                feats = lax.top_k(r, cfg.k_features)[1].astype(jnp.int32)
            k_pad = next_pow2(cfg.k_features)
            if k_pad > cfg.k_features:
                feats = jnp.pad(
                    feats,
                    ((0, 0), (0, k_pad - cfg.k_features)),
                    constant_values=cfg.n_features,
                )
            feats = feats.reshape(T, n_nodes, k_pad)
            d_hist = k_pad
        else:
            feats = None
            d_hist = d_pad

        def make_hist_src(feats=feats, local=local):
            if not subset:
                return packed                           # (n, d_pad/4) shared
            lc0 = jnp.clip(local, 0, n_nodes - 1)       # (T, n)
            row_feats = jax.vmap(lambda f, l: f[l])(feats, lc0)
            if use_contract:
                return jax.vmap(
                    lambda rf_: _contract_gather(packed, rf_)
                )(row_feats)                            # (T, n, k_pad) i32
            return jax.vmap(
                lambda rf_: _bins_at(packed, None, rf_)
            )(row_feats)                                # (T, n, k_pad) i32

        # the SAME per-tree plan as _build_tree — resolve_tree_batch's
        # budget is what accounts for the xT transients, NOT these gates,
        # so both builders always pick the same strategy per level
        plan = level_plan(n, d_pad, level, cfg, dt)
        r_sub, n_pad_c, Fc = plan.r_sub, plan.n_pad, plan.f_chunk
        use_wide = plan.strategy == "pallas_sel_wide"
        use_sel = plan.strategy == "pallas_sel"
        use_compact = use_wide or use_sel or plan.strategy == "pallas"
        if use_wide:
            # a tree at a time through the sequential builder's own level
            # (its sums are the kernel's, in place: nothing to flatten over
            # the batch), so the two builders' tables agree by construction
            def wide_level(a):
                hist, parent, frontier = _hist_compact(
                    None, a[0], a[1], n_nodes=n_nodes, n_slots=d_hist, nb=nb,
                    r_sub=r_sub, n_pad=n_pad_c, f_chunk=Fc,
                    variance=(cfg.impurity == "variance"),
                    full_bins=packed, feats=a[2], wide=True,
                )
                return hist, parent, frontier.sel_tiles

            hist_full, parent, sel = lax.map(wide_level, (seg, sw, feats))
            sel_tiles = sel_tiles + sel
        elif use_sel:
            hist_full, parent = _hist_compact_batched(
                None, seg, sw, n_nodes=n_nodes, nb=nb, r_sub=r_sub,
                n_pad=n_pad_c, f_chunk=Fc,
                variance=(cfg.impurity == "variance"),
                full_bins=packed, feats=feats,
            )
        elif use_compact:
            hist_full, parent = _hist_compact_batched(
                make_hist_src(), seg, sw, n_nodes=n_nodes, nb=nb,
                r_sub=r_sub, n_pad=n_pad_c, f_chunk=Fc,
                variance=(cfg.impurity == "variance"),
            )
        else:
            parent = _seg_sum_trees(sw, seg, n_nodes + 1)[:, :n_nodes]
        parent = _allred(parent)
        leaf = leaf.at[:, offset : offset + n_nodes].set(parent)
        pcount = _count(parent, cfg.impurity)           # (T, n_nodes)
        pimp = _impurity(parent, cfg.impurity)

        # (the winner's left statistics are the sequential builder's to
        # hand down: this builder closes nothing at birth)
        bsf = jax.vmap(
            lambda h, p, pc, pi, rf_: _best_splits_from_hist(
                h, p, pc, pi, rf_, nb, cfg
            )[:3]
        )

        if use_compact:
            hist_full = _allred(hist_full)
            if subset:
                realf_full = feats.transpose(0, 2, 1)   # (T, k_pad, n_nodes)
            else:
                realf_full = jnp.broadcast_to(
                    jnp.arange(d_hist, dtype=jnp.int32)[None, :, None],
                    (T, d_hist, n_nodes),
                )
            Fc2 = d_hist
            while Fc2 > 1 and Fc2 * n_nodes * nb * S > 4 * _HIST_BUDGET:
                Fc2 //= 2
            bg = jnp.full((T, n_nodes), -jnp.inf, dt)
            bf = jnp.zeros((T, n_nodes), jnp.int32)
            bb = jnp.zeros((T, n_nodes), jnp.int32)
            for c0 in range(0, d_hist, Fc2):
                g, f, b = bsf(
                    hist_full[:, c0 : c0 + Fc2], parent, pcount, pimp,
                    realf_full[:, c0 : c0 + Fc2],
                )
                upd = g > bg
                bg = jnp.where(upd, g, bg)
                bf = jnp.where(upd, f, bf)
                bb = jnp.where(upd, b, bb)
        else:
            use_matmul = plan.strategy == "matmul"

            hist_src = make_hist_src()
            budget = (1 << 25) if (subset and not use_matmul) else _HIST_BUDGET
            F = _chunk_features(d_hist, n_nodes, nb, S, budget)
            n_chunks = d_hist // F
            if use_matmul:
                C_lvl = min(_ROW_CHUNK, n)
                f_cap = max(1, (1 << 26) // (C_lvl * nb))
                f_cap = 1 << (f_cap.bit_length() - 1)
                F = min(F, f_cap)
                n_chunks = d_hist // F

            def _hist_scatter_b(binc, *, n_nodes, in_level, local, sw):
                """(T, F, n_nodes, nb, S) via ONE fused global scatter:
                tree t's (node, bin) cells live at segment offset
                t*(n_nodes*nb+1), so per (tree, feature, cell) the
                accumulation visits the same rows in the same order as
                the sequential _hist_scatter — bitwise identical."""
                F = binc.shape[-1]
                num = n_nodes * nb + 1
                bc = binc if binc.ndim == 3 else binc[None]
                ids = jnp.where(
                    in_level[:, :, None],
                    local[:, :, None] * nb + bc,
                    n_nodes * nb,
                )                                       # (T, n, F)
                gids = ids + (
                    num * jnp.arange(T, dtype=jnp.int32)
                )[:, None, None]
                gflat = gids.reshape(T * n, F)
                if S <= 16:
                    hist = jnp.stack(
                        [
                            jax.vmap(
                                lambda col, c=sw[:, :, s].reshape(
                                    T * n
                                ): jax.ops.segment_sum(
                                    c, col, num_segments=T * num
                                ),
                                in_axes=1,
                            )(gflat)                    # (F, T*num)
                            for s in range(S)
                        ],
                        axis=-1,
                    )                                   # (F, T*num, S)
                else:
                    swf = sw.reshape(T * n, S)
                    hist = jax.vmap(
                        lambda col: jax.ops.segment_sum(
                            swf, col, num_segments=T * num
                        ),
                        in_axes=1,
                    )(gflat)
                hist = hist.reshape(F, T, num, S)[:, :, : n_nodes * nb, :]
                return hist.reshape(F, T, n_nodes, nb, S).transpose(
                    1, 0, 2, 3, 4
                )

            def _hist_matmul_b(binc, *, n_nodes, in_level, local, sw):
                """(T, F, n_nodes, nb, S) via one-hot contractions. With
                shared bins (no subset) the T node-onehots stack into a
                single tall-skinny (T*n_nodes, C) x (C, F*nb) MXU matmul
                per stat — the tree-batched dispatch shape this builder
                exists for. Variance stats and per-tree bins (forced
                matmul + subset) use a T-batched dot_general instead:
                each batch element is exactly the sequential (n_nodes, C)
                x (C, F*nb) GEMM, preserving its accumulation order —
                the flat stacking changes the GEMM's M extent, which
                measurably perturbs f32 accumulation at the last ulp
                (integer one-hot stats are exact either way, so
                classification keeps the fused form)."""
                F = binc.shape[-1]
                C = min(_ROW_CHUNK, n)
                nc = -(-n // C)
                node_ar = jnp.arange(n_nodes, dtype=jnp.int32)
                bin_ar = jnp.arange(nb, dtype=jnp.int32)
                prec = (
                    lax.Precision.HIGHEST
                    if cfg.impurity == "variance"
                    else None
                )
                shared_bins = binc.ndim == 2

                def row_body(ri, acc):
                    start = jnp.minimum(ri * C, n - C)
                    loc = lax.dynamic_slice(local, (0, start), (T, C))
                    lvl = lax.dynamic_slice(in_level, (0, start), (T, C))
                    swc = lax.dynamic_slice(sw, (0, start, 0), (T, C, S))
                    fresh = (start + jnp.arange(C)) >= ri * C
                    Noh = (
                        (loc[:, :, None] == node_ar[None, None, :])
                        & lvl[:, :, None]
                        & fresh[None, :, None]
                    ).astype(dt)                        # (T, C, n_nodes)
                    if shared_bins and prec is None:
                        bcc = lax.dynamic_slice(binc, (start, 0), (C, F))
                        Boh = (
                            bcc[:, :, None] == bin_ar[None, None, :]
                        ).astype(dt).reshape(C, F * nb)
                        out = jnp.stack(
                            [
                                jnp.matmul(
                                    (Noh * swc[:, :, s][:, :, None])
                                    .transpose(0, 2, 1)
                                    .reshape(T * n_nodes, C),
                                    Boh,
                                    precision=prec,
                                ).reshape(T, n_nodes, F * nb)
                                for s in range(S)
                            ],
                            axis=-1,
                        )                               # (T, n_nodes, F*nb, S)
                    elif shared_bins:
                        bcc = lax.dynamic_slice(binc, (start, 0), (C, F))
                        Boh = jnp.broadcast_to(
                            (bcc[:, :, None] == bin_ar[None, None, :])
                            .astype(dt)
                            .reshape(C, F * nb)[None],
                            (T, C, F * nb),
                        )
                        out = jnp.stack(
                            [
                                lax.dot_general(
                                    (Noh * swc[:, :, s][:, :, None])
                                    .transpose(0, 2, 1),
                                    Boh,
                                    (((2,), (1,)), ((0,), (0,))),
                                    precision=prec,
                                )
                                for s in range(S)
                            ],
                            axis=-1,
                        )
                    else:
                        bcc = lax.dynamic_slice(
                            binc, (0, start, 0), (T, C, F)
                        )
                        Boh = (
                            bcc[:, :, :, None] == bin_ar
                        ).astype(dt).reshape(T, C, F * nb)
                        out = jnp.stack(
                            [
                                lax.dot_general(
                                    (Noh * swc[:, :, s][:, :, None])
                                    .transpose(0, 2, 1),
                                    Boh,
                                    (((2,), (1,)), ((0,), (0,))),
                                    precision=prec,
                                )
                                for s in range(S)
                            ],
                            axis=-1,
                        )
                    return acc + out

                acc = lax.fori_loop(
                    0, nc, row_body,
                    jnp.zeros((T, n_nodes, F * nb, S), dt),
                )
                return acc.reshape(T, n_nodes, F, nb, S).transpose(
                    0, 2, 1, 3, 4
                )

            def chunk_body(carry, ci, *, n_nodes=n_nodes, parent=parent,
                           pcount=pcount, pimp=pimp, feats=feats, F=F,
                           in_level=in_level, local=local, sw=sw,
                           use_matmul=use_matmul, subset=subset,
                           hist_src=hist_src):
                bg, bf, bb = carry
                if subset:
                    binc = lax.dynamic_slice(
                        hist_src, (0, 0, ci * F), (T, n, F)
                    ).astype(jnp.int32)
                else:
                    binc = _unpack_bins(hist_src, ci * F, F)
                make = _hist_matmul_b if use_matmul else _hist_scatter_b
                hist = make(
                    binc, n_nodes=n_nodes, in_level=in_level,
                    local=local, sw=sw,
                )
                hist = _allred(hist)
                if subset:
                    realf = lax.dynamic_slice(
                        feats, (0, 0, ci * F), (T, n_nodes, F)
                    ).transpose(0, 2, 1)                # (T, F, n_nodes)
                else:
                    realf = jnp.broadcast_to(
                        (ci * F + jnp.arange(F, dtype=jnp.int32))
                        [None, :, None],
                        (T, F, n_nodes),
                    )
                g, f, b = bsf(hist, parent, pcount, pimp, realf)
                upd = g > bg
                return (
                    jnp.where(upd, g, bg),
                    jnp.where(upd, f, bf),
                    jnp.where(upd, b, bb),
                ), None

            init = (
                jnp.full((T, n_nodes), -jnp.inf, dt),
                jnp.zeros((T, n_nodes), jnp.int32),
                jnp.zeros((T, n_nodes), jnp.int32),
            )
            (bg, bf, bb), _ = lax.scan(
                chunk_body, init, jnp.arange(n_chunks)
            )

        do_split = (
            jnp.isfinite(bg)
            & (bg >= max(cfg.min_info_gain, 1e-9))
            & _can_split(parent, cfg)
        )                                               # (T, n_nodes)
        feat = feat.at[:, offset : offset + n_nodes].set(
            jnp.where(do_split, bf, -1)
        )
        thr_bin = thr_bin.at[:, offset : offset + n_nodes].set(
            jnp.where(do_split, bb, 0)
        )
        gains = gains.at[:, offset : offset + n_nodes].set(
            jnp.where(do_split, bg, jnp.zeros_like(bg))
        )

        lc = jnp.clip(local, 0, n_nodes - 1)
        row_feat = jnp.take_along_axis(bf, lc, axis=1)  # (T, n)
        if use_contract:
            row_bin = jax.vmap(
                lambda rf_: _contract_gather(packed, rf_[:, None])[:, 0]
            )(row_feat)
        else:
            row_bin = jax.vmap(
                lambda rf_: _bins_at(packed, None, rf_[:, None])[:, 0]
            )(row_feat)
        go_right = (row_bin > jnp.take_along_axis(bb, lc, axis=1)).astype(
            jnp.int32
        )
        child = 2 * node + 1 + go_right
        moves = in_level & jnp.take_along_axis(do_split, lc, axis=1)
        node = jnp.where(moves, child, node)

    out = {
        "feature": feat,
        "threshold_bin": thr_bin,
        "leaf_stats": leaf,
        "gain": gains,
        "live_rows": jnp.stack(live_rows, axis=1)       # (T, levels)
        if live_rows
        else jnp.zeros((T, 0), jnp.int32),
        "closed_at_birth": jnp.zeros((T,), jnp.int32),
        "sel_tiles": sel_tiles,
    }
    if return_rows:
        out["node"] = node
    return out


def _build_trees_batched(
    bins: jax.Array,    # (n, d_pad/4) int32 bins four to a word
    stats: jax.Array,   # (n, S) float
    valid: jax.Array,   # (n,) float row mask
    keys: jax.Array,    # (T, 2) uint32
    cfg: ForestConfig,
) -> Dict[str, jax.Array]:
    """RF front half of the batched builder: per-tree bootstrap weights as
    a leading batch axis. RNG goes through ``lax.map`` of the sequential
    builder's exact split/poisson calls, so every tree draws identical
    weights to ``_build_tree(key)`` — the root of the bit-identity
    guarantee."""
    n = bins.shape[0]
    dt = stats.dtype
    kk = lax.map(jax.random.split, keys)                # (T, 2, 2)
    kb, kf = kk[:, 0], kk[:, 1]
    if cfg.bootstrap:
        logical = jnp.clip(
            jnp.cumsum(valid.astype(jnp.int32)) - 1, 0, n - 1
        )
        draws = lax.map(
            lambda k: jax.random.poisson(k, 1.0, (n,)), kb
        ).astype(dt)                                    # (T, n)
        w = draws[:, logical] * valid[None, :]
    else:
        w = jnp.broadcast_to(valid.astype(dt), (keys.shape[0], n))
    sw = stats[None] * w[:, :, None]                    # (T, n, S)
    return _grow_trees_batched(bins, sw, kf, cfg)


# ---------------------------------------------------------------------------
# forest build over the mesh
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("mesh", "cfg", "gather", "tree_batch")
)
def build_forest(
    bins: jax.Array,   # (N_pad, d_pad/4) int32 bins four to a word, dp-sharded
    mask: jax.Array,   # (N_pad,) float, dp-sharded
    stats: jax.Array,  # (N_pad, S) float, dp-sharded
    keys: jax.Array,   # (n_dp, trees_per_device, 2) uint32, dp-sharded
    *,
    mesh: Mesh,
    cfg: ForestConfig,
    gather: bool = False,
    tree_batch: int = 1,
) -> Dict[str, jax.Array]:
    """Each device grows ``trees_per_device`` trees; the stacked forest
    materializes via the out-sharding — the analog of the reference's
    allGather of serialized treelite bytes (``tree.py:319-366``).

    ``gather=False`` matches the reference's semantics exactly: each tree
    sees only its worker's row partition (the per-worker local cuRF fit,
    ``tree.py:269-402``), which costs tree quality as worker count grows.
    ``gather=True`` is the TPU-first improvement the reference cannot
    afford over NCCL: one ICI ``all_gather`` of the binned matrix
    (n x d bytes — 33 MB at 131k x 256, ~3 GB at the 1M x 3000 reference
    shape) gives every tree the FULL dataset, making quality independent
    of worker count while growth stays collective-free."""

    def per_device(bins_l, mask_l, stats_l, keys_l):
        if gather:
            bins_l = lax.all_gather(bins_l, DP_AXIS, axis=0, tiled=True)
            mask_l = lax.all_gather(mask_l, DP_AXIS, axis=0, tiled=True)
            stats_l = lax.all_gather(stats_l, DP_AXIS, axis=0, tiled=True)
        kl = keys_l[0]
        t_local = kl.shape[0]
        if tree_batch > 1 and t_local % tree_batch == 0:
            # tree-batched growth: (G, B, 2) key batches, B trees per
            # level dispatch (bit-identical to the sequential path —
            # see _grow_trees_batched)
            out = lax.map(
                lambda kb: _build_trees_batched(
                    bins_l, stats_l, mask_l, kb, cfg
                ),
                kl.reshape(t_local // tree_batch, tree_batch, 2),
            )
            return jax.tree_util.tree_map(
                lambda a: a.reshape((t_local,) + a.shape[2:]), out
            )
        return lax.map(
            lambda k: _build_tree(bins_l, stats_l, mask_l, k, cfg), kl
        )

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows()),
        out_specs=LAYOUT.rows(),
        check_vma=False,
    )(bins, mask, stats, keys)


# ---------------------------------------------------------------------------
# inference
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_depth", "use_contract"))
def forest_apply(
    X: jax.Array,        # (n, d)
    feat: jax.Array,     # (T, M) int32, -1 = leaf
    thr: jax.Array,      # (T, M) raw-space thresholds (x >= thr -> right)
    *,
    max_depth: int,
    use_contract: bool | None = None,  # None = backend/width heuristic;
                                       # explicit value for cross-branch tests
) -> jax.Array:
    """Leaf index per (tree, row): vectorized level-synchronous descent.

    Per (tree, level) the descent needs two per-row values from the node
    tables (split feature, threshold) and one from X. TPU element
    gathers run ~1e8/s while ROW gathers are width-flat, so the tables
    ride as one (M, 2) f32 table gathered whole rows (feature ids < 2^24
    are f32-exact), and the X lookup becomes a dense lane contraction at
    moderate d. Measured v5e at 131k rows x 56 trees x depth 13:
    4.6 s -> 0.72 s (scripts history, round 4)."""
    n, d = X.shape

    # dense X-lane contraction beats take_along_axis up to ~1k features
    # (n*d compare-select work vs n serialized element gathers); fall
    # back to the gather past that, and everywhere off-TPU
    if use_contract is None:
        use_contract = jax.default_backend() == "tpu" and d <= 1024
    iota_d = jnp.arange(d, dtype=jnp.int32)

    def one_tree(tb):
        def body(_, node):
            g = tb[node]                         # (n, 2) one row gather
            nf = g[:, 0].astype(jnp.int32)
            tv = g[:, 1]
            if use_contract:
                sel = nf[:, None] == iota_d[None, :]
                xv = jnp.where(sel, X, 0.0).sum(axis=1)
            else:
                xv = jnp.take_along_axis(
                    X, jnp.clip(nf, 0, d - 1)[:, None], axis=1
                )[:, 0]
            go_right = (xv >= tv).astype(jnp.int32)
            child = 2 * node + 1 + go_right
            return jnp.where(nf < 0, node, child)

        return lax.fori_loop(0, max_depth, body, jnp.zeros((n,), jnp.int32))

    # table dtype: at least f32 (feature ids are exact only below 256 in
    # bf16 / 2048 in f16 — narrow thresholds widen losslessly instead),
    # and f64 thresholds stay f64 so boundary decisions are unperturbed
    tdt = jnp.promote_types(thr.dtype, jnp.float32)
    tbl = jnp.stack([feat.astype(tdt), thr.astype(tdt)], axis=-1)
    return jax.vmap(one_tree)(tbl)


# --- two-hop subtree descent (bin space, zero per-row gathers) -------------
#
# The level-synchronous descent above pays one (n,2)-row gather per
# (tree, level): T*depth*n ~ 95M gathered rows at the bench shape, and the
# chip's gather engine tops out near 4e8 rows/s — an architectural wall
# ~25x short of GPU FIL-class inference (reference tree.py:557-591). The
# two-hop formulation removes per-row gathers entirely by exploiting the
# full-binary-tree layout (node i's children at 2i+1/2i+2, levels laid out
# contiguously, so every level-L slice reshapes to (2^k1, 2^(L-k1)) per
# level-k1 subtree):
#
#   hop 1 (levels 0..k1-1): the root subtree is SHARED by all rows, so its
#     2^k1-1 tests evaluate as ONE bf16 matmul of the binned rows against
#     the subtree's feature one-hot (bin ids and feature ids are small
#     ints — exact in bf16), then k1 arithmetic bit-navigation steps;
#   hop 2 (levels k1..D): each row's level-k1 subtree is one of 2^k1, so
#     its (feature, threshold) table arrives by a one-hot contraction over
#     the 2^k1 axis on the MXU (again exact small ints), the row-specific
#     feature bins come from the word-packed contraction gather, and k2
#     more bit-navigation steps reach the leaf. Leaf values are selected
#     the same way (f32 one-hot contraction + lane select).
#
# All comparisons happen in BIN space (x >= edges[f,b]  <=>  bin(x) > b,
# the exact training-side routing rule), so results are bit-identical to
# the raw-threshold descent wherever the model carries its bin tables.


def _navigate(enc, steps, L):
    """Heap-local descent over payload array enc (n, L) int32, heap order:
    enc[i] = 0 at a leaf (stop) else 1 + go_right_bit, so each step is
    ``i -> 2i + enc[i]`` while enc[i] > 0.

    The step-s lookup touches only the depth-s heap slice
    ``enc[:, 2^s-1 : 2^(s+1)-1]`` — a width-2^s lane one-hot — so total
    select work across all steps is one full pass over enc (n*L elements)
    instead of steps * n * L. Rows frozen at a shallower depth (i < lo)
    are guarded from reading a clipped lane. Returns (i, stopped_early):
    rows that complete all `steps` land at index >= L = 2^steps - 1."""
    n = enc.shape[0]
    i = jnp.zeros((n,), jnp.int32)
    for s in range(steps):
        lo = (1 << s) - 1
        w = 1 << s
        sl = lax.slice_in_dim(enc, lo, lo + w, axis=1)
        il = jnp.clip(i - lo, 0, w - 1)
        lanes = jnp.arange(w, dtype=jnp.int32)
        e = jnp.where(lanes[None, :] == il[:, None], sl, 0).sum(axis=1)
        e = jnp.where(i >= lo, e, 0)
        i = jnp.where(e > 0, 2 * i + e, i)
    return i, i < L


def _twohop_group(xb16, packed, feat_g, thr_g, val_g, *, max_depth, d):
    """One tree-group pass of the two-hop descent.

    xb16 (n, d) bf16 bins; packed (n, d/4) i32; feat_g (G, M) i32;
    thr_g (G, M) i32; val_g (G, M, V) f32 or None. Returns
    (leaf_ids (G, n) i32, values (n, V) f32 summed over the group or None).
    """
    n = xb16.shape[0]
    G, M = feat_g.shape
    D = max_depth
    k1 = max(min(7, D), D - 6)
    k2 = D - k1
    n1 = (1 << k1) - 1          # hop-1 internal candidate nodes 0..n1-1
    iota_d = jnp.arange(d, dtype=jnp.int32)
    nint = (1 << k2) - 1 if k2 > 0 else 0

    leaf_ids = []
    vals_sum = None
    # phase A (per tree): hop-1 navigation + hop-2 table rows + byte indices
    ph = []
    for g in range(G):
        feat_t = feat_g[g]
        thr_t = thr_g[g]
        # ---- hop 1: shared root subtree
        f1 = feat_t[:n1]                                    # (n1,)
        oh1 = (f1[:, None] == iota_d[None, :]).astype(jnp.bfloat16)
        tests1 = jax.lax.dot_general(
            xb16, oh1, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (n, n1)
        bits1 = (tests1 > thr_t[:n1].astype(jnp.float32)).astype(jnp.int32)
        enc1 = (1 + bits1) * (f1 >= 0)[None, :].astype(jnp.int32)
        i1, done1 = _navigate(enc1, k1, n1)
        if k2 == 0:
            leaf_ids.append(i1)
            if val_g is not None:
                v = val_g[g][i1]                            # (n, V) row gather
                vals_sum = v if vals_sum is None else vals_sum + v
            continue

        l7 = jnp.clip(i1 - n1, 0, (1 << k1) - 1)            # subtree id
        # ---- hop 2: per-subtree local tables, heap order m = 2^delta-1+j.
        # The per-row table read is ONE row gather from a tiny
        # (2^k1, 2*nint) table: a (n, 2^k1) one-hot matmul of the same
        # selection measures ~4 ms/tree at ANY precision (~2 TF/s
        # effective on the skinny shape) while the gather engine does
        # these rows in ~0.3 ms/tree — gathers win 10x here.
        sub_f = []
        sub_t = []
        for delta in range(k2):
            off = (1 << (k1 + delta)) - 1
            cnt = 1 << (k1 + delta)
            sh = (1 << k1, 1 << delta)
            sub_f.append(feat_t[off : off + cnt].reshape(sh))
            sub_t.append(thr_t[off : off + cnt].reshape(sh))
        tbl2 = jnp.concatenate(sub_f + sub_t, axis=1)       # (2^k1, 2*nint)
        rrow = tbl2[l7]                                     # (n, 2*nint)
        rfeat = rrow[:, :nint]
        rthr = rrow[:, nint:]
        ridx = jnp.clip(rfeat, 0, d - 1)
        ph.append((i1, done1, l7, rfeat, rthr, ridx))

    if k2 == 0:
        return jnp.stack(leaf_ids, axis=0), vals_sum

    # phase C (per tree): hop-2 navigation + leaf/value resolution
    for g, (i1, done1, l7, rfeat, rthr, ridx) in enumerate(ph):
        xv = _contract_gather(packed, ridx)                 # (n, nint) i32
        bits2 = ((xv > rthr) & (rfeat >= 0)).astype(jnp.int32)
        enc2 = (1 + bits2) * (rfeat >= 0).astype(jnp.int32)
        enc2 = jnp.where(done1[:, None], 0, enc2)
        m, _ = _navigate(enc2, k2, nint)
        # done1 rows keep i1; others: global id from (l7, local heap m)
        delta = jnp.zeros_like(m)
        for j in range(1, k2 + 1):
            delta = delta + (m + 1 >= (1 << j)).astype(jnp.int32)
        pd = jnp.left_shift(jnp.int32(1), delta)            # 2^delta
        j_local = m - (pd - 1)
        gid = ((1 << k1) * pd - 1) + l7 * pd + j_local
        leaf = jnp.where(done1, i1, gid)
        leaf_ids.append(leaf)

        if val_g is not None:
            v = val_g[g][leaf]                              # (n, V) row gather
            vals_sum = v if vals_sum is None else vals_sum + v

    return jnp.stack(leaf_ids, axis=0), vals_sum


def _twohop_drive(xb, feat, thr_bin, values, *, max_depth, group):
    """Shared driver for the two-hop descent: byte-gather row alignment,
    bf16 cast + word packing, tree-group loop, and row unpadding. With
    ``values`` None returns stacked (T, n) leaf ids; otherwise the (n, V)
    value sum over trees."""
    T = feat.shape[0]
    n0 = xb.shape[0]
    xb16 = xb.astype(jnp.bfloat16)
    packed = _pack_bins(xb)
    ids_out = []
    acc = None
    for g0 in range(0, T, group):
        ids, v = _twohop_group(
            xb16, packed, feat[g0 : g0 + group],
            thr_bin[g0 : g0 + group],
            None if values is None else values[g0 : g0 + group],
            max_depth=max_depth, d=xb.shape[1],
        )
        ids_out.append(ids)
        if values is not None:
            acc = v if acc is None else acc + v
    if values is None:
        return jnp.concatenate(ids_out, axis=0)[:, :n0]
    return acc[:n0]


@functools.partial(jax.jit, static_argnames=("max_depth", "group"))
def forest_apply_bins(
    xb: jax.Array,       # (n, d_pad) uint8 bin ids
    feat: jax.Array,     # (T, M) int32, -1 = leaf
    thr_bin: jax.Array,  # (T, M) int32 (bin(x) > thr_bin -> right)
    *,
    max_depth: int,
    group: int = 8,
) -> jax.Array:
    """Leaf node index per (tree, row) via the two-hop subtree descent."""
    return _twohop_drive(
        xb, feat, thr_bin, None, max_depth=max_depth, group=group
    )


@functools.partial(jax.jit, static_argnames=("max_depth", "group"))
def rf_eval_bins(
    xb: jax.Array,       # (n, d_pad) uint8 bin ids
    feat: jax.Array,     # (T, M) int32, -1 = leaf
    thr_bin: jax.Array,  # (T, M) int32
    values: jax.Array,   # (T, M, V) f32 per-node leaf stats
    *,
    max_depth: int,
    group: int = 8,
) -> jax.Array:
    """Sum over trees of each tree's leaf value vector, (n, V)."""
    return _twohop_drive(
        xb, feat, thr_bin, values, max_depth=max_depth, group=group
    )


@functools.partial(
    jax.jit, static_argnames=("max_depth", "group", "pred_dtype")
)
def rf_classify_bins(
    xb: jax.Array,       # (n, d_pad) uint8 bin ids
    feat: jax.Array,
    thr_bin: jax.Array,
    leaf_prob: jax.Array,  # (T, M, C) normalized leaf distributions
    *,
    max_depth: int,
    group: int = 8,
    pred_dtype=None,
):
    """Spark RF vote semantics via the two-hop bin-space descent: the
    summed-over-trees leaf distribution arrives directly from
    ``rf_eval_bins`` — no (T, n, C) materialization. ``group`` bounds the
    per-tree-group transients (smaller = leaner alongside big residents).
    ``pred_dtype`` sets the prediction dtype (legacy ``rf_classify``
    returns predictions in X.dtype; callers pass their row dtype here to
    keep that contract — default float32 for compatibility)."""
    raw = rf_eval_bins(
        xb, feat, thr_bin, leaf_prob, max_depth=max_depth, group=group
    )
    prob = raw / feat.shape[0]
    pred = jnp.argmax(raw, axis=1).astype(pred_dtype or jnp.float32)
    return pred, prob, raw


@functools.partial(jax.jit, static_argnames=("max_depth", "group"))
def rf_regress_bins(
    xb: jax.Array,
    feat: jax.Array,
    thr_bin: jax.Array,
    leaf_value: jax.Array,  # (T, M) per-tree leaf means
    *,
    max_depth: int,
    group: int = 8,
) -> jax.Array:
    s = rf_eval_bins(
        xb, feat, thr_bin, leaf_value[..., None], max_depth=max_depth,
        group=group,
    )
    return s[:, 0] / leaf_value.shape[0]


# ---------------------------------------------------------------------------
# FIL-style packed-forest inference engine
# ---------------------------------------------------------------------------
#
# The two-hop bins path above still walks trees one at a time inside each
# group: per tree one skinny hop-1 matmul, one table gather, one
# contraction gather — each a separate XLA op with its own fusion
# boundary, ~70 ms of contraction gathers plus per-op overhead at the
# bench forest. cuML's FIL closes the same gap on GPU by re-laying the
# forest into an interleaved SoA blob and descending a row tile through
# ALL trees per level in lockstep. The TPU analog here:
#
#   * ``pack_forest`` (host, once per model) re-lays the heap-ordered
#     (T, M) tensors breadth-first into lane-width-padded SoA blocks:
#     hop-1 root subtrees as (T_pad, n1) slabs driving ONE all-tree bf16
#     one-hot matmul, and hop-2 per-subtree (feature, threshold) tables
#     as (T_pad * 2^k1, 64) slabs the traversal kernel row-selects on
#     the MXU.
#   * ``rf_pallas.packed_traverse`` fuses the whole hop-2 phase — table
#     row-select, lane-shuffle byte gather of the row's feature bins,
#     masked bit-navigation, global-leaf-id arithmetic — for every tree
#     into ONE pallas_call per row block, removing the per-tree dispatch
#     and gather-engine costs that dominated the bins path.
#   * leaf payloads are then accumulated tree-sequentially in the exact
#     order ``_twohop_drive`` uses (group-8 partial sums), so packed
#     results are BIT-IDENTICAL to the bins path: leaf indices are
#     integers (exact by construction) and the f32 payload sums
#     reassociate identically.


class PackedForest(NamedTuple):
    """Breadth-first interleaved SoA forest layout (``pack_forest``).

    Arrays are plain numpy (host) so models can persist them via the
    standard attribute round-trip and ship them to device once per
    process. ``feat2``/``thr2`` are empty (0, 64) when ``k2 == 0`` —
    forests shallow enough that hop-1 alone reaches every leaf.
    """

    feat1: np.ndarray    # (T_pad, n1) int32 hop-1 root subtrees, -1 = leaf
    thr1: np.ndarray     # (T_pad, n1) int32 bin thresholds
    feat2: np.ndarray    # (T_pad * 2^k1, 64) int32 hop-2 tables, -1 pad
    thr2: np.ndarray     # (T_pad * 2^k1, 64) int32
    n_trees: int         # real tree count T (payload accumulation bound)
    k1: int              # hop-1 depth (root-subtree levels)
    k2: int              # hop-2 depth (per-subtree levels)
    max_depth: int


def pack_forest(feat, thr_bin, *, max_depth: int) -> PackedForest:
    """Re-lay a trained forest for lockstep traversal (host, once).

    ``feat``/``thr_bin`` are the (T, M) heap-ordered int32 tensors the
    builder emits. The split point k1/k2 matches ``_twohop_group``
    exactly (k1 = max(min(7, D), D-6)) so packed descent reproduces the
    same leaf indices. Trees are padded to a multiple of 8 with all-leaf
    sentinels (feat = -1): padding trees navigate to leaf 0 and are
    sliced out of payload accumulation. The hop-2 tables interleave
    per-subtree rows — table row ``t * 2^k1 + s`` holds subtree ``s`` of
    tree ``t`` with its ``2^k2 - 1`` internal nodes in heap-local
    breadth-first order along lanes (lane m = local heap slot m), padded
    to the 64-lane shuffle width with leaf sentinels.
    """
    feat = np.asarray(feat, dtype=np.int32)
    thr = np.asarray(thr_bin, dtype=np.int32)
    T, M = feat.shape
    D = int(max_depth)
    k1 = max(min(7, D), D - 6)
    k2 = D - k1
    n1 = (1 << k1) - 1
    T_pad = -(-T // 8) * 8
    featp = np.pad(feat, ((0, T_pad - T), (0, 0)), constant_values=-1)
    thrp = np.pad(thr, ((0, T_pad - T), (0, 0)))
    feat1 = np.ascontiguousarray(featp[:, :n1])
    thr1 = np.ascontiguousarray(thrp[:, :n1])
    LANES = 64  # nint = 2^k2 - 1 <= 63 always (k2 <= 6)
    if k2 == 0:
        feat2 = np.full((0, LANES), -1, np.int32)
        thr2 = np.zeros((0, LANES), np.int32)
    else:
        K1 = 1 << k1
        f2 = np.full((T_pad, K1, LANES), -1, np.int32)
        t2 = np.zeros((T_pad, K1, LANES), np.int32)
        for delta in range(k2):
            off = (1 << (k1 + delta)) - 1
            cnt = 1 << (k1 + delta)
            w = 1 << delta
            lo = (1 << delta) - 1  # heap-local lane offset of this level
            f2[:, :, lo : lo + w] = featp[:, off : off + cnt].reshape(
                T_pad, K1, w
            )
            t2[:, :, lo : lo + w] = thrp[:, off : off + cnt].reshape(
                T_pad, K1, w
            )
        feat2 = f2.reshape(T_pad * K1, LANES)
        thr2 = t2.reshape(T_pad * K1, LANES)
    return PackedForest(
        feat1=feat1, thr1=thr1, feat2=feat2, thr2=thr2,
        n_trees=T, k1=k1, k2=k2, max_depth=D,
    )


def _packed_hop1(xb16, feat1, thr1, *, k1):
    """All-tree hop-1: every root subtree's tests in ONE bf16 one-hot
    matmul (exact — bin and feature ids are small ints) followed by a
    tree-batched bit-navigation. Returns (n, T_pad) int32 heap indices;
    rows stopped at a hop-1 leaf hold index < 2^k1 - 1."""
    n, d = xb16.shape
    T_pad, n1 = feat1.shape
    iota_d = jnp.arange(d, dtype=jnp.int32)
    f1 = feat1.reshape(T_pad * n1)
    oh1 = (f1[:, None] == iota_d[None, :]).astype(jnp.bfloat16)
    tests1 = lax.dot_general(
        xb16, oh1, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (n, T_pad*n1)
    thr_f = thr1.reshape(T_pad * n1).astype(jnp.float32)
    bits1 = (tests1 > thr_f[None, :]).astype(jnp.int32)
    enc1 = ((1 + bits1) * (f1 >= 0)[None, :].astype(jnp.int32)).reshape(
        n, T_pad, n1
    )
    i = jnp.zeros((n, T_pad), jnp.int32)
    for s in range(k1):
        lo = (1 << s) - 1
        w = 1 << s
        sl = lax.slice_in_dim(enc1, lo, lo + w, axis=2)   # (n, T, w)
        il = jnp.clip(i - lo, 0, w - 1)
        lanes = jnp.arange(w, dtype=jnp.int32)
        e = jnp.where(lanes[None, None, :] == il[..., None], sl, 0).sum(
            axis=2
        )
        e = jnp.where(i >= lo, e, 0)
        i = jnp.where(e > 0, 2 * i + e, i)
    return i


def _packed_payload(leaf, values, *, n_trees, group):
    """Tree-sequential payload accumulation over packed leaf ids, in the
    EXACT association ``_twohop_drive`` uses (per-group partial sums in
    tree order, then sequential across groups) so packed f32 sums are
    bit-identical to the bins path's."""
    acc = None
    for g0 in range(0, n_trees, group):
        vals_sum = None
        for t in range(g0, min(g0 + group, n_trees)):
            v = values[t][leaf[:, t]]                    # (n, V) row gather
            vals_sum = v if vals_sum is None else vals_sum + v
        acc = vals_sum if acc is None else acc + vals_sum
    return acc


@functools.partial(
    jax.jit, static_argnames=("k1", "k2", "max_depth", "interpret")
)
def forest_apply_packed(
    xb: jax.Array,       # (n, d_pad) uint8 bin ids
    feat1: jax.Array,    # (T_pad, n1) int32
    thr1: jax.Array,     # (T_pad, n1) int32
    feat2: jax.Array,    # (T_pad * 2^k1, 64) int32
    thr2: jax.Array,     # (T_pad * 2^k1, 64) int32
    *,
    k1: int,
    k2: int,
    max_depth: int,
    interpret=None,
) -> jax.Array:
    """Global leaf index per (row, tree): (n, T_pad) int32, lockstep over
    all trees. Callers gate on ``rf_pallas.packed_traverse_ok`` first —
    this function assumes the traversal kernel lowers (or interprets)."""
    from .rf_pallas import TRAVERSE_BLOCK, packed_traverse

    n0, d_pad = xb.shape
    n = -(-n0 // TRAVERSE_BLOCK) * TRAVERSE_BLOCK
    if n > n0:
        xb = jnp.pad(xb, ((0, n - n0), (0, 0)))
    xb16 = xb.astype(jnp.bfloat16)
    i1 = _packed_hop1(xb16, feat1, thr1, k1=k1)          # (n, T_pad)
    if k2 == 0:
        return i1[:n0]
    packed = _pack_bins(xb)                              # (n, d_pad/4)
    leaf = packed_traverse(
        packed, i1, feat2, thr2, k1=k1, k2=k2, d_pad=d_pad,
        interpret=interpret,
    )
    return leaf[:n0]


@functools.partial(
    jax.jit, static_argnames=("k1", "k2", "max_depth", "group", "interpret")
)
def rf_eval_packed(
    xb: jax.Array,
    feat1: jax.Array,
    thr1: jax.Array,
    feat2: jax.Array,
    thr2: jax.Array,
    values: jax.Array,   # (T, M, V) per-node leaf payloads (REAL trees)
    *,
    k1: int,
    k2: int,
    max_depth: int,
    group: int = 8,
    interpret=None,
) -> jax.Array:
    """Sum over trees of each tree's leaf payload vector, (n, V) — the
    packed-engine equivalent of ``rf_eval_bins``, bit-identical to it
    (same leaf indices, same f32 accumulation order)."""
    leaf = forest_apply_packed(
        xb, feat1, thr1, feat2, thr2, k1=k1, k2=k2, max_depth=max_depth,
        interpret=interpret,
    )
    return _packed_payload(
        leaf, values, n_trees=values.shape[0], group=group
    )


@functools.partial(
    jax.jit,
    static_argnames=("k1", "k2", "max_depth", "group", "pred_dtype",
                     "interpret"),
)
def rf_classify_packed(
    xb: jax.Array,
    feat1: jax.Array,
    thr1: jax.Array,
    feat2: jax.Array,
    thr2: jax.Array,
    leaf_prob: jax.Array,  # (T, M, C) normalized leaf distributions
    *,
    k1: int,
    k2: int,
    max_depth: int,
    group: int = 8,
    pred_dtype=None,
    interpret=None,
):
    """Spark RF vote semantics through the packed engine — same contract
    (and bit-identical outputs) as ``rf_classify_bins``."""
    raw = rf_eval_packed(
        xb, feat1, thr1, feat2, thr2, leaf_prob,
        k1=k1, k2=k2, max_depth=max_depth, group=group,
        interpret=interpret,
    )
    prob = raw / leaf_prob.shape[0]
    pred = jnp.argmax(raw, axis=1).astype(pred_dtype or jnp.float32)
    return pred, prob, raw


@functools.partial(
    jax.jit, static_argnames=("k1", "k2", "max_depth", "group", "interpret")
)
def rf_regress_packed(
    xb: jax.Array,
    feat1: jax.Array,
    thr1: jax.Array,
    feat2: jax.Array,
    thr2: jax.Array,
    leaf_value: jax.Array,  # (T, M) per-tree leaf means
    *,
    k1: int,
    k2: int,
    max_depth: int,
    group: int = 8,
    interpret=None,
) -> jax.Array:
    s = rf_eval_packed(
        xb, feat1, thr1, feat2, thr2, leaf_value[..., None],
        k1=k1, k2=k2, max_depth=max_depth, group=group,
        interpret=interpret,
    )
    return s[:, 0] / leaf_value.shape[0]


@functools.partial(jax.jit, static_argnames=("max_depth",))
def rf_classify(
    X: jax.Array,
    feat: jax.Array,
    thr: jax.Array,
    leaf_prob: jax.Array,  # (T, M, C) per-tree normalized leaf distributions
    *,
    max_depth: int,
):
    """Spark RF vote semantics: rawPrediction = sum over trees of each
    tree's normalized leaf class distribution; probability = raw/numTrees."""
    leaves = forest_apply(X, feat, thr, max_depth=max_depth)        # (T, n)
    probs = jax.vmap(lambda lp, lv: lp[lv])(leaf_prob, leaves)      # (T, n, C)
    raw = probs.sum(axis=0)
    prob = raw / feat.shape[0]
    pred = jnp.argmax(raw, axis=1).astype(X.dtype)
    return pred, prob, raw


@functools.partial(jax.jit, static_argnames=("max_depth",))
def rf_regress(
    X: jax.Array,
    feat: jax.Array,
    thr: jax.Array,
    leaf_value: jax.Array,  # (T, M) per-tree leaf means
    *,
    max_depth: int,
) -> jax.Array:
    leaves = forest_apply(X, feat, thr, max_depth=max_depth)
    vals = jax.vmap(lambda lv, ix: lv[ix])(leaf_value, leaves)      # (T, n)
    return vals.mean(axis=0)


def next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())
