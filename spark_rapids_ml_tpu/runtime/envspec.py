"""Typed registry of every ``TPUML_*`` environment knob.

Single source of truth for name, type, default, validation domain, and
one-line doc of each variable. All library reads go through
:func:`get` — ``tpuml_lint`` rule TPU001 rejects raw ``os.environ``
access to ``TPUML_*`` names anywhere else, and TPU002 cross-checks this
registry against the committed docs tables (``scripts/gen_config_docs.py``
regenerates them from here).

Deliberately stdlib-only (no jax/numpy, no relative imports): the linter
and the doc generator load this file directly via ``importlib`` without
importing the package, so the doc-drift check runs even where jax does
not.

Parse conventions (uniform across every variable, unlike the ad-hoc
``int(os.environ[...])`` reads this replaced):

- unset or empty string -> the registered default (shell ``FOO= cmd``
  patterns mean "unset", never "parse the empty string");
- bools accept ``1/0, true/false, yes/no, on/off`` case-insensitively;
- choice values are stripped and lowercased before matching;
- any other malformed value raises :class:`EnvSpecError` naming the
  variable, the offending value, and the accepted domain.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Optional, Tuple


class EnvSpecError(ValueError):
    """A ``TPUML_*`` variable failed to parse or validate.

    Subclasses ``ValueError`` so pre-registry callers that caught
    ``ValueError`` from bare ``int()`` parses keep working.
    """


@dataclass(frozen=True)
class EnvVar:
    """One registered knob. ``type`` is int|float|bool|str|path|choice."""

    name: str
    type: str
    default: Any
    doc: str
    choices: Optional[Tuple[str, ...]] = None
    minimum: Optional[float] = None  # inclusive lower bound (int/float)
    exclusive_minimum: Optional[float] = None  # strict lower bound
    category: str = "general"
    # docs files (repo-relative) whose prose must mention this variable;
    # TPU002 enforces membership. configuration.md is implied for all.
    also_documented_in: Tuple[str, ...] = ()

    def domain(self) -> str:
        """Human-readable accepted domain, used in error messages."""
        if self.type == "choice":
            assert self.choices is not None
            return "one of " + "|".join(self.choices)
        if self.type == "bool":
            return "a boolean (1/0, true/false, yes/no, on/off)"
        bound = ""
        if self.minimum is not None:
            bound = f" >= {self.minimum:g}"
        elif self.exclusive_minimum is not None:
            bound = f" > {self.exclusive_minimum:g}"
        return {"int": "an integer", "float": "a number"}.get(
            self.type, "a string"
        ) + bound

    def default_repr(self) -> str:
        """Default as shown in the generated docs table."""
        if self.default is None:
            return "unset"
        if self.type == "bool":
            return "1" if self.default else "0"
        if self.type == "float":
            return f"{self.default:g}"
        return str(self.default)


_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off"))


def _registry(*specs: EnvVar) -> Dict[str, EnvVar]:
    out: Dict[str, EnvVar] = {}
    for s in specs:
        assert s.name not in out, f"duplicate registration {s.name}"
        out[s.name] = s
    return out


SPEC: Dict[str, EnvVar] = _registry(
    # --- multi-process rendezvous (parallel/context.py) -------------------
    EnvVar(
        "TPUML_COORDINATOR", "str", None,
        "Address of process 0 (e.g. `10.0.0.1:8476`) for the multi-host "
        "rendezvous; provided by the launcher (the reference's NCCL-uid "
        "allGather bootstrap). Unset = single-process.",
        category="distributed",
    ),
    EnvVar(
        "TPUML_NUM_PROCS", "int", 1,
        "Total process count of the multi-host world; provided by the "
        "launcher together with `TPUML_COORDINATOR`.",
        minimum=1, category="distributed",
    ),
    EnvVar(
        "TPUML_PROC_ID", "int", 0,
        "This process's rank in `[0, TPUML_NUM_PROCS)`; provided by the "
        "launcher together with `TPUML_COORDINATOR`.",
        minimum=0, category="distributed",
    ),
    # --- 2-D mesh / model axis (parallel/mesh.py, parallel/layout.py) -----
    EnvVar(
        "TPUML_MESH_MP", "str", "off",
        "Model-parallel (`mp`) degree of the 2-D `(dp, mp)` device mesh: "
        "`off` (default) keeps the 1-D row-sharded mesh (mp=1, "
        "bit-identical to the pre-2-D behavior), an integer pins the mp "
        "degree (clamped to the device count), `auto` picks the smallest "
        "power-of-two degree whose per-device model-axis shard (Gram "
        "block / centroid block / IVF list shard) fits the HBM budget "
        "(`TPUML_MESH_MP_BUDGET`). See `docs/mesh.md` for axis semantics "
        "and the tolerance contract.",
        category="distributed",
        also_documented_in=("docs/mesh.md",),
    ),
    EnvVar(
        "TPUML_MESH_MP_BUDGET", "float", None,
        "HBM budget in bytes for one device's model-axis shard under "
        "`TPUML_MESH_MP=auto` (default: a quarter of the device's "
        "reported memory, 4 GB fallback) — the same convention as the "
        "gang-fit and tree-batch resolvers.",
        exclusive_minimum=0, category="distributed",
        also_documented_in=("docs/mesh.md",),
    ),
    EnvVar(
        "TPUML_MP_GRAM", "choice", "auto",
        "Per-kernel gate for the feature-sharded (SUMMA-blocked) Gram/"
        "covariance accumulators (PCA, LinearRegression, streamed "
        "suffstats): `auto` shards the d-axis over mp when the mesh has "
        "mp>1 and d divides evenly, `off` pins the replicated 1-D "
        "accumulator on any mesh.",
        choices=("auto", "off"), category="distributed",
        also_documented_in=("docs/mesh.md",),
    ),
    EnvVar(
        "TPUML_MP_KMEANS", "choice", "auto",
        "Per-kernel gate for centroid-sharded KMeans (k-axis over mp, "
        "per-shard partial argmin + global min-reduce): `auto` shards "
        "when the mesh has mp>1 and k >= mp, `off` pins the replicated "
        "centroid table.",
        choices=("auto", "off"), category="distributed",
        also_documented_in=("docs/mesh.md",),
    ),
    EnvVar(
        "TPUML_MP_IVF", "choice", "auto",
        "Per-kernel gate for list-sharded IVF-Flat search (cluster lists "
        "partitioned over mp instead of whole-index replication): `auto` "
        "shards when the mesh has mp>1 and nlist >= mp, `off` pins the "
        "replicated index.",
        choices=("auto", "off"), category="distributed",
        also_documented_in=("docs/mesh.md",),
    ),
    # --- ingest / streaming ----------------------------------------------
    EnvVar(
        "TPUML_STREAM_THRESHOLD_BYTES", "int", None,
        "Dataset size above which fits stream automatically instead of "
        "materializing (default: 60% of one device's reported memory, or "
        "8 GiB when the backend reports none).",
        exclusive_minimum=0, category="streaming",
    ),
    EnvVar(
        "TPUML_STREAM_PREFETCH", "int", 2,
        "Look-ahead depth of the streaming decode thread (host memory: "
        "that many chunk buffers); `0` disables prefetch entirely.",
        minimum=0, category="streaming",
    ),
    EnvVar(
        "TPUML_STREAM_SYNC_EVERY", "int", 4,
        "Host-side backpressure period of streaming loops, in chunks "
        "between blocking device syncs (bounds pending-transfer host "
        "memory); `0` disables the periodic sync.",
        minimum=0, category="streaming",
    ),
    EnvVar(
        "TPUML_WIRE_DTYPE", "choice", "f32",
        "Host->device wire encoding of streamed feature chunks: `f32` "
        "ships the storage dtype unchanged (the default — bit-identical "
        "results); `f16` downcasts on host and upcasts on device; `int8` / "
        "`f8` quantize per chunk column on host (affine / e4m3 scaled) and "
        "dequantize inside the jitted fold step; `auto` probes the first "
        "chunk's quantization error and picks the narrowest encoding "
        "within tolerance (see `docs/streaming_performance.md`). "
        "Infeasible explicit requests warn and fall back.",
        choices=("auto", "f32", "f16", "int8", "f8"), category="streaming",
        also_documented_in=("docs/streaming_performance.md",),
    ),
    EnvVar(
        "TPUML_STREAM_STAGE_DEPTH", "int", 2,
        "Look-ahead depth of the device-staging ring: a background thread "
        "wire-encodes and `device_put`s up to that many chunks ahead of "
        "the fold loop, so decode, host->device transfer, and accumulate "
        "overlap. `0` stages serially on the consumer thread (the "
        "pre-ring behavior). Fold order and results are identical at any "
        "depth (see `docs/streaming_performance.md`).",
        minimum=0, category="streaming",
        also_documented_in=("docs/streaming_performance.md",),
    ),
    EnvVar(
        "TPUML_STREAM_SHARD_FILES", "bool", False,
        "Per-host sharded ingest: each process of a multi-host world "
        "streams only its round-robin subset of the parquet files "
        "(`files[process_index::process_count]`), so N hosts pull N files "
        "concurrently; partial statistics combine through the existing "
        "cross-process allreduce. Identity in a single-process world "
        "(see `docs/streaming_performance.md`).",
        category="streaming",
        also_documented_in=("docs/streaming_performance.md",),
    ),
    # --- native layer -----------------------------------------------------
    EnvVar(
        "TPUML_LIB", "path", None,
        "Path to a prebuilt `libtpuml.so` (skips the cmake build).",
        category="native",
    ),
    EnvVar(
        "TPUML_BLAS_LIB", "path", None,
        "Path to a cblas shared object for the native layer (default: "
        "auto-discovered from the numpy/scipy wheels).",
        category="native",
    ),
    # --- kmeans -----------------------------------------------------------
    EnvVar(
        "TPUML_LANE_PAD", "int", None,
        "KMeans feature lane-padding multiple override (default: 128 on "
        "TPU, off elsewhere). Padding to the lane multiple is HBM-free on "
        "TPU and removes XLA's defensive copy of X around the Lloyd loop "
        "at `d % 128 != 0`. The zero columns are written on the device "
        "(`parallel/mesh.shard_rows(cols=...)` puts the frame at its own "
        "width into a zero buffer of the padded one); the host frame is "
        "never padded or copied.",
        minimum=0, category="kmeans",
    ),
    EnvVar(
        "TPUML_KMEANS_MATMUL_DTYPE", "choice", None,
        "Operand dtype of KMeans' two MXU contractions (f32 accumulation; "
        "the final cost pass always runs f32). Also an estimator kwarg "
        "`matmul_dtype`, which wins over the env.",
        choices=("float32", "bfloat16"), category="kmeans",
    ),
    # --- logreg -----------------------------------------------------------
    EnvVar(
        "TPUML_LOGREG_OBJECTIVE_DTYPE", "choice", "float32",
        "Dtype of the X copy the L-BFGS objective reads (statistics/"
        "params/accumulation stay f32; bf16 halves HBM bytes of the "
        "bandwidth-bound eval). Also an estimator kwarg `objective_dtype`, "
        "which wins over the env.",
        choices=("float32", "bfloat16"), category="logreg",
    ),
    # --- gang fit ---------------------------------------------------------
    EnvVar(
        "TPUML_GANG_FIT", "str", "off",
        "Gang-scheduled batched fitting of a fitMultiple/CrossValidator "
        "grid: `off` (default) keeps the sequential per-param loop, `auto` "
        "fits each static bucket of the grid as one batched device "
        "dispatch over the shared resident X, an integer pins the lane "
        "width (clamped to the HBM budget). Continuous params (regParam, "
        "elasticNetParam, tol) ride traced lane arrays; static params "
        "split dispatch groups (see `docs/gang_fit.md`).",
        category="gang-fit",
        also_documented_in=("docs/gang_fit.md",),
    ),
    EnvVar(
        "TPUML_GANG_FIT_BUDGET", "float", None,
        "HBM budget in bytes for gang-fit per-lane residents (default: a "
        "quarter of the device's reported memory, 4 GB fallback). The lane "
        "width is clamped so the batched objective's `(n, B, K)` "
        "temporaries fit.",
        exclusive_minimum=0, category="gang-fit",
        also_documented_in=("docs/gang_fit.md",),
    ),
    # --- random forest ----------------------------------------------------
    EnvVar(
        "TPUML_RF_ROWS_PER_TREE", "choice", "auto",
        "`all`: every tree sees the full dataset (one `all_gather` of the "
        "uint8 binned matrix); `local`: only its worker's partition (the "
        "reference's exact semantics); `auto`: gather when the gathered "
        "operands fit `TPUML_RF_GATHER_BUDGET_BYTES`.",
        choices=("auto", "all", "local"), category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_GATHER_BUDGET_BYTES", "float", 4e9,
        "Gathered-operand budget for `TPUML_RF_ROWS_PER_TREE=auto`.",
        exclusive_minimum=0, category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_SCATTER_EQ_FLOPS", "float", 5e5,
        "Histogram strategy cost-model constant: per-level crossover "
        "between MXU one-hot matmuls and scatter-adds; re-tune for other "
        "chip generations (see `docs/rf_performance.md`).",
        exclusive_minimum=0, category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_SEL_HBM_BUDGET", "float", None,
        "HBM budget in bytes for the fused-selection histogram path's "
        "residents (default: 3/4 of the device's reported memory, or a "
        "16 GB-class fallback).",
        exclusive_minimum=0, category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_FORCE_STRATEGY", "choice", "auto",
        "Histogram build strategy: `auto` = per-level cost model, "
        "`matmul`/`scatter` pin one formulation, `compact` forces the "
        "node-contiguous Pallas path on every eligible level (falls back "
        "to scatter where its lowering is not).",
        choices=("auto", "matmul", "scatter", "compact"),
        category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_CONTRACT_GATHER", "choice", "auto",
        "Subset-extraction strategy of the fused-selection path: `auto` "
        "(TPU at moderate widths), `on`, or `off`. Rides the static "
        "ForestConfig so it participates in the jit cache key.",
        choices=("auto", "on", "off"), category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_APPLY", "choice", "auto",
        "Forest inference path: `auto` takes the two-hop bin-space descent "
        "on a TPU (bounded compile; 2x the raw-threshold descent at 3000 "
        "columns) and the raw-threshold descent elsewhere; "
        "`legacy`/`bins`/`packed` pin one engine — the packed-forest "
        "lockstep engine only ever runs pinned: its compile grows faster "
        "than the tree count (see `docs/rf_performance.md`).",
        choices=("auto", "legacy", "bins", "packed"), category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_CHECK_FINITE", "bool", False,
        "Opt-in NaN/Inf screen on every transform batch at the serving "
        "boundary (a full host pass, so off by default). Fit always "
        "rejects non-finite features; without this flag, transform-time "
        "NaN silently routes to bin 0 in the bin-space descents.",
        category="random-forest",
    ),
    EnvVar(
        "TPUML_RF_TREE_BATCH", "str", "auto",
        "Trees advanced per batched level dispatch inside one worker: "
        "`auto` sizes the batch to the HBM budget (histogram tile scales "
        "xT), `off` pins the sequential per-tree builder, an integer pins "
        "a batch width (clamped to a divisor of the dispatch group). "
        "Batched and sequential builders are bit-identical at the same "
        "keys (see `docs/rf_performance.md`).",
        category="random-forest",
        also_documented_in=("docs/rf_performance.md",),
    ),
    EnvVar(
        "TPUML_RF_TREE_BATCH_BUDGET", "float", None,
        "HBM budget in bytes for the tree-batched builder's per-level "
        "residents under `TPUML_RF_TREE_BATCH=auto` (default: a quarter "
        "of the fused-selection budget, see `TPUML_RF_SEL_HBM_BUDGET`).",
        exclusive_minimum=0, category="random-forest",
    ),
    # --- gradient boosted trees ------------------------------------------
    EnvVar(
        "TPUML_GBT_ROUND_LOG_EVERY", "int", 0,
        "Log training-loss progress every N boosting rounds during "
        "GBTClassifier/GBTRegressor fit (0 = off; each probe is a host "
        "fetch of the margin vector).",
        minimum=0, category="gbt",
    ),
    # --- knn / umap -------------------------------------------------------
    EnvVar(
        "TPUML_KNN_TOPK", "choice", "auto",
        "Tile top-k implementation: `auto` = fused Pallas distance+top-k "
        "kernel when eligible, else the partial-reduce tile path; "
        "`partial` forces the XLA tile path with `lax.approx_max_k` "
        "(routes around the fused kernel); `sort` forces full `lax.top_k`.",
        choices=("auto", "sort", "partial"), category="knn",
    ),
    EnvVar(
        "TPUML_UMAP_GRAPH", "choice", "auto",
        "UMAP kNN-graph engine: `exact` pins the brute-force sweep; `ivf` "
        "requests the IVF-Flat approximate engine (warns + falls back to "
        "exact when the shape is infeasible); `auto` uses IVF only at or "
        "above `TPUML_ANN_GATE_ROWS` rows, so defaults stay bit-identical "
        "to the exact graph (see `docs/ann_performance.md`).",
        choices=("auto", "exact", "ivf"), category="umap",
        also_documented_in=(
            "docs/ann_performance.md", "docs/umap_performance.md",
        ),
    ),
    EnvVar(
        "TPUML_ANN_NLIST", "int", None,
        "IVF-Flat coarse-quantizer list count override (default: a "
        "`sqrt(n_rows)`-scaled heuristic). Applies to the "
        "`ApproximateNearestNeighbors` estimator (where `algoParams` wins "
        "over the env) and the `TPUML_UMAP_GRAPH=ivf` graph stage.",
        minimum=2, category="knn",
        also_documented_in=("docs/ann_performance.md",),
    ),
    EnvVar(
        "TPUML_ANN_NPROBE", "int", None,
        "IVF-Flat probe count override — lists scanned per query (default: "
        "`max(6, nlist/8)`, a ~12%-of-lists scan fraction). Recall/throughput "
        "knob; `algoParams` wins over the env on the estimator.",
        minimum=1, category="knn",
        also_documented_in=("docs/ann_performance.md",),
    ),
    EnvVar(
        "TPUML_ANN_GATE_ROWS", "int", 131072,
        "Row count at which `auto` graph/ANN dispatch starts preferring "
        "the IVF engine over the exact sweep (below it the index build + "
        "probe overhead beats nothing). Tests lower it to force the IVF "
        "path on small fixtures.",
        minimum=1, category="knn",
        also_documented_in=("docs/ann_performance.md",),
    ),
    EnvVar(
        "TPUML_UMAP_OPT", "choice", "auto",
        "UMAP SGD engine for fit and the transform refine pass: `auto` "
        "prefers the VMEM-resident Pallas engine when the lowering probe "
        "accepts the config, falling back to the jitted XLA epoch loop; "
        "`pallas` forces the kernel where eligible (warns + falls back "
        "when not); `xla` pins the epoch loop (see "
        "`docs/umap_performance.md`).",
        choices=("auto", "pallas", "xla"), category="umap",
    ),
    # --- serving (docs/serving.md) ----------------------------------------
    EnvVar(
        "TPUML_SERVE_BATCH_WINDOW_US", "int", 2000,
        "Micro-batching coalesce window in microseconds: after the first "
        "request of a batch arrives, the dispatcher keeps draining the "
        "queue for this long before padding and launching, trading p50 "
        "latency for batch fill. `0` dispatches every drain immediately "
        "(still coalescing whatever is already queued). Only read by an "
        "explicitly constructed `serving.ServingRuntime` — no serving "
        "thread or file exists otherwise.",
        minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_MAX_BUCKET_ROWS", "int", 2048,
        "Largest padded request-batch bucket, in rows. Coalesced rows "
        "are padded up to the next power of two and capped here, so the "
        "compiled-shape set per model is at most "
        "`log2(max_bucket_rows) - 2` programs; larger coalesced batches "
        "split across buckets. Rounded down to a power of two (>= 8).",
        minimum=8, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_HBM_BUDGET", "float", None,
        "Device-memory budget in bytes for the serving model registry's "
        "resident buffers (packed forests, projection/coefficient "
        "matrices, UMAP tables + IVF indexes). Loading past the budget "
        "evicts least-recently-used models first; a single model larger "
        "than the budget is rejected. Unset = no eviction. The running "
        "total is filed under the `serve_registry` site of the "
        "`hbm_budget_bytes`/`hbm_live_bytes` gauges when tracing is on.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_WARMUP", "bool", True,
        "Eager per-bucket warmup at registry load: compile every padded "
        "bucket shape of a model's transform program before the first "
        "request, so steady-state serving never pays a compile (the "
        "`retrace_storms == 0` contract). `0` warms lazily instead — "
        "the first request at each bucket runs under a per-bucket "
        "warmup span and eats the compile.",
        category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_DEFAULT_DEADLINE_MS", "float", None,
        "Default per-request deadline in milliseconds for "
        "`ServingRuntime.predict(..., deadline_ms=)` callers that pass "
        "none. A request whose deadline expires while queued is failed "
        "with a typed `DeadlineExceeded` *before* padding/dispatch, and "
        "admission sheds (`deadline_unmeetable`) when the estimated "
        "wait already exceeds the deadline. Unset = no deadline: "
        "requests wait indefinitely, exactly the pre-deadline behavior.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_QUEUE_LIMIT", "int", None,
        "Bound on queued (admitted, not yet dispatched) serving "
        "requests. Enqueues past the bound are rejected with a typed "
        "`Overloaded` (counted on `serve_shed_total{reason=queue_full}`)"
        " instead of growing the queue without limit. Unset = unbounded "
        "queue, the pre-admission behavior.",
        minimum=1, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_BREAKER_FAILS", "int", 0,
        "Consecutive dispatch failures that trip a model's circuit "
        "breaker from closed to open; while open, requests for that "
        "model fast-fail at admission (`serve_shed_total{reason="
        "breaker_open}`) and `/readyz` reports 503. `0` (default) "
        "disables the breaker entirely.",
        minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_SERVE_BREAKER_COOLDOWN_MS", "float", 1000.0,
        "How long an open circuit breaker blocks before moving to "
        "half-open and admitting a single probe request; the probe's "
        "outcome closes (success) or re-opens (failure) the breaker. "
        "Only read when `TPUML_SERVE_BREAKER_FAILS` > 0.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    # --- pod-scale serving router (serving/router.py, docs/serving.md) ----
    EnvVar(
        "TPUML_ROUTER_REPLICAS", "int", 2,
        "Default replica count for a `serving.Router()` constructed "
        "without an explicit replica list: the router builds this many "
        "in-process loopback `ServingRuntime` replicas (ranks 0..N-1). "
        "Only read by an explicitly constructed router — no router "
        "thread, replica, or metric series exists otherwise.",
        minimum=1, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_ROUTER_POLICY", "choice", "p2c",
        "Replica-picking policy of the serving router: `p2c` (default) "
        "scores two rotating candidates by EWMA-estimated wait and "
        "queue depth and takes the better (power-of-two-choices — "
        "near-least-loaded at O(2) probes); `round_robin` ignores load; "
        "`least_loaded` scores every replica on every request. All "
        "policies route around breaker-open and unhealthy replicas.",
        choices=("p2c", "round_robin", "least_loaded"),
        category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_ROUTER_BREAKER_FAILS", "int", 3,
        "Consecutive *dispatch-fault* failures (not typed sheds) that "
        "trip a replica's router-side circuit breaker; while open the "
        "replica is routed around, not queued behind, and re-probed "
        "after `TPUML_ROUTER_BREAKER_COOLDOWN_MS`. `0` disables the "
        "router breakers.",
        minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_ROUTER_BREAKER_COOLDOWN_MS", "float", 1000.0,
        "How long an open router-side replica breaker blocks before "
        "moving to half-open and admitting a single probe request. "
        "Only read when `TPUML_ROUTER_BREAKER_FAILS` > 0.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_ROUTER_REROUTES", "int", 1,
        "How many *additional* replicas the router tries when the "
        "picked replica sheds at admission (queue full, deadline "
        "unmeetable, draining). `0` = no rerouting: the first pick's "
        "shed is the caller's shed.",
        minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_REPLICA_RANK", "int", None,
        "Replica rank of a subprocess serving worker "
        "(`serving/_replica_worker.py`); set by the parent "
        "`SubprocessReplica` transport, never by hand. The worker's "
        "runtime rank-stamps its warmup spans and residency reports "
        "with this value.",
        minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    # --- continuous-training lifecycle (serving/lifecycle.py) -------------
    EnvVar(
        "TPUML_LIFECYCLE_REFRESH_MS", "float", 300000.0,
        "Default period between `RefreshDriver` re-fit cycles in "
        "milliseconds (5 minutes). Only read by an explicitly "
        "constructed driver — no driver object means no refresh "
        "thread, no scheduled fits, no metric series.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_LIFECYCLE_DRIFT_WINDOW", "int", 256,
        "Served output rows accumulated per drift-scoring window: the "
        "first full window freezes the reference histogram, every "
        "later one scores a PSI observation into `serve_drift_score`. "
        "Smaller windows detect faster but are noisier.",
        minimum=16, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_LIFECYCLE_DRIFT_BINS", "int", 16,
        "Histogram bins of the drift reference, placed at the first "
        "window's quantiles (equal-mass, so every bin starts at "
        "1/bins probability and the PSI epsilon floor is never the "
        "signal).",
        minimum=4, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_CANARY_FRACTION", "float", 0.125,
        "Fraction of a canaried model's admitted traffic mirrored to "
        "the candidate (deterministic request-counter picking, no "
        "RNG). Callers always receive the live version's output; the "
        "mirror only feeds scoring.",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_CANARY_MIN_REQUESTS", "int", 32,
        "Mirrored (live, shadow) pairs a canary must score before the "
        "promote-or-rollback verdict; an SLO-burn alert rolls back "
        "immediately without waiting for this count.",
        minimum=1, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_CANARY_MIN_SCORE", "float", 0.99,
        "Minimum shadow-vs-live agreement score (r2 for continuous "
        "outputs, accuracy for integral labels — scored through "
        "`evaluation.prediction_agreement`) for a canary to promote; "
        "anything under rolls back and opens the version breaker.",
        category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    EnvVar(
        "TPUML_CANARY_COOLDOWN_MS", "float", 60000.0,
        "How long a model's version breaker stays open after a canary "
        "rollback: further swap/canary attempts for that name raise a "
        "typed error until the cooldown passes (half-open then admits "
        "one probe attempt).",
        exclusive_minimum=0, category="serving",
        also_documented_in=("docs/serving.md",),
    ),
    # --- fit scheduler (docs/scheduler.md) --------------------------------
    EnvVar(
        "TPUML_SCHED_QUEUE_LIMIT", "int", None,
        "Bound on queued (admitted, not yet dispatched) fit jobs in a "
        "`runtime.FitScheduler`. Submits past the bound are rejected "
        "with a typed `Overloaded` (counted on "
        "`sched_shed_total{reason=queue_full}`) instead of growing the "
        "queue without limit. Unset = unbounded queue. Only read by an "
        "explicitly constructed scheduler — no thread or metric series "
        "exists otherwise.",
        minimum=1, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    EnvVar(
        "TPUML_SCHED_QUANTUM_MS", "float", None,
        "Device quantum for scheduled fits, in milliseconds. A fit "
        "whose quantum expires checkpoints at its next iteration "
        "boundary (via the `FitCheckpointer`, so `TPUML_CKPT_DIR` must "
        "be set for preemption to engage), yields the device, and is "
        "re-queued; the resumed dispatch continues from the committed "
        "iteration with the same-seed parity the segmented solvers "
        "guarantee. Unset = fits run to completion once dispatched.",
        exclusive_minimum=0, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    EnvVar(
        "TPUML_SCHED_BREAKER_FAILS", "int", 0,
        "Consecutive fit failures that trip a tenant's circuit breaker "
        "from closed to open; while open, that tenant's submits "
        "fast-fail at admission (`sched_shed_total{reason="
        "breaker_open}`). `0` (default) disables the breaker entirely.",
        minimum=0, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    EnvVar(
        "TPUML_SCHED_BREAKER_COOLDOWN_MS", "float", 1000.0,
        "How long an open per-tenant breaker blocks before moving to "
        "half-open and admitting a single probe fit; the probe's "
        "outcome closes (success) or re-opens (failure) the breaker. "
        "Only read when `TPUML_SCHED_BREAKER_FAILS` > 0.",
        exclusive_minimum=0, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    EnvVar(
        "TPUML_SCHED_AGING_MS", "float", 10000.0,
        "Aging horizon for deadline-free fit jobs: a job with no "
        "deadline is ordered as if due `aging_ms` after submit, so "
        "EDF ordering (and gang-bucket packing built on it) can never "
        "starve it behind a stream of deadline-bearing arrivals.",
        exclusive_minimum=0, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    EnvVar(
        "TPUML_SCHED_DEFAULT_DEADLINE_MS", "float", None,
        "Default per-job deadline in milliseconds for "
        "`FitScheduler.submit(..., deadline_ms=)` callers that pass "
        "none. A job whose deadline expires while queued is failed "
        "with a typed `DeadlineExceeded` before dispatch, and "
        "admission sheds (`deadline_unmeetable`) when the EWMA fit-"
        "time estimate says the deadline cannot be met. Unset = no "
        "deadline: jobs wait indefinitely.",
        exclusive_minimum=0, category="scheduler",
        also_documented_in=("docs/scheduler.md",),
    ),
    # --- CI / notebooks ---------------------------------------------------
    EnvVar(
        "TPUML_NB_CPU", "bool", False,
        "Pin the generated notebooks to the CPU backend when executing "
        "headless (exported by `ci/run_notebooks.py`); unset = default "
        "backend, i.e. the TPU.",
        category="ci",
    ),
    # --- resilience (docs/fault_tolerance.md) -----------------------------
    EnvVar(
        "TPUML_CKPT_DIR", "path", None,
        "Directory for periodic fit snapshots of the iterative solvers "
        "(streamed KMeans Lloyd, L-BFGS host loop, UMAP SGD); unset = "
        "checkpointing off. A refit with the same params/seed resumes "
        "from the last committed snapshot and matches an uninterrupted "
        "fit exactly.",
        category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    EnvVar(
        "TPUML_CKPT_EVERY", "int", 1,
        "Snapshot cadence in solver iterations (UMAP: epochs). Only read "
        "when `TPUML_CKPT_DIR` is set.",
        minimum=1, category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    EnvVar(
        "TPUML_RETRIES", "int", 0,
        "Retry budget for transient failures at the distributed bootstrap "
        "and host-to-device chunk staging (default 0 = single attempt). "
        "`RESOURCE_EXHAUSTED` staging errors additionally degrade by "
        "halving the chunk within the budget.",
        minimum=0, category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    EnvVar(
        "TPUML_BACKOFF_MS", "float", 100.0,
        "Base delay for the exponential-backoff-with-jitter retry "
        "schedule (doubles per attempt, capped at 30 s, equal jitter).",
        exclusive_minimum=0, category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    EnvVar(
        "TPUML_FAULT_SPEC", "str", "",
        "Deterministic fault injection for resilience testing: comma-"
        "separated `scope:point:index:action` entries (`ingest:chunk` / "
        "`sgd:epoch` / `gbt:round` / `init:connect` / `serve:admit` / "
        "`serve:dispatch` / `serve:transfer` / `sched:admit` / "
        "`sched:preempt` / `sched:resume` / `sched:dispatch` sites; "
        "`raise`/`preempt`/`oom` actions; 0-based per-site hit index, "
        "each entry fires once).",
        category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    EnvVar(
        "TPUML_CV_FAILFAST", "bool", True,
        "`1` (reference semantics): any failed fold/param fit aborts "
        "`CrossValidator.fit`. `0` records the failed combo as worst-"
        "metric (±inf in `avgMetrics`) and keeps searching; raises only "
        "if every combo failed.",
        category="resilience",
        also_documented_in=("docs/fault_tolerance.md",),
    ),
    # --- observability (docs/observability.md) ----------------------------
    EnvVar(
        "TPUML_TRACE", "path", None,
        "Directory for structured telemetry output: a Chrome-trace/"
        "Perfetto JSON shard (`trace-r<rank>-<pid>.json`), a JSONL span "
        "event log (`events-r<rank>-<pid>.jsonl`), and Prometheus/JSON "
        "metric dumps on request — process-index-tagged so multi-host "
        "runs sharing one directory stay disjoint "
        "(`scripts/merge_traces.py` merges the shards). Unset (the "
        "default) keeps the whole telemetry path inert: no files, no "
        "span allocation, outputs bit-identical.",
        category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_TELEMETRY_RETRACE_LIMIT", "int", 16,
        "Retrace-watchdog threshold: warn once per span site when XLA "
        "compilations attributed to it exceed this count in steady state "
        "(the runtime enforcement of lint rule TPU003). `0` disables the "
        "watchdog. The listener installs when `TPUML_TRACE` is set or "
        "this variable is set explicitly.",
        minimum=0, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_TELEMETRY_RESERVOIR", "int", 512,
        "Bound of each histogram metric's observation ring (a "
        "deterministic last-N window feeding the exported quantiles); "
        "running count/sum/min/max are exact regardless of the bound.",
        minimum=1, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    # --- live operations plane (runtime/opsplane.py) ----------------------
    EnvVar(
        "TPUML_OPS_PORT", "int", None,
        "Port of the in-process ops HTTP server (`/metrics`, `/healthz`, "
        "`/readyz`, `/statusz`, `/flight`); `0` binds an ephemeral port. "
        "Setting it also activates the flight recorder and the SLO "
        "burn-rate evaluator. Unset (the default) is fully inert: no "
        "listening socket, no background thread, no files.",
        minimum=0, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_OPS_HOST", "str", "127.0.0.1",
        "Bind address of the ops HTTP server. Loopback by default — the "
        "endpoints expose span names and model names, so widening the "
        "bind is an explicit decision. Only read when `TPUML_OPS_PORT` "
        "is set.",
        category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_FLIGHT_DIR", "path", None,
        "Directory for flight-recorder crash dumps "
        "(`flight-r<rank>-<pid>.json`, rank-tagged like trace shards): "
        "written on SIGTERM, at interpreter exit, and on the first SLO "
        "burn alert. Setting it activates the flight recorder even "
        "without `TPUML_OPS_PORT`. Unset = dumps fall back to the "
        "`TPUML_TRACE` directory, or are skipped entirely when neither "
        "is set (the `/flight` endpoint still serves the in-memory "
        "ring).",
        category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_FLIGHT_EVENTS", "int", 2048,
        "Bound of the flight recorder's in-memory ring: the last N "
        "completed spans and instant events kept for `/flight` and the "
        "crash-dump paths (a deterministic last-N window, like the "
        "histogram reservoir). Only read while the recorder is active.",
        minimum=1, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_SLO_EVAL_MS", "int", 1000,
        "Tick period of the SLO burn-rate evaluator in milliseconds: "
        "each tick snapshots the metric registry and scores every "
        "`runtime/slo.py` catalog entry over its short/long burn "
        "windows. Only read while the ops plane is active.",
        minimum=10, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_SLO_BURN_THRESHOLD", "float", 1.0,
        "Burn-rate multiple at which an SLO alert fires: alert when "
        "BOTH the short and long windows burn error budget at or above "
        "this rate (1.0 = exactly exhausting the budget). Raising it "
        "tolerates faster burns; only read while the ops plane is "
        "active.",
        exclusive_minimum=0, category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    EnvVar(
        "TPUML_LOCK_WITNESS", "choice", "off",
        "Runtime lock-order witness (`runtime/lockwitness.py`): `1` "
        "(alias `count`) makes every cataloged lock constructed after "
        "this point an instrumented wrapper that checks the "
        "`runtime/lockspec.py` rank hierarchy at each acquire, counts "
        "violations in `lock_order_violations_total`, and exports "
        "per-lock `lock_hold_ms`/`lock_wait_ms` histograms; `raise` "
        "escalates the first occurrence of each violation to an "
        "exception. `off` (the default) constructs raw `threading` "
        "primitives — zero overhead, no metric series.",
        choices=("off", "1", "count", "raise"), category="observability",
        also_documented_in=("docs/observability.md",),
    ),
    # --- measured autotuner (runtime/autotune.py) -------------------------
    EnvVar(
        "TPUML_AUTOTUNE", "choice", "off",
        "Measured knob autotuner (`runtime/autotune.py`): `off` (the "
        "default) disables every cache read and probe — resolvers use "
        "their static heuristics and outputs are bit-identical to an "
        "untuned run; `on` consults the shape-keyed tuning cache before "
        "each `auto` resolver's heuristic and probes candidate values "
        "with short dispatches of the real jitted work on a miss; "
        "`force` re-probes even over an existing cache entry "
        "(overwriting stale winners). See `docs/autotune.md` for the "
        "search strategy and fitness definition.",
        choices=("off", "on", "force"), category="autotune",
        also_documented_in=("docs/autotune.md",),
    ),
    EnvVar(
        "TPUML_AUTOTUNE_CACHE", "path", None,
        "Directory of the persistent tuning cache "
        "(`autotune-cache.json`, atomic tmp+rename, written by rank 0 "
        "only). Unset with `TPUML_AUTOTUNE=on` keeps tuned winners "
        "in-process (probes still run; nothing is persisted). Corrupt "
        "or truncated files are tolerated: the tuner warns once and "
        "falls back to heuristics.",
        category="autotune",
        also_documented_in=("docs/autotune.md",),
    ),
    EnvVar(
        "TPUML_AUTOTUNE_BUDGET_MS", "float", 2000,
        "Wall-clock probe budget per (knob, shape) search, in "
        "milliseconds. The successive-halving search stops starting new "
        "measurements once the budget is spent and keeps the best "
        "candidate measured so far (the heuristic default is always "
        "measured first, so a truncated search can never do worse than "
        "no tuner).",
        exclusive_minimum=0, category="autotune",
        also_documented_in=("docs/autotune.md",),
    ),
)


def registered_names() -> Tuple[str, ...]:
    return tuple(SPEC)


def parse(name: str, raw: Optional[str]) -> Any:
    """Parse+validate a raw string for ``name`` (None/"" -> default)."""
    try:
        var = SPEC[name]
    except KeyError:
        raise EnvSpecError(
            f"{name} is not a registered TPUML_* variable "
            f"(spark_rapids_ml_tpu/runtime/envspec.py is the registry)"
        ) from None
    if raw is None or raw == "":
        return var.default

    if var.type in ("str", "path"):
        return raw
    if var.type == "choice":
        v = raw.strip().lower()
        assert var.choices is not None
        if v not in var.choices:
            raise EnvSpecError(f"{name}={raw!r} must be {var.domain()}")
        return v
    if var.type == "bool":
        v = raw.strip().lower()
        if v in _TRUE:
            return True
        if v in _FALSE:
            return False
        raise EnvSpecError(f"{name}={raw!r} must be {var.domain()}")
    # numeric
    try:
        num: Any = int(raw) if var.type == "int" else float(raw)
    except ValueError:
        raise EnvSpecError(
            f"{name}={raw!r} is not {var.domain()}"
        ) from None
    if var.minimum is not None and num < var.minimum:
        raise EnvSpecError(f"{name}={raw!r} must be >= {var.minimum:g}")
    if var.exclusive_minimum is not None and num <= var.exclusive_minimum:
        raise EnvSpecError(
            f"{name}={raw!r} must be > {var.exclusive_minimum:g}"
        )
    return num


def get(name: str, *, env: Optional[Mapping[str, str]] = None) -> Any:
    """The parsed, validated value of registered variable ``name``.

    Reads the live environment on every call (tests flip these between
    fits); callers that need trace-cache safety resolve once outside jit
    or at module import and pass the value through static args — see
    `docs/static_analysis.md` (TPU003).
    """
    source = os.environ if env is None else env
    return parse(name, source.get(name))


def get_raw(name: str) -> Optional[str]:
    """Raw string value (no parsing); None when unset. ``name`` must be
    registered — unregistered names raise like :func:`get`."""
    if name not in SPEC:
        return parse(name, None)  # raises EnvSpecError naming the registry
    return os.environ.get(name)


def is_set(name: str) -> bool:
    """True when ``name`` is present AND non-empty in the environment."""
    if name not in SPEC:
        parse(name, None)  # raises EnvSpecError naming the registry
    return bool(os.environ.get(name))


# --- docs table generation (scripts/gen_config_docs.py + TPU002) ----------

TABLE_BEGIN = "<!-- tpuml-envspec:begin (generated by scripts/gen_config_docs.py — edit envspec.py, not this table) -->"
TABLE_END = "<!-- tpuml-envspec:end -->"


def doc_table_lines() -> Tuple[str, ...]:
    """The generated markdown table for ``docs/configuration.md``,
    including the begin/end markers TPU002 anchors its drift check on."""
    rows = [
        TABLE_BEGIN,
        "| variable | type | default | meaning |",
        "|---|---|---|---|",
    ]
    for var in SPEC.values():
        typ = var.type if var.type != "choice" else "|".join(var.choices or ())
        rows.append(
            f"| `{var.name}` | {typ} | {var.default_repr()} | {var.doc} |"
        )
    rows.append(TABLE_END)
    return tuple(rows)
