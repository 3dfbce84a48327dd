"""RandomForest — Spark ML drop-ins, TPU-native histogram forest builder.

Reference: ``/root/reference/python/src/spark_rapids_ml/tree.py`` (614 LoC
shared base driving per-worker cuML RandomForest fits, treelite model
allGather at :319-366), ``classification.py:298-648`` (classifier) and
``regression.py:787-1068`` (regressor). Param-mapping parity with
``tree.py:66-110``: ``maxBins→n_bins``, ``maxDepth→max_depth``,
``numTrees→n_estimators``, ``impurity→split_criterion``,
``featureSubsetStrategy→max_features``, ``bootstrap→bootstrap``,
``seed→random_state``, ``minInstancesPerNode→min_samples_leaf``;
``subsamplingRate``/``maxMemoryInMB``/``cacheNodeIds``/``checkpointInterval``/
``minWeightFractionPerNode`` accepted-but-ignored; ``weightCol``/``leafCol``
unsupported (raise). (Improvement over the reference: ``minInfoGain`` is
honored rather than ignored.)

The compute path is ``ops/tree_kernels.py``: quantize → level-wise histogram
splits, trees split across mesh devices exactly like the reference splits
trees across workers (``tree.py:256-267``), zero collectives during growth.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..parallel.layout import LAYOUT

from ..core import (
    FitFunc,
    FitInputs,
    _TpuEstimatorSupervised,
    _TpuModel,
    batch_to_device,
    output_to_host,
)
from ..data.dataframe import DataFrame
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasSeed,
    TypeConverters,
    _mk,
)
from ..parallel.mesh import DP_AXIS, fetch_global
from ..ops.tree_kernels import (
    resolve_contract_gather,
    resolve_hist_strategy,
    resolve_tree_batch,
    ForestConfig,
    binize,
    build_forest,
    max_nodes,
    next_pow2,
    plan_levels,
    plan_reads,
    quantile_edges,
    rf_classify,
    rf_regress,
)
from ..runtime import counters, envspec, telemetry
from ..runtime.checkpoint import FitCheckpointer, array_digest
from ..runtime.faults import fault_site
from ..runtime.scheduler import preempt_point

_MAX_SUPPORTED_DEPTH = 18  # full binary layout: 2^(d+1)-1 nodes per tree


def _str_or_numerical(value: str) -> Union[str, float, int]:
    """Parse featureSubsetStrategy strings that encode numbers (reference
    ``utils._str_or_numerical``, used at ``tree.py:94-105``)."""
    try:
        return int(value)
    except ValueError:
        pass
    try:
        return float(value)
    except ValueError:
        pass
    return value


class _RandomForestClass:
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # reference ``tree.py:66-91``
        return {
            "maxBins": "n_bins",
            "maxDepth": "max_depth",
            "numTrees": "n_estimators",
            "impurity": "split_criterion",
            "featureSubsetStrategy": "max_features",
            "bootstrap": "bootstrap",
            "seed": "random_state",
            "minInstancesPerNode": "min_samples_leaf",
            "minInfoGain": "min_impurity_decrease",
            "maxMemoryInMB": "",
            "cacheNodeIds": "",
            "checkpointInterval": "",
            "subsamplingRate": "",
            "minWeightFractionPerNode": "",
            # weightCol stays unmapped (raise-on-set): see the guard note
            # at the ``weightCol`` Param declaration below before wiring
            # real-valued row weights through
            "weightCol": None,
            "leafCol": None,
        }

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        # reference ``tree.py:93-110``
        def _tree_mapping(v: Any) -> Union[None, str, float, int]:
            if isinstance(v, (int, float)):
                return v
            maybe = _str_or_numerical(str(v))
            if isinstance(maybe, (int, float)):
                return maybe
            mapping: Dict[str, Union[str, float]] = {
                "onethird": 1.0 / 3.0,
                "all": 1.0,
                "auto": "auto",
                "sqrt": "sqrt",
                "log2": "log2",
            }
            if maybe not in mapping:
                raise ValueError(f"Unsupported featureSubsetStrategy: {v!r}")
            return mapping[maybe]

        return {"max_features": _tree_mapping}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_estimators": 100,
            "max_depth": 16,
            "n_bins": 128,
            "max_features": "auto",
            "bootstrap": True,
            "min_samples_leaf": 1,
            "min_samples_split": 2,
            "min_impurity_decrease": 0.0,
            "random_state": None,
        }


class _RandomForestParams(
    HasFeaturesCol, HasFeaturesCols, HasLabelCol, HasPredictionCol, HasSeed
):
    numTrees = _mk("numTrees", "number of trees", TypeConverters.toInt)
    maxDepth = _mk("maxDepth", "maximum tree depth", TypeConverters.toInt)
    maxBins = _mk("maxBins", "max histogram bins per feature", TypeConverters.toInt)
    impurity = _mk("impurity", "split criterion", TypeConverters.toString)
    featureSubsetStrategy = _mk(
        "featureSubsetStrategy",
        "features considered per split: auto|all|sqrt|log2|onethird|fraction|n",
        TypeConverters.toString,
    )
    bootstrap = _mk("bootstrap", "bootstrap-sample rows per tree", TypeConverters.toBoolean)
    minInstancesPerNode = _mk(
        "minInstancesPerNode", "min rows per child node", TypeConverters.toInt
    )
    minInfoGain = _mk("minInfoGain", "min gain for a split", TypeConverters.toFloat)
    subsamplingRate = _mk("subsamplingRate", "row subsample rate (ignored)", TypeConverters.toFloat)
    maxMemoryInMB = _mk("maxMemoryInMB", "memory hint (ignored)", TypeConverters.toInt)
    cacheNodeIds = _mk("cacheNodeIds", "node-id caching (ignored)", TypeConverters.toBoolean)
    checkpointInterval = _mk("checkpointInterval", "checkpointing (ignored)", TypeConverters.toInt)
    minWeightFractionPerNode = _mk(
        "minWeightFractionPerNode", "min weight fraction (ignored)", TypeConverters.toFloat
    )
    # GUARD: keep weightCol unsupported until the histogram reduction is
    # re-audited. The builder's cumsum boundary-diff strategy
    # (``ops/tree_kernels.py`` ``_use_cumsum``) is gated on stats staying
    # EXACT in f32 prefix sums, which holds because bootstrap row weights
    # are small integers (Poisson, mean 1) — count columns stay integers
    # below the 2^24 mantissa bound. Arbitrary real-valued weights break
    # that exactness argument; wiring weightCol through would need the
    # cumsum gate forced off (or a weight-scale analysis) first.
    weightCol = _mk("weightCol", "weight column (unsupported)", TypeConverters.toString)
    leafCol = _mk("leafCol", "leaf index column (unsupported)", TypeConverters.toString)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            numTrees=20,
            maxDepth=5,
            maxBins=32,
            featureSubsetStrategy="auto",
            bootstrap=True,
            minInstancesPerNode=1,
            minInfoGain=0.0,
            subsamplingRate=1.0,
            seed=0,
        )

    def getNumTrees(self) -> int:
        return self.getOrDefault("numTrees")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")

    def getMaxBins(self) -> int:
        return self.getOrDefault("maxBins")

    def getImpurity(self) -> str:
        return self.getOrDefault("impurity")

    def getFeatureSubsetStrategy(self) -> str:
        return self.getOrDefault("featureSubsetStrategy")


def _resolve_k_features(
    max_features: Union[str, float, int], d: int, is_classification: bool
) -> int:
    """Resolve the per-node feature-sample count (cuML max_features
    semantics; 'auto' follows Spark: sqrt for classification, 1/3 for
    regression)."""
    if max_features == "auto":
        k = math.ceil(math.sqrt(d)) if is_classification else math.ceil(d / 3.0)
    elif max_features == "sqrt":
        k = math.ceil(math.sqrt(d))
    elif max_features == "log2":
        k = math.ceil(math.log2(max(d, 2)))
    elif isinstance(max_features, int):
        k = max_features
    elif isinstance(max_features, float):
        k = math.ceil(max_features * d)
    else:
        raise ValueError(f"Unsupported max_features: {max_features!r}")
    return max(1, min(int(k), d))


def _quantize_features(inputs: "FitInputs", n_bins: int, d_pad: int, algo: str):
    """Device quantile sketch -> device binize, shared by the forest and
    boosting fits. The sketch sorts a sample of the VALID rows on the device
    (``ops.tree_kernels.quantile_edges``: runs of consecutive rows spread
    over the whole frame, so a sorted dataset still gives even bins, and
    mask-aware so padding rows never enter it); only the edges and one flag
    cross to the host."""
    with telemetry.span(
        "forest.sketch", rows=int(min(inputs.n_rows, inputs.X.shape[0]))
    ) as sp:
        edges, finite = quantile_edges(inputs.X, inputs.mask, n_bins=n_bins)
        edges_np = fetch_global(edges, inputs.mesh)
        # Input contract: features must be FINITE. binize routes NaN to bin 0
        # (compare-count semantics; see its docstring) where searchsorted
        # would route it to the top bin — consistent between fit and
        # transform, but silently different from engines that impute. The
        # sketch screens its sample on the device;
        # TPUML_RF_CHECK_FINITE=1 extends the check to every transform batch.
        ok = bool(fetch_global(finite, inputs.mesh))
        sp.set_attr(bytes=int(edges_np.nbytes) + 1)
    if not ok:
        raise ValueError(
            f"{algo} features contain NaN/Inf; clean or "
            "impute before fit (binize would route non-finite "
            "values to bin 0)"
        )
    with telemetry.span("forest.binize", cols=int(d_pad)):
        bins = binize(inputs.X, jnp.asarray(edges_np), d_pad=d_pad)
    return edges_np, bins


def _bins_width(d: int, subset: bool) -> int:
    """Columns of the binned matrix. The fused-selection histogram kernel
    (a per-node feature subset at more than 1024 columns) reads whole rows
    of it at every level, so there the width is the lane multiple (3000 ->
    3072: a quarter less to read, gather and keep than 4096); every other
    strategy chunks the columns by powers of two and keeps the power of two."""
    lanes = -(-d // 128) * 128
    return lanes if subset and lanes > 1024 else next_pow2(d)


def _dispatch_group(t_local: int) -> int:
    """Trees grown by one dispatched program: ONE size for the whole fit, so
    a fit compiles its growth once (a last group of another size cost a
    second Mosaic compile of minutes at 500,000 x 3072). Up to 8 trees, all
    of them; beyond, the largest of 8..4 that divides the count (50 -> 5), or
    8 with the last group filled by trees that are grown and dropped. It
    bounds a dispatch to seconds (a multi-minute single dispatch can outlive
    remote-runtime health checks) while the compile is shared."""
    if t_local <= 8:
        return t_local
    return next((g for g in (8, 7, 6, 5, 4) if t_local % g == 0), 8)


class _RandomForestEstimator(_RandomForestClass, _TpuEstimatorSupervised, _RandomForestParams):
    """Shared fit machinery (reference ``_RandomForestEstimator``,
    ``tree.py:230-420``)."""

    _is_classification = False
    _default_impurity = "variance"

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimatorSupervised.__init__(self)
        _RandomForestParams.__init__(self)
        self._setDefault(impurity=self._default_impurity)
        self._set_params(**kwargs)

    def setNumTrees(self, value: int) -> "_RandomForestEstimator":
        self._set_params(numTrees=value)
        return self

    def setMaxDepth(self, value: int) -> "_RandomForestEstimator":
        self._set_params(maxDepth=value)
        return self

    def setMaxBins(self, value: int) -> "_RandomForestEstimator":
        self._set_params(maxBins=value)
        return self

    def setImpurity(self, value: str) -> "_RandomForestEstimator":
        self._set_params(impurity=value)
        return self

    def setFeatureSubsetStrategy(self, value: str) -> "_RandomForestEstimator":
        self._set_params(featureSubsetStrategy=value)
        return self

    def setSeed(self, value: int) -> "_RandomForestEstimator":
        self._set_params(seed=value)
        return self

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # reference fits all param maps inside one pass (``tree.py:368-400``)
        return True

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        # reference ``classification.py:505-513`` / ``regression.py:972-980``
        from ..evaluation import (
            MulticlassClassificationEvaluator,
            RegressionEvaluator,
        )

        if self._is_classification:
            return isinstance(evaluator, MulticlassClassificationEvaluator)
        return isinstance(evaluator, RegressionEvaluator)

    # -- label handling ----------------------------------------------------
    def _process_labels(self, y_host: np.ndarray) -> int:
        """Returns n_stats (classifier: validates integer labels, returns
        n_classes; regressor: 3 moment slots)."""
        raise NotImplementedError

    def _label_stats(self, y: jax.Array, n_stats: int) -> jax.Array:
        """Device-side per-row sufficient-stat vectors from labels."""
        raise NotImplementedError

    def _impurity_name(self, params: Dict[str, Any]) -> str:
        raise NotImplementedError

    # -- fit ---------------------------------------------------------------
    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        label_col = self.getOrDefault("labelCol")
        y_host_raw = np.asarray(dataset.column(label_col))
        n_stats = self._process_labels(y_host_raw)
        is_classification = self._is_classification

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            max_depth = int(params["max_depth"])
            if max_depth > _MAX_SUPPORTED_DEPTH:
                raise ValueError(
                    f"maxDepth={max_depth} exceeds supported depth "
                    f"{_MAX_SUPPORTED_DEPTH} (full binary node layout)"
                )
            n_trees = int(params["n_estimators"])
            if n_trees < 1:
                raise ValueError("numTrees must be >= 1")
            n_bins = int(min(params["n_bins"], max(2, inputs.n_rows)))
            if n_bins > 256:
                # uint8 bin storage; quantile histograms gain nothing past 256
                self.logger.warning("maxBins=%d clamped to 256", n_bins)
                n_bins = 256
            d = inputs.n_features
            seed = int(params.get("random_state") or 0)
            k_features = _resolve_k_features(
                params["max_features"], d, is_classification
            )
            d_pad = _bins_width(d, k_features < d)

            # 1) quantize features (device quantile sketch -> device binize)
            edges_np, bins = _quantize_features(
                inputs, n_bins, d_pad, "RandomForest"
            )

            # 2) per-row sufficient stats
            stats = self._label_stats(inputs.y, n_stats)

            # 3) per-device tree split (reference ``tree.py:256-267``), in
            # dispatch groups of one size: tree t's key is split(key, T)[t]
            # whatever the grouping; the trees that fill the last group reuse
            # the first key and are dropped
            n_dp = inputs.mesh.shape[DP_AXIS]
            t_local = -(-n_trees // n_dp)
            group = _dispatch_group(t_local)
            n_groups = -(-t_local // group)
            keys_np = np.asarray(
                jax.random.split(jax.random.PRNGKey(seed), n_dp * t_local)
            ).reshape(n_dp, t_local, 2)
            fill = n_groups * group - t_local
            if fill:
                keys_np = np.concatenate(
                    [keys_np, np.repeat(keys_np[:, :1], fill, axis=1)], axis=1
                )
            # make_array_from_callback: each process materializes only its
            # addressable shards (device_put of a multi-host-sharded host
            # array is not possible)
            keys = jax.make_array_from_callback(
                keys_np.shape,
                NamedSharding(inputs.mesh, LAYOUT.rows()),
                lambda idx: keys_np[idx],
            )

            cfg = ForestConfig(
                max_depth=max_depth,
                n_bins=n_bins,
                n_features=d,
                n_stats=n_stats,
                impurity=self._impurity_name(params),
                k_features=k_features,
                min_samples_leaf=int(params["min_samples_leaf"]),
                min_info_gain=float(params.get("min_impurity_decrease", 0.0) or 0.0),
                min_samples_split=int(params.get("min_samples_split", 2)),
                bootstrap=bool(params["bootstrap"]),
                hist_strategy=resolve_hist_strategy(),
                contract_gather=resolve_contract_gather(),
                # X stays on the device through the growth (the frame is the
                # caller's, and a later lane of a fitMultiple bins it again)
                held_bytes=int(inputs.X.nbytes // inputs.mesh.devices.size),
            )
            # rows-per-tree mode: "all" gathers the binned matrix to every
            # device (quality independent of worker count — the TPU-first
            # upgrade over the reference's partition-local trees), "local"
            # keeps the reference's exact per-worker semantics, "auto"
            # gathers when the gathered operands fit a memory budget
            mode = envspec.get("TPUML_RF_ROWS_PER_TREE")
            n_pad_global = bins.shape[0]
            gathered_bytes = n_pad_global * (
                d_pad + n_stats * stats.dtype.itemsize + 4
            )
            budget = float(envspec.get("TPUML_RF_GATHER_BUDGET_BYTES"))
            gather = n_dp > 1 and (
                mode == "all" or (mode == "auto" and gathered_bytes <= budget)
            )
            # tree-batched growth (TPUML_RF_TREE_BATCH): B trees advance
            # one level per dispatch, bit-identical to sequential at the
            # same keys — the budget sees the rows each tree actually
            # trains on (gathered vs local shard) and the width it reads
            rows_per_tree = n_pad_global if gather else n_pad_global // n_dp
            tree_batch = resolve_tree_batch(group, cfg, rows_per_tree, d_pad)
            strategies, declined = plan_levels(
                rows_per_tree, d_pad, cfg, stats.dtype
            )
            hist_cols, hist_calls = plan_reads(
                rows_per_tree, d_pad, cfg, stats.dtype
            )
            # per key: list of host arrays shaped (n_dp, group, ...)
            pieces: Dict[str, List[np.ndarray]] = {}
            # the spans every fit carries (PERF.md section 3): the launch
            # names the program whose runs the device trace is read for, the
            # fetch closes when the last group's tables are on the host
            with telemetry.span(
                "solver.launch",
                program="build_forest",
                trees=n_trees,
                group=group,
                groups=n_groups,
                tree_batch=tree_batch,
                cols=int(d_pad),
            ):
                pass
            with telemetry.span("solver.fetch", groups=n_groups):
                for g in range(n_groups):
                    with telemetry.span(
                        "forest.grow_group",
                        group=g,
                        trees=group,
                        tree_batch=tree_batch,
                        hist_strategy=cfg.hist_strategy,
                        gather=gather,
                        strategy=strategies,
                        levels_declined=len(declined),
                        **({"declined": str(declined)} if declined else {}),
                        # what a level's histogram read: bins columns a live
                        # row, kernel calls a chunk of live rows
                        hist_cols=hist_cols,
                        hist_calls=hist_calls,
                    ) as grow:
                        outg = jax.block_until_ready(
                            build_forest(
                                bins, inputs.mask, stats,
                                keys[:, g * group : (g + 1) * group],
                                mesh=inputs.mesh, cfg=cfg, gather=gather,
                                tree_batch=tree_batch,
                            )
                        )
                        # the program has ended: the fetch waits on no device
                        # work, and the group's live-row counts come with its
                        # tables
                        with telemetry.span("forest.fetch_group", group=g):
                            for k, a in outg.items():
                                h = fetch_global(a, inputs.mesh)
                                pieces.setdefault(k, []).append(
                                    h.reshape(n_dp, group, *h.shape[1:])
                                )
                        live = pieces["live_rows"][-1]  # (n_dp, group, levels)
                        grow.set_attr(
                            # rows the group's levels worked on (positive
                            # weight, in an OPEN node of the level), over
                            # every row on every level of every tree
                            live_share=float(
                                live.sum() / max(1, live.size * rows_per_tree)
                            ),
                            live_rows_by_level=live.mean(axis=(0, 1)).tolist(),
                            # nodes a tree closed when it made them: their
                            # rows left the levels' work one level sooner
                            closed_at_birth=float(
                                pieces["closed_at_birth"][-1].mean()
                            ),
                        )

            # interleave device-major -> tree-major so the slice to n_trees
            # takes trees evenly from every device
            def _gather(key: str) -> np.ndarray:
                # (n_dp, t_local, ...): the trees that filled the last group go
                a = np.concatenate(pieces[key], axis=1)[:, :t_local]
                return np.swapaxes(a, 0, 1).reshape(
                    -1, *a.shape[2:]
                )[:n_trees]

            with telemetry.span("forest.assemble", trees=n_trees):
                feat = _gather("feature")
                thr_bin = _gather("threshold_bin")
                leaf_stats = _gather("leaf_stats")
                gains = _gather("gain")

                # bin thresholds -> raw feature-space values (x >= thr -> right)
                thr = np.where(
                    feat >= 0,
                    edges_np[np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, n_bins - 2)],
                    0.0,
                ).astype(np.float32)

            return {
                "features": feat.astype(np.int32),
                "thresholds": thr,
                "leaf_stats": leaf_stats.astype(np.float32),
                "gains": gains.astype(np.float32),
                "n_classes": n_stats if is_classification else 0,
                "num_features": d,
                # bin-space tables for the two-hop descent (inference):
                # x >= edges[f, b] <=> bin(x) > b, the exact training-side
                # routing rule, so bin-space transform matches the raw
                # thresholds bit-for-bit. Absent in pre-round-5 saves —
                # loaders fall back to the raw-threshold descent. A node that
                # does not split holds 0 (as ``thresholds`` holds 0.0): every
                # reader looks at a split node's entry only.
                "threshold_bins": thr_bin.astype(np.int32),
                "bin_edges": edges_np.astype(np.float32),
            }

        return _fit


class _ForestModelBase(_TpuModel):
    """Shared fitted-forest surface: node-table accessors, structure
    introspection, and the three-engine transform dispatch
    (packed lockstep > bin-space descent > raw-threshold descent).

    RandomForest and GBT models both ride this base — the engines only
    need ``features``/``threshold_bins``/``bin_edges`` tables plus a
    per-node payload, which subclasses supply (leaf vote distributions /
    means for the forest, margin contributions for boosting)."""

    # -- forest structure --------------------------------------------------
    @property
    def _features_arr(self) -> np.ndarray:
        return np.asarray(self._model_attributes["features"])

    @property
    def _thresholds_arr(self) -> np.ndarray:
        return np.asarray(self._model_attributes["thresholds"])

    @property
    def _leaf_stats_arr(self) -> np.ndarray:
        return np.asarray(self._model_attributes["leaf_stats"])

    @property
    def _gains_arr(self) -> np.ndarray:
        return np.asarray(self._model_attributes["gains"])

    @property
    def _max_depth_built(self) -> int:
        m = self._features_arr.shape[1]
        return int(math.log2(m + 1)) - 1

    def _apply_mode(self) -> str:
        """Validated transform-engine selector. TPUML_RF_APPLY=legacy
        forces the raw-threshold descent, =bins the per-tree bin-space
        descent (incl. CPU, for parity tests), =packed the packed-forest
        lockstep engine (falls back down the chain if its kernel cannot
        lower); auto takes bins on a TPU and legacy elsewhere."""
        return str(envspec.get("TPUML_RF_APPLY"))

    def _bins_apply_ready(self, mode: Optional[str] = None) -> bool:
        """True when transform can use the bin-space descents: the model
        carries its bin tables (round-5+ fits) and the built depth fits
        the two-hop split (k1 <= 8). ``mode`` overrides the env-resolved
        selector (parity tests pin an explicit engine)."""
        mode = self._apply_mode() if mode is None else mode
        if mode == "legacy":
            return False
        has = (
            self._model_attributes.get("threshold_bins") is not None
            and self._model_attributes.get("bin_edges") is not None
        )
        ok = has and self._max_depth_built <= 14
        if mode in ("bins", "packed"):
            return ok
        return ok and jax.default_backend() == "tpu"

    def _packed_apply_ready(self, mode: Optional[str] = None) -> bool:
        """True when transform can use the packed-forest engine: bin
        tables present AND the lockstep traversal kernel lowers for this
        forest shape (or the forest is shallow enough that hop-1 alone
        reaches every leaf — no kernel needed)."""
        mode = self._apply_mode() if mode is None else mode
        # only where pinned: the traversal kernel unrolls every tree and its
        # compile grows faster than the tree count (80 s at 8 trees, not
        # finished at 56), where the bins engine compiles in 10 s whatever
        # the forest and transformed rf_dbx's 500,000 x 3000 rows through 8
        # trees in 1.03 s (the raw-threshold descent: 2.15 s; PERF.md, PR 35)
        if mode != "packed" or not self._bins_apply_ready(mode):
            return False
        from ..ops.rf_pallas import packed_traverse_ok

        pf = self._ensure_packed()
        if pf.k2 == 0:
            return True
        d = int(np.asarray(self._model_attributes["bin_edges"]).shape[0])
        words = -(-d // 4)  # binize pads features to the word boundary
        return packed_traverse_ok(pf.feat1.shape[0], pf.k1, pf.k2, words)

    def _ensure_packed(self):
        """The packed SoA forest layout, computed once per model and
        persisted through the standard attribute round-trip: saved models
        reload PRE-PACKED (the arrays land in model.npz; ``pack_forest``
        never reruns after a load)."""
        pf = getattr(self, "_packed_cache", None)
        if pf is not None:
            return pf
        from ..ops.tree_kernels import PackedForest, pack_forest

        ma = self._model_attributes
        if ma.get("packed_feat1") is not None and ma.get("packed_meta") is not None:
            meta = np.asarray(ma["packed_meta"]).astype(np.int64)
            pf = PackedForest(
                feat1=np.asarray(ma["packed_feat1"], dtype=np.int32),
                thr1=np.asarray(ma["packed_thr1"], dtype=np.int32),
                feat2=np.asarray(ma["packed_feat2"], dtype=np.int32),
                thr2=np.asarray(ma["packed_thr2"], dtype=np.int32),
                n_trees=int(meta[0]), k1=int(meta[1]), k2=int(meta[2]),
                max_depth=int(meta[3]),
            )
        else:
            pf = pack_forest(
                self._features_arr,
                np.asarray(ma["threshold_bins"]),
                max_depth=self._max_depth_built,
            )
            ma["packed_feat1"] = pf.feat1
            ma["packed_thr1"] = pf.thr1
            ma["packed_feat2"] = pf.feat2
            ma["packed_thr2"] = pf.thr2
            ma["packed_meta"] = np.asarray(
                [pf.n_trees, pf.k1, pf.k2, pf.max_depth], dtype=np.int32
            )
        self._packed_cache = pf
        return pf

    def _make_binize_for_apply(self) -> Callable[[np.ndarray], jax.Array]:
        """Per-batch quantizer with the edges table hoisted device-side
        ONCE (a streaming transform calls the returned fn per batch)."""
        from ..ops.tree_kernels import binize

        edges = jnp.asarray(np.asarray(self._model_attributes["bin_edges"]))
        d = edges.shape[0]
        d_pad = -(-d // 4) * 4  # word-packing alignment
        if envspec.get("TPUML_RF_CHECK_FINITE"):
            # opt-in serving-boundary guard for the finite-input contract
            # (binize routes NaN to bin 0; see its docstring + the fit
            # boundary check) — a full host pass per batch, so off by
            # default on the hot path
            def _binz(Xb):
                if not np.isfinite(np.asarray(Xb)).all():
                    raise ValueError(
                        "RandomForest transform batch contains NaN/Inf "
                        "(finite-input contract, TPUML_RF_CHECK_FINITE=1)"
                    )
                return binize(jnp.asarray(Xb), edges, d_pad=d_pad)

            return _binz

        def _binz_batch(Xb):
            with telemetry.span("forest.binize_batch", rows=int(Xb.shape[0])):
                return binize(jnp.asarray(Xb), edges, d_pad=d_pad)

        return _binz_batch

    # -- shared transform dispatch -----------------------------------------
    # Classification and regression route through ONE engine resolution:
    # packed lockstep traversal when its kernel lowers, the per-tree
    # bin-space descent when bin tables exist, the raw-threshold descent
    # otherwise. The resolved closure (device-resident operands + jitted
    # callable) is cached on the model; ``core._apply_batched`` + the
    # device-staging flag micro-batch rows through it with the next batch
    # staged host->device while the current one computes.

    _transform_device_staging = True

    def _stage_timer(self):
        from ..utils.profiling import StageTimer

        st = getattr(self, "_transform_stage_timer", None)
        if st is None:
            st = StageTimer(f"{type(self).__name__}.transform")
            self._transform_stage_timer = st
        return st

    def _resolve_transform_engine(self, mode: Optional[str] = None) -> str:
        """bins (a TPU, bin tables, depth <= 14) else legacy under ``auto``;
        packed only where ``mode`` pins it (default: the env-resolved
        `TPUML_RF_APPLY`). The serving registry resolves
        with the default mode on purpose: serving promises bit-identity
        with direct transform, and the packed/legacy descents differ by
        one f32 ulp in vote normalization on some inputs — same engine,
        same bits."""
        if self._packed_apply_ready(mode):
            return "packed"
        if self._bins_apply_ready(mode):
            return "bins"
        return "legacy"

    def _get_tpu_transform_func(
        self,
        dataset: Optional[DataFrame] = None,
        engine: Optional[str] = None,
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        engine = engine or self._resolve_transform_engine()
        key = (engine, tuple(self._out_cols()))
        cache = getattr(self, "_transform_engine_cache", None)
        if cache is None:
            # dict, not a single slot: closures resolved under different
            # engines (parity tests flip TPUML_RF_APPLY) coexist without
            # thrashing each other's jitted programs
            cache = self._transform_engine_cache = {}
        fn = cache.get(key)
        if fn is None:
            with telemetry.span(
                "forest.transform_engine",
                engine=engine,
                trees=self.getNumTrees(),
                depth=self._max_depth_built,
                cols=self.numFeatures,
            ):
                inner = getattr(self, f"_{engine}_transform_fn")()

            def fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
                # one batch through the engine, to its columns on the host
                with telemetry.span(
                    "forest.descent", engine=engine, rows=int(Xb.shape[0])
                ):
                    return inner(Xb)

            cache[key] = fn
        return fn

    def _out_cols(self) -> List[str]:
        return [self.getOrDefault("predictionCol")]

    def _packed_transform_fn(self):
        raise NotImplementedError

    def _bins_transform_fn(self):
        raise NotImplementedError

    def _legacy_transform_fn(self):
        raise NotImplementedError

    @property
    def numFeatures(self) -> int:
        return int(self._model_attributes["num_features"])

    def getNumTrees(self) -> int:
        # NOTE: the fitted tree count, intentionally NOT a ``numTrees``
        # property — that name is the Param and must stay a Param
        return int(self._features_arr.shape[0])

    @property
    def treeWeights(self) -> List[float]:
        return [1.0] * self.getNumTrees()

    @property
    def totalNumNodes(self) -> int:
        # every split adds two children to the initial root
        return int(self.getNumTrees() + 2 * (self._features_arr >= 0).sum())

    def _leaf_counts(self) -> np.ndarray:
        """(T, M) row counts behind every node."""
        ls = self._leaf_stats_arr
        if int(self._model_attributes["n_classes"]) > 0:
            return ls.sum(axis=2)
        return ls[:, :, 0]

    @property
    def featureImportances(self) -> np.ndarray:
        """Gain-weighted importances, Spark semantics: per-tree importance of
        feature f = sum over f's split nodes of gain * node row count;
        normalized per tree, averaged, normalized to sum 1."""
        feat, gains = self._features_arr, self._gains_arr
        counts = self._leaf_counts()
        d = self.numFeatures
        total = np.zeros(d)
        for t in range(feat.shape[0]):
            split = feat[t] >= 0
            contrib = np.zeros(d)
            np.add.at(contrib, feat[t][split], (gains[t] * counts[t])[split])
            s = contrib.sum()
            if s > 0:
                total += contrib / s
        s = total.sum()
        return total / s if s > 0 else total

    @property
    def trees(self) -> List[Dict[str, Any]]:
        """Per-tree nested-dict export (the reference keeps per-tree JSON from
        cuML for ``cpu()`` translation, ``tree.py:319-366``)."""
        out = []
        feat, thr = self._features_arr, self._thresholds_arr
        leaf = self._leaf_stats_arr
        for t in range(feat.shape[0]):
            def build(i: int) -> Dict[str, Any]:
                if feat[t, i] < 0:
                    return {"leaf_value": leaf[t, i].tolist()}
                return {
                    "split_feature": int(feat[t, i]),
                    "threshold": float(thr[t, i]),
                    "left_child": build(2 * i + 1),
                    "right_child": build(2 * i + 2),
                }

            out.append(build(0))
        return out

    def toDebugString(self) -> str:
        lines = [
            f"{type(self).__name__} with {self.getNumTrees()} trees, "
            f"{self.totalNumNodes} nodes, depth<={self._max_depth_built}"
        ]
        return "\n".join(lines)

    # -- multi-model support (CV single-pass) ------------------------------
    @classmethod
    def _combine(cls, models: List["_RandomForestModel"]) -> "_RandomForestModel":
        """Forests are ragged across param maps (different numTrees/maxDepth),
        so unlike the coefficient models the combined model keeps the
        sub-model list and evaluates them against ONE feature extraction
        (the reference likewise combines treelite sub-models,
        ``tree.py:600-614``)."""
        combined = models[0].copy()
        combined._cv_models = list(models)
        return combined

    def _eval_models(self) -> List["_ForestModelBase"]:
        return getattr(self, "_cv_models", None) or [self]


class _RandomForestModel(_RandomForestClass, _ForestModelBase, _RandomForestParams):
    """Shared model surface (reference ``_RandomForestModel``,
    ``tree.py:423-614``)."""

    def __init__(self, **attrs: Any) -> None:
        _ForestModelBase.__init__(self, **attrs)
        _RandomForestParams.__init__(self)


# ---------------------------------------------------------------------------
# classifier
# ---------------------------------------------------------------------------


class RandomForestClassifier(_RandomForestEstimator, HasProbabilityCol, HasRawPredictionCol):
    """``RandomForestClassifier(numTrees=50, maxDepth=13).fit(df)`` — drop-in
    for ``pyspark.ml.classification.RandomForestClassifier`` (reference
    ``classification.py:308-513``)."""

    _is_classification = True
    _default_impurity = "gini"

    # pyspark's ProbabilisticClassifier param surface: accepted so Spark
    # code constructs unchanged; setting it raises the reference's
    # unsupported-param error (cuRF has no per-class vote thresholds —
    # reference classification.py maps it to None the same way)
    thresholds = _mk(
        "thresholds", "per-class vote thresholds (unsupported)",
        TypeConverters.toListFloat,
    )

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        m = dict(super()._param_mapping())
        m["thresholds"] = None
        return m

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        m = dict(super()._param_value_mapping())

        def _crit(v: str) -> str:
            if v not in ("gini", "entropy"):
                raise ValueError(f"Unsupported impurity for classification: {v!r}")
            return v

        m["split_criterion"] = _crit
        return m

    def _process_labels(self, y_host: np.ndarray) -> int:
        from ..parallel.mesh import global_label_summary

        ls = global_label_summary(y_host)
        if ls["total"] == 0:
            raise ValueError("Labels column is empty")
        if ls["y_min"] < 0 or not ls["all_int"]:
            raise RuntimeError("Labels MUST be non-negative integers")
        return max(int(ls["y_max"]) + 1, 2)

    def _label_stats(self, y: jax.Array, n_stats: int) -> jax.Array:
        return jax.nn.one_hot(y.astype(jnp.int32), n_stats, dtype=jnp.float32)

    def _impurity_name(self, params: Dict[str, Any]) -> str:
        return str(params.get("split_criterion", "gini"))

    def _create_model(self, result: Dict[str, Any]) -> "RandomForestClassificationModel":
        return RandomForestClassificationModel(**result)


class RandomForestClassificationModel(
    _RandomForestModel, HasProbabilityCol, HasRawPredictionCol
):
    """Reference ``classification.py:516-648``."""

    @property
    def numClasses(self) -> int:
        return int(self._model_attributes["n_classes"])

    @property
    def classes_(self) -> np.ndarray:
        return np.arange(self.numClasses, dtype=np.float64)

    def _leaf_probs(self) -> np.ndarray:
        ls = self._leaf_stats_arr
        tot = np.maximum(ls.sum(axis=2, keepdims=True), 1e-12)
        return (ls / tot).astype(np.float32)

    def _out_cols(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _packed_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_classify_packed

        pred_col, prob_col, raw_col = self._out_cols()
        pf = self._ensure_packed()
        feat1, thr1 = jnp.asarray(pf.feat1), jnp.asarray(pf.thr1)
        feat2, thr2 = jnp.asarray(pf.feat2), jnp.asarray(pf.thr2)
        leafp = jnp.asarray(self._leaf_probs())
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                pred, prob, raw = rf_classify_packed(
                    binz(Xb), feat1, thr1, feat2, thr2, leafp,
                    k1=pf.k1, k2=pf.k2, max_depth=pf.max_depth,
                    pred_dtype=np.dtype(Xb.dtype),
                )
            with st.stage("host_out"):
                return {
                    pred_col: output_to_host(pred),
                    prob_col: output_to_host(prob),
                    raw_col: output_to_host(raw),
                }

        return _fn

    def _bins_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_classify_bins

        pred_col, prob_col, raw_col = self._out_cols()
        feat = jnp.asarray(self._features_arr)
        leafp = jnp.asarray(self._leaf_probs())
        depth = self._max_depth_built
        thrb = jnp.asarray(
            np.asarray(self._model_attributes["threshold_bins"])
        )
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                pred, prob, raw = rf_classify_bins(
                    binz(Xb), feat, thrb, leafp,
                    max_depth=depth, pred_dtype=np.dtype(Xb.dtype),
                )
            with st.stage("host_out"):
                return {
                    pred_col: output_to_host(pred),
                    prob_col: output_to_host(prob),
                    raw_col: output_to_host(raw),
                }

        return _fn

    def _legacy_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        pred_col, prob_col, raw_col = self._out_cols()
        feat = jnp.asarray(self._features_arr)
        thr = jnp.asarray(self._thresholds_arr)
        leafp = jnp.asarray(self._leaf_probs())
        depth = self._max_depth_built

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            pred, prob, raw = rf_classify(
                batch_to_device(Xb), feat, jnp.asarray(thr, Xb.dtype), leafp,
                max_depth=depth,
            )
            return {
                pred_col: output_to_host(pred),
                prob_col: output_to_host(prob),
                raw_col: output_to_host(raw),
            }

        return _fn

    # -- single-row API ----------------------------------------------------
    def predict(self, vector: Any) -> float:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return float(fn(x)[self.getOrDefault("predictionCol")][0])

    def predictProbability(self, vector: Any) -> np.ndarray:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return fn(x)[self.getOrDefault("probabilityCol")][0]

    def predictRaw(self, vector: Any) -> np.ndarray:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return fn(x)[self.getOrDefault("rawPredictionCol")][0]

    def _transformEvaluate(self, dataset: DataFrame, evaluator: Any) -> List[float]:
        from ..evaluation import MulticlassClassificationEvaluator
        from ..metrics import MulticlassMetrics

        if not isinstance(evaluator, MulticlassClassificationEvaluator):
            raise NotImplementedError(
                f"Evaluator {type(evaluator).__name__} is not supported"
            )
        X = self._extract_features_for_transform(dataset)
        y = np.asarray(dataset.column(evaluator.getLabelCol()), dtype=np.float64)
        need_probs = evaluator.getMetricName() == "logLoss"
        results = []
        for m in self._eval_models():
            out = m._apply_batched(m._get_tpu_transform_func(dataset), X)
            results.append(
                MulticlassMetrics.from_predictions(
                    y,
                    out[m.getOrDefault("predictionCol")],
                    out[m.getOrDefault("probabilityCol")] if need_probs else None,
                    evaluator.getEps(),
                ).evaluate(evaluator)
            )
        return results


# ---------------------------------------------------------------------------
# regressor
# ---------------------------------------------------------------------------


class RandomForestRegressor(_RandomForestEstimator):
    """``RandomForestRegressor(numTrees=30, maxDepth=6).fit(df)`` — drop-in
    for ``pyspark.ml.regression.RandomForestRegressor`` (reference
    ``regression.py:802-973``)."""

    _is_classification = False
    _default_impurity = "variance"

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        m = dict(super()._param_value_mapping())

        def _crit(v: str) -> str:
            if v != "variance":
                raise ValueError(f"Unsupported impurity for regression: {v!r}")
            return v

        m["split_criterion"] = _crit
        return m

    def _process_labels(self, y_host: np.ndarray) -> int:
        from ..parallel.mesh import global_label_summary

        if global_label_summary(y_host)["total"] == 0:
            raise ValueError("Labels column is empty")
        return 3  # (weight, w*y, w*y^2)

    def _label_stats(self, y: jax.Array, n_stats: int) -> jax.Array:
        yf = y.astype(jnp.float32)
        return jnp.stack([jnp.ones_like(yf), yf, yf * yf], axis=1)

    def _impurity_name(self, params: Dict[str, Any]) -> str:
        return "variance"

    def _create_model(self, result: Dict[str, Any]) -> "RandomForestRegressionModel":
        return RandomForestRegressionModel(**result)


class RandomForestRegressionModel(_RandomForestModel):
    """Reference ``regression.py:976-1068``."""

    def _leaf_means(self) -> np.ndarray:
        ls = self._leaf_stats_arr
        return (ls[:, :, 1] / np.maximum(ls[:, :, 0], 1e-12)).astype(np.float32)

    def _packed_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_regress_packed

        (pred_col,) = self._out_cols()
        pf = self._ensure_packed()
        feat1, thr1 = jnp.asarray(pf.feat1), jnp.asarray(pf.thr1)
        feat2, thr2 = jnp.asarray(pf.feat2), jnp.asarray(pf.thr2)
        leafv = jnp.asarray(self._leaf_means())
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                pred = rf_regress_packed(
                    binz(Xb), feat1, thr1, feat2, thr2, leafv,
                    k1=pf.k1, k2=pf.k2, max_depth=pf.max_depth,
                )
            with st.stage("host_out"):
                return {pred_col: output_to_host(pred, dtype=Xb.dtype)}

        return _fn

    def _bins_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_regress_bins

        (pred_col,) = self._out_cols()
        feat = jnp.asarray(self._features_arr)
        leafv = jnp.asarray(self._leaf_means())
        depth = self._max_depth_built
        thrb = jnp.asarray(
            np.asarray(self._model_attributes["threshold_bins"])
        )
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                pred = rf_regress_bins(
                    binz(Xb), feat, thrb, leafv,
                    max_depth=depth,
                )
            with st.stage("host_out"):
                return {pred_col: output_to_host(pred, dtype=Xb.dtype)}

        return _fn

    def _legacy_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        (pred_col,) = self._out_cols()
        feat = jnp.asarray(self._features_arr)
        thr = self._thresholds_arr
        leafv = jnp.asarray(self._leaf_means())
        depth = self._max_depth_built

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            pred = rf_regress(
                batch_to_device(Xb), feat, jnp.asarray(thr, Xb.dtype), leafv,
                max_depth=depth,
            )
            return {pred_col: output_to_host(pred)}

        return _fn

    def predict(self, vector: Any) -> float:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return float(fn(x)[self.getOrDefault("predictionCol")][0])

    def _transformEvaluate(self, dataset: DataFrame, evaluator: Any) -> List[float]:
        from ..evaluation import RegressionEvaluator
        from ..metrics import RegressionMetrics

        if not isinstance(evaluator, RegressionEvaluator):
            raise NotImplementedError(
                f"Evaluator {type(evaluator).__name__} is not supported"
            )
        X = self._extract_features_for_transform(dataset)
        y = np.asarray(dataset.column(evaluator.getLabelCol()), dtype=np.float64)
        return [
            RegressionMetrics.from_predictions(
                y,
                m._apply_batched(m._get_tpu_transform_func(dataset), X)[
                    m.getOrDefault("predictionCol")
                ],
            ).evaluate(evaluator)
            for m in self._eval_models()
        ]


# ---------------------------------------------------------------------------
# gradient-boosted trees
# ---------------------------------------------------------------------------
#
# Spark ML drop-ins for GBTClassifier / GBTRegressor on the SAME binned-
# histogram engine: each boosting round grows its trees through the
# tree-batched level-wise builder (``ops/tree_kernels._grow_trees_batched``)
# with data-parallel histogram psums (``ops/gbt_kernels.gbt_round``), and
# fitted models reuse the forest transform engines (packed lockstep /
# bin-space descent) with margin-contribution leaf payloads.


class _GBTClass:
    _default_loss = "squared"

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # pyspark.ml GBT param surface -> backend names (the same scheme
        # as the forest mapping above; sklearn-style backend names)
        return {
            "maxIter": "n_estimators",
            "maxDepth": "max_depth",
            "maxBins": "n_bins",
            "stepSize": "learning_rate",
            "lossType": "loss",
            "featureSubsetStrategy": "max_features",
            "minInstancesPerNode": "min_samples_leaf",
            "minInfoGain": "min_impurity_decrease",
            "seed": "random_state",
            "impurity": "",          # Spark GBT impurity is fixed variance
            "maxMemoryInMB": "",
            "cacheNodeIds": "",
            "checkpointInterval": "",
            "subsamplingRate": "",
            "minWeightFractionPerNode": "",
            "validationTol": "",
            "validationIndicatorCol": None,
            "weightCol": None,
            "leafCol": None,
        }

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        return {"max_features": _RandomForestClass._param_value_mapping()["max_features"]}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        # Spark GBT defaults: maxIter=20, maxDepth=5, maxBins=32,
        # stepSize=0.1, featureSubsetStrategy="all"
        return {
            "n_estimators": 20,
            "max_depth": 5,
            "n_bins": 32,
            "learning_rate": 0.1,
            "max_features": 1.0,
            "min_samples_leaf": 1,
            "min_impurity_decrease": 0.0,
            "random_state": None,
            "loss": cls._default_loss,
        }


class _GBTParams(
    HasFeaturesCol, HasFeaturesCols, HasLabelCol, HasPredictionCol, HasSeed
):
    maxIter = _mk("maxIter", "number of boosting rounds", TypeConverters.toInt)
    maxDepth = _mk("maxDepth", "maximum tree depth", TypeConverters.toInt)
    maxBins = _mk("maxBins", "max histogram bins per feature", TypeConverters.toInt)
    stepSize = _mk("stepSize", "learning rate (shrinkage)", TypeConverters.toFloat)
    lossType = _mk("lossType", "loss function", TypeConverters.toString)
    impurity = _mk("impurity", "split criterion (fixed: variance)", TypeConverters.toString)
    featureSubsetStrategy = _mk(
        "featureSubsetStrategy",
        "features considered per split: all|auto|sqrt|log2|onethird|fraction|n",
        TypeConverters.toString,
    )
    minInstancesPerNode = _mk(
        "minInstancesPerNode", "min rows per child node", TypeConverters.toInt
    )
    minInfoGain = _mk("minInfoGain", "min gain for a split", TypeConverters.toFloat)
    subsamplingRate = _mk("subsamplingRate", "row subsample rate (ignored)", TypeConverters.toFloat)
    maxMemoryInMB = _mk("maxMemoryInMB", "memory hint (ignored)", TypeConverters.toInt)
    cacheNodeIds = _mk("cacheNodeIds", "node-id caching (ignored)", TypeConverters.toBoolean)
    checkpointInterval = _mk("checkpointInterval", "checkpointing (ignored)", TypeConverters.toInt)
    minWeightFractionPerNode = _mk(
        "minWeightFractionPerNode", "min weight fraction (ignored)", TypeConverters.toFloat
    )
    validationTol = _mk("validationTol", "early-stop tolerance (ignored)", TypeConverters.toFloat)
    validationIndicatorCol = _mk(
        "validationIndicatorCol", "validation split column (unsupported)",
        TypeConverters.toString,
    )
    weightCol = _mk("weightCol", "weight column (unsupported)", TypeConverters.toString)
    leafCol = _mk("leafCol", "leaf index column (unsupported)", TypeConverters.toString)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            maxIter=20,
            maxDepth=5,
            maxBins=32,
            stepSize=0.1,
            featureSubsetStrategy="all",
            minInstancesPerNode=1,
            minInfoGain=0.0,
            subsamplingRate=1.0,
            seed=0,
        )

    def getMaxIter(self) -> int:
        return self.getOrDefault("maxIter")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")

    def getMaxBins(self) -> int:
        return self.getOrDefault("maxBins")

    def getStepSize(self) -> float:
        return self.getOrDefault("stepSize")

    def getLossType(self) -> str:
        return self.getOrDefault("lossType")

    def getFeatureSubsetStrategy(self) -> str:
        return self.getOrDefault("featureSubsetStrategy")


class _GBTEstimator(_GBTClass, _TpuEstimatorSupervised, _GBTParams):
    """Shared boosting-fit machinery: quantize once, then sequential
    rounds of ``gbt_round`` — each round one tree-batched build on the
    current gradient field, with margins advanced in place on device."""

    _is_classification = False

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimatorSupervised.__init__(self)
        _GBTParams.__init__(self)
        self._setDefault(lossType=self._default_loss)
        self._set_params(**kwargs)

    def setMaxIter(self, value: int) -> "_GBTEstimator":
        self._set_params(maxIter=value)
        return self

    def setMaxDepth(self, value: int) -> "_GBTEstimator":
        self._set_params(maxDepth=value)
        return self

    def setMaxBins(self, value: int) -> "_GBTEstimator":
        self._set_params(maxBins=value)
        return self

    def setStepSize(self, value: float) -> "_GBTEstimator":
        self._set_params(stepSize=value)
        return self

    def setLossType(self, value: str) -> "_GBTEstimator":
        self._set_params(lossType=value)
        return self

    def setFeatureSubsetStrategy(self, value: str) -> "_GBTEstimator":
        self._set_params(featureSubsetStrategy=value)
        return self

    def setSeed(self, value: int) -> "_GBTEstimator":
        self._set_params(seed=value)
        return self

    # subclass hooks -------------------------------------------------------
    def _process_labels(self, y_host: np.ndarray) -> int:
        """Validate labels; classifier returns n_classes, regressor 0."""
        raise NotImplementedError

    def _check_loss(self, loss: str) -> str:
        raise NotImplementedError

    # fit ------------------------------------------------------------------
    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        label_col = self.getOrDefault("labelCol")
        y_host_raw = np.asarray(dataset.column(label_col))
        n_classes = self._process_labels(y_host_raw)
        is_classification = self._is_classification

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            import time as _time

            from ..ops.gbt_kernels import GBTConfig, gbt_loss, gbt_round

            t0 = _time.perf_counter()
            max_depth = int(params["max_depth"])
            if max_depth > _MAX_SUPPORTED_DEPTH:
                raise ValueError(
                    f"maxDepth={max_depth} exceeds supported depth "
                    f"{_MAX_SUPPORTED_DEPTH} (full binary node layout)"
                )
            n_rounds = int(params["n_estimators"])
            if n_rounds < 1:
                raise ValueError("maxIter must be >= 1")
            lr = float(params["learning_rate"])
            self._check_loss(str(params["loss"]))
            n_bins = int(min(params["n_bins"], max(2, inputs.n_rows)))
            if n_bins > 256:
                self.logger.warning("maxBins=%d clamped to 256", n_bins)
                n_bins = 256
            d = inputs.n_features
            d_pad = next_pow2(d)
            seed = int(params.get("random_state") or 0)

            edges_np, bins = _quantize_features(inputs, n_bins, d_pad, "GBT")

            # loss kind + output head width. Spark's GBTClassifier is
            # binary-only; K>2 extends it sklearn-style (one tree per
            # class per round on softmax gradients)
            if is_classification:
                if n_classes == 2:
                    loss_kind, n_out, n_v = "logistic", 1, 1
                else:
                    loss_kind, n_out, n_v = "multinomial", n_classes, n_classes
            else:
                loss_kind, n_out, n_v = "squared", 1, 1
            n_stats = 3 if loss_kind == "squared" else 4

            # F0: the constant margin minimizing the bare loss (sklearn
            # init conventions: mean / log-odds / log-priors)
            yv = y_host_raw.astype(np.float64)
            if loss_kind == "squared":
                init = np.array([yv.mean()], dtype=np.float32)
            elif loss_kind == "logistic":
                p1 = float(np.clip(yv.mean(), 1e-6, 1.0 - 1e-6))
                init = np.array([np.log(p1 / (1.0 - p1))], dtype=np.float32)
            else:
                prior = np.bincount(
                    yv.astype(np.int64), minlength=n_classes
                ) / max(1, len(yv))
                init = np.log(np.clip(prior, 1e-6, None)).astype(np.float32)

            cfg = GBTConfig(
                loss=loss_kind,
                n_out=n_out,
                learning_rate=lr,
                tree=ForestConfig(
                    max_depth=max_depth,
                    n_bins=n_bins,
                    n_features=d,
                    n_stats=n_stats,
                    impurity="variance",
                    k_features=_resolve_k_features(
                        params["max_features"], d, is_classification
                    ),
                    min_samples_leaf=int(params["min_samples_leaf"]),
                    min_info_gain=float(
                        params.get("min_impurity_decrease", 0.0) or 0.0
                    ),
                    min_samples_split=int(params.get("min_samples_split", 2)),
                    bootstrap=False,
                    hist_strategy=resolve_hist_strategy(),
                    contract_gather=resolve_contract_gather(),
                ),
            )

            n_pad_global = bins.shape[0]
            margins = jax.make_array_from_callback(
                (n_pad_global, n_v),
                NamedSharding(inputs.mesh, LAYOUT.rows()),
                lambda idx: np.ascontiguousarray(
                    np.broadcast_to(init[None, :], (n_pad_global, n_v))[idx]
                ),
            )
            keys_np = np.asarray(
                jax.random.split(jax.random.PRNGKey(seed), n_rounds)
            )
            log_every = int(envspec.get("TPUML_GBT_ROUND_LOG_EVERY"))

            def _concat_tables(rounds_out: List[Dict[str, Any]]) -> Dict[str, Any]:
                """Host forest tables from the per-round outputs (or from
                a checkpoint prefix entry — the casts are idempotent)."""
                return {
                    "feature": np.concatenate(
                        [np.asarray(o["feature"]) for o in rounds_out], axis=0
                    ).astype(np.int32),
                    "threshold_bin": np.concatenate(
                        [np.asarray(o["threshold_bin"]) for o in rounds_out],
                        axis=0,
                    ).astype(np.int32),
                    "leaf_stats": np.concatenate(
                        [np.asarray(o["leaf_stats"]) for o in rounds_out],
                        axis=0,
                    ).astype(np.float32),
                    "gain": np.concatenate(
                        [np.asarray(o["gain"]) for o in rounds_out], axis=0
                    ).astype(np.float32),
                    "values": np.concatenate(
                        [np.asarray(o["values"]) for o in rounds_out], axis=0
                    ).astype(np.float32),
                }

            # checkpoint/resume over the boosting loop: per-round RNG is
            # keys_np[r] — a function of the ABSOLUTE round index — and
            # the f32 margins round-trip through npz bitwise, so a
            # resumed fit is same-seed identical to an uninterrupted one
            ckpt = FitCheckpointer.from_env("gbt", {
                "loss": loss_kind, "n_rounds": n_rounds, "lr": lr,
                "max_depth": max_depth, "n_bins": n_bins, "d": d,
                "n_rows": inputs.n_rows, "seed": seed,
                "edges": array_digest(edges_np),
                "init": array_digest(init),
            })

            t_quant = _time.perf_counter()
            outs = []
            r0 = 0
            resumed = ckpt.load() if ckpt.enabled else None
            if resumed is not None:
                r0, saved, _ = resumed
                margins = jax.make_array_from_callback(
                    (n_pad_global, n_v),
                    NamedSharding(inputs.mesh, LAYOUT.rows()),
                    lambda idx: np.ascontiguousarray(saved["margins"][idx]),
                )
                # the committed forest prefix rides as one pseudo-round
                # entry; _concat_tables flattens it with the new rounds
                outs.append({
                    k: saved[k]
                    for k in (
                        "feature", "threshold_bin", "leaf_stats", "gain",
                        "values",
                    )
                })
                counters.bump("resumed_fits")
                counters.note("resumed_from", r0)
                self.logger.info(
                    "GBT resume: restored %d/%d committed rounds", r0, n_rounds
                )
            for r in range(r0, n_rounds):
                fault_site("gbt:round")
                out = gbt_round(
                    bins, inputs.mask, inputs.y, margins,
                    jnp.asarray(keys_np[r]), mesh=inputs.mesh, cfg=cfg,
                )
                margins = out.pop("margins")
                outs.append(out)
                if ckpt.enabled:
                    def _snapshot() -> Dict[str, Any]:
                        return {
                            "margins": np.asarray(margins), **_concat_tables(outs)
                        }

                    if (r + 1) % ckpt.every == 0:
                        ckpt.save(r + 1, _snapshot())
                    preempt_point(ckpt, r + 1, _snapshot)
                if log_every and (r + 1) % log_every == 0:
                    lv = float(
                        np.asarray(
                            gbt_loss(
                                inputs.y, margins, inputs.mask,
                                mesh=inputs.mesh, loss=loss_kind,
                            )
                        )
                    )
                    self.logger.info(
                        "GBT round %d/%d: train %s loss %.6f",
                        r + 1, n_rounds, loss_kind, lv,
                    )
            # one host fetch per table after the loop (rounds are data-
            # dependent through the margins, so growth itself is the
            # serialization point, not these copies)
            tables = _concat_tables(outs)
            feat = tables["feature"]
            thr_bin = tables["threshold_bin"]
            leaf_stats = tables["leaf_stats"]
            gains = tables["gain"]
            values = tables["values"]
            ckpt.clear()
            t_boost = _time.perf_counter()

            thr = np.where(
                feat >= 0,
                edges_np[
                    np.clip(feat, 0, d - 1), np.clip(thr_bin, 0, n_bins - 2)
                ],
                0.0,
            ).astype(np.float32)

            return {
                "features": feat,
                "thresholds": thr,
                "threshold_bins": thr_bin,
                "bin_edges": edges_np.astype(np.float32),
                "leaf_stats": leaf_stats,
                "gains": gains,
                # lr-scaled margin contributions, the EXACT f32 numbers
                # that advanced the training margins (device-computed in
                # gbt_round) — transform margins reproduce training
                # margins bit-for-bit
                "leaf_values": values,
                "init_margin": init,
                "n_classes": n_classes if is_classification else 0,
                "num_features": d,
                "learning_rate": lr,
                "n_rounds": n_rounds,
                "loss": loss_kind,
                "_fit_report": {
                    "quantize_seconds": t_quant - t0,
                    "boost_seconds": t_boost - t_quant,
                    "rounds": n_rounds,
                    "trees": int(feat.shape[0]),
                    "seconds_per_round": (t_boost - t_quant) / n_rounds,
                },
            }

        return _fit


class _GBTModel(_GBTClass, _ForestModelBase, _GBTParams):
    """Shared fitted-GBT surface: the forest transform engines driven
    with margin-contribution payloads summed over trees."""

    def __init__(self, **attrs: Any) -> None:
        _ForestModelBase.__init__(self, **attrs)
        _GBTParams.__init__(self)

    @property
    def _leaf_values_arr(self) -> np.ndarray:
        return np.asarray(self._model_attributes["leaf_values"])

    @property
    def _init_margin_arr(self) -> np.ndarray:
        return np.asarray(
            self._model_attributes["init_margin"], dtype=np.float32
        ).reshape(-1)

    def getNumRounds(self) -> int:
        return int(self._model_attributes["n_rounds"])

    def _leaf_counts(self) -> np.ndarray:
        # GBT stats are (w, r, r^2[, h]) — slot 0 is the row count for
        # every loss (the RF base sums class slots when n_classes > 0)
        return self._leaf_stats_arr[:, :, 0]

    def _payload_values(self) -> np.ndarray:
        """(T, M, V) per-node margin contributions: multiclass trees are
        rounds-major, tree t contributes to class t % K; binary and
        regression heads are single-column."""
        lv = self._leaf_values_arr.astype(np.float32)
        K = int(self._model_attributes.get("n_classes") or 0)
        if K > 2:
            T, M = lv.shape
            out = np.zeros((T, M, K), dtype=np.float32)
            out[
                np.arange(T)[:, None],
                np.arange(M)[None, :],
                (np.arange(T) % K)[:, None],
            ] = lv
            return out
        return lv[:, :, None]

    def _margins_from_eval(self, summed: jax.Array) -> np.ndarray:
        return np.asarray(summed) + self._init_margin_arr[None, :]

    def _margin_outputs(
        self, marg: np.ndarray, x_dtype: np.dtype
    ) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    # -- the three engines (shared shape; payload = margin contributions) --
    def _packed_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_eval_packed

        pf = self._ensure_packed()
        feat1, thr1 = jnp.asarray(pf.feat1), jnp.asarray(pf.thr1)
        feat2, thr2 = jnp.asarray(pf.feat2), jnp.asarray(pf.thr2)
        vals = jnp.asarray(self._payload_values())
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                s = rf_eval_packed(
                    binz(Xb), feat1, thr1, feat2, thr2, vals,
                    k1=pf.k1, k2=pf.k2, max_depth=pf.max_depth,
                )
            with st.stage("host_out"):
                return self._margin_outputs(
                    self._margins_from_eval(s), np.dtype(Xb.dtype)
                )

        return _fn

    def _bins_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import rf_eval_bins

        feat = jnp.asarray(self._features_arr)
        thrb = jnp.asarray(np.asarray(self._model_attributes["threshold_bins"]))
        vals = jnp.asarray(self._payload_values())
        depth = self._max_depth_built
        binz = self._make_binize_for_apply()
        st = self._stage_timer()

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            with st.stage("dispatch"):
                s = rf_eval_bins(binz(Xb), feat, thrb, vals, max_depth=depth)
            with st.stage("host_out"):
                return self._margin_outputs(
                    self._margins_from_eval(s), np.dtype(Xb.dtype)
                )

        return _fn

    def _legacy_transform_fn(self) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        from ..ops.tree_kernels import forest_apply

        feat = jnp.asarray(self._features_arr)
        thr = jnp.asarray(self._thresholds_arr)
        vals = jnp.asarray(self._payload_values())
        depth = self._max_depth_built

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            leaf = forest_apply(
                batch_to_device(Xb), feat, jnp.asarray(thr, Xb.dtype),
                max_depth=depth,
            )                                            # (T, n)
            s = jax.vmap(lambda v, li: v[li])(vals, leaf).sum(axis=0)
            return self._margin_outputs(
                self._margins_from_eval(s), np.dtype(Xb.dtype)
            )

        return _fn

    def predict(self, vector: Any) -> float:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return float(fn(x)[self.getOrDefault("predictionCol")][0])


class GBTClassifier(_GBTEstimator, HasProbabilityCol, HasRawPredictionCol):
    """``GBTClassifier(maxIter=20, maxDepth=5).fit(df)`` — drop-in for
    ``pyspark.ml.classification.GBTClassifier`` on the binned-histogram
    engine. Binary uses logistic loss (Spark semantics); label counts
    above 2 extend to softmax boosting, one tree per class per round."""

    _is_classification = True
    _default_loss = "logistic"

    def _process_labels(self, y_host: np.ndarray) -> int:
        from ..parallel.mesh import global_label_summary

        ls = global_label_summary(y_host)
        if ls["total"] == 0:
            raise ValueError("Labels column is empty")
        if ls["y_min"] < 0 or not ls["all_int"]:
            raise RuntimeError("Labels MUST be non-negative integers")
        return max(int(ls["y_max"]) + 1, 2)

    def _check_loss(self, loss: str) -> str:
        if loss != "logistic":
            raise ValueError(
                f"Unsupported lossType for GBTClassifier: {loss!r} "
                "(only 'logistic')"
            )
        return loss

    def _create_model(self, result: Dict[str, Any]) -> "GBTClassificationModel":
        report = result.pop("_fit_report", None)
        model = GBTClassificationModel(**result)
        if report is not None:
            model._fit_report = report
        return model


class GBTClassificationModel(_GBTModel, HasProbabilityCol, HasRawPredictionCol):
    @property
    def numClasses(self) -> int:
        return int(self._model_attributes["n_classes"])

    @property
    def classes_(self) -> np.ndarray:
        return np.arange(self.numClasses, dtype=np.float64)

    def _out_cols(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _margin_outputs(
        self, marg: np.ndarray, x_dtype: np.dtype
    ) -> Dict[str, np.ndarray]:
        pred_col, prob_col, raw_col = self._out_cols()
        if self.numClasses == 2:
            m = marg[:, 0].astype(np.float64)
            p1 = 1.0 / (1.0 + np.exp(-m))
            prob = np.stack([1.0 - p1, p1], axis=1)
            raw = np.stack([-m, m], axis=1)
            pred = (p1 > 0.5).astype(x_dtype)
        else:
            raw = marg.astype(np.float64)
            e = np.exp(raw - raw.max(axis=1, keepdims=True))
            prob = e / e.sum(axis=1, keepdims=True)
            pred = raw.argmax(axis=1).astype(x_dtype)
        return {
            pred_col: pred,
            prob_col: prob.astype(np.float32),
            raw_col: raw.astype(np.float32),
        }

    def predictProbability(self, vector: Any) -> np.ndarray:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return fn(x)[self.getOrDefault("probabilityCol")][0]

    def predictRaw(self, vector: Any) -> np.ndarray:
        x = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        fn = self._get_tpu_transform_func()
        return fn(x)[self.getOrDefault("rawPredictionCol")][0]


class GBTRegressor(_GBTEstimator):
    """``GBTRegressor(maxIter=20, maxDepth=5).fit(df)`` — drop-in for
    ``pyspark.ml.regression.GBTRegressor`` (squared-error loss)."""

    _is_classification = False
    _default_loss = "squared"

    def _process_labels(self, y_host: np.ndarray) -> int:
        from ..parallel.mesh import global_label_summary

        if global_label_summary(y_host)["total"] == 0:
            raise ValueError("Labels column is empty")
        return 0

    def _check_loss(self, loss: str) -> str:
        if loss == "absolute":
            raise ValueError(
                "lossType='absolute' is not supported (leaf values come "
                "from closed-form Newton steps; use 'squared')"
            )
        if loss != "squared":
            raise ValueError(
                f"Unsupported lossType for GBTRegressor: {loss!r} "
                "(only 'squared')"
            )
        return loss

    def _create_model(self, result: Dict[str, Any]) -> "GBTRegressionModel":
        report = result.pop("_fit_report", None)
        model = GBTRegressionModel(**result)
        if report is not None:
            model._fit_report = report
        return model


class GBTRegressionModel(_GBTModel):
    def _margin_outputs(
        self, marg: np.ndarray, x_dtype: np.dtype
    ) -> Dict[str, np.ndarray]:
        (pred_col,) = self._out_cols()
        return {pred_col: marg[:, 0].astype(x_dtype)}
