"""Core estimator/model framework — the reference ``core.py`` re-designed TPU-first.

Reference architecture (``/root/reference/python/src/spark_rapids_ml/core.py``):
Spark barrier tasks each ingest Arrow batches into device arrays, bootstrap a
NCCL communicator, and call a per-algorithm closure returned by
``_get_cuml_fit_func``; rank 0 yields the model row back to the driver
(``core.py:615-780``). Transform is an embarrassingly-parallel pandas UDF
(``core.py:1463-1568``).

TPU-native redesign: there is no task/driver split — the host process owns a
``jax.sharding.Mesh``; ``_pre_process_data`` shards the design matrix over
the ``dp`` axis with ``NamedSharding`` and the per-algorithm fit function is
a **jitted global-math function** (psum/all_gather inserted by XLA's SPMD
partitioner, playing the role the NCCL allreduce played inside cuML).
The subclass contract is preserved one-to-one:

  reference hook                      this framework
  ---------------------------------   ---------------------------------
  ``_get_cuml_fit_func``              ``_get_tpu_fit_func``
  ``_get_cuml_transform_func``        ``_get_tpu_transform_func``
  ``_out_schema``                     (models return named arrays)
  ``_pre_process_data``               ``_pre_process_data``
  ``_require_nccl_ucx``               (absent — the mesh always exists)
  ``fitMultiple``/``_combine``        same names, same single-pass contract
  ``_transformEvaluate``              same name, same sufficient-stats design
"""

from __future__ import annotations

import json
import os
import shutil
from abc import abstractmethod
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from .data.dataframe import DataFrame, _is_sparse
from .params import Params, _TpuParams, HasLabelCol, HasPredictionCol, HasWeightCol
from .runtime import autotune, envspec, telemetry
from .parallel.mesh import (
    device_bytes_limit,
    global_row_count,
    make_mesh,
    resolve_mesh_mp,
    row_sharding,
    shard_aligned,
    shard_rows,
)
from .utils.logging import get_logger


def _resolve_feature_matrix(obj: "_TpuParams", dataset: DataFrame):
    """Resolve the feature columns of ``dataset`` into one matrix.

    Single implementation shared by the fit and transform paths (reference
    column selection: ``core.py:449-546`` fit, ``core.py:1183-1303``
    transform). Returns ``(X_dense, X_sparse)`` — exactly one is non-None;
    ``X_sparse`` is a host scipy CSR and is only returned when the sparse
    opt-in resolves to True (``enable_sparse_data_optim`` semantics,
    reference ``params.py:42-63``).
    """
    input_col, input_cols = obj._get_input_columns()
    if input_cols is not None:
        mats = [np.asarray(dataset.column(c)).reshape(-1, 1) for c in input_cols]
        return np.concatenate(mats, axis=1), None
    col = dataset.column(input_col)
    if _is_sparse(col):
        use_sparse = True
        if obj.hasParam("enable_sparse_data_optim") and obj.isDefined(
            "enable_sparse_data_optim"
        ):
            if obj.getOrDefault("enable_sparse_data_optim") is False:
                use_sparse = False
        if use_sparse:
            return None, col
        return np.asarray(col.todense()), None
    X = np.asarray(col)
    if X.ndim != 2:
        raise ValueError(f"Features column {input_col!r} must be a 2-D vector column")
    return X, None

def _resolve_features_f32(obj: "_TpuParams", dataset: DataFrame) -> np.ndarray:
    """Resolve features to one dense contiguous float32 matrix — the shared
    path for float32-only algorithms (kNN, UMAP; reference ``knn.py:289-292``
    converts all inputs to float32)."""
    X, X_sparse = _resolve_feature_matrix(obj, dataset)
    if X is None:
        X = np.asarray(X_sparse.todense())
    return np.ascontiguousarray(np.asarray(X, dtype=np.float32))


def _x64_ctx(dtype: Any):
    """Scoped x64 enablement for the float64 path.

    The reference supports f64 inputs end-to-end (``float32_inputs=False``,
    reference ``params.py:301-305``). JAX truncates to 32-bit by default and
    toggling ``jax_enable_x64`` globally from a library import would change
    numerics of unrelated user code — so widen only around our own
    device_put/compute when the resolved input dtype is f64.
    """
    import contextlib

    from jax._src.config import enable_x64

    if jnp.dtype(dtype) == jnp.dtype("float64"):
        return enable_x64(True)
    return contextlib.nullcontext()


# one-time (per process) debug log of the gang-fit static-bucket partition
_GANG_PARTITION_LOGGED = False


def _default_gang_budget() -> float:
    """Default HBM budget for gang-fit lane residents: a quarter of the
    device memory limit (of a nominal 16 GB on the CPU test mesh)."""
    return device_bytes_limit(16 << 30) / 4.0


def _gang_env_on() -> bool:
    """Cheap gate: is ``TPUML_GANG_FIT`` set to anything but off?

    Deliberately does NOT validate the value — :func:`resolve_gang_fit`
    does, so a typo'd value raises ``EnvSpecError`` on the gang path
    instead of silently running sequential."""
    return str(envspec.get("TPUML_GANG_FIT")).strip().lower() != "off"


def resolve_gang_fit(n_lanes: int, lane_bytes: float) -> int:
    """Lanes fitted per gang dispatch (1 = the sequential per-param loop).

    ``TPUML_GANG_FIT``: ``off`` (default) keeps the sequential path, an
    integer pins a lane width, ``auto`` targets the whole static bucket.
    The result is clamped to the widest gang whose per-lane residents
    (estimator's ``_gang_lane_bytes`` estimate — dominated by the (n, B, K)
    logits block and its backward twin) fit the HBM budget
    (``TPUML_GANG_FIT_BUDGET``, default a quarter of device memory) —
    mirroring the ``TPUML_RF_TREE_BATCH`` resolver.
    """
    raw = str(envspec.get("TPUML_GANG_FIT")).strip().lower()
    if raw == "off":
        return 1
    tune_key = None
    if raw == "auto":
        want = n_lanes
        if autotune.active():
            tune_key = autotune.shape_key(
                n=n_lanes, d=int(lane_bytes), dtype="lane_bytes"
            )
            tuned = autotune.consult("gang_fit", tune_key)
            if isinstance(tuned, int) and 1 <= tuned <= n_lanes:
                want = tuned
                tune_key = None  # provenance already filed by consult
    else:
        try:
            want = int(raw)
        except ValueError:
            raise envspec.EnvSpecError(
                f"TPUML_GANG_FIT={raw!r}: expected 'auto', 'off', or a "
                "positive integer"
            ) from None
        if want < 1:
            raise envspec.EnvSpecError(
                f"TPUML_GANG_FIT={want}: lane width must be >= 1"
            )
    budget = envspec.get("TPUML_GANG_FIT_BUDGET")
    budget = float(budget) if budget else _default_gang_budget()
    fit = max(1, int(budget // max(1.0, float(lane_bytes))))
    lanes = max(1, min(want, fit))
    if tune_key is not None:
        autotune.record_heuristic("gang_fit", tune_key, lanes)
    telemetry.record_hbm_estimate("gang_fit", float(lane_bytes) * lanes)
    return lanes


@dataclass
class FitInputs:
    """Everything a fit function needs: the sharded design matrix + metadata.

    Replaces the reference's per-task ``(dfs, params)`` closure inputs
    (``core.py:749-762``) and ``PartitionDescriptor`` (``utils.py:163-200``):
    ragged partitions become an even row-shard plus a validity mask.
    """

    X: Optional[jax.Array]           # (N_pad, d_padded) row-sharded over dp; None until placed
    mask: Optional[jax.Array]        # (N_pad,) 1.0 valid / 0.0 padding
    mesh: Any
    n_rows: int                      # true (unpadded) row count
    n_features: int                  # true (logical) feature count
    y: Optional[jax.Array] = None    # (N_pad,) labels, padded with 0
    weight: Optional[jax.Array] = None
    X_sparse: Optional[Any] = None   # host scipy CSR when the sparse path is on
    dtype: Any = jnp.float32
    csize: int = 1                   # per-device row-chunk size (scan kernels)
    n_features_padded: int = 0       # X's column count incl. lane padding
    X_host: Optional[np.ndarray] = None  # the frame before it is placed (an estimator that places its own: PCA, LinearRegression)
    y_host: Optional[np.ndarray] = None  # its labels, where such an estimator has them
    folded: Optional[Any] = None     # what such an estimator's fold left on the devices, for the later lanes


# fit function: (inputs, params_dict) -> dict of named numpy arrays/scalars
FitFunc = Callable[[FitInputs, Dict[str, Any]], Dict[str, Any]]


@dataclass
class StreamInputs:
    """Chunked-fit inputs: a re-iterable source instead of resident arrays.

    The out-of-core analog of :class:`FitInputs` (reference Arrow-batch
    streaming + UVM, ``core.py:699-741``): device memory holds one chunk
    slab plus algorithm state, never the dataset.
    """

    source: Any                      # data.chunks.ChunkSource
    mesh: Any
    n_rows: int
    n_features: int
    dtype: Any = jnp.float32
    chunk_rows: int = 1 << 16


# streaming fit function: (stream_inputs, params_dict) -> named arrays
StreamFitFunc = Callable[[StreamInputs, Dict[str, Any]], Dict[str, Any]]


def _default_stream_threshold_bytes() -> int:
    """Dataset size above which fit streams instead of materializing.

    Overridable via ``TPUML_STREAM_THRESHOLD_BYTES``. Default: 60% of one
    device's reported memory (the design matrix must leave room for Gram
    temporaries) times the local device count; 8 GiB on the CPU backend,
    which reports no memory limit."""
    env = envspec.get("TPUML_STREAM_THRESHOLD_BYTES")
    if env is not None:
        return int(env)
    if jax.local_devices()[0].platform == "cpu":
        return 8 << 30
    return int(0.6 * device_bytes_limit(0) * len(jax.local_devices()))


class _TpuEstimator(Params, _TpuParams):
    """Abstract estimator (reference ``_CumlEstimator``, ``core.py:834-1032``)."""

    def __init__(self) -> None:
        super().__init__()
        self._init_tpu_params()
        self.logger = get_logger(type(self))

    # ---- subclass hooks --------------------------------------------------
    @abstractmethod
    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        ...

    @abstractmethod
    def _create_model(self, result: Dict[str, Any]) -> "_TpuModel":
        ...

    def _require_label(self) -> bool:
        return isinstance(self, HasLabelCol)

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        return False

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return False

    def _get_tpu_streaming_fit_func(
        self, dataset: DataFrame
    ) -> Optional[StreamFitFunc]:
        """Chunked out-of-core fit, or None when the algorithm requires the
        resident-matrix path. Engaged by :meth:`_should_stream`."""
        return None

    # ---- gang-fit hooks --------------------------------------------------
    def _gang_fit_groups(
        self, param_sets: List[Dict[str, Any]]
    ) -> Optional[List[Tuple[Any, List[int]]]]:
        """Static-bucket partition of ``param_sets`` for the gang path: a
        list of ``(bucket_key, [lane indices])`` where every lane in a
        bucket shares the batched kernel's *static* parameters (continuous
        params ride traced ``(B,)`` lane arrays and never split a bucket).
        ``None`` (default): estimator has no gang path."""
        return None

    def _get_tpu_gang_fit_func(
        self, dataset: DataFrame
    ) -> Optional[Callable[..., List[Dict[str, Any]]]]:
        """Gang companion of :meth:`_get_tpu_fit_func`: returns
        ``fn(inputs, group_param_sets, **fold_kwargs) -> [result, ...]``
        fitting one whole static bucket in a single device dispatch, or
        ``None`` when this dataset can't gang (e.g. degenerate labels)."""
        return None

    def _gang_fit_supports_folds(self) -> bool:
        """Whether the gang fit func accepts ``fold_id``/``lane_fold``/
        ``n_folds`` for fold-masked CV lanes."""
        return False

    def _gang_lane_bytes(self, inputs: "FitInputs") -> float:
        """Estimated HBM bytes each additional gang lane keeps resident
        (drives the ``TPUML_GANG_FIT_BUDGET`` clamp). Default assumes a
        few f32 row-vector temporaries per lane."""
        return 16.0 * float(inputs.X.shape[0])

    def _gang_dispatch(
        self,
        inputs: "FitInputs",
        param_sets: List[Dict[str, Any]],
        *,
        gang_fit: Callable[..., List[Dict[str, Any]]],
        cls_name: str,
        fold_id: Optional[jax.Array] = None,
        lane_folds: Optional[List[int]] = None,
        n_folds: int = 0,
        allow_singletons: bool = False,
    ) -> Tuple[Dict[int, Dict[str, Any]], Dict[int, Dict[str, Any]], Dict[int, Dict[str, int]]]:
        """Fit as many lanes of ``param_sets`` as the resolver allows in
        batched device dispatches. Returns ``(results, reports, res_deltas)``
        keyed by lane index; lanes NOT in the maps fall through to the
        caller's sequential loop (singleton chunks stay sequential so solo
        numerics are untouched, unless ``allow_singletons`` — the fold-masked
        CV path — where even a stray lane needs the batched kernel)."""
        global _GANG_PARTITION_LOGGED
        groups = self._gang_fit_groups(param_sets)
        if not groups:
            return {}, {}, {}
        from .runtime import counters as _res_counters
        from .utils.profiling import annotate

        lane_bytes = float(self._gang_lane_bytes(inputs))
        min_chunk = 1 if allow_singletons else 2
        plan: List[Tuple[Any, List[int]]] = []
        for key, idxs in groups:
            width = resolve_gang_fit(len(idxs), lane_bytes)
            if width < min_chunk:
                continue
            for c0 in range(0, len(idxs), width):
                chunk = idxs[c0 : c0 + width]
                if len(chunk) >= min_chunk:
                    plan.append((key, chunk))
        if not plan:
            return {}, {}, {}
        if not _GANG_PARTITION_LOGGED:
            self.logger.debug(
                "gang-fit static-bucket partition: %s",
                [(str(k), len(c)) for k, c in plan],
            )
            _GANG_PARTITION_LOGGED = True
        results: Dict[int, Dict[str, Any]] = {}
        reports: Dict[int, Dict[str, Any]] = {}
        deltas: Dict[int, Dict[str, int]] = {}
        for key, chunk in plan:
            res_base = _res_counters.snapshot()
            group_ps = [param_sets[i] for i in chunk]
            kw: Dict[str, Any] = {}
            if fold_id is not None:
                assert lane_folds is not None
                kw = dict(
                    fold_id=fold_id,
                    lane_fold=np.asarray([lane_folds[i] for i in chunk], np.int32),
                    n_folds=n_folds,
                )
            with annotate(f"{cls_name}.gang_fit"), telemetry.span(
                f"{cls_name}.gang_fit",
                lanes=len(chunk),
                bucket=str(key),
            ):
                outs = gang_fit(inputs, group_ps, **kw)
            res_delta = _res_counters.delta_since(res_base)
            _res_counters.bump("gang_dispatches")
            _res_counters.bump("gang_lanes_total", len(chunk))
            for lane_pos, i in enumerate(chunk):
                results[i] = outs[lane_pos]
                deltas[i] = res_delta
                reports[i] = {
                    "gang_lanes": len(chunk),
                    "gang_groups": len(plan),
                    "gang_bucket": str(key),
                }
                if lane_folds is not None:
                    reports[i]["gang_fold"] = int(lane_folds[i])
        return results, reports, deltas

    def _gang_cv_fit_multiple(
        self,
        dataset: DataFrame,
        paramMaps: Sequence[Dict[Any, Any]],
        n_folds: int,
        seed: int,
    ) -> Optional[List[List["_TpuModel"]]]:
        """Fold-masked gang CV: fit the whole ``n_folds × len(paramMaps)``
        grid as gang lanes over ONE resident X, each lane's objective
        masking ``fold_id == lane_fold`` rows on the fly. Returns
        ``models[fold][map]`` or ``None`` (caller falls back to the
        per-fold sequential path). All-or-nothing: a grid that can't gang
        completely is declined rather than half-ganged."""
        if not _gang_env_on():
            return None
        if self._gang_fit_supports_folds() is False:
            return None
        stream_func = self._get_tpu_streaming_fit_func(dataset)
        if stream_func is not None and self._should_stream(dataset):
            # fold masking needs the resident design matrix
            return None
        gang_fit = self._get_tpu_gang_fit_func(dataset)
        if gang_fit is None:
            return None
        with _x64_ctx(np.float64 if not self._float32_inputs else np.float32):
            return self._gang_cv_fit_x64scoped(
                dataset, paramMaps, n_folds, seed, gang_fit
            )

    def _gang_cv_fit_x64scoped(
        self,
        dataset: DataFrame,
        paramMaps: Sequence[Dict[Any, Any]],
        n_folds: int,
        seed: int,
        gang_fit: Callable[..., List[Dict[str, Any]]],
    ) -> Optional[List[List["_TpuModel"]]]:
        from .data.dataframe import kfold_ids
        from .utils.profiling import annotate

        self._apply_verbosity()
        cls_name = type(self).__name__
        with annotate(f"{cls_name}.preprocess"), telemetry.span(
            "preprocess", gang_cv=True
        ):
            inputs = self._pre_process_data(dataset)
        # the SAME seeded draw kfold() makes, so masked lanes see exactly
        # the rows the sequential per-fold path trains on
        fold_host = kfold_ids(dataset.count(), n_folds, seed)
        fold_dev = shard_aligned(
            fold_host.astype(np.int32), inputs.mesh, inputs.X.shape[0]
        )
        estimators: List[_TpuEstimator] = []
        map_param_sets: List[Dict[str, Any]] = []
        for pm in paramMaps:
            est = self.copy()
            self._copy_tpu_params(est)
            kw = {p.name if hasattr(p, "name") else p: v for p, v in pm.items()}
            est._set_params(**kw)
            estimators.append(est)
            map_param_sets.append(dict(est._tpu_params))
        lanes = [(f, j) for f in range(n_folds) for j in range(len(paramMaps))]
        lane_ps = [map_param_sets[j] for _, j in lanes]
        lane_folds = [f for f, _ in lanes]
        results, reports, deltas = self._gang_dispatch(
            inputs,
            lane_ps,
            gang_fit=gang_fit,
            cls_name=cls_name,
            fold_id=fold_dev,
            lane_folds=lane_folds,
            n_folds=n_folds,
            allow_singletons=True,
        )
        if len(results) < len(lanes):
            return None
        out: List[List[_TpuModel]] = []
        for f in range(n_folds):
            row: List[_TpuModel] = []
            for j in range(len(paramMaps)):
                i = lanes.index((f, j))
                est = estimators[j]
                model = est._create_model(results[i])
                est._copyValues(model)
                est._copy_tpu_params(model)
                model._resilience_report = deltas.get(i, {})
                model._fit_report = reports[i]
                row.append(model)
            out.append(row)
        return out

    def _resolved_weight_col(self) -> Optional[str]:
        """The explicitly-set weight column, or None — the ONE definition
        of weight-col eligibility shared by the stream gate and both data
        planes."""
        if (
            isinstance(self, HasWeightCol)
            and self.hasParam("weightCol")
            and self.isSet("weightCol")
            and self.getOrDefault("weightCol") is not None
        ):
            return self.getOrDefault("weightCol")
        return None

    # ---- streaming decision / data plane --------------------------------
    def _should_stream(self, dataset: DataFrame) -> bool:
        if self._streaming is not None:
            return bool(self._streaming)
        from .data.dataframe import ParquetScanFrame

        input_col, input_cols = self._get_input_columns()
        if isinstance(dataset, ParquetScanFrame) and not dataset.is_materialized():
            # multi-column features are resident-only, and streaming can
            # only read DISK-backed columns: a chained stage whose
            # features/label col is a prior transform's in-memory output
            # (AugmentedScanFrame) takes the materializing path
            if input_cols is not None:
                return False
            needed = [input_col]
            if self._require_label():
                needed.append(self.getOrDefault("labelCol"))
            wcol = self._resolved_weight_col()
            if wcol is not None:
                needed.append(wcol)
            return all(dataset.has_disk_column(c) for c in needed)
        if input_cols is not None:
            n_features = len(input_cols)
        else:
            col = dataset.column(input_col)
            if (
                _is_sparse(col)
                and self.hasParam("enable_sparse_data_optim")
                and self.isDefined("enable_sparse_data_optim")
                and self.getOrDefault("enable_sparse_data_optim") is True
            ):
                # explicit sparse opt-in (reference ``params.py:42-63``):
                # chunked-CSR streaming is the sparse compute path — the
                # matrix must never densify in full
                return True
            n_features = int(col.shape[1]) if col.ndim == 2 or _is_sparse(col) else 1
        itemsize = 4 if self._float32_inputs else 8
        # GLOBAL row count: the stream-vs-resident decision is a
        # compile-time constant all ranks must agree on (ranks deciding
        # differently would issue mismatched collectives and deadlock)
        est_bytes = global_row_count(dataset.count()) * n_features * itemsize
        return est_bytes > _default_stream_threshold_bytes()

    def _pre_process_stream(self, dataset: DataFrame) -> StreamInputs:
        import jax as _jax

        from .data.chunks import (
            ArrayChunkSource,
            CSRChunkSource,
            auto_chunk_rows,
        )
        from .data.dataframe import ParquetScanFrame
        from .parallel.mesh import local_mesh

        if _jax.process_count() > 1:
            # streaming is partition-local: each process streams its chunks
            # through its OWN chips; cross-process combination happens at
            # the sufficient-statistics level (ops/streaming.py allreduces
            # partials — the reference's per-worker Arrow stream + NCCL
            # allreduce architecture)
            mesh = local_mesh()
        else:
            mesh = make_mesh(self.num_workers)
        label_col = (
            self.getOrDefault("labelCol") if self._require_label() else None
        )
        weight_col = self._resolved_weight_col()

        input_col, input_cols = self._get_input_columns()
        scan_cols_on_disk = all(
            dataset.has_disk_column(c)
            for c in [input_col, label_col, weight_col]
            if c is not None
        ) if isinstance(dataset, ParquetScanFrame) else False
        if (
            isinstance(dataset, ParquetScanFrame)
            and not dataset.is_materialized()
            and scan_cols_on_disk
        ):
            # NOT scan_cols_on_disk: a column lives only in memory (e.g. a
            # prior streaming transform's output, possibly SHADOWING a
            # same-named disk column) — the in-memory branch below reads
            # the authoritative values via dataset.column()
            if input_cols is not None:
                raise ValueError(
                    "streaming fit over a parquet scan requires a single "
                    "vector features column (featuresCols is resident-only)"
                )
            source = dataset.chunk_source(
                features_col=input_col, label_col=label_col, weight_col=weight_col
            )
            dtype = np.float32 if self._float32_inputs else np.float64
        else:
            X, X_sparse = _resolve_feature_matrix(self, dataset)
            y = (
                np.asarray(dataset.column(label_col))
                if label_col is not None
                else None
            )
            w = (
                np.asarray(dataset.column(weight_col))
                if weight_col is not None
                else None
            )
            if X_sparse is not None:
                dtype = np.float32 if self._float32_inputs else np.float64
                source = CSRChunkSource(X_sparse, y, w)
            else:
                dtype = self._target_dtype(X)
                source = ArrayChunkSource(X, y, w)

        chunk_rows = self._stream_chunk_rows or auto_chunk_rows(
            source.n_features, np.dtype(dtype).itemsize, mesh.shape["dp"]
        )
        n_dp = mesh.shape["dp"]
        chunk_rows = max(n_dp, (chunk_rows // n_dp) * n_dp)
        return StreamInputs(
            source=source,
            mesh=mesh,
            n_rows=global_row_count(int(source.n_rows)),
            n_features=int(source.n_features),
            dtype=jnp.dtype(dtype),
            chunk_rows=int(chunk_rows),
        )

    # ---- data plane ------------------------------------------------------
    def _target_dtype(self, X: Optional[np.ndarray]) -> Any:
        if self._float32_inputs:
            return np.float32
        if X is not None and X.dtype == np.float64:
            return np.float64
        return np.float32

    def _chunk_rows(self, n_rows: int, n_dp: int) -> int:
        """Per-device scan chunk size; subclasses with chunked-scan kernels
        override (rows are padded so each shard is a multiple of this)."""
        return 1

    @staticmethod
    def _equal_chunk_rows(n_rows: int, n_dp: int, cap: int) -> int:
        """Smallest chunk <= cap that divides each device's shard into equal
        pieces: bounds padding to < n_chunks rows/device (vs up to cap-1)."""
        per_dev = max(1, -(-n_rows // n_dp))
        n_chunks = -(-per_dev // cap)
        return -(-per_dev // n_chunks)

    @staticmethod
    def rows_chunkable(n_padded_rows: int, mesh: Any, csize: int) -> bool:
        """True when a row-sharded array of ``n_padded_rows`` can take a
        chunked-scan kernel path: a real chunk size and per-device rows
        divisible by it (the ``shard_rows`` padding invariant). Single
        source of truth for the gate used by PCA/LinearRegression fits."""
        from .parallel.mesh import DP_AXIS

        return (
            csize is not None
            and csize > 1
            and n_padded_rows % (csize * mesh.shape[DP_AXIS]) == 0
        )

    def _feature_pad_multiple(self) -> int:
        """Column multiple the design matrix is zero-padded to on the device
        (0 = none). Estimators whose fit kernel reads X inside a
        ``while_loop`` (KMeans) override: at lane-unaligned d XLA inserts a
        defensive full copy of X around the loop, and on TPU the minor dim
        is physically tiled to 128 anyway, so explicit zero columns cost no
        extra HBM while removing the 2x copy. The pad is the device's:
        ``_pre_process_data`` hands the padded width to ``shard_rows``,
        which puts the host frame as it is into a zero buffer of that width
        (no ``np.pad``, no second host copy of X)."""
        return 0

    def _x_placement_dtype(self) -> Optional[Any]:
        """Device dtype the design matrix is PLACED in (None = the resolved
        input dtype). Estimators whose fit kernel reads X in a narrower
        dtype (LogisticRegression's bf16 objective) override: placing X
        narrow from the host halves H2D bytes and — critically — avoids an
        in-program ``astype``, which would hold the wide argument and the
        narrow copy live at once (OOM at near-HBM scales). Labels, weights,
        masks and solver state keep the resolved input dtype."""
        return None

    def _model_axis_bytes(self, n_features_padded: int, dtype) -> float:
        """Bytes of the largest structure the estimator can shard along the
        model (``mp``) axis — what ``TPUML_MESH_MP=auto`` budgets against.
        Default: the d×d Gram/covariance accumulator (PCA, the linear
        solvers). Estimators whose model axis is not feature-squared
        (KMeans centroids, IVF lists) override."""
        return float(n_features_padded) ** 2 * np.dtype(dtype).itemsize

    def _host_inputs(self, dataset: DataFrame) -> FitInputs:
        """The host part of preprocessing: the feature column resolved,
        cast and made contiguous, the mesh and the chunk size — a
        :class:`FitInputs` whose frame is still on the host (``X_host``),
        with nothing placed. ``_pre_process_data`` places it; an estimator
        that places its own frame (PCA) returns this as it is."""
        X, X_sparse = _resolve_feature_matrix(self, dataset)
        if X_sparse is not None:
            # Sparse path: the device arrays are densified (TPUs have no
            # sparse MXU path); the host CSR is kept on FitInputs so solvers
            # with a dedicated sparse formulation (LogisticRegression) can
            # stream it instead. Reference CSR ingestion: ``core.py:196-241``.
            n_rows, n_features = X_sparse.shape
            dtype = self._target_dtype(None)
        else:
            dtype = self._target_dtype(X)
            X = np.ascontiguousarray(X, dtype=dtype)
            n_rows, n_features = X.shape
        pad_mult = self._feature_pad_multiple()
        d_padded = int(n_features)
        if pad_mult > 0 and n_features % pad_mult:
            d_padded = -(-int(n_features) // pad_mult) * pad_mult
        # model-axis degree is resolved AFTER the feature width is known so
        # TPUML_MESH_MP=auto can budget against the estimator's dominant
        # model-axis structure (the d×d Gram by default)
        mp = resolve_mesh_mp(self._model_axis_bytes(d_padded, dtype))
        mesh = make_mesh(self.num_workers, mp=mp)
        # chunk size must be agreed across the process world (it shapes the
        # compiled program and its collectives): derive it from the GLOBAL
        # row count, never the local partition size
        n_global = global_row_count(int(n_rows))
        csize = self._chunk_rows(n_global, mesh.shape["dp"])
        if X_sparse is not None:
            X = np.asarray(X_sparse.todense(), dtype=dtype)
        place = self._x_placement_dtype()
        if place is not None and np.dtype(dtype) == np.dtype(np.float32):
            X = X.astype(place)
        return FitInputs(
            X=None,
            mask=None,
            mesh=mesh,
            n_rows=n_global,
            n_features=int(n_features),
            X_sparse=X_sparse,
            dtype=jnp.dtype(dtype),
            csize=csize,
            n_features_padded=d_padded,
            X_host=X,
        )

    def _places_its_own_frame(self, inputs: FitInputs) -> bool:
        """Whether the fit function places the frame itself (``X_host``,
        ``y_host``), folding each row block as it lands
        (``parallel.mesh.shard_rows(fold=)``): an estimator whose fit is a
        sum over rows (PCA, LinearRegression) answers True where its fold
        applies."""
        return False

    def _pre_process_data(self, dataset: DataFrame) -> FitInputs:
        """Put-then-solve: the frame, its mask, labels and weights on the
        devices before the fit function runs — for a solver that needs the
        whole frame at once (every pass of L-BFGS and Lloyd reads every
        row). An estimator whose fit is a sum over rows owns its placement
        instead (:meth:`_places_its_own_frame`): it gets the host part
        alone."""
        inputs = self._host_inputs(dataset)
        dtype = inputs.dtype
        if self._require_label():
            label_col = self.getOrDefault("labelCol")
            inputs.y_host = np.asarray(dataset.column(label_col), dtype=dtype)
        if self._places_its_own_frame(inputs):
            return inputs
        # the zero columns up to d_padded are written on the device
        inputs.X, inputs.mask = shard_rows(
            inputs.X_host, inputs.mesh, inputs.csize, cols=inputs.n_features_padded
        )
        inputs.X_host = None
        if inputs.y_host is not None:
            inputs.y = shard_aligned(inputs.y_host, inputs.mesh, inputs.X.shape[0])
            inputs.y_host = None
        wcol = self._resolved_weight_col()
        if wcol is not None:
            if wcol not in dataset:
                raise ValueError(
                    f"weightCol {wcol!r} not found in dataset columns {dataset.columns}"
                )
            w_host = np.asarray(dataset.column(wcol), dtype=dtype)
            inputs.weight = shard_aligned(w_host, inputs.mesh, inputs.X.shape[0])
        return inputs

    # ---- fit -------------------------------------------------------------
    def fit(self, dataset: DataFrame, params: Optional[Dict[Any, Any]] = None) -> "_TpuModel":
        if params:
            est = self.copy()
            self._copy_tpu_params(est)
            kw = {p.name if hasattr(p, "name") else p: v for p, v in params.items()}
            est._set_params(**kw)
            return est.fit(dataset)
        models = self._fit_internal(dataset, None)
        return models[0]

    def fitMultiple(
        self, dataset: DataFrame, paramMaps: Sequence[Dict[Any, Any]]
    ) -> Iterator[Tuple[int, "_TpuModel"]]:
        """Fit all param maps in ONE data pass (reference ``core.py:863-892``):
        the design matrix is sharded onto the mesh once and every param set
        reuses the resident device arrays."""
        if self._enable_fit_multiple_in_single_pass():
            models = self._fit_internal(dataset, list(paramMaps))
        else:
            models = [self.fit(dataset, pm) for pm in paramMaps]
        return _FitMultipleIterator(models)

    def _fit_internal(
        self, dataset: DataFrame, paramMaps: Optional[List[Dict[Any, Any]]]
    ) -> List["_TpuModel"]:
        with _x64_ctx(np.float64 if not self._float32_inputs else np.float32):
            return self._fit_internal_x64scoped(dataset, paramMaps)

    def _fit_internal_x64scoped(
        self, dataset: DataFrame, paramMaps: Optional[List[Dict[Any, Any]]]
    ) -> List["_TpuModel"]:
        # root telemetry span: every preprocess/dispatch/streaming span
        # of this fit nests under it, so the exported trace accounts the
        # fit's full wall time
        with telemetry.span(f"{type(self).__name__}.fit"):
            return self._fit_lanes_x64scoped(dataset, paramMaps)

    def _fit_coscheduled(
        self, dataset: DataFrame, estimators: List["_TpuEstimator"]
    ) -> List["_TpuModel"]:
        """Gang entry point for the fit scheduler (`runtime/scheduler.py`):
        fit several ready estimator instances of this class over one shared
        dataset in a single pass — one preprocess sharding the design
        matrix once, gang-batched lanes when the kernel supports it (same
        `TPUML_GANG_FIT` gating as `fitMultiple`), sequential lanes
        otherwise. Returns models order-aligned with ``estimators``."""
        with _x64_ctx(np.float64 if not self._float32_inputs else np.float32):
            with telemetry.span(
                f"{type(self).__name__}.fit", coscheduled=len(estimators)
            ):
                return self._fit_lanes_x64scoped(
                    dataset, None, coscheduled=estimators
                )

    def _fit_lanes_x64scoped(
        self,
        dataset: DataFrame,
        paramMaps: Optional[List[Dict[Any, Any]]],
        coscheduled: Optional[List["_TpuEstimator"]] = None,
    ) -> List["_TpuModel"]:
        # phase annotations land as named ranges on the profiler timeline
        # (the reference's NVTX ranges, ``RapidsRowMatrix.scala:62,70``)
        from .utils.profiling import annotate

        self._apply_verbosity()
        cls_name = type(self).__name__
        stream_func = self._get_tpu_streaming_fit_func(dataset)
        streaming = stream_func is not None and self._should_stream(dataset)
        if streaming:
            self.logger.info(
                "Streaming fit engaged (out-of-core chunked ingestion)."
            )
            with annotate(f"{cls_name}.preprocess"), telemetry.span(
                "preprocess", streaming=True
            ):
                inputs: Any = self._pre_process_stream(dataset)
            fit_func: Any = stream_func
        else:
            with annotate(f"{cls_name}.preprocess"), telemetry.span(
                "preprocess", streaming=False
            ):
                inputs = self._pre_process_data(dataset)
            fit_func = self._get_tpu_fit_func(dataset)
        models: List[_TpuModel] = []
        param_sets: List[Dict[str, Any]]
        if coscheduled is not None:
            # scheduler gang: the lanes are ready estimator instances
            # (each tenant's own object), not paramMaps over self
            estimators = list(coscheduled)
            param_sets = [dict(est._tpu_params) for est in estimators]
        elif paramMaps is None:
            param_sets = [dict(self._tpu_params)]
            estimators = [self]
        else:
            estimators = []
            param_sets = []
            for pm in paramMaps:
                est = self.copy()
                self._copy_tpu_params(est)
                kw = {p.name if hasattr(p, "name") else p: v for p, v in pm.items()}
                est._set_params(**kw)
                estimators.append(est)
                param_sets.append(dict(est._tpu_params))
        from .runtime import counters as _res_counters

        # gang path: batch param lanes sharing static kernel params into one
        # device dispatch over the resident X. Env-gated (TPUML_GANG_FIT,
        # default off) so the default path below is bit-identical to HEAD;
        # any lane the gang declines (off, singleton bucket, streaming,
        # estimator without a gang kernel) falls through to the loop.
        gang_results: Dict[int, Dict[str, Any]] = {}
        gang_reports: Dict[int, Dict[str, Any]] = {}
        gang_deltas: Dict[int, Dict[str, int]] = {}
        gang_tuned: List[Dict[str, Any]] = []
        if not streaming and len(param_sets) > 1 and _gang_env_on():
            gang_fit = self._get_tpu_gang_fit_func(dataset)
            if gang_fit is not None:
                with autotune.collect() as gang_tuned:
                    gang_results, gang_reports, gang_deltas = (
                        self._gang_dispatch(
                            inputs,
                            param_sets,
                            gang_fit=gang_fit,
                            cls_name=cls_name,
                        )
                    )

        for lane, (est, ps) in enumerate(zip(estimators, param_sets)):
            if lane in gang_results:
                model = est._create_model(gang_results[lane])
                est._copyValues(model)
                est._copy_tpu_params(model)
                model._resilience_report = gang_deltas.get(lane, {})
                fit_report = gang_reports[lane]
                if gang_tuned:
                    fit_report = dict(fit_report or {})
                    fit_report["autotuned"] = list(gang_tuned)
                model._fit_report = fit_report
                models.append(model)
                continue
            res_base = _res_counters.snapshot()
            with autotune.collect() as tuned, annotate(
                f"{cls_name}.fit"
            ), telemetry.span("fit.dispatch", lane=lane, streaming=streaming):
                result = fit_func(inputs, ps)
            # fit provenance (model-axis degree, per-shard bytes, ...) rides
            # out of the kernel beside the model arrays; strip it before the
            # estimator unpacks result into model constructor kwargs. Absent
            # on the defaults path — reports attach only when a knob engaged.
            fit_report = result.pop("_fit_report", None) if isinstance(result, dict) else None
            if tuned:
                # knob decisions the tuner made during this dispatch —
                # value + provenance (cache_hit|probed|heuristic). Absent
                # (never an empty list) while TPUML_AUTOTUNE is off.
                fit_report = dict(fit_report or {})
                fit_report["autotuned"] = list(tuned)
            model = est._create_model(result)
            est._copyValues(model)
            est._copy_tpu_params(model)
            # resilience provenance: what the runtime had to do to land
            # this fit (retries/halvings/resume). Empty dict — and no log
            # line — on the clean path.
            res_delta = _res_counters.delta_since(res_base)
            model._resilience_report = res_delta
            if fit_report:
                model._fit_report = fit_report
            if res_delta:
                self.logger.info("resilience events during fit: %s", res_delta)
            if streaming:
                # ingest provenance: the wire encoding + pipeline depths the
                # chunk stream actually used (resolved knobs, not requested)
                from .ops.streaming import last_ingest_report

                model._ingest_report = last_ingest_report()
            models.append(model)
        return models

    # ---- persistence -----------------------------------------------------
    def write(self) -> "_Writer":
        return _Writer(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_Reader":
        return _Reader(cls)

    @classmethod
    def load(cls, path: str) -> "_TpuEstimator":
        return cls.read().load(path)

    def _get_model_attributes(self) -> Optional[Dict[str, Any]]:
        return None


class _FitMultipleIterator:
    """Thread-safe (index, model) iterator (reference ``core.py:789-831``)."""

    def __init__(self, models: List["_TpuModel"]):
        import threading

        self._models = models
        self._index = 0
        self._lock = threading.Lock()

    def __iter__(self) -> "_FitMultipleIterator":
        return self

    def __next__(self) -> Tuple[int, "_TpuModel"]:
        with self._lock:
            i = self._index
            if i >= len(self._models):
                raise StopIteration
            self._index += 1
        return i, self._models[i]


class _TpuEstimatorSupervised(_TpuEstimator, HasLabelCol):
    """Adds label handling (reference ``_CumlEstimatorSupervised``,
    ``core.py:1039-1092``)."""

    def _require_label(self) -> bool:
        return True


def batch_to_device(Xb: Any, put: Callable[[Any], jax.Array] = jnp.asarray) -> jax.Array:
    """A transform's host batch handed to the runtime (``put``: what the
    site called before, ``jnp.asarray`` or ``jax.device_put``) inside a
    ``transform.h2d`` span — the host's own seconds in the put, and the
    ``bytes`` that cross. A batch that is on the device already (a staged
    one) passes through bare: its bytes were counted where it crossed."""
    if not isinstance(Xb, np.ndarray):
        return put(Xb)
    with telemetry.span("transform.h2d", bytes=int(Xb.nbytes)):
        return put(Xb)


def output_to_host(v: Any, dtype: Any = None) -> np.ndarray:
    """A transform program's output as a host array (``np.asarray``: the
    host blocks until the program has run and the answer is back) inside a
    ``transform.d2h`` span. A host array passes through bare."""
    if isinstance(v, np.ndarray):
        return np.asarray(v, dtype=dtype)
    with telemetry.span("transform.d2h"):
        return np.asarray(v, dtype=dtype)


class _TpuModel(Params, _TpuParams):
    """Abstract fitted model (reference ``_CumlModel``, ``core.py:1101-1364``)."""

    # subclasses list their array attributes for persistence
    _model_attribute_names: List[str] = []

    # resilience events observed during this model's fit (runtime/counters
    # delta; {} on a clean path). Class-level default so models that never
    # went through a fit loop (e.g. load()ed from disk) still expose it.
    _resilience_report: Dict[str, int] = {}

    # gang-fit provenance ({"gang_lanes": B, "gang_groups": G,
    # "gang_bucket": key} when this model came out of a batched dispatch;
    # {} on the sequential path).
    _fit_report: Dict[str, Any] = {}

    # ingest provenance of a STREAMED fit (resolved wire dtype + pipeline
    # depths from ops.streaming.last_ingest_report); {} for resident fits
    # and load()ed models.
    _ingest_report: Dict[str, Any] = {}

    def __init__(self, **model_attributes: Any) -> None:
        super().__init__()
        self._init_tpu_params()
        self._model_attributes = model_attributes
        self.logger = get_logger(type(self))

    def _get_model_attributes(self) -> Dict[str, Any]:
        return self._model_attributes

    # ---- transform -------------------------------------------------------
    @abstractmethod
    def _get_tpu_transform_func(
        self, dataset: Optional[DataFrame] = None
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        """Return fn: host feature batch (n, d) -> dict of output columns.

        The returned fn should wrap a jitted kernel; core handles batching
        and column wiring (reference ``_get_cuml_transform_func``,
        ``core.py:1137-1167``)."""
        ...

    def _out_cols(self) -> List[str]:
        cols = []
        if isinstance(self, HasPredictionCol):
            cols.append(self.getOrDefault("predictionCol"))
        return cols

    def _memoized_transform_fn(
        self,
        key: Tuple[Any, ...],
        build: Callable[[], Callable[[np.ndarray], Dict[str, np.ndarray]]],
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        """Cache a transform closure on the model, keyed by everything it
        hoisted (output columns, engine knobs, params). A fresh closure
        per ``transform()`` call means a fresh ``jax.jit`` object — its
        trace cache starts empty, so every call retraces and re-stages
        the hoisted operands. Repeated transforms (the serving hot path)
        must hit the same jitted program, so the closure lives here."""
        cache = getattr(self, "_transform_fn_cache", None)
        if cache is None:
            cache = self._transform_fn_cache = {}
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
        return fn

    def transform(self, dataset: DataFrame) -> DataFrame:
        """Append prediction/output columns (reference ``core.py:1463-1568``).

        Embarrassingly parallel: rows are processed in device-sized batches;
        no collectives (matching the reference, which builds no communicator
        for transform)."""
        # root telemetry span: the wall a caller times around transform(),
        # feature extraction before the batches and the output frame after
        with telemetry.span(f"{type(self).__name__}.transform.call"):
            return self._transform_in_span(dataset)

    def _transform_in_span(self, dataset: DataFrame) -> DataFrame:
        from .data.dataframe import AugmentedScanFrame, ParquetScanFrame
        from .utils.profiling import annotate

        name = type(self).__name__
        self._apply_verbosity()
        if isinstance(dataset, ParquetScanFrame) and not dataset.is_materialized():
            # out-of-core transform (the reference transforms per Arrow
            # batch, ``core.py:1463-1568``): stream chunks through the
            # jitted transform; host memory holds the OUTPUT columns only
            # (O(n) scalars/embeddings), never the feature matrix. Only
            # when the input column lives ON DISK: a chained transform
            # whose featuresCol is a prior stage's in-memory output column
            # (AugmentedScanFrame) takes the materializing path below.
            input_col, input_cols = self._get_input_columns()
            if input_cols is None and dataset.has_disk_column(input_col):
                np_dtype = np.dtype(
                    np.float32 if self._float32_inputs else np.float64
                )
                with _x64_ctx(np_dtype):
                    fn = self._get_tpu_transform_func(dataset)
                    with annotate(f"{name}.transform"), telemetry.span(
                        f"{name}.transform", streamed=True
                    ):
                        out_columns = self._apply_streamed(fn, dataset, input_col)
                    self._log_transform_stages()
                return AugmentedScanFrame(dataset, out_columns)
        X = self._extract_features_for_transform(dataset)
        with _x64_ctx(X.dtype):
            fn = self._get_tpu_transform_func(dataset)
            with annotate(f"{name}.transform"), telemetry.span(
                f"{name}.transform", streamed=False
            ):
                out_columns = self._apply_batched(fn, X)
            self._log_transform_stages()
        with telemetry.span(
            "transform.assemble",
            columns=len(out_columns),
            bytes=sum(int(c.nbytes) for c in out_columns.values()),
        ):
            out = dataset
            for col_name, col in out_columns.items():
                out = out.withColumn(col_name, col)
        return out

    def _log_transform_stages(self) -> None:
        """Emit the per-stage wall-clock breakdown a transform engine
        accumulated (models attach a ``profiling.StageTimer`` as
        ``_transform_stage_timer``; no-op otherwise)."""
        st = getattr(self, "_transform_stage_timer", None)
        if st is not None:
            st.log_summary(self.logger)

    def _apply_streamed(
        self,
        fn: Callable[[np.ndarray], Dict[str, np.ndarray]],
        scan: Any,
        input_col: str,
    ) -> Dict[str, np.ndarray]:
        source = scan.chunk_source(features_col=input_col)
        bs = self._transform_batch_rows()
        dtype = np.float32 if self._float32_inputs else np.float64
        chunks: Dict[str, List[np.ndarray]] = {}
        for chunk in source.iter_chunks(bs, dtype=dtype):
            Xb = np.ascontiguousarray(chunk.X[: chunk.n_valid], dtype=dtype)
            for k, v in fn(Xb).items():
                chunks.setdefault(k, []).append(np.asarray(v)[: chunk.n_valid])
        return {k: np.concatenate(v, axis=0) for k, v in chunks.items()}

    def _extract_features_for_transform(self, dataset: DataFrame) -> np.ndarray:
        with telemetry.span("transform.extract") as e_span:
            X, X_sparse = _resolve_feature_matrix(self, dataset)
            if X is None:
                X = np.asarray(X_sparse.todense())
            dtype = np.float32 if self._float32_inputs else X.dtype
            out = np.ascontiguousarray(X, dtype=dtype)
            e_span.set_attr(bytes=int(out.nbytes), copied=out is not X)
            return out

    def _transform_batch_rows(self) -> int:
        return 1 << 17  # 131072 rows/batch keeps HBM use bounded

    # Models whose transform kernels accept committed device arrays set
    # this: ``_apply_batched`` then puts batch i+1 on the device before it
    # asks for batch i's outputs, so the put crosses the link while batch i
    # computes (the async dispatch returns before device work finishes).
    # Every other model gets a host slice and puts it inside its own
    # ``transform.apply``: nothing crosses ahead of its batch.
    _transform_device_staging = False

    def _apply_batched(
        self,
        fn: Callable[[np.ndarray], Dict[str, np.ndarray]],
        X: np.ndarray,
    ) -> Dict[str, np.ndarray]:
        staging = self._transform_device_staging
        n = X.shape[0]
        bs = self._transform_batch_rows()

        def stage(lo: int, batch: int) -> Any:
            with telemetry.span(
                "transform.stage", rows=min(bs, n - lo), batch=batch
            ):
                Xb = X[lo : lo + bs]
                return batch_to_device(Xb, jax.device_put) if staging else Xb

        chunks: Dict[str, List[np.ndarray]] = {}
        nxt = stage(0, 0)
        for batch, lo in enumerate(range(0, max(n, 1), bs)):
            cur = nxt
            hi = min(lo + bs, n)
            if hi < n:
                # stage the NEXT batch before materializing this batch's
                # outputs (the fetch below blocks on the device): a put
                # ahead where the model stages, a host slice where not
                nxt = stage(hi, batch + 1)
            with telemetry.span("transform.apply", rows=hi - lo, batch=batch):
                part = fn(cur)
            with telemetry.span("transform.fetch", rows=hi - lo, batch=batch):
                for k, v in part.items():
                    chunks.setdefault(k, []).append(output_to_host(v)[: hi - lo])
        if n <= bs:
            return {k: v[0] for k, v in chunks.items()}
        with telemetry.span(
            "transform.assemble",
            columns=len(chunks),
            bytes=sum(int(c.nbytes) for v in chunks.values() for c in v),
        ):
            return {k: np.concatenate(v, axis=0) for k, v in chunks.items()}

    # ---- multi-model support (CV single-pass) ----------------------------
    @classmethod
    def _combine(cls, models: List["_TpuModel"]) -> "_TpuModel":
        raise NotImplementedError(f"{cls.__name__} does not support _combine")

    def _transformEvaluate(self, dataset: DataFrame, evaluator: Any) -> List[float]:
        raise NotImplementedError(
            f"{type(self).__name__} does not support _transformEvaluate"
        )

    # ---- persistence -----------------------------------------------------
    def write(self) -> "_Writer":
        return _Writer(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_Reader":
        return _Reader(cls)

    @classmethod
    def load(cls, path: str) -> "_TpuModel":
        return cls.read().load(path)

    def cpu(self) -> "_TpuModel":
        """The reference converts to a Spark JVM model (``feature.py:365-379``);
        Spark-free, the model already runs on CPU via jax — return self. For
        serving *outside* this framework entirely, :meth:`to_sklearn` exports
        a stock fitted scikit-learn estimator."""
        return self

    def to_sklearn(self):
        """Export to a fitted scikit-learn estimator (accelerator-free
        serving; the analog of the reference's Spark-model conversion in
        ``cpu()``). See :mod:`spark_rapids_ml_tpu.export`."""
        from .export import to_sklearn

        return to_sklearn(self)


class _TpuModelWithPredictionCol(_TpuModel, HasPredictionCol):
    pass


# ---------------------------------------------------------------------------
# Persistence (reference ``core.py:244-331``): metadata JSON + npz arrays.
# ---------------------------------------------------------------------------


class _Writer:
    def __init__(self, instance: Union[_TpuEstimator, _TpuModel]):
        self._instance = instance
        self._overwrite = False

    def overwrite(self) -> "_Writer":
        self._overwrite = True
        return self

    def save(self, path: str) -> None:
        inst = self._instance
        if os.path.exists(path):
            if self._overwrite:
                shutil.rmtree(path)
            else:
                raise FileExistsError(f"Path {path} exists; use write().overwrite()")
        os.makedirs(path)
        params = {}
        for p in inst.params:
            if inst.isSet(p):
                v = inst.getOrDefault(p)
                params[p.name] = v if _json_ok(v) else str(v)
        defaults = {}
        for p in inst.params:
            if inst.hasDefault(p):
                v = inst._defaultParamMap[p]
                defaults[p.name] = v if _json_ok(v) else str(v)
        meta = {
            "class": f"{type(inst).__module__}.{type(inst).__name__}",
            "uid": inst.uid,
            "paramMap": params,
            "defaultParamMap": defaults,
            "tpuParams": {k: v for k, v in inst._tpu_params.items() if _json_ok(v)},
            "numWorkers": inst._num_workers,
            "float32Inputs": inst._float32_inputs,
            "streaming": inst._streaming,
            "streamChunkRows": inst._stream_chunk_rows,
        }
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=2, default=str)
        attrs = inst._get_model_attributes()
        if attrs is not None:
            arrays = {}
            scalars = {}
            for k, v in attrs.items():
                a = np.asarray(v)
                if a.dtype == object:
                    scalars[k] = v
                elif a.ndim == 0 and _json_ok(v):
                    scalars[k] = v if not isinstance(v, np.generic) else v.item()
                else:
                    arrays[k] = a
            if arrays:
                np.savez(os.path.join(path, "model.npz"), **arrays)
            with open(os.path.join(path, "attributes.json"), "w") as f:
                json.dump(scalars, f, indent=2, default=str)


class _Reader:
    def __init__(self, cls: type):
        self._cls = cls

    def load(self, path: str) -> Any:
        import importlib

        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        module_name, cls_name = meta["class"].rsplit(".", 1)
        module = importlib.import_module(module_name)
        cls = getattr(module, cls_name)

        attrs: Dict[str, Any] = {}
        npz_path = os.path.join(path, "model.npz")
        if os.path.exists(npz_path):
            with np.load(npz_path, allow_pickle=False) as z:
                attrs.update({k: z[k] for k in z.files})
        attrs_json = os.path.join(path, "attributes.json")
        if os.path.exists(attrs_json):
            with open(attrs_json) as f:
                attrs.update(json.load(f))

        if issubclass(cls, _TpuModel):
            inst = cls(**attrs)
        else:
            inst = cls()
        for name, v in meta.get("paramMap", {}).items():
            if inst.hasParam(name):
                inst._set(**{name: v})
        inst._tpu_params.update(meta.get("tpuParams", {}))
        inst._num_workers = meta.get("numWorkers")
        inst._float32_inputs = meta.get("float32Inputs", True)
        inst._streaming = meta.get("streaming")
        inst._stream_chunk_rows = meta.get("streamChunkRows")
        return inst


def _json_ok(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False
