"""PCA — Spark ML drop-in, TPU-native fit/transform.

Reference: ``/root/reference/python/src/spark_rapids_ml/feature.py`` (447 LoC).
API parity targets:
  * params: ``k`` (mapped to backend ``n_components``, reference
    ``feature.py:61-75``), ``inputCol``/``featuresCol``/``featuresCols``,
    ``outputCol``.
  * model attributes: ``mean_``, ``components_``, ``explained_variance_``,
    ``explained_variance_ratio_``, ``singular_values_``, plus Spark-style
    ``pc`` / ``explainedVariance``.
  * transform semantics: Spark's PCA does NOT mean-center at transform time;
    the reference compensates cuML's centering by adding the projected mean
    back (``feature.py:426-439``). We compute ``X @ pc`` directly.

TPU-native fit (vs reference's cuML ``PCAMG.fit``, ``feature.py:216-259``):
the covariance is a sum over rows, so the estimator places its own frame and
folds every row block into float32-exact shifted sums as the block lands on
its device (``ops.linalg.gram_fold`` under ``parallel.mesh.shard_rows``), the
Gram running under the frame's crossing; one finish program then psums the
partials over the dp mesh axis, re-centres, and takes the k leading
eigenpairs of the replicated d×d covariance (``ops.linalg.topk_eigh``) with a
deterministic sign flip.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core import (
    FitFunc,
    FitInputs,
    _TpuEstimator,
    _TpuModel,
    batch_to_device,
    output_to_host,
)
from ..data.dataframe import DataFrame
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasInputCol,
    HasOutputCol,
    Param,
    TypeConverters,
    _mk,
)
from ..ops.linalg import (
    cov_from_gram_folds,
    gram_block_rows,
    gram_fold,
    gram_fold_zeros,
    gram_pallas_declined,
    gram_tile,
    host_mean_sample,
    mean_and_cov,
    mean_and_cov_chunked,
    mp_gram_blocks,
    rows_minor,
    topk_eigh,
)
from ..parallel.mesh import DP_AXIS, allreduce_sum_host, row_sharding, shard_rows
from ..runtime import telemetry


class PCAClass:
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # reference ``feature.py:61-75``
        return {"k": "n_components"}

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        return {}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {"n_components": None, "whiten": False}


class _PCAParams(HasInputCol, HasOutputCol, HasFeaturesCol, HasFeaturesCols):
    k = _mk("k", "number of principal components", TypeConverters.toInt)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(outputCol="pca_features")

    def getK(self) -> int:
        return self.getOrDefault("k")


@functools.partial(jax.jit, static_argnames=("k",))
def _pca_from_cov(mean: jax.Array, cov: jax.Array, n: jax.Array, k: int):
    """Finalize PCA from (mean, covariance, count) — shared by the resident
    and streaming fits so both produce bit-identical model attributes."""
    evals, evecs = topk_eigh(cov, k)
    evals = jnp.maximum(evals, 0.0)
    total_var = jnp.trace(cov)
    # singular values of the centered matrix: sqrt(λ·(n-1))
    singular_values = jnp.sqrt(evals * (n - 1.0))
    return {
        "mean": mean,
        "components": evecs.T,            # (k, d)
        "explained_variance": evals,
        "explained_variance_ratio": evals / total_var,
        "singular_values": singular_values,
    }


@functools.partial(
    jax.jit, static_argnames=("k", "mesh", "csize", "mp_blocks")
)
def _pca_fit_kernel(
    X: jax.Array, mask: jax.Array, k: int, mesh=None, csize=None,
    mp_blocks: bool = False,
):
    """The whole fit as ONE program over a frame that is already resident:
    covariance, then :func:`_pca_from_cov`. What ``PCA.fit`` runs only where
    the Gram's accumulator is column-sharded over the mesh's mp axis
    (``mp_blocks``; static, resolve with ``mp_gram_blocks`` outside jit; the
    blocked covariance rides out in the result so the caller can measure its
    per-shard bytes) — every other resident fit folds the row blocks under
    the frame's crossing (:class:`_GramFold`, :func:`_pca_finish`) and never
    hands the frame to one program. Also the multi-chip dry run's
    (``__graft_entry__``).

    With ``mesh``/``csize`` (rows dp-sharded, padded to a per-device
    ``csize`` multiple) the covariance is accumulated block by block in one
    float32-exact pass over X (``mean_and_cov_chunked``: the Pallas Gram
    kernel over a rows-minor shard, XLA's blocked pass otherwise) — at
    double-digit-GB row counts the fused form can materialize the centered
    copy of X and OOM; without them (2-D (dp, mp)-sharded dry runs) the fused
    global-math path is used."""
    if mesh is not None and _TpuEstimator.rows_chunkable(
        X.shape[0], mesh, csize
    ):
        mean, cov, n = mean_and_cov_chunked(
            X, mask, mesh, csize, mp_blocks=mp_blocks
        )
    else:
        mean, cov, n = mean_and_cov(X, mask)
    out = _pca_from_cov(mean, cov, n, k)
    if mp_blocks:
        out["cov"] = cov
    return out


@functools.partial(jax.jit, static_argnames=("k", "mesh", "d", "pallas"))
def _pca_finish(G, s, cnt, mean_hat, k: int, mesh, d: int, pallas: bool):
    """What is left of a fit when the last row block has been folded: the
    devices' partial sums to the covariance (``cov_from_gram_folds``: a psum
    over dp, the rank-one correction) and :func:`_pca_from_cov`. Takes the
    accumulators, not the frame. The covariance and the count ride out too:
    a ``fitMultiple``'s later lanes run :func:`_pca_from_cov` on them."""
    mean, cov, n = cov_from_gram_folds(G, s, cnt, mean_hat, mesh, d, pallas)
    return _pca_from_cov(mean, cov, n, k), cov, n


class _GramFold:
    """PCA's fold over the row blocks of its frame (``parallel.mesh.RowFold``):
    each block's shifted Gram, row sum and count are added into its device's
    running float32 accumulators by one :func:`gram_fold` program, dispatched
    when the block's put has been issued — so it runs while the next block
    crosses the link.

    μ̂, which every block is shifted by, has to exist before block 0 is
    folded: it comes from the HOST array (``host_mean_sample``: runs of rows
    from all over it, summed over the process world), taken inside the first
    call, while block 0 is on its way. Which pass folds (the Pallas kernel or
    XLA's blocked one) is the gate's answer for the FIRST block's shape, asked
    once a fit: the accumulators have one form, and a short tail block that
    the device keeps the other way round costs a relayout of that tail alone.
    """

    def __init__(self, x_host: np.ndarray, csize: int):
        self.x_host, self.csize = x_host, csize
        self.d, self.dtype = x_host.shape[1], x_host.dtype
        self.mean_hat: Optional[np.ndarray] = None
        self._on_device: Dict[Any, jax.Array] = {}
        self.attrs: Dict[str, Any] = {}

    def _first_call(self, rows: jax.Array, device) -> None:
        s, c = allreduce_sum_host(*host_mean_sample(self.x_host))
        self.mean_hat = (s / max(float(c), 1.0)).astype(self.dtype)
        n, d = rows.shape
        declined = gram_pallas_declined(n, d, self.dtype, device)
        self.pallas = not declined
        self.block = gram_block_rows(n, d, self.csize, self.dtype.itemsize)
        self.attrs = {
            "precision": "highest",
            "rows_minor": jax.default_backend() == "tpu" and rows_minor(device, n, d, self.dtype),
            "gram": "pallas" if self.pallas else "xla",
            "tile": gram_tile(d)[0] if self.pallas else self.block,
        }
        if declined:
            self.attrs["declined"] = declined

    def __call__(self, state, rows: jax.Array, row0: int, valid: int):
        (device,) = rows.devices()
        if self.mean_hat is None:
            self._first_call(rows, device)
        if device not in self._on_device:
            self._on_device[device] = jax.device_put(self.mean_hat, device)
        if state is None:
            state = gram_fold_zeros(self.d, self.dtype, self.pallas, device)
        return gram_fold(
            state, rows, self._on_device[device], np.int32(valid), pallas=self.pallas, block=self.block
        )

    def sums(self, states: Dict[Any, Any], mesh) -> tuple:
        """Every device's state as three global arrays, stacked along axis 0
        and sharded over dp (a device that got no block: zeros)."""
        sh = row_sharding(mesh)
        per_device = [
            states[dev] or gram_fold_zeros(self.d, self.dtype, self.pallas, dev)
            for dev in sh.addressable_devices_indices_map((mesh.shape[DP_AXIS],))
        ]
        n_dp = mesh.shape[DP_AXIS]
        return tuple(
            jax.make_array_from_single_device_arrays(
                (n_dp * parts[0].shape[0],) + parts[0].shape[1:], sh, list(parts)
            )
            for parts in zip(*per_device)
        )


@jax.jit
def _project(Xb: jax.Array, components: jax.Array) -> jax.Array:
    """``Xb·componentsᵀ``: Spark semantics, no mean removal (reference
    ``feature.py:426-439``). The components are an argument, so every model
    of one width and k runs the one program. HIGHEST: an f32 dot on the MXU
    at default precision is one bf16 pass, 2⁻⁹ a product; the k columns are
    nothing beside the batch's way to the chip."""
    return jnp.matmul(Xb, components.T, precision=jax.lax.Precision.HIGHEST)


class PCA(PCAClass, _TpuEstimator, _PCAParams):
    """``PCA(k=3).fit(df)`` — drop-in for ``pyspark.ml.feature.PCA``."""

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimator.__init__(self)
        _PCAParams.__init__(self)
        self._set_params(**kwargs)

    def setK(self, value: int) -> "PCA":
        self._set_params(k=value)
        return self

    def setInputCol(self, value: str) -> "PCA":
        self._set_params(inputCol=value)
        return self

    def setOutputCol(self, value: str) -> "PCA":
        self._set_params(outputCol=value)
        return self

    def _chunk_rows(self, n_rows: int, n_dp: int) -> int:
        # route resident fits through the blocked covariance pass: its
        # temporaries are one row block, so a near-HBM-sized X cannot OOM on
        # the centered copy (see mean_and_cov_chunked); the chunk is the
        # upper bound of that block and the size of the mean's sample
        return self._equal_chunk_rows(n_rows, n_dp, 65_536)

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # one placement and one fold; the later lanes reuse the covariance
        return True

    def _pre_process_data(self, dataset: DataFrame) -> FitInputs:
        """The host part alone (column, dtype, contiguity, mesh, chunk size).
        The fit is a sum over rows, so the fit function places the frame
        itself and folds each row block as it lands (:class:`_GramFold`): the
        frame's crossing lies inside ``fit.dispatch``, with the Gram under it."""
        return self._host_inputs(dataset)

    def _fit_under_the_put(self, inputs: FitInputs, k: int) -> Dict[str, Any]:
        """Place the frame with the Gram folded under its crossing, then
        dispatch the finish program. Leaves the frame and the covariance on
        ``inputs`` for the later lanes of a ``fitMultiple``."""
        fold = _GramFold(inputs.X_host, inputs.csize)
        inputs.X, inputs.mask, states = shard_rows(inputs.X_host, inputs.mesh, inputs.csize, fold=fold)
        inputs.X_host = None
        if fold.mean_hat is None:
            raise ValueError("PCA.fit: the dataset has no rows")
        with telemetry.span(
            "solver.launch",
            program=f"{gram_fold.__name__},{_pca_finish.__name__}",
            gram_under_put=True,
            **fold.attrs,
        ):
            out, cov, n = _pca_finish(
                *fold.sums(states, inputs.mesh), fold.mean_hat, k=k, mesh=inputs.mesh,
                d=fold.d, pallas=fold.pallas,
            )
        inputs.folded = (out["mean"], cov, n)
        return out

    def _fit_mp_blocked(self, inputs: FitInputs, k: int, mp: int):
        """The Gram's accumulator column-sharded over the mesh's mp axis: the
        frame goes up first, then ONE program reads it whole
        (:func:`_pca_fit_kernel`); nothing runs under the put."""
        if inputs.X is None:
            inputs.X, inputs.mask = shard_rows(inputs.X_host, inputs.mesh, inputs.csize)
            inputs.X_host = None
        n_local = inputs.X.shape[0] // inputs.mesh.shape[DP_AXIS]
        d = inputs.X.shape[1]
        device = inputs.mesh.devices.flat[0]
        with telemetry.span(
            "solver.launch",
            program=_pca_fit_kernel.__name__,
            gram_under_put=False,
            precision="highest",
            rows_minor=jax.default_backend() == "tpu" and rows_minor(device, n_local, d, inputs.X.dtype),
            gram="xla",
            tile=gram_block_rows(n_local, d, inputs.csize, inputs.X.dtype.itemsize),
            declined=gram_pallas_declined(n_local, d, inputs.X.dtype, device, mp_blocks=True),
        ):
            out = _pca_fit_kernel(
                inputs.X, inputs.mask, k, mesh=inputs.mesh, csize=inputs.csize, mp_blocks=True,
            )
        cov = out.pop("cov")
        return out, {"mp_degree": mp, "gram_shard_bytes": int(cov.addressable_shards[0].data.nbytes)}

    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params.get("n_components") or self.getK())
            if k > inputs.n_features:
                raise ValueError(
                    f"k={k} must be <= number of features {inputs.n_features}"
                )
            mp = mp_gram_blocks(inputs.mesh, inputs.n_features_padded)
            report = None
            if mp > 1 and inputs.csize > 1:
                out, report = self._fit_mp_blocked(inputs, k, mp)
            elif inputs.folded is None:
                out = self._fit_under_the_put(inputs, k)
            else:
                # a later lane of a fitMultiple: the first lane's covariance
                with telemetry.span("solver.launch", program=_pca_from_cov.__name__, gram_under_put=False):
                    out = _pca_from_cov(*inputs.folded, k)
            # the first fetch blocks until the fit program has run
            with telemetry.span("solver.fetch", k=k, d=inputs.n_features):
                result = {key: np.asarray(v) for key, v in out.items()}
            if report:
                result["_fit_report"] = report
            return result

        return _fit

    def _get_tpu_streaming_fit_func(self, dataset: DataFrame):
        """Out-of-core fit: two chunked passes (mean, then centered Gram)
        accumulate the d×d covariance with O(chunk + d²) device memory; the
        eigh finalize is shared with the resident kernel."""
        from ..core import StreamInputs
        from ..ops.streaming import streamed_suffstats

        def _fit(inputs: StreamInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params.get("n_components") or self.getK())
            if k > inputs.n_features:
                raise ValueError(
                    f"k={k} must be <= number of features {inputs.n_features}"
                )
            stats = streamed_suffstats(
                inputs.source, inputs.mesh, inputs.chunk_rows, inputs.dtype,
                with_y=False, fit_intercept=True,
            )
            report = stats.pop("_mp_report", None)
            cov = stats["G"] / (stats["n"] - 1.0)
            out = _pca_from_cov(stats["mean_x"], cov, stats["n"], k)
            result = {key: np.asarray(v) for key, v in out.items()}
            if report:
                result["_fit_report"] = report
            return result

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "PCAModel":
        return PCAModel(**result)


class PCAModel(PCAClass, _TpuModel, _PCAParams):
    def __init__(self, **attrs: Any) -> None:
        _TpuModel.__init__(self, **attrs)
        _PCAParams.__init__(self)

    # -- attribute surface (reference model attrs + Spark names) -----------
    @property
    def mean_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["mean"])

    @property
    def components_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["components"])

    @property
    def explained_variance_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["explained_variance"])

    @property
    def explained_variance_ratio_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["explained_variance_ratio"])

    @property
    def singular_values_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["singular_values"])

    @property
    def pc(self) -> np.ndarray:
        """Spark-style principal-components matrix, shape (d, k)."""
        return self.components_.T

    @property
    def explainedVariance(self) -> np.ndarray:
        return self.explained_variance_ratio_

    def setInputCol(self, value: str) -> "PCAModel":
        self._set_params(inputCol=value)
        return self

    def setOutputCol(self, value: str) -> "PCAModel":
        self._set_params(outputCol=value)
        return self

    # -- transform ---------------------------------------------------------
    def _get_tpu_transform_func(
        self, dataset: Optional[DataFrame] = None
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        out_col = self.getOrDefault("outputCol")

        def _build() -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
            components = jnp.asarray(self.components_)  # (k, d)

            def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
                return {out_col: output_to_host(_project(batch_to_device(Xb), components))}

            return _fn

        return self._memoized_transform_fn(("pca", out_col), _build)

    def _out_cols(self):
        return [self.getOrDefault("outputCol")]
