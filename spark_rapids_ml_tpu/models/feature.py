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
one jitted global-math function over the row-sharded design matrix — masked
mean + float32-exact Gram (psum'd over the dp mesh axis), the k leading
eigenpairs of the replicated d×d covariance (``ops.linalg.topk_eigh``),
deterministic sign flip.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core import FitFunc, FitInputs, _TpuEstimator, _TpuModel
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
    gram_block_rows,
    gram_pallas_declined,
    gram_tile,
    mean_and_cov,
    mean_and_cov_chunked,
    mp_gram_blocks,
    rows_minor,
    topk_eigh,
)
from ..parallel.mesh import DP_AXIS
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
    """Resident-fit kernel. With ``mesh``/``csize`` (rows dp-sharded, padded
    to a per-device ``csize`` multiple) the covariance is accumulated block
    by block in one float32-exact pass over X (``mean_and_cov_chunked``: the
    Pallas Gram kernel over a rows-minor shard, XLA's blocked pass
    otherwise) — at double-digit-GB row counts the fused form can
    materialize the centered copy of X and OOM; without them (e.g. 2-D
    (dp, mp)-sharded dry runs) the fused global-math path is used.
    ``mp_blocks`` (static; resolve with ``mp_gram_blocks`` outside jit)
    column-shards the Gram accumulator over the mesh's mp axis; the blocked
    covariance also rides out in the result so the caller can measure its
    per-shard bytes."""
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

    def _gram_launch_attrs(self, inputs: FitInputs, use_mp: bool) -> Dict[str, Any]:
        """What ``solver.launch`` says of the Gram pass: the gate that
        ``mean_and_cov_chunked`` takes at trace time, asked again on the
        host."""
        n_local = inputs.X.shape[0] // inputs.mesh.shape[DP_AXIS]
        d = inputs.X.shape[1]
        device = inputs.mesh.devices.flat[0]
        attrs: Dict[str, Any] = {
            "precision": "highest",
            "rows_minor": jax.default_backend() == "tpu" and rows_minor(device, n_local, d, inputs.X.dtype),
        }
        if not _TpuEstimator.rows_chunkable(inputs.X.shape[0], inputs.mesh, inputs.csize):
            return dict(attrs, gram="xla_fused", tile=inputs.X.shape[0])
        declined = gram_pallas_declined(n_local, d, inputs.X.dtype, device, mp_blocks=use_mp)
        if declined:
            tile = gram_block_rows(n_local, d, inputs.csize, inputs.X.dtype.itemsize)
            return dict(attrs, gram="xla", tile=tile, declined=declined)
        return dict(attrs, gram="pallas", tile=gram_tile(d)[0])

    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params.get("n_components") or self.getK())
            if k > inputs.n_features:
                raise ValueError(
                    f"k={k} must be <= number of features {inputs.n_features}"
                )
            mp = mp_gram_blocks(inputs.mesh, inputs.X.shape[1])
            use_mp = mp > 1 and _TpuEstimator.rows_chunkable(
                inputs.X.shape[0], inputs.mesh, inputs.csize
            )
            with telemetry.span(
                "solver.launch",
                program=_pca_fit_kernel.__name__,
                **self._gram_launch_attrs(inputs, use_mp),
            ):
                out = _pca_fit_kernel(
                    inputs.X, inputs.mask, k, mesh=inputs.mesh,
                    csize=inputs.csize, mp_blocks=use_mp,
                )
            report = None
            if use_mp:
                cov = out.pop("cov")
                report = {
                    "mp_degree": mp,
                    "gram_shard_bytes": int(
                        cov.addressable_shards[0].data.nbytes
                    ),
                }
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
                return {out_col: np.asarray(_project(jnp.asarray(Xb), components))}

            return _fn

        return self._memoized_transform_fn(("pca", out_col), _build)

    def _out_cols(self):
        return [self.getOrDefault("outputCol")]
