"""LinearRegression — Spark ML drop-in, TPU-native fit/transform.

Reference: ``/root/reference/python/src/spark_rapids_ml/regression.py:171-784``.
Param mapping parity (reference ``regression.py:172-205``):
``elasticNetParam→l1_ratio``, ``regParam→alpha``, ``maxIter→max_iter``,
``tol→tol``, ``fitIntercept→fit_intercept``, ``standardization→normalize``,
``solver`` value-mapped (auto/normal/l-bfgs), ``loss`` squaredError only,
``aggregationDepth`` accepted-but-ignored.

Solver selection (reference picks cuML class by regularization,
``regression.py:502-559``): here l1=0 → closed-form Cholesky on the psum'd
Gram (the eig/ridge path, incl. Spark's standardized-penalty semantics that
the reference reproduces via the alpha×M rescale at :530-537); l1>0 → FISTA
on the precomputed quadratic form (replaces ``CDMG``).

``fitMultiple`` fits every param map from ONE pass of sufficient statistics
(reference single-pass loop: ``regression.py:591-608``); ``_combine`` stacks
models for single-pass CV evaluation (reference ``regression.py:750-773``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

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
    HasElasticNetParam,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasWeightCol,
    TypeConverters,
    _mk,
)
from ..ops.linalg import mp_gram_blocks
from ..ops.linreg_kernels import (
    linreg_suffstats,
    linreg_suffstats_chunked,
    solve_elasticnet,
    solve_elasticnet_batched,
    solve_normal,
)
from ..runtime import telemetry


class LinearRegressionClass:
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "regParam": "alpha",
            "elasticNetParam": "l1_ratio",
            "maxIter": "max_iter",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            "standardization": "standardization",
            "solver": "solver",
            "loss": "loss",
            "aggregationDepth": "",
            "epsilon": "",
            "maxBlockSizeInMB": "",
            # weightCol is consumed natively by the data plane (weighted
            # moments) — no backend mapping needed
        }

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        def _loss(v: str) -> str:
            if v != "squaredError":
                raise ValueError(
                    f"Only squaredError loss is supported, got {v!r}"
                )
            return v

        def _solver(v: str) -> str:
            if v not in ("auto", "normal", "l-bfgs"):
                raise ValueError(f"Unsupported solver {v!r}")
            return v

        return {"loss": _loss, "solver": _solver}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "alpha": 0.0,
            "l1_ratio": 0.0,
            "max_iter": 100,
            "tol": 1e-6,
            "fit_intercept": True,
            "standardization": True,
            "solver": "auto",
            "loss": "squaredError",
        }


class _LinearRegressionParams(
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasWeightCol,
):
    solver = _mk("solver", "solver: auto | normal | l-bfgs", TypeConverters.toString)
    loss = _mk("loss", "loss function (squaredError)", TypeConverters.toString)
    aggregationDepth = _mk("aggregationDepth", "tree aggregate depth (ignored)", TypeConverters.toInt)
    epsilon = _mk("epsilon", "huber epsilon (ignored)", TypeConverters.toFloat)
    maxBlockSizeInMB = _mk("maxBlockSizeInMB", "block size hint (ignored)", TypeConverters.toFloat)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            maxIter=100, regParam=0.0, elasticNetParam=0.0, tol=1e-6,
            solver="auto", loss="squaredError", aggregationDepth=2, epsilon=1.35,
        )

    def getSolver(self) -> str:
        return self.getOrDefault("solver")


class LinearRegression(
    LinearRegressionClass, _TpuEstimatorSupervised, _LinearRegressionParams
):
    """``LinearRegression(regParam=1e-5).fit(df)`` — drop-in for
    ``pyspark.ml.regression.LinearRegression``."""

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimatorSupervised.__init__(self)
        _LinearRegressionParams.__init__(self)
        self._set_params(**kwargs)

    def setMaxIter(self, value: int) -> "LinearRegression":
        self._set_params(maxIter=value)
        return self

    def setRegParam(self, value: float) -> "LinearRegression":
        self._set_params(regParam=value)
        return self

    def setElasticNetParam(self, value: float) -> "LinearRegression":
        self._set_params(elasticNetParam=value)
        return self

    def setStandardization(self, value: bool) -> "LinearRegression":
        self._set_params(standardization=value)
        return self

    def setFitIntercept(self, value: bool) -> "LinearRegression":
        self._set_params(fitIntercept=value)
        return self

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return True

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        from ..evaluation import RegressionEvaluator

        return isinstance(evaluator, RegressionEvaluator)

    @staticmethod
    def _launch_solver(
        stats: Dict[str, jax.Array], params: Dict[str, Any], dtype: Any
    ) -> Tuple[str, Tuple[Any, Any, Any]]:
        """Solver dispatch on precomputed sufficient statistics: the jitted
        solver's name and its (coefficients, intercept, iterations), still
        on the device."""
        alpha = float(params["alpha"])
        l1_ratio = float(params["l1_ratio"])
        standardization = bool(params["standardization"])
        l1 = alpha * l1_ratio
        l2 = alpha * (1.0 - l1_ratio)
        if l1 == 0.0:
            beta, intercept = solve_normal(
                stats, jnp.asarray(l2, dtype), standardization=standardization
            )
            return solve_normal.__name__, (beta, intercept, 1)
        return solve_elasticnet.__name__, solve_elasticnet(
            stats,
            jnp.asarray(l1, dtype),
            jnp.asarray(l2, dtype),
            standardization=standardization,
            max_iter=int(params["max_iter"]),
            tol=float(params["tol"]),
        )

    @staticmethod
    def _fetch_solution(beta: Any, intercept: Any, it: Any) -> Dict[str, Any]:
        """The solver's results on the host; the first conversion blocks
        until the programs before it have run."""
        with telemetry.span("solver.fetch") as f_span:
            result = {
                "coefficients": np.asarray(beta),
                "intercept": float(intercept),
                "n_iter": int(it),
            }
            f_span.set_attr(n_iter=result["n_iter"])
        return result

    @staticmethod
    def _solve_from_stats(
        stats: Dict[str, jax.Array], params: Dict[str, Any], dtype: Any
    ) -> Dict[str, Any]:
        """Launch, then fetch — shared by the resident and streaming fits
        so the two paths cannot diverge."""
        _, out = LinearRegression._launch_solver(stats, params, dtype)
        return LinearRegression._fetch_solution(*out)

    def _chunk_rows(self, n_rows: int, n_dp: int) -> int:
        # route resident fits through the chunked suffstats scan: bounds
        # temporaries to O(chunk·d) so a near-HBM-sized X cannot OOM on the
        # centered √w-scaled copy (see linreg_suffstats_chunked)
        return self._equal_chunk_rows(n_rows, n_dp, 65_536)

    # ---- gang-fit path ---------------------------------------------------
    def _gang_fit_groups(self, param_sets: List[Dict[str, Any]]):
        # only the ITERATIVE solver lanes gang (batched FISTA); l1 == 0
        # lanes are one Cholesky each — already a single dispatch over the
        # shared suffstats, nothing to amortize — and fall through to the
        # sequential loop by being left out of the partition.
        groups: Dict[Any, List[int]] = {}
        for i, ps in enumerate(param_sets):
            if float(ps["alpha"]) * float(ps["l1_ratio"]) == 0.0:
                continue
            key = (
                bool(ps["fit_intercept"]),
                bool(ps["standardization"]),
                int(ps["max_iter"]),
            )
            groups.setdefault(key, []).append(i)
        return list(groups.items()) or None

    def _gang_lane_bytes(self, inputs: FitInputs) -> float:
        # FISTA state is O(d) per lane over the replicated d×d system
        return 32.0 * float(inputs.n_features)

    def _get_tpu_gang_fit_func(self, dataset: DataFrame):
        stats_cache: Dict[bool, Dict[str, jax.Array]] = {}

        def _gang_fit(
            inputs: FitInputs, group_ps: List[Dict[str, Any]]
        ) -> List[Dict[str, Any]]:
            ps0 = group_ps[0]
            fit_intercept = bool(ps0["fit_intercept"])
            if fit_intercept not in stats_cache:
                csize = inputs.csize
                if self.rows_chunkable(inputs.X.shape[0], inputs.mesh, csize):
                    stats_cache[fit_intercept] = linreg_suffstats_chunked(
                        inputs.X, inputs.mask, inputs.y, inputs.weight,
                        mesh=inputs.mesh, csize=csize,
                        fit_intercept=fit_intercept,
                        weighted=inputs.weight is not None,
                    )
                else:
                    stats_cache[fit_intercept] = linreg_suffstats(
                        inputs.X, inputs.mask, inputs.y, inputs.weight,
                        fit_intercept=fit_intercept,
                    )
            l1 = jnp.asarray(
                [float(ps["alpha"]) * float(ps["l1_ratio"]) for ps in group_ps],
                inputs.dtype,
            )
            l2 = jnp.asarray(
                [
                    float(ps["alpha"]) * (1.0 - float(ps["l1_ratio"]))
                    for ps in group_ps
                ],
                inputs.dtype,
            )
            tol = jnp.asarray([float(ps["tol"]) for ps in group_ps], inputs.dtype)
            beta, intercept, it = solve_elasticnet_batched(
                stats_cache[fit_intercept],
                l1,
                l2,
                standardization=bool(ps0["standardization"]),
                max_iter=int(ps0["max_iter"]),
                tol=tol,
            )
            beta_h = np.asarray(beta)
            intercept_h = np.asarray(intercept)
            it_h = np.asarray(it)
            return [
                {
                    "coefficients": beta_h[b],
                    "intercept": float(intercept_h[b]),
                    "n_iter": int(it_h[b]),
                }
                for b in range(len(group_ps))
            ]

        return _gang_fit

    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        stats_cache: Dict[bool, Dict[str, jax.Array]] = {}

        blocked_mp: Dict[bool, int] = {}

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            fit_intercept = bool(params["fit_intercept"])
            with telemetry.span("solver.launch") as l_span:
                programs = []
                if fit_intercept not in stats_cache:
                    # the single data pass — shared by every param map
                    csize = inputs.csize
                    mp = mp_gram_blocks(inputs.mesh, inputs.X.shape[1])
                    if self.rows_chunkable(inputs.X.shape[0], inputs.mesh, csize):
                        stats_cache[fit_intercept] = linreg_suffstats_chunked(
                            inputs.X, inputs.mask, inputs.y, inputs.weight,
                            mesh=inputs.mesh, csize=csize,
                            fit_intercept=fit_intercept,
                            weighted=inputs.weight is not None,
                            mp_blocks=mp > 1,
                        )
                        blocked_mp[fit_intercept] = mp
                        programs.append(linreg_suffstats_chunked.__name__)
                    else:
                        stats_cache[fit_intercept] = linreg_suffstats(
                            inputs.X, inputs.mask, inputs.y, inputs.weight,
                            fit_intercept=fit_intercept,
                        )
                        blocked_mp[fit_intercept] = 1
                        programs.append(linreg_suffstats.__name__)
                solver, out = self._launch_solver(
                    stats_cache[fit_intercept], params, inputs.dtype
                )
                # the jitted functions this launch dispatched, in order: the
                # data pass that waits for the frame, then the solve
                l_span.set_attr(program=",".join(programs + [solver]))
            result = self._fetch_solution(*out)
            mp = blocked_mp[fit_intercept]
            if mp > 1:
                G = stats_cache[fit_intercept]["G"]
                result["_fit_report"] = {
                    "mp_degree": mp,
                    "gram_shard_bytes": int(
                        G.addressable_shards[0].data.nbytes
                    ),
                }
            return result

        return _fit

    def _get_tpu_streaming_fit_func(self, dataset: DataFrame):
        """Out-of-core fit: the sufficient statistics (Gram, Xᵀy, moments)
        accumulate over two chunked passes; every solver (Cholesky, FISTA)
        and every param map then reuses them with zero further data passes —
        the streaming analog of the resident single-pass ``fitMultiple``."""
        from ..core import StreamInputs
        from ..ops.streaming import streamed_suffstats

        stats_cache: Dict[bool, Dict[str, jax.Array]] = {}

        def _fit(inputs: StreamInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            fit_intercept = bool(params["fit_intercept"])
            if fit_intercept not in stats_cache:
                stats_cache[fit_intercept] = streamed_suffstats(
                    inputs.source, inputs.mesh, inputs.chunk_rows, inputs.dtype,
                    with_y=True, fit_intercept=fit_intercept,
                )
            stats = dict(stats_cache[fit_intercept])
            report = stats.pop("_mp_report", None)
            result = self._solve_from_stats(stats, params, inputs.dtype)
            if report:
                result["_fit_report"] = report
            return result

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "LinearRegressionModel":
        return LinearRegressionModel(**result)


class LinearRegressionModel(
    LinearRegressionClass, _TpuModel, _LinearRegressionParams
):
    def __init__(self, **attrs: Any) -> None:
        _TpuModel.__init__(self, **attrs)
        _LinearRegressionParams.__init__(self)

    @property
    def coefficients(self) -> np.ndarray:
        """(d,) for a single model; (m, d) for a CV-combined multi-model."""
        return np.asarray(self._model_attributes["coefficients"])

    @property
    def intercept(self) -> Any:
        return self._model_attributes["intercept"]

    @property
    def numFeatures(self) -> int:
        return int(np.atleast_2d(self.coefficients).shape[1])

    @property
    def hasSummary(self) -> bool:
        return False

    def predict(self, vector: Any) -> float:
        x = np.asarray(vector, dtype=np.float64).ravel()
        return float(x @ np.asarray(self.coefficients).ravel() + float(self.intercept))

    @classmethod
    def _combine(cls, models: List["LinearRegressionModel"]) -> "LinearRegressionModel":
        """Stack models for single-pass multi-model evaluation (reference
        ``regression.py:750-773``)."""
        coefs = np.stack([np.atleast_1d(np.asarray(m.coefficients)) for m in models])
        intercepts = np.asarray([float(m.intercept) for m in models])
        combined = cls(coefficients=coefs, intercept=intercepts, n_iter=0)
        models[0]._copyValues(combined)
        models[0]._copy_tpu_params(combined)
        return combined

    @property
    def _is_multi_model(self) -> bool:
        return np.asarray(self._model_attributes["coefficients"]).ndim == 2

    def _transformEvaluate(self, dataset: DataFrame, evaluator: Any) -> List[float]:
        """ONE data pass computes every model's predictions and reduces them
        to tiny moment buffers (reference ``regression.py:89-141`` computes
        per-partition sufficient-stats rows; here the pass is a single
        batched device sweep)."""
        from ..evaluation import RegressionEvaluator
        from ..metrics import RegressionMetrics

        if not isinstance(evaluator, RegressionEvaluator):
            raise NotImplementedError(
                f"Evaluator {type(evaluator).__name__} is not supported"
            )
        X = self._extract_features_for_transform(dataset)
        preds = self._apply_batched(self._get_tpu_transform_func(dataset), X)[
            self.getOrDefault("predictionCol")
        ]
        y = np.asarray(dataset.column(evaluator.getLabelCol()), dtype=np.float64)
        P = preds[:, None] if preds.ndim == 1 else preds  # (n, m)
        return [
            RegressionMetrics.from_predictions(y, P[:, j]).evaluate(evaluator)
            for j in range(P.shape[1])
        ]

    def _get_tpu_transform_func(
        self, dataset: Optional[DataFrame] = None
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        pred_col = self.getOrDefault("predictionCol")

        def _build() -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
            coef_np = np.asarray(self.coefficients)
            b_np = np.asarray(self.intercept)
            if coef_np.ndim == 1:
                @jax.jit
                def _predict(Xb: jax.Array) -> jax.Array:
                    w = jnp.asarray(coef_np, dtype=Xb.dtype)
                    return Xb @ w + jnp.asarray(b_np, dtype=Xb.dtype)
            else:
                @jax.jit
                def _predict(Xb: jax.Array) -> jax.Array:
                    W = jnp.asarray(coef_np, dtype=Xb.dtype)  # (m, d)
                    return (
                        Xb @ W.T + jnp.asarray(b_np, dtype=Xb.dtype)[None, :]
                    )

            def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
                return {pred_col: output_to_host(_predict(batch_to_device(Xb)))}

            return _fn

        return self._memoized_transform_fn(("linreg", pred_col), _build)
