"""Classification estimators — Spark ML drop-ins, TPU-native fit/transform.

LogisticRegression reference:
``/root/reference/python/src/spark_rapids_ml/classification.py:651-1562``.
Param-mapping parity (reference ``classification.py:652-671``):
``maxIter→max_iter``, ``regParam→C`` (value-mapped 1/x), ``elasticNetParam→
l1_ratio``, ``tol→tol``, ``fitIntercept→fit_intercept``, ``standardization→
standardization``, ``family`` accepted-but-ignored (auto-detected),
``threshold``/``thresholds``/``weightCol``/``aggregationDepth``/coefficient
bounds unsupported (raise on set).

Fit is the jitted distributed L-BFGS/OWL-QN in ``ops/logreg_kernels.py``.
``fitMultiple`` reuses the device-resident design matrix for every param map
(reference single-pass loop ``classification.py:1137-1154``); ``_combine``
stacks models for single-pass CV evaluation (``classification.py:1504-1519``).
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
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    TypeConverters,
    _mk,
)
from ..ops.logreg_kernels import logreg_fit, logreg_fit_batched, logreg_predict, objective_read_dtype
from ..runtime import envspec, telemetry
from ..utils.logging import get_logger


def _resolve_objective_dtype(params: Dict[str, Any]) -> str:
    """Validated objective dtype from the kwarg or env (empty string means
    unset; typos error rather than silently running f32)."""
    v = (
        params.get("objective_dtype")
        or envspec.get("TPUML_LOGREG_OBJECTIVE_DTYPE")
    )
    v = str(v)
    if v not in ("float32", "bfloat16"):
        raise ValueError(
            f"objective_dtype must be float32|bfloat16, got {v!r}"
        )
    return v


class LogisticRegressionClass:
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # reference ``classification.py:652-671``
        return {
            "maxIter": "max_iter",
            "regParam": "C",
            "elasticNetParam": "l1_ratio",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            "threshold": None,
            "thresholds": None,
            "standardization": "standardization",
            "weightCol": None,
            "aggregationDepth": None,
            "family": "",
            "lowerBoundsOnCoefficients": None,
            "upperBoundsOnCoefficients": None,
            "lowerBoundsOnIntercepts": None,
            "upperBoundsOnIntercepts": None,
            "maxBlockSizeInMB": None,
        }

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        # Spark regParam -> inverse-regularization C (reference
        # ``classification.py:676-678``); C=0 encodes "no penalty"
        def _c(x: float) -> float:
            if x > 0.0:
                return 1.0 / x
            if x == 0.0:
                return 0.0
            raise ValueError(f"regParam must be >= 0, got {x}")

        return {"C": _c}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "fit_intercept": True,
            "standardization": True,
            "C": 0.0,
            "l1_ratio": 0.0,
            "max_iter": 100,
            "tol": 1e-6,
            "objective_dtype": None,
        }


class _LogisticRegressionParams(
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasEnableSparseDataOptim,
):
    family = _mk(
        "family", "binomial | multinomial | auto (auto-detected)", TypeConverters.toString
    )
    threshold = _mk("threshold", "binary prediction threshold (unsupported)", TypeConverters.toFloat)
    thresholds = _mk("thresholds", "per-class thresholds (unsupported)", TypeConverters.toListFloat)
    weightCol = _mk("weightCol", "weight column (unsupported)", TypeConverters.toString)
    aggregationDepth = _mk("aggregationDepth", "tree aggregate depth (unsupported)", TypeConverters.toInt)
    maxBlockSizeInMB = _mk("maxBlockSizeInMB", "block size hint (unsupported)", TypeConverters.toFloat)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            maxIter=100,
            regParam=0.0,
            elasticNetParam=0.0,
            tol=1e-6,
            family="auto",
        )

    def getFamily(self) -> str:
        return self.getOrDefault("family")


class LogisticRegression(
    LogisticRegressionClass, _TpuEstimatorSupervised, _LogisticRegressionParams
):
    """``LogisticRegression(regParam=0.01).fit(df)`` — drop-in for
    ``pyspark.ml.classification.LogisticRegression``. Labels must be
    non-negative integers (reference ``classification.py:1103-1112``)."""

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimatorSupervised.__init__(self)
        _LogisticRegressionParams.__init__(self)
        self._set_params(**kwargs)

    def setMaxIter(self, value: int) -> "LogisticRegression":
        self._set_params(maxIter=value)
        return self

    def setRegParam(self, value: float) -> "LogisticRegression":
        self._set_params(regParam=value)
        return self

    def setElasticNetParam(self, value: float) -> "LogisticRegression":
        self._set_params(elasticNetParam=value)
        return self

    def setTol(self, value: float) -> "LogisticRegression":
        self._set_params(tol=value)
        return self

    def setFitIntercept(self, value: bool) -> "LogisticRegression":
        self._set_params(fitIntercept=value)
        return self

    def setStandardization(self, value: bool) -> "LogisticRegression":
        self._set_params(standardization=value)
        return self

    def setProbabilityCol(self, value: str) -> "LogisticRegression":
        self._set_params(probabilityCol=value)
        return self

    def setRawPredictionCol(self, value: str) -> "LogisticRegression":
        self._set_params(rawPredictionCol=value)
        return self

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return True

    def _x_placement_dtype(self):
        """bf16 objective reads start at placement: X goes to device in
        bf16 (half the H2D bytes, zero-copy inside ``logreg_fit``) instead
        of being converted in-program, which would hold the f32 argument
        AND the bf16 copy live (OOM at near-HBM scales). Resolved from the
        ESTIMATOR-level setting: fitMultiple param maps share one placed X,
        so a per-map override cannot re-place it (a map asking f32 over a
        bf16-placed X still reads bf16 — solver state is f32 either way).
        Whether placement actually applies is core's decision: it narrows
        only when the RESOLVED input dtype is f32 (so f64 compat fits are
        never silently rounded), which covers float32_inputs=False over
        f32 data too."""
        import jax.numpy as jnp

        if _resolve_objective_dtype(self._tpu_params) == "bfloat16":
            return jnp.bfloat16
        return None

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        from ..evaluation import MulticlassClassificationEvaluator

        return isinstance(evaluator, MulticlassClassificationEvaluator)

    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        # label analysis happens on host, once, outside jit (the class count
        # is a static shape parameter of the compiled program). It must be
        # GLOBAL: in a multi-process world each rank sees only its
        # partition, and ranks disagreeing on n_classes (or on the
        # degenerate single-label early-return) would compile different
        # collectives and deadlock.
        from ..parallel.mesh import global_label_summary

        label_col = self.getOrDefault("labelCol")
        ls = global_label_summary(np.asarray(dataset.column(label_col)))
        if ls["total"] == 0:
            raise ValueError("Labels column is empty")
        if ls["y_min"] < 0 or not ls["all_int"]:
            raise RuntimeError(
                "Labels MUST be non-negative integers, got values outside that set"
            )
        # Spark semantics: numClasses = max(label) + 1
        n_classes = max(int(ls["y_max"]) + 1, 2)
        single_label = ls["all_same"]
        single_label_val = ls["first"]

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            multinomial = n_classes > 2
            fit_intercept = bool(params["fit_intercept"])

            if single_label and n_classes == 2:
                # single-label degenerate case (reference
                # ``classification.py:1119-1132``): all-0 or all-1 labels
                class_val = single_label_val
                if fit_intercept:
                    return {
                        "coef_": np.zeros((1, inputs.n_features)),
                        "intercept_": np.asarray(
                            [np.inf if class_val == 1.0 else -np.inf]
                        ),
                        "n_classes": n_classes,
                        "multinomial": False,
                        "n_iter": 0,
                        "objective": 0.0,
                    }

            c = float(params["C"])
            reg = 1.0 / c if c > 0.0 else 0.0
            l1_ratio = float(params["l1_ratio"])
            # the gate logreg_fit takes at trace time, asked again on the
            # host so the span says which loss+gradient the program runs
            from ..ops.logreg_pallas import binary_pass_tile, logreg_pallas_declined
            from ..parallel.mesh import DP_AXIS

            shard = (inputs.X.shape[0] // inputs.mesh.shape[DP_AXIS], inputs.X.shape[1])
            device = inputs.mesh.devices.flat[0]
            objective_dtype = _resolve_objective_dtype(params)
            declined = logreg_pallas_declined(
                *shard, n_classes if multinomial else 1,
                objective_read_dtype(inputs.X, inputs.mesh, objective_dtype), device,
            )
            launch = {"loss_grad": "xla_autodiff", "declined": declined} if declined else {"loss_grad": "pallas_fused"}
            if not declined and not multinomial:
                launch["tile"] = binary_pass_tile(*shard, device)[0]
            with telemetry.span("solver.launch", program=logreg_fit.__name__, **launch):
                out = logreg_fit(
                    inputs.X,
                    inputs.mask,
                    inputs.y,
                    n_classes=n_classes,
                    multinomial=multinomial,
                    fit_intercept=fit_intercept,
                    standardization=bool(params["standardization"]),
                    l1=jnp.asarray(reg * l1_ratio, inputs.dtype),
                    l2=jnp.asarray(reg * (1.0 - l1_ratio), inputs.dtype),
                    use_l1=reg * l1_ratio > 0.0,
                    max_iter=int(params["max_iter"]),
                    tol=jnp.asarray(float(params["tol"]), inputs.dtype),
                    # rows are dp-sharded by _pre_process_data: lets the TPU
                    # path use the fused Pallas loss+grad pass
                    mesh=inputs.mesh,
                    # bf16 objective reads (f32 accumulation) via framework
                    # kwarg or env; default full f32
                    objective_dtype=objective_dtype,
                )
            # the first fetch blocks until the frame is on the device and
            # the solver's program has run
            with telemetry.span("solver.fetch") as f_span:
                result = {
                    "coef_": np.asarray(out["coef_"]),
                    "intercept_": np.asarray(out["intercept_"]),
                    "n_classes": n_classes,
                    "multinomial": multinomial,
                    "n_iter": int(out["n_iter"]),
                    "objective": float(out["objective"]),
                }
                n_evals = int(out["n_evals"])
                f_span.set_attr(n_iter=result["n_iter"], n_evals=n_evals)
            # provenance, not a model attribute: core strips it before the
            # constructor, so nothing persisted changes
            result["_fit_report"] = {"n_evals": n_evals}
            return result

        return _fit

    # ---- gang-fit path ---------------------------------------------------
    @staticmethod
    def _gang_reg_pair(ps: Dict[str, Any]) -> Tuple[float, float]:
        """Per-lane (l1, l2) strengths from the stored C/l1_ratio params —
        the same arithmetic the solo ``_fit`` uses."""
        c = float(ps["C"])
        reg = 1.0 / c if c > 0.0 else 0.0
        l1_ratio = float(ps["l1_ratio"])
        return reg * l1_ratio, reg * (1.0 - l1_ratio)

    def _gang_fit_groups(
        self, param_sets: List[Dict[str, Any]]
    ) -> Optional[List[Tuple[Any, List[int]]]]:
        # static kernel params split buckets; l1/l2/tol ride traced (B,)
        # arrays. use_l1 is static on purpose: OWL-QN's direction sign-fix
        # and orthant projection are NOT identities at l1=0, so plain and
        # OWL-QN lanes compile different programs.
        groups: Dict[Tuple[Any, ...], List[int]] = {}
        for i, ps in enumerate(param_sets):
            l1, _ = self._gang_reg_pair(ps)
            key = (
                bool(ps["fit_intercept"]),
                bool(ps["standardization"]),
                l1 > 0.0,
                int(ps["max_iter"]),
                _resolve_objective_dtype(ps),
            )
            groups.setdefault(key, []).append(i)
        return list(groups.items())

    def _gang_fit_supports_folds(self) -> bool:
        return True

    def _gang_lane_bytes(self, inputs: FitInputs) -> float:
        # dominated by the (n, B, K) logits block and its backward twin:
        # ~4 such f32 temporaries live per objective evaluation
        k_eff = float(getattr(self, "_gang_k_eff", 1))
        return 16.0 * float(inputs.X.shape[0]) * k_eff

    def _get_tpu_gang_fit_func(self, dataset: DataFrame):
        from ..parallel.mesh import global_label_summary

        label_col = self.getOrDefault("labelCol")
        ls = global_label_summary(np.asarray(dataset.column(label_col)))
        if ls["total"] == 0 or ls["y_min"] < 0 or not ls["all_int"]:
            return None  # solo path raises the user-facing error
        if ls["all_same"]:
            # degenerate single-label fits bypass the solver entirely
            return None
        n_classes = max(int(ls["y_max"]) + 1, 2)
        multinomial = n_classes > 2
        self._gang_k_eff = n_classes if multinomial else 1

        def _gang_fit(
            inputs: FitInputs,
            group_ps: List[Dict[str, Any]],
            *,
            fold_id: Any = None,
            lane_fold: Any = None,
            n_folds: int = 0,
        ) -> List[Dict[str, Any]]:
            ps0 = group_ps[0]
            pairs = [self._gang_reg_pair(ps) for ps in group_ps]
            l1 = jnp.asarray([p[0] for p in pairs], inputs.dtype)
            l2 = jnp.asarray([p[1] for p in pairs], inputs.dtype)
            tol = jnp.asarray([float(ps["tol"]) for ps in group_ps], inputs.dtype)
            out = logreg_fit_batched(
                inputs.X,
                inputs.mask,
                inputs.y,
                n_classes=n_classes,
                multinomial=multinomial,
                fit_intercept=bool(ps0["fit_intercept"]),
                standardization=bool(ps0["standardization"]),
                l1=l1,
                l2=l2,
                use_l1=bool(pairs[0][0] > 0.0),
                max_iter=int(ps0["max_iter"]),
                tol=tol,
                mesh=inputs.mesh,
                objective_dtype=_resolve_objective_dtype(ps0),
                fold_id=fold_id,
                lane_fold=(
                    None if lane_fold is None else jnp.asarray(lane_fold, jnp.int32)
                ),
                n_folds=int(n_folds),
            )
            coef = np.asarray(out["coef_"])
            intercept = np.asarray(out["intercept_"])
            n_iter = np.asarray(out["n_iter"])
            objective = np.asarray(out["objective"])
            return [
                {
                    "coef_": coef[b],
                    "intercept_": intercept[b],
                    "n_classes": n_classes,
                    "multinomial": multinomial,
                    "n_iter": int(n_iter[b]),
                    "objective": float(objective[b]),
                }
                for b in range(len(group_ps))
            ]

        return _gang_fit

    def _get_tpu_streaming_fit_func(self, dataset: DataFrame):
        """Out-of-core fit: host-driven L-BFGS/OWL-QN where every objective
        evaluation is one chunked pass over the data (the re-read-per-
        iteration cost cuML's out-of-core QN pays, reference
        ``classification.py:955-1140``); label analysis is its own streaming
        pass instead of a column materialization."""
        from ..core import StreamInputs
        from ..ops.streaming import streamed_label_stats, streamed_logreg_fit

        label_cache: Dict[str, Any] = {}

        def _fit(inputs: StreamInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            if not label_cache:
                label_cache.update(
                    streamed_label_stats(inputs.source, inputs.chunk_rows)
                )
            ls = label_cache
            if ls["y_min"] < 0 or not ls["all_int"]:
                raise RuntimeError(
                    "Labels MUST be non-negative integers, got values outside that set"
                )
            # Spark semantics: numClasses = max(label) + 1
            n_classes = max(int(ls["y_max"]) + 1, 2)
            multinomial = n_classes > 2
            fit_intercept = bool(params["fit_intercept"])

            if ls["all_same"] and n_classes == 2 and fit_intercept:
                # single-label degenerate case (reference
                # ``classification.py:1119-1132``)
                class_val = float(ls["first"])
                return {
                    "coef_": np.zeros((1, inputs.n_features)),
                    "intercept_": np.asarray(
                        [np.inf if class_val == 1.0 else -np.inf]
                    ),
                    "n_classes": n_classes,
                    "multinomial": False,
                    "n_iter": 0,
                    "objective": 0.0,
                }

            c = float(params["C"])
            reg = 1.0 / c if c > 0.0 else 0.0
            l1_ratio = float(params["l1_ratio"])
            if _resolve_objective_dtype(params) != "float32":
                # validate AND be explicit: the streamed fit's bottleneck
                # is chunk ingest (the wire-dtype path already narrows
                # transfers), so bf16 objective reads do not apply here
                get_logger(type(self)).warning(
                    "objective_dtype=bfloat16 applies to the resident fit "
                    "only; the streaming fit reads chunks at wire dtype"
                )
            # checkpoint identity: the L-BFGS walk is fully determined by
            # the objective config + data; shape/size stand in for a data
            # digest (a content pass would cost a full extra read)
            from ..runtime.checkpoint import FitCheckpointer

            ckpt = FitCheckpointer.from_env(
                "logreg",
                {
                    "n_classes": n_classes,
                    "multinomial": multinomial,
                    "fit_intercept": fit_intercept,
                    "standardization": bool(params["standardization"]),
                    "l1": reg * l1_ratio,
                    "l2": reg * (1.0 - l1_ratio),
                    "max_iter": int(params["max_iter"]),
                    "tol": float(params["tol"]),
                    "n_rows": int(inputs.n_rows),
                    "d": int(inputs.n_features),
                },
            )
            out = streamed_logreg_fit(
                inputs.source,
                inputs.mesh,
                inputs.chunk_rows,
                inputs.dtype,
                n_classes=n_classes,
                multinomial=multinomial,
                fit_intercept=fit_intercept,
                standardization=bool(params["standardization"]),
                l1=reg * l1_ratio,
                l2=reg * (1.0 - l1_ratio),
                max_iter=int(params["max_iter"]),
                tol=float(params["tol"]),
                checkpointer=ckpt if ckpt.enabled else None,
            )
            return {
                "coef_": np.asarray(out["coef_"]),
                "intercept_": np.asarray(out["intercept_"]),
                "n_classes": n_classes,
                "multinomial": multinomial,
                "n_iter": int(out["n_iter"]),
                "objective": float(out["objective"]),
            }

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "LogisticRegressionModel":
        return LogisticRegressionModel(**result)


class LogisticRegressionModel(
    LogisticRegressionClass, _TpuModel, _LogisticRegressionParams
):
    def __init__(self, **attrs: Any) -> None:
        _TpuModel.__init__(self, **attrs)
        _LogisticRegressionParams.__init__(self)

    # -- attribute surface (Spark model API) -------------------------------
    @property
    def coef_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["coef_"])

    @property
    def intercept_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["intercept_"])

    @property
    def numClasses(self) -> int:
        return int(self._model_attributes["n_classes"])

    @property
    def numFeatures(self) -> int:
        return int(self.coef_.shape[-1])

    @property
    def _multinomial(self) -> bool:
        v = self._model_attributes["multinomial"]
        if isinstance(v, str):  # JSON round-trip through persistence
            return v == "True"
        return bool(np.asarray(v))

    @property
    def coefficients(self) -> np.ndarray:
        """Binary-model coefficient vector (Spark raises for multinomial)."""
        if self._multinomial:
            raise RuntimeError(
                "Multinomial model: use coefficientMatrix instead of coefficients"
            )
        return self.coef_.reshape(-1)

    @property
    def intercept(self) -> float:
        if self._multinomial:
            raise RuntimeError(
                "Multinomial model: use interceptVector instead of intercept"
            )
        return float(self.intercept_.reshape(-1)[0])

    @property
    def coefficientMatrix(self) -> np.ndarray:
        return np.atleast_2d(self.coef_)

    @property
    def interceptVector(self) -> np.ndarray:
        return np.atleast_1d(self.intercept_)

    @property
    def classes_(self) -> np.ndarray:
        return np.arange(self.numClasses, dtype=np.float64)

    @property
    def hasSummary(self) -> bool:
        return False

    @property
    def n_iter_(self) -> int:
        return int(self._model_attributes.get("n_iter", 0))

    # -- single-row helpers (Spark model API) ------------------------------
    def _scores(self, x: np.ndarray) -> np.ndarray:
        coef = np.atleast_2d(self.coef_).astype(np.float64)
        b = np.atleast_1d(self.intercept_).astype(np.float64)
        return x @ coef.T + b

    def predict(self, vector: Any) -> float:
        x = np.asarray(vector, dtype=np.float64).ravel()
        s = self._scores(x[None, :])[0]
        if self._multinomial:
            return float(np.argmax(s))
        return float(s[0] > 0)

    def predictRaw(self, vector: Any) -> np.ndarray:
        x = np.asarray(vector, dtype=np.float64).ravel()
        s = self._scores(x[None, :])[0]
        if self._multinomial:
            return s
        return np.asarray([-s[0], s[0]])

    def predictProbability(self, vector: Any) -> np.ndarray:
        raw = self.predictRaw(vector)
        if self._multinomial:
            e = np.exp(raw - raw.max())
            return e / e.sum()
        p1 = 1.0 / (1.0 + np.exp(-raw[1]))
        return np.asarray([1.0 - p1, p1])

    # -- transform ---------------------------------------------------------
    def _out_cols(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _get_tpu_transform_func(
        self, dataset: Optional[DataFrame] = None
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")
        return self._memoized_transform_fn(
            ("logreg", pred_col, prob_col, raw_col),
            lambda: self._build_transform_fn(pred_col, prob_col, raw_col),
        )

    def _build_transform_fn(
        self, pred_col: str, prob_col: str, raw_col: str
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        coef_np = np.atleast_2d(self.coef_)
        b_np = np.atleast_1d(self.intercept_)
        multinomial = self._multinomial
        if not self._is_multi_model and not np.all(np.isfinite(b_np)):
            # degenerate single-label model: ±inf intercept would poison the
            # matmul; emit constant predictions directly
            const_pred = 1.0 if b_np.reshape(-1)[0] > 0 else 0.0

            def _const(Xb: np.ndarray) -> Dict[str, np.ndarray]:
                n = Xb.shape[0]
                pred = np.full((n,), const_pred, dtype=Xb.dtype)
                prob = np.zeros((n, 2), dtype=Xb.dtype)
                prob[:, int(const_pred)] = 1.0
                raw = np.zeros((n, 2), dtype=Xb.dtype)
                raw[:, int(const_pred)] = np.inf
                raw[:, 1 - int(const_pred)] = -np.inf
                return {pred_col: pred, prob_col: prob, raw_col: raw}

            return _const

        if self._is_multi_model:
            # CV-combined model: coef_ (m, K, d) -> per-model outputs
            # prediction (n, m), probability (n, m, K), raw (n, m, K)
            coef3 = self.coef_

            @jax.jit
            def _predict_multi(Xb: jax.Array):
                C = jnp.asarray(coef3, dtype=Xb.dtype)      # (m, K, d)
                B = jnp.asarray(np.atleast_2d(b_np), dtype=Xb.dtype)  # (m, K)
                scores = jnp.einsum("nd,mkd->nmk", Xb, C) + B[None, :, :]
                if multinomial:
                    raw = scores
                    prob = jax.nn.softmax(scores, axis=2)
                    pred = jnp.argmax(scores, axis=2).astype(Xb.dtype)
                else:
                    z = scores[..., 0]
                    raw = jnp.stack([-z, z], axis=2)
                    p1 = jax.nn.sigmoid(z)
                    prob = jnp.stack([1.0 - p1, p1], axis=2)
                    pred = (p1 > 0.5).astype(Xb.dtype)
                return pred, prob, raw

            def _fn_multi(Xb: np.ndarray) -> Dict[str, np.ndarray]:
                pred, prob, raw = _predict_multi(batch_to_device(Xb))
                return {
                    pred_col: output_to_host(pred),
                    prob_col: output_to_host(prob),
                    raw_col: output_to_host(raw),
                }

            return _fn_multi

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            pred, prob, raw = logreg_predict(
                batch_to_device(Xb),
                jnp.asarray(coef_np, dtype=Xb.dtype),
                jnp.asarray(b_np, dtype=Xb.dtype),
                multinomial=multinomial,
            )
            # device arrays: every caller materializes the columns itself
            # (core._apply_batched under its transform.fetch span, the
            # serving dispatcher after its transfer fault site), so the wait
            # for the batch lands where it is named
            return {pred_col: pred, prob_col: prob, raw_col: raw}

        return _fn

    # -- multi-model support (CV single-pass) ------------------------------
    @classmethod
    def _combine(
        cls, models: List["LogisticRegressionModel"]
    ) -> "LogisticRegressionModel":
        """Stack models for single-pass multi-model evaluation (reference
        ``classification.py:1504-1519``)."""
        coefs = np.stack([np.atleast_2d(m.coef_) for m in models])  # (m, K, d)
        intercepts = np.stack([np.atleast_1d(m.intercept_) for m in models])
        combined = cls(
            coef_=coefs,
            intercept_=intercepts,
            n_classes=models[0].numClasses,
            multinomial=models[0]._multinomial,
            n_iter=0,
            objective=0.0,
        )
        models[0]._copyValues(combined)
        models[0]._copy_tpu_params(combined)
        return combined

    @property
    def _is_multi_model(self) -> bool:
        return self.coef_.ndim == 3

    def _transformEvaluate(self, dataset: DataFrame, evaluator: Any) -> List[float]:
        """ONE data pass -> per-model confusion/log-loss sufficient stats ->
        metric values (reference ``classification.py:153-272``)."""
        from ..evaluation import MulticlassClassificationEvaluator
        from ..metrics import MulticlassMetrics

        if not isinstance(evaluator, MulticlassClassificationEvaluator):
            raise NotImplementedError(
                f"Evaluator {type(evaluator).__name__} is not supported"
            )
        X = self._extract_features_for_transform(dataset)
        out = self._apply_batched(self._get_tpu_transform_func(dataset), X)
        preds = out[self.getOrDefault("predictionCol")]
        probs = out[self.getOrDefault("probabilityCol")]
        y = np.asarray(dataset.column(evaluator.getLabelCol()), dtype=np.float64)
        need_probs = evaluator.getMetricName() == "logLoss"
        if preds.ndim == 1:
            preds, probs = preds[:, None], probs[:, None, :]
        return [
            MulticlassMetrics.from_predictions(
                y,
                preds[:, j],
                probs[:, j, :] if need_probs else None,
                evaluator.getEps(),
            ).evaluate(evaluator)
            for j in range(preds.shape[1])
        ]
