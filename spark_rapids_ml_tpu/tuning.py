"""Hyper-parameter tuning — single-pass CrossValidator.

Reference: ``/root/reference/python/src/spark_rapids_ml/tuning.py`` (177 LoC).
Its key optimization (``tuning.py:91-148``): when the estimator supports it,
fit **all** param maps in one data pass (``est.fitMultiple``), ``_combine``
the models into one multi-model, and evaluate every model in **one**
transform pass (``model._transformEvaluate``) per fold — instead of Spark's
per-param-map jobs. The same structure is kept here: the design matrix is
sharded onto the device mesh once per fold and every candidate reuses it;
folds run on a thread pool (reference ``tuning.py:106-129``).

``ParamGridBuilder`` is provided locally (the reference imports Spark's).
"""

from __future__ import annotations

import itertools
import os
import threading
from multiprocessing.pool import ThreadPool
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .core import _TpuEstimator, _TpuModel
from .data.dataframe import DataFrame, kfold
from .evaluation import Evaluator
from .params import Param, Params, TypeConverters, _mk
from .runtime import counters as _res_counters
from .runtime import envspec, telemetry
from .utils.logging import get_logger


def _cv_failfast() -> bool:
    """``TPUML_CV_FAILFAST`` (default 1 = reference semantics: any failed
    fold/param fit aborts the grid search). ``0`` records the failed combo
    as worst-metric and keeps searching — graceful degradation for long
    grids where one pathological combo (divergent solver, OOM) should not
    discard every other result."""
    return bool(envspec.get("TPUML_CV_FAILFAST"))

# Serializes per-fold device work under parallel CV (see run_fold in
# CrossValidator.fit): concurrent first-compiles of one jitted fit from
# multiple threads were seen to deadlock on an earlier jax; not re-tested on
# the installed 0.9.0, so the lock stays.
_FOLD_DEVICE_LOCK = threading.Lock()


class ParamGridBuilder:
    """Drop-in for ``pyspark.ml.tuning.ParamGridBuilder``."""

    def __init__(self) -> None:
        self._param_grid: Dict[Param, List[Any]] = {}

    def addGrid(self, param: Param, values: Sequence[Any]) -> "ParamGridBuilder":
        if not isinstance(param, Param):
            raise TypeError("param must be an instance of Param")
        self._param_grid[param] = list(values)
        return self

    def baseOn(self, *args: Any) -> "ParamGridBuilder":
        if isinstance(args[0], dict):
            self.baseOn(*args[0].items())
            return self
        for param, value in args:
            self.addGrid(param, [value])
        return self

    def build(self) -> List[Dict[Param, Any]]:
        keys = list(self._param_grid.keys())
        grid_values = [self._param_grid[k] for k in keys]
        return [
            dict(zip(keys, combo)) for combo in itertools.product(*grid_values)
        ]


class _CrossValidatorParams(Params):
    numFolds = _mk("numFolds", "number of folds (>= 2)", TypeConverters.toInt)
    seed = _mk("seed", "random seed for fold assignment", TypeConverters.toInt)
    parallelism = _mk("parallelism", "thread-pool width over folds", TypeConverters.toInt)
    collectSubModels = _mk(
        "collectSubModels", "keep all sub-models on the CV model", TypeConverters.toBoolean
    )

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(numFolds=3, seed=0, parallelism=1, collectSubModels=False)

    def getNumFolds(self) -> int:
        return self.getOrDefault("numFolds")

    def getSeed(self) -> int:
        return self.getOrDefault("seed")

    def getParallelism(self) -> int:
        return self.getOrDefault("parallelism")


class CrossValidator(_CrossValidatorParams):
    """Drop-in for ``pyspark.ml.tuning.CrossValidator`` with the reference's
    single-pass fast path (reference ``tuning.py:45-148``)."""

    def __init__(
        self,
        estimator: Optional[_TpuEstimator] = None,
        estimatorParamMaps: Optional[List[Dict[Param, Any]]] = None,
        evaluator: Optional[Evaluator] = None,
        numFolds: int = 3,
        seed: int = 0,
        parallelism: int = 1,
        **kwargs: Any,
    ) -> None:
        super().__init__()
        self._est = estimator
        self._epm = estimatorParamMaps
        self._eva = evaluator
        self._set(numFolds=numFolds, seed=seed, parallelism=parallelism)
        for name, value in kwargs.items():
            if not self.hasParam(name):
                raise ValueError(f"Unknown param {name!r} for CrossValidator")
            self._set(**{name: value})
        self.logger = get_logger(type(self))

    # -- component accessors (pyspark API) ---------------------------------
    def setEstimator(self, value: _TpuEstimator) -> "CrossValidator":
        self._est = value
        return self

    def getEstimator(self) -> _TpuEstimator:
        return self._est

    def setEstimatorParamMaps(self, value: List[Dict[Param, Any]]) -> "CrossValidator":
        self._epm = value
        return self

    def getEstimatorParamMaps(self) -> List[Dict[Param, Any]]:
        return self._epm

    def setEvaluator(self, value: Evaluator) -> "CrossValidator":
        self._eva = value
        return self

    def getEvaluator(self) -> Evaluator:
        return self._eva

    def setNumFolds(self, value: int) -> "CrossValidator":
        self._set(numFolds=value)
        return self

    def setParallelism(self, value: int) -> "CrossValidator":
        self._set(parallelism=value)
        return self

    def setSeed(self, value: int) -> "CrossValidator":
        self._set(seed=value)
        return self

    # -- fit ---------------------------------------------------------------
    def fit(self, dataset: DataFrame) -> "CrossValidatorModel":
        est, epm, eva = self._est, self._epm, self._eva
        if est is None or epm is None or eva is None:
            raise ValueError("estimator, estimatorParamMaps and evaluator must be set")
        num_models = len(epm)
        n_folds = self.getNumFolds()
        if n_folds < 2:
            raise ValueError("numFolds must be >= 2")

        # fast path requires the estimator's model to implement _combine +
        # _transformEvaluate (reference gate: ``tuning.py:96-99``)
        single_pass = est._supportsTransformEvaluate(eva)

        folds = kfold(dataset, n_folds, self.getSeed())
        collect_sub = bool(self.getOrDefault("collectSubModels"))

        failfast = _cv_failfast()
        # tolerant mode sentinel: a failed combo can never win the argmax/
        # argmin (and is visibly ±inf in avgMetrics)
        worst = -np.inf if eva.isLargerBetter() else np.inf

        # gang path: fit the whole folds × maps grid as fold-masked lanes
        # over ONE resident X (TPUML_GANG_FIT; estimator declines with None
        # and the per-fold path below runs unchanged). Runs before the
        # thread pool spins up, so no device lock is needed here.
        gang_grid: Optional[List[List[_TpuModel]]] = None
        if single_pass:
            try:
                gang_grid = est._gang_cv_fit_multiple(
                    dataset, epm, n_folds, self.getSeed()
                )
            except envspec.EnvSpecError:
                raise  # config errors surface regardless of failfast mode
            except Exception:
                if failfast:
                    raise
                self.logger.exception(
                    "gang CV fit failed; falling back to the per-fold path "
                    "(TPUML_CV_FAILFAST=0)"
                )
                gang_grid = None

        def run_fold(i: int) -> Tuple[np.ndarray, Optional[List[_TpuModel]]]:
            with telemetry.span("cv.fold", fold=i):
                return _run_fold(i)

        def _run_fold(
            i: int,
        ) -> Tuple[np.ndarray, Optional[List[_TpuModel]]]:
            # Device passes are serialized across fold threads: an earlier
            # jax could deadlock (futex wedge inside the dispatch lock) when
            # several threads race the *first* compile of the same jitted
            # fit. The lock covers ONLY device work — fold selection,
            # host-side _combine stacking, and metric aggregation run
            # outside the critical section so fold threads overlap there.
            train, validation = folds[i]
            if single_pass:
                try:
                    if gang_grid is not None:
                        models: List[_TpuModel] = gang_grid[i]
                    else:
                        with _FOLD_DEVICE_LOCK:
                            # ONE barrier-pass fit of all maps
                            models = [m for _, m in est.fitMultiple(train, epm)]
                    # host numpy stacking — no device work
                    combined = type(models[0])._combine(models)
                    with _FOLD_DEVICE_LOCK:
                        # ONE evaluate pass for every candidate
                        vals = combined._transformEvaluate(validation, eva)
                    return (
                        np.asarray(vals, dtype=np.float64),
                        models if collect_sub else None,
                    )
                except Exception:
                    if failfast:
                        raise
                    # the single-pass fit is all-or-nothing; fall through
                    # to the per-param-map loop so only the offending
                    # combos are recorded as failed
                    self.logger.exception(
                        "fold %d: single-pass fit failed; retrying "
                        "per-param-map (TPUML_CV_FAILFAST=0)", i
                    )
            vals, models = [], []
            for j, pm in enumerate(epm):
                try:
                    with _FOLD_DEVICE_LOCK:
                        model = est.fit(train, pm)
                        transformed = model.transform(validation)
                    # metric aggregation is host-side — outside the lock
                    vals.append(eva.evaluate(transformed))
                except Exception:
                    if failfast:
                        raise
                    self.logger.exception(
                        "fold %d param map %d: fit/evaluate failed; "
                        "recording worst metric (TPUML_CV_FAILFAST=0)",
                        i, j,
                    )
                    _res_counters.bump("cv_failed_fits")
                    vals.append(worst)
                    model = None
                if collect_sub:
                    models.append(model)
            return (
                np.asarray(vals, dtype=np.float64),
                models if collect_sub else None,
            )

        par = max(1, self.getParallelism())
        if par > 1:
            with ThreadPool(processes=min(par, n_folds)) as pool:
                # pool threads inherit the caller's span stack so fold
                # spans nest under the surrounding fit/tuning span
                fold_results = pool.map(
                    telemetry.bind_context(run_fold), range(n_folds)
                )
        else:
            fold_results = [run_fold(i) for i in range(n_folds)]
        metrics_per_fold = [m for m, _ in fold_results]
        sub_models = [s for _, s in fold_results] if collect_sub else None

        avg = np.mean(np.stack(metrics_per_fold), axis=0)
        best_idx = int(np.argmax(avg) if eva.isLargerBetter() else np.argmin(avg))
        if not np.isfinite(avg[best_idx]):
            raise RuntimeError(
                "CrossValidator: every param map failed in tolerant mode "
                "(TPUML_CV_FAILFAST=0) — no finite metric to select a best "
                "model from"
            )
        self.logger.info(
            "CrossValidator: best param map %d with avg metric %.6f",
            best_idx,
            avg[best_idx],
        )
        best_model = est.fit(dataset, epm[best_idx])
        cv_model = CrossValidatorModel(
            bestModel=best_model,
            avgMetrics=list(avg),
            stdMetrics=list(np.std(np.stack(metrics_per_fold), axis=0)),
        )
        cv_model.subModels = sub_models
        cv_model._est, cv_model._epm, cv_model._eva = est, epm, eva
        return cv_model


class CrossValidatorModel(_CrossValidatorParams):
    """Fitted CV model wrapping the best model (pyspark API surface)."""

    def __init__(
        self,
        bestModel: Optional[_TpuModel] = None,
        avgMetrics: Optional[List[float]] = None,
        stdMetrics: Optional[List[float]] = None,
    ) -> None:
        super().__init__()
        self.bestModel = bestModel
        self.avgMetrics = avgMetrics or []
        self.stdMetrics = stdMetrics or []
        self.subModels: Optional[List[_TpuModel]] = None

    def transform(self, dataset: DataFrame) -> DataFrame:
        return self.bestModel.transform(dataset)

    # -- persistence: delegate to the best model + metrics sidecar ---------
    def save(self, path: str) -> None:
        import json
        import os

        self.bestModel.save(path)
        with open(os.path.join(path, "cv_metadata.json"), "w") as f:
            json.dump(
                {"avgMetrics": self.avgMetrics, "stdMetrics": self.stdMetrics}, f
            )

    @classmethod
    def load(cls, path: str) -> "CrossValidatorModel":
        import json
        import os

        from .core import _Reader

        best = _Reader(_TpuModel).load(path)
        meta_path = os.path.join(path, "cv_metadata.json")
        avg, std = [], []
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                m = json.load(f)
            avg, std = m.get("avgMetrics", []), m.get("stdMetrics", [])
        return cls(bestModel=best, avgMetrics=avg, stdMetrics=std)
