"""KMeans — Spark ML drop-in, TPU-native fit/transform.

Reference: ``/root/reference/python/src/spark_rapids_ml/clustering.py``
(491 LoC; cuML ``KMeansMG`` fit at :340-378, per-batch predict transform at
:458-491). Param mapping parity (reference ``clustering.py:59-82``):
``initMode→init``, ``k→n_clusters``, ``maxIter→max_iter``,
``seed→random_state``, ``tol→tol``; ``distanceMeasure`` only supports
"euclidean"; ``weightCol`` unsupported.

TPU-native fit (vs cuML's NCCL-allreduce Lloyd):
  * k-means|| seeding (Spark's default initMode): device passes compute
    min-distances and candidate weights (``ops/kmeans_kernels.py``), the
    small weighted k-means++ reduction of ~l·steps candidates runs on host;
  * Lloyd loop = ONE compiled ``lax.while_loop`` with per-device chunked
    scans and ``psum`` of (sums, counts, cost) over the dp mesh axis.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

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
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
    HasTol,
    HasWeightCol,
    TypeConverters,
    _mk,
)
from ..ops.kmeans_kernels import (
    _kmeans_lloyd_1d,
    _kmeans_lloyd_mp,
    count_closest,
    kmeans_lloyd,
    min_sq_dists,
    mp_kmeans_shards,
    pairwise_sq_dists,
)
from ..ops.kmeans_pallas import kmeans_pallas_declined, lloyd_tile
from ..parallel.mesh import DP_AXIS
from ..runtime import envspec, telemetry

_CHUNK = 4096


class KMeansClass:
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "k": "n_clusters",
            "initMode": "init",
            "initSteps": "init_steps",
            "maxIter": "max_iter",
            "seed": "random_state",
            "tol": "tol",
            "distanceMeasure": "distance_measure",
            "weightCol": None,
            "solver": "",
            "maxBlockSizeInMB": "",
        }

    @classmethod
    def _param_value_mapping(cls) -> Dict[str, Callable[[Any], Any]]:
        def _check_init(v: str) -> str:
            if v not in ("k-means||", "random"):
                raise ValueError(f"Unsupported initMode: {v!r}")
            return v

        def _check_dist(v: str) -> str:
            if v != "euclidean":
                raise ValueError(
                    f"Only euclidean distance is supported, got {v!r}"
                )
            return v

        return {"init": _check_init, "distance_measure": _check_dist}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_clusters": 2,
            "init": "k-means||",
            "init_steps": 2,
            "max_iter": 20,
            "tol": 1e-4,
            "random_state": 1,
            "oversampling_factor": 2.0,
            "distance_measure": "euclidean",
            "matmul_dtype": None,
        }


class _KMeansParams(
    HasFeaturesCol, HasFeaturesCols, HasPredictionCol, HasMaxIter, HasTol, HasSeed, HasWeightCol
):
    k = _mk("k", "number of clusters", TypeConverters.toInt)
    initMode = _mk("initMode", "init algorithm: k-means|| or random", TypeConverters.toString)
    initSteps = _mk("initSteps", "k-means|| init steps", TypeConverters.toInt)
    distanceMeasure = _mk("distanceMeasure", "distance measure", TypeConverters.toString)
    # accepted-but-ignored Spark >= 3.4 params (""-mapped)
    solver = _mk("solver", "optimization solver (ignored)", TypeConverters.toString)
    maxBlockSizeInMB = _mk(
        "maxBlockSizeInMB", "block size hint (ignored)", TypeConverters.toFloat
    )

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(
            k=2, initMode="k-means||", initSteps=2, maxIter=20, tol=1e-4,
            distanceMeasure="euclidean",
        )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def getInitMode(self) -> str:
        return self.getOrDefault("initMode")


class KMeans(KMeansClass, _TpuEstimator, _KMeansParams):
    """``KMeans(k=1000, maxIter=30).fit(df)`` — drop-in for
    ``pyspark.ml.clustering.KMeans``."""

    def __init__(self, **kwargs: Any) -> None:
        _TpuEstimator.__init__(self)
        _KMeansParams.__init__(self)
        self._set_params(**kwargs)

    def setK(self, value: int) -> "KMeans":
        self._set_params(k=value)
        return self

    def setMaxIter(self, value: int) -> "KMeans":
        self._set_params(maxIter=value)
        return self

    def setTol(self, value: float) -> "KMeans":
        self._set_params(tol=value)
        return self

    def setSeed(self, value: int) -> "KMeans":
        self._set_params(seed=value)
        return self

    def setInitMode(self, value: str) -> "KMeans":
        self._set_params(initMode=value)
        return self

    def _chunk_rows(self, n_rows: int, n_dp: int) -> int:
        return self._equal_chunk_rows(n_rows, n_dp, _CHUNK)

    @staticmethod
    def _resolve_matmul_dtype(params):
        """Validated (early, before any seeding work) bf16-matmul option;
        returns a jnp dtype or None. Kwarg beats TPUML_KMEANS_MATMUL_DTYPE."""
        # registry read: empty-string env (a shell-default pattern) means
        # unset, and a malformed env value names the variable in the error
        mm = (
            params.get("matmul_dtype")
            or envspec.get("TPUML_KMEANS_MATMUL_DTYPE")
            or None
        )
        if mm is not None and str(mm) not in ("float32", "bfloat16"):
            raise ValueError(
                f"matmul_dtype must be float32|bfloat16, got {mm!r}"
            )
        return jnp.bfloat16 if str(mm) == "bfloat16" else None

    def _feature_pad_multiple(self) -> int:
        """Lloyd's ``while_loop`` triggers a defensive full copy of X at
        lane-unaligned d (~2x matrix HBM at exactly the reference's d=3000
        shape); zero columns are invariant under Lloyd updates (zero-seeded
        centers stay zero, distances/costs unchanged) and TPU tiles the
        minor dim to 128 physically anyway, so the padding is HBM-free.
        The zero columns are written on the device by ``shard_rows``
        (``cols``), not by a host ``np.pad`` of the frame (7.8 s at
        500,000 x 3000; PERF.md §6, PR 30).
        ``TPUML_LANE_PAD`` overrides (CI exercises the path on CPU)."""
        env = envspec.get("TPUML_LANE_PAD")
        if env is not None:
            return int(env)
        import jax

        return 128 if jax.default_backend() == "tpu" else 0

    # ---- seeding ---------------------------------------------------------
    # ONE sampling implementation serves both the resident and streaming
    # fits, parameterized over a slice "owner" — each rank owns the global
    # logical rows [offset, offset+n_local) and keeps only O(n_local) host
    # state. The rng consumption sequence is part of the contract (same
    # seed => identical seeding on every path and every rank), so the
    # logic must not fork: uniform draws happen in rank-lockstep segments
    # (segmented draws of one generator consume the identical stream as a
    # single full-range draw).
    #
    # owner keys:
    #   offset, n_local — this rank's slice of [0, n_rows)
    #   gather_local(sorted_local_idx) -> rows of MY slice (host)
    #   assemble(my_rows) -> all ranks' rows, rank-order (identity when
    #                        the owner spans the full range)
    #   min_d2_vs(cands) -> (n_local,) min sq dist of my slice to cands
    #   reduce_sum(x) -> world sum (identity single-owner)
    #   count_closest(cands) -> world closest-row counts per candidate

    @staticmethod
    def _rng_slice(
        rng: np.random.Generator, n_rows: int, offset: int, n_local: int
    ) -> np.ndarray:
        """Lockstep uniforms for [0, n_rows), keeping only this rank's
        slice."""
        if offset:
            rng.random(offset)
        r = rng.random(n_local)
        rest = n_rows - offset - n_local
        if rest:
            rng.random(rest)
        return r

    @staticmethod
    def _gather_global(owner: Dict[str, Any], idx: np.ndarray) -> np.ndarray:
        """Rows for sorted GLOBAL indices: each rank serves its own hits;
        rank-order assembly reproduces the sorted order."""
        idx = np.sort(np.asarray(idx, np.int64))
        off, nl = owner["offset"], owner["n_local"]
        mine = idx[(idx >= off) & (idx < off + nl)] - off
        return owner["assemble"](owner["gather_local"](mine))

    @staticmethod
    def _seed_random(
        n_rows: int, k: int, rng: np.random.Generator, owner: Dict[str, Any]
    ) -> np.ndarray:
        idx = rng.choice(n_rows, size=k, replace=n_rows < k)
        return KMeans._gather_global(owner, idx)

    @staticmethod
    def _seed_scalable_kmeanspp(
        n_rows: int,
        k: int,
        steps: int,
        oversample: float,
        rng: np.random.Generator,
        owner: Dict[str, Any],
    ) -> np.ndarray:
        """k-means|| (Bahmani et al.): sample ~l=oversample*k candidates per
        round with prob l*d²/Σd², then reduce candidates to k centers with
        weighted k-means++ on host (the candidate set is tiny)."""
        l = max(int(oversample * k), 1)
        off, nl = owner["offset"], owner["n_local"]
        first = int(rng.integers(0, n_rows))
        cands = KMeans._gather_global(owner, np.asarray([first]))
        local_d2 = np.asarray(owner["min_d2_vs"](cands), np.float64)
        for _ in range(steps):
            total = float(owner["reduce_sum"](float(local_d2.sum())))
            if total <= 0:
                break
            r = KMeans._rng_slice(rng, n_rows, off, nl)
            sel = np.nonzero(r < np.minimum(l * local_d2 / total, 1.0))[0]
            new = owner["assemble"](owner["gather_local"](sel))
            if len(new) == 0:
                continue
            cands = np.concatenate([cands, new], axis=0)
            local_d2 = np.minimum(
                local_d2, np.asarray(owner["min_d2_vs"](new), np.float64)
            )
        if len(cands) < k:
            # not enough candidates — top up with random rows
            extra = KMeans._seed_random(n_rows, k - len(cands), rng, owner)
            return np.concatenate([cands, extra], axis=0)
        if len(cands) == k:
            return cands
        weights = np.asarray(owner["count_closest"](cands), np.float64)
        return _weighted_kmeanspp(cands.astype(np.float64), weights, k, rng)

    def _resident_owner(self, inputs: FitInputs) -> Dict[str, Any]:
        """Full-range owner: every rank computes identical samples; the
        device gathers are collective-safe because all ranks issue them
        with identical arguments."""
        from ..parallel.mesh import fetch_global, gather_rows_global

        # seeding addresses "logical valid rows 0..n_rows"; padded-array
        # positions of those rows come from the mask (padding is at the
        # end single-process but interleaved per-process block multi-host)
        valid_pos = np.nonzero(fetch_global(inputs.mask, inputs.mesh) > 0)[0]

        def gather_local(idx: np.ndarray) -> np.ndarray:
            if len(idx) == 0:
                d = inputs.n_features_padded or inputs.n_features
                return np.empty((0, d), np.float32)
            return gather_rows_global(inputs.X, valid_pos[idx], inputs.mesh)

        def min_d2_vs(cands: np.ndarray) -> np.ndarray:
            return np.asarray(
                fetch_global(
                    min_sq_dists(
                        inputs.X, inputs.mask, jnp.asarray(cands, inputs.dtype),
                        mesh=inputs.mesh, csize=inputs.csize,
                    ),
                    inputs.mesh,
                ),
                np.float64,
            )[valid_pos]

        def count_closest_fn(cands: np.ndarray) -> np.ndarray:
            return fetch_global(
                count_closest(
                    inputs.X, inputs.mask, jnp.asarray(cands, inputs.dtype),
                    mesh=inputs.mesh, csize=inputs.csize,
                ),
                inputs.mesh,
            )

        return {
            "offset": 0,
            "n_local": inputs.n_rows,
            "gather_local": gather_local,
            "assemble": lambda rows: rows,
            "min_d2_vs": min_d2_vs,
            "reduce_sum": lambda x: x,
            "count_closest": count_closest_fn,
        }

    def _init_random(self, inputs: FitInputs, k: int, rng: np.random.Generator) -> np.ndarray:
        return self._seed_random(inputs.n_rows, k, rng, self._resident_owner(inputs))

    def _init_scalable_kmeanspp(
        self,
        inputs: FitInputs,
        k: int,
        steps: int,
        oversample: float,
        rng: np.random.Generator,
    ) -> np.ndarray:
        return self._seed_scalable_kmeanspp(
            inputs.n_rows, k, steps, oversample, rng,
            self._resident_owner(inputs),
        )

    # ---- fit -------------------------------------------------------------
    def _get_tpu_fit_func(self, dataset: DataFrame) -> FitFunc:
        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params["n_clusters"])
            if k > inputs.n_rows:
                raise ValueError(f"k={k} must be <= number of rows {inputs.n_rows}")
            mm = self._resolve_matmul_dtype(params)
            rng = np.random.default_rng(int(params.get("random_state") or 0))
            init = str(params.get("init"))
            # the seeding gathers its rows from the device, so this span
            # also holds the wait for the frame to be there
            with telemetry.span("kmeans.init", mode=init, k=k) as i_span:
                if init == "random":
                    centers0 = self._init_random(inputs, k, rng)
                else:
                    centers0 = self._init_scalable_kmeanspp(
                        inputs, k, int(params.get("init_steps", 2)),
                        float(params.get("oversampling_factor", 2.0)), rng,
                    )
                i_span.set_attr(rows_gathered=len(centers0))
                centers0 = jnp.asarray(centers0, dtype=inputs.dtype)
            mp = mp_kmeans_shards(inputs.mesh, k)
            # the gate _chunk_stats takes at trace time, asked again on the
            # host so the span says which Lloyd step the program runs
            n_local = inputs.X.shape[0] // inputs.mesh.shape[DP_AXIS]
            d_pad = inputs.X.shape[1]
            declined = "mp" if mp > 1 else kmeans_pallas_declined(
                n_local, d_pad, k, inputs.X.dtype, mm
            )
            with telemetry.span(
                "solver.launch",
                program=(_kmeans_lloyd_mp if mp > 1 else _kmeans_lloyd_1d).__name__,
                kernel="xla" if declined else "pallas",
                tile=inputs.csize if declined else lloyd_tile(d_pad, k, mm)[0],
                **({"declined": declined} if declined else {}),
            ):
                centers, cost, n_iter = kmeans_lloyd(
                    inputs.X,
                    inputs.mask,
                    centers0,
                    mesh=inputs.mesh,
                    csize=inputs.csize,
                    max_iter=int(params["max_iter"]),
                    tol=float(params["tol"]),
                    # bf16 matmul operands / f32 accumulation on the two MXU
                    # contractions (~2x); final cost pass stays f32
                    matmul_dtype=mm,
                )
            # the first fetch blocks until the Lloyd program has run
            with telemetry.span("solver.fetch") as f_span:
                # strip lane-padding columns (zero by the Lloyd invariant)
                result = {
                    "cluster_centers": np.asarray(centers)[:, : inputs.n_features],
                    "training_cost": float(cost),
                    "n_iter": int(n_iter),
                }
                # passes over X that assign every row: the iterations and
                # the cost pass
                f_span.set_attr(
                    n_iter=result["n_iter"], n_evals=result["n_iter"] + 1
                )
            if mp > 1:
                kb = -(-k // mp)
                result["_fit_report"] = {
                    "mp_degree": mp,
                    "centroid_shard_bytes": int(
                        kb
                        * inputs.n_features_padded
                        * jnp.dtype(inputs.dtype).itemsize
                    ),
                }
            return result

        return _fit

    def _get_tpu_streaming_fit_func(self, dataset: DataFrame):
        """Out-of-core fit: seeding and Lloyd each run as chunked passes —
        device memory holds one chunk slab plus k×d centroid state; the only
        O(n) host state is the 8-byte/row min-distance array k-means||
        keeps (the dataset itself never materializes)."""
        from ..core import StreamInputs
        from ..ops.streaming import (
            streamed_count_closest,
            streamed_kmeans_lloyd,
            streamed_min_sq_dists_update,
            streamed_rows_at,
        )

        def _stream_owner(inputs: StreamInputs) -> Dict[str, Any]:
            """Slice owner: each rank owns its partition's rows in the
            process-major global order and keeps only O(local) host state."""
            import jax as _jax

            from ..parallel.mesh import (
                allgather_host,
                allgather_ragged_rows,
                allreduce_sum_host,
            )

            nproc = _jax.process_count()
            offset = 0
            if nproc > 1:
                counts = allgather_host(
                    np.asarray([inputs.source.n_rows])
                ).ravel().astype(np.int64)
                offset = int(counts[: _jax.process_index()].sum())

            def gather_local(idx: np.ndarray) -> np.ndarray:
                return streamed_rows_at(
                    inputs.source, inputs.chunk_rows, idx, inputs.dtype
                )

            def min_d2_vs(cands: np.ndarray) -> np.ndarray:
                return streamed_min_sq_dists_update(
                    inputs.source, inputs.mesh, inputs.chunk_rows, inputs.dtype,
                    cands, None,
                )

            def count_closest_fn(cands: np.ndarray) -> np.ndarray:
                local = streamed_count_closest(
                    inputs.source, inputs.mesh, inputs.chunk_rows, inputs.dtype,
                    cands,
                )
                (total,) = allreduce_sum_host(local)
                return total

            return {
                "offset": offset,
                "n_local": int(inputs.source.n_rows),
                "gather_local": gather_local,
                "assemble": (
                    allgather_ragged_rows if nproc > 1 else (lambda rows: rows)
                ),
                "min_d2_vs": min_d2_vs,
                "reduce_sum": (
                    (lambda x: float(allreduce_sum_host(np.asarray([x]))[0][0]))
                    if nproc > 1
                    else (lambda x: x)
                ),
                "count_closest": count_closest_fn,
            }

        def _fit(inputs: StreamInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params["n_clusters"])
            if k > inputs.n_rows:
                raise ValueError(f"k={k} must be <= number of rows {inputs.n_rows}")
            mm = self._resolve_matmul_dtype(params)  # validate before seeding
            rng = np.random.default_rng(int(params.get("random_state") or 0))
            owner = _stream_owner(inputs)
            if params.get("init") == "random":
                centers0 = self._seed_random(inputs.n_rows, k, rng, owner)
            else:
                centers0 = self._seed_scalable_kmeanspp(
                    inputs.n_rows, k, int(params.get("init_steps", 2)),
                    float(params.get("oversampling_factor", 2.0)), rng, owner,
                )
            # checkpoint identity: seeding is deterministic (seeded rng +
            # chunked passes), so refit regenerates the same centers0 and
            # its digest proves the Lloyd walk being resumed is this one
            from ..runtime.checkpoint import FitCheckpointer, array_digest

            ckpt = FitCheckpointer.from_env(
                "kmeans",
                {
                    "k": k,
                    "d": int(inputs.source.n_features),
                    "n_rows": int(inputs.n_rows),
                    "max_iter": int(params["max_iter"]),
                    "tol": float(params["tol"]),
                    "seed": int(params.get("random_state") or 0),
                    "init": str(params.get("init")),
                    "matmul_dtype": str(mm),
                    "centers0": array_digest(centers0),
                },
            )
            centers, cost, n_iter = streamed_kmeans_lloyd(
                inputs.source,
                inputs.mesh,
                inputs.chunk_rows,
                inputs.dtype,
                np.asarray(centers0),
                max_iter=int(params["max_iter"]),
                tol=float(params["tol"]),
                matmul_dtype=mm,
                checkpointer=ckpt if ckpt.enabled else None,
            )
            return {
                "cluster_centers": np.asarray(centers),
                "training_cost": float(cost),
                "n_iter": int(n_iter),
            }

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "KMeansModel":
        return KMeansModel(**result)


class KMeansModel(KMeansClass, _TpuModel, _KMeansParams):
    def __init__(self, **attrs: Any) -> None:
        _TpuModel.__init__(self, **attrs)
        _KMeansParams.__init__(self)

    @property
    def cluster_centers_(self) -> np.ndarray:
        return np.asarray(self._model_attributes["cluster_centers"])

    def clusterCenters(self) -> List[np.ndarray]:
        return list(self.cluster_centers_)

    @property
    def trainingCost(self) -> float:
        """Sum of squared distances to closest center (Spark
        ``summary.trainingCost`` analog)."""
        return float(self._model_attributes["training_cost"])

    @property
    def numIter(self) -> int:
        return int(self._model_attributes["n_iter"])

    def predict(self, vector: Any) -> int:
        """Single-vector predict (the reference falls back to the CPU model,
        ``clustering.py:445-449``; here the same kernel serves both).
        The jitted assigner is cached — rebuilding it per call would retrace."""
        pred_col = self.getOrDefault("predictionCol")
        if getattr(self, "_predict_fn_col", None) != pred_col:
            self._predict_fn = self._get_tpu_transform_func()
            self._predict_fn_col = pred_col
        out = self._predict_fn(np.asarray(vector, dtype=np.float32).reshape(1, -1))
        return int(out[pred_col][0])

    def _get_tpu_transform_func(
        self, dataset: Optional[DataFrame] = None
    ) -> Callable[[np.ndarray], Dict[str, np.ndarray]]:
        pred_col = self.getOrDefault("predictionCol")
        centers_np = self.cluster_centers_

        placed: Dict[Any, jax.Array] = {}  # the centers on the device, per batch dtype

        def _fn(Xb: np.ndarray) -> Dict[str, np.ndarray]:
            Xd = batch_to_device(Xb)
            if Xd.dtype not in placed:
                placed[Xd.dtype] = jnp.asarray(centers_np, dtype=Xd.dtype)
            return {pred_col: output_to_host(_assign_nearest(Xd, placed[Xd.dtype]))}

        return _fn


@jax.jit
def _assign_nearest(Xb: jax.Array, centers: jax.Array) -> jax.Array:
    """Index of the nearest center per row. The centers are an ARGUMENT: one
    program per batch shape serves every model (a closure over them was a
    new program, compiled or fetched, for every model that transformed)."""
    d2 = pairwise_sq_dists(Xb, centers)
    return jnp.argmin(d2, axis=1).astype(jnp.int32)


def _weighted_kmeanspp(
    cands: np.ndarray, weights: np.ndarray, k: int, rng: np.random.Generator
) -> np.ndarray:
    """Weighted k-means++ over the (small) k-means|| candidate set."""
    m = len(cands)
    w = np.maximum(weights, 1e-12)
    centers = np.empty((k, cands.shape[1]), dtype=cands.dtype)
    first = rng.choice(m, p=w / w.sum())
    centers[0] = cands[first]
    min_d2 = ((cands - centers[0]) ** 2).sum(axis=1)
    for i in range(1, k):
        p = w * min_d2
        tot = p.sum()
        if tot <= 0:
            # all remaining candidates coincide with chosen centers
            centers[i:] = cands[rng.choice(m, size=k - i)]
            break
        centers[i] = cands[rng.choice(m, p=p / tot)]
        d2 = ((cands - centers[i]) ** 2).sum(axis=1)
        min_d2 = np.minimum(min_d2, d2)
    return centers
