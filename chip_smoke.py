#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the dense-estimator path starts on the chip.

One process drives PCA, KMeans, LinearRegression, LogisticRegression and exact
kNN once, through the public estimators (``fit`` then ``transform`` /
``kneighbors``, one ``save``/``load`` round trip), on seeded data generated
here, and holds every result against a plain float32/float64 reference
written in this file with numpy and ``jax.numpy`` only (nothing below imports
the package's ``ops`` or ``models`` except to ask each phase's kernel gate).

    python chip_smoke.py            # one chip: pca kmeans linreg logreg knn
    python chip_smoke.py --chips 4  # four chips: pca kmeans knn at num_workers=4

Every phase prints one JSON line; the LAST line of stdout is exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
``"ok": true`` and exit code 0 need a TPU with the asked-for chip count, every
phase within its tolerance and every Pallas gate True. There is no mode that
prints ``"ok": true`` off the chip: with ``JAX_PLATFORMS=cpu`` and explicit
small sizes (``--rows 8192 --knn-items 4096 --knn-queries 256``) the phases
and comparisons run as a rehearsal and the script still ends ``"ok": false``,
exit 1; at the default (chip-sized) shapes off the chip it refuses at once.
The script pins no platform, starts no process and retries nothing. Timings
are smoke timings of a cold run, compile included — not benchmark numbers.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from spark_rapids_ml_tpu.classification import LogisticRegression  # noqa: E402
from spark_rapids_ml_tpu.clustering import KMeans  # noqa: E402
from spark_rapids_ml_tpu.data import DataFrame  # noqa: E402
from spark_rapids_ml_tpu.feature import PCA, PCAModel  # noqa: E402
from spark_rapids_ml_tpu.knn import NearestNeighbors  # noqa: E402
from spark_rapids_ml_tpu.regression import LinearRegression  # noqa: E402
from spark_rapids_ml_tpu.utils.platform import enable_compile_cache  # noqa: E402

D = 256                 # feature width
PCA_K = 3
KMEANS_K = 1024         # cut to rows // 16 when a rehearsal asks for fewer rows
KMEANS_ITERS = 10
LOGREG_ITERS = 20
KNN_K = 16
KNN_CHECK = 1024        # queries held against brute force
SUB = 262_144           # row subsample for transform / accuracy checks
BLOB_SPREAD = 10.0      # centre scale over within-blob sigma (= 1)
KNN_SPREAD = 1.0        # kNN data: ||x||² ~ d², so f32 rounding stays far below
                        # the gap between neighbours and "exact" can be tested
STRONG = (5.0, 4.0, 3.0)  # three planted directions: a clear top-3 eigengap
DEFAULT_SIZES = {"rows": 4_194_304, "knn_items": 1_048_576, "knn_queries": 65_536}
_GEN_ROWS = 1 << 18     # rows per generation chunk (own seeded stream each)
_REF_ROWS = 1 << 16     # rows per reference scan chunk


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def check(name: str, value: float, tol: float, op: str = "<=") -> dict:
    value = float(value)
    ok = bool(np.isfinite(value)) and (value <= tol if op == "<=" else value >= tol)
    return {"name": name, "value": value, "op": op, "tol": tol, "ok": bool(ok)}


# --------------------------------------------------------------------------
# seeded data, generated here
# --------------------------------------------------------------------------
def blob_centres(seed: int, kb: int, spread: float = BLOB_SPREAD) -> np.ndarray:
    """(kb, D) f64 generating centres: isotropic N(0, spread²) plus three
    planted orthonormal directions scaled by STRONG."""
    rng = np.random.default_rng([seed, 0])
    q3, _ = np.linalg.qr(rng.standard_normal((D, len(STRONG))))
    c = rng.standard_normal((kb, D))
    c += (rng.standard_normal((kb, len(STRONG))) * np.asarray(STRONG)) @ q3.T
    return spread * c


def blobs(seed: int, stream: int, n: int, centres: np.ndarray):
    """n rows f32: centre of a uniformly drawn blob + N(0, 1) noise. Chunks are
    drawn from independent seeded streams, so threads do not change the data."""
    X = np.empty((n, D), np.float32)
    blob = np.empty((n,), np.int32)
    c32 = centres.astype(np.float32)

    def fill(ci: int) -> None:
        lo, hi = ci * _GEN_ROWS, min((ci + 1) * _GEN_ROWS, n)
        rng = np.random.default_rng([seed, stream, ci])
        b = rng.integers(0, len(c32), hi - lo, dtype=np.int32)
        x = rng.standard_normal((hi - lo, D), dtype=np.float32)
        x += c32[b]
        X[lo:hi], blob[lo:hi] = x, b

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(-(-n // _GEN_ROWS))))
    return X, blob


def make_labels(seed: int, X: np.ndarray):
    """LinReg y = Xw + b + 0.1·noise (f32); LogReg y ~ Bernoulli(sigmoid(Xv + c))
    with logits of about unit scale, so the problem is not separable."""
    rng = np.random.default_rng([seed, 7])
    w = (rng.standard_normal(D) / np.sqrt(D)).astype(np.float32)
    v = (rng.standard_normal(D) / (np.sqrt(D) * BLOB_SPREAD)).astype(np.float32)
    y_lin = X @ w + np.float32(0.5)
    y_lin += np.float32(0.1) * rng.standard_normal(len(X), dtype=np.float32)
    logits = X @ v - np.float32(0.25)
    y_log = (rng.random(len(X), dtype=np.float32) < 1.0 / (1.0 + np.exp(-logits)))
    return y_lin, y_log.astype(np.float32)


# --------------------------------------------------------------------------
# plain references (numpy f64 on the host, jax.numpy f32 on device 0)
# --------------------------------------------------------------------------
def host_moments_f64(X: np.ndarray, y: np.ndarray):
    """One f64 pass over ALL rows: Σ[x|y], [x|y]ᵀ[x|y]. Feeds the PCA
    covariance and the LinReg normal equations."""
    n, d = X.shape
    s, G = np.zeros(d + 1), np.zeros((d + 1, d + 1))
    for lo in range(0, n, _REF_ROWS):
        z = np.empty((min(_REF_ROWS, n - lo), d + 1))
        z[:, :d], z[:, d] = X[lo : lo + _REF_ROWS], y[lo : lo + _REF_ROWS]
        s += z.sum(axis=0)
        G += z.T @ z
    return n, s, G


def ref_pca(n, s, G):
    d = len(s) - 1
    mean = s[:d] / n
    cov = (G[:d, :d] - n * np.outer(mean, mean)) / (n - 1.0)
    evals, evecs = np.linalg.eigh(cov)
    return evals[::-1], evecs[:, ::-1]


def ref_linreg(n, s, G):
    """f64 least squares on the centred normal equations of all rows."""
    d = len(s) - 1
    m = s / n
    Gc = G - n * np.outer(m, m)
    beta = np.linalg.lstsq(Gc[:d, :d], Gc[:d, d], rcond=None)[0]
    return beta, m[d] - m[:d] @ beta


def _chunked(X: jax.Array) -> jax.Array:
    """(chunks, rows, d) view for the reference scans: the largest divisor of
    the row count that is at most _REF_ROWS."""
    n = X.shape[0]
    c = next(c for c in range(min(n, _REF_ROWS), 0, -1) if n % c == 0)
    return X.reshape(n // c, c, X.shape[1])


@functools.partial(jax.jit, static_argnames=("iters",))
def ref_lloyd(X: jax.Array, C0: jax.Array, iters: int):
    """Plain Lloyd: `iters` full iterations from C0 (an empty cluster keeps its
    centre), then the inertia at the final centres. f32, highest precision."""
    Xc = _chunked(X)
    k = C0.shape[0]

    def stats(C):
        csq = (C * C).sum(axis=1)

        def body(acc, x):
            d2 = (x * x).sum(axis=1)[:, None] - 2.0 * (x @ C.T) + csq[None, :]
            oh = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=x.dtype)
            cost = jnp.maximum(d2.min(axis=1), 0.0).sum()
            return (acc[0] + oh.T @ x, acc[1] + oh.sum(axis=0), acc[2] + cost), None

        init = (jnp.zeros_like(C), jnp.zeros((k,), C.dtype), jnp.zeros((), C.dtype))
        return lax.scan(body, init, Xc)[0]

    def step(C, _):
        sums, cnt, _ = stats(C)
        return jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None], C), None

    C = lax.scan(step, C0, None, length=iters)[0]
    return C, stats(C)[2]


@jax.jit
def _logreg_terms(X: jax.Array, y: jax.Array, w: jax.Array, b: jax.Array):
    """Mean logloss, its gradient and Hessian in (w, b) over all rows."""
    d = X.shape[1]

    def body(acc, xy):
        x, yv = xy
        z = x @ w + b
        p = jax.nn.sigmoid(z)
        xa = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)
        loss = (jax.nn.softplus(z) - yv * z).sum()
        g = xa.T @ (p - yv)
        H = (xa * (p * (1.0 - p))[:, None]).T @ xa
        return (acc[0] + loss, acc[1] + g, acc[2] + H), None

    init = (jnp.zeros(()), jnp.zeros((d + 1,)), jnp.zeros((d + 1, d + 1)))
    yc = y.reshape(_chunked(X).shape[:2])
    loss, g, H = lax.scan(body, init, (_chunked(X), yc))[0]
    n = X.shape[0]
    return loss / n, g / n, H / n


def ref_logreg(X: jax.Array, y: jax.Array, steps: int = 12):
    """Plain Newton on the unpenalised mean logloss (the optimum the
    estimator's L-BFGS heads for); the f64 solves run on the host."""
    theta = np.zeros(X.shape[1] + 1)
    for _ in range(steps):
        t32 = jnp.asarray(theta, jnp.float32)
        loss, g, H = _logreg_terms(X, y, t32[:-1], t32[-1])
        g, H = np.asarray(g, np.float64), np.asarray(H, np.float64)
        theta = theta - np.linalg.solve(H + 1e-10 * np.eye(len(g)), g)
        if np.abs(g).max() < 1e-7:
            break
    return theta[:-1], theta[-1]


def logreg_objective(X: jax.Array, y: jax.Array, w, b) -> float:
    w32, b32 = jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(_logreg_terms(X, y, w32, b32)[0])


@functools.partial(jax.jit, static_argnames=("kc",))
def _knn_candidates(q: jax.Array, x: jax.Array, kc: int):
    d2 = (x * x).sum(axis=1)[None, :] - 2.0 * (q @ x.T)
    return lax.top_k(-d2, kc)[1]


def ref_knn(Xq: np.ndarray, Xi: np.ndarray, k: int):
    """Brute force for the check queries: f32 candidate sets per item block on
    the device (k + 16 each, far more than rounding can reorder), then exact
    f64 distances of those candidates on the host. Returns the true k nearest
    (ids, distances) per query."""
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(Xq)
        blk = min(len(Xi), 1 << 17)
        kc = min(k + 16, blk)
        cands = [
            np.asarray(_knn_candidates(q, jnp.asarray(Xi[lo : lo + blk]), kc)) + lo
            for lo in range(0, len(Xi) - blk + 1, blk)
        ]
        if len(Xi) % blk:
            lo = len(Xi) - blk
            cands.append(np.asarray(_knn_candidates(q, jnp.asarray(Xi[lo:]), kc)) + lo)
    cand = np.concatenate(cands, axis=1)
    ids = np.empty((len(Xq), k), np.int64)
    dist = np.empty((len(Xq), k))
    for r in range(len(Xq)):
        c = np.unique(cand[r])
        dd = np.sqrt(((Xi[c].astype(np.float64) - Xq[r]) ** 2).sum(axis=1))
        o = np.argsort(dd, kind="stable")[:k]
        ids[r], dist[r] = c[o], dd[o]
    return ids, dist


# --------------------------------------------------------------------------
# device bookkeeping
# --------------------------------------------------------------------------
def memory(on_tpu: bool) -> list:
    """Per-device bytes_limit / peak_bytes_in_use. The CPU backend reports
    none; on the chip a missing figure is an error."""
    out = []
    for dev in jax.devices():
        stats = dev.memory_stats()
        if on_tpu and not (stats and "bytes_limit" in stats and "peak_bytes_in_use" in stats):
            raise RuntimeError(f"device {dev} reports no bytes_limit/peak_bytes_in_use")
        out.append({
            "device": dev.id,
            "bytes_limit": stats.get("bytes_limit") if stats else None,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use") if stats else None,
        })
    return out


def gate(name: str, value: bool, on_tpu: bool) -> dict:
    """Which kernel path the phase takes at the smoke shape: the phase's own
    static gate, asked here. Off the chip it says False (XLA path) and is
    printed as found; on the chip anything but True fails the phase."""
    return {"gate": name, "pallas": bool(value), "ok": bool(value) or not on_tpu}


# --------------------------------------------------------------------------
# phases: each returns a dict with "checks" (and optionally "kernel")
# --------------------------------------------------------------------------
def phase_pca(ctx) -> dict:
    from spark_rapids_ml_tpu.ops import linalg

    X, chips = ctx["X"], ctx["chips"]
    declined = linalg.gram_pallas_declined(len(X) // chips, D, jnp.float32)
    t0 = time.perf_counter()
    model = PCA(k=PCA_K, inputCol="features", num_workers=chips).fit(ctx["df"])
    t_fit = time.perf_counter() - t0
    mem = memory(ctx["on_tpu"])
    checks = []
    if chips > 1:
        # nothing may have put everything on the first chip: read BEFORE this
        # script places any array of its own on a device
        quarter = X.nbytes / chips
        peaks = [m["peak_bytes_in_use"] or float("nan") for m in mem]   # CPU reports none: fails
        checks.append(check("min_device_peak_over_shard", min(peaks) / quarter, 0.9, ">="))
        checks.append(check("device0_peak_over_whole_X", peaks[0] / X.nbytes, 0.6))

    t0 = time.perf_counter()
    n, s, G = ctx["moments"] = host_moments_f64(X, ctx["y_lin"])
    evals, evecs = ref_pca(n, s, G)
    t_ref = time.perf_counter() - t0
    comp = np.asarray(model.components_, np.float64)          # (k, d)
    ev = np.asarray(model.explained_variance_, np.float64)
    # tolerance: f32 Gram over n rows against f64; the planted top-3 gap keeps
    # the subspace well conditioned
    checks.append(check("explained_variance_rel_err",
                        np.abs(ev / evals[:PCA_K] - 1.0).max(), 1e-4))
    q_fit = np.linalg.qr(comp.T)[0]
    sv = np.linalg.svd(q_fit.T @ evecs[:, :PCA_K], compute_uv=False)
    checks.append(check("sin_max_principal_angle",
                        np.sqrt(max(0.0, 1.0 - sv.min() ** 2)), 1e-3))

    sub = DataFrame({"features": X[: min(len(X), SUB)]})
    t0 = time.perf_counter()
    proj = np.asarray(model.transform(sub).column(model.getOrDefault("outputCol")))
    t_tr = time.perf_counter() - t0
    want = sub.column("features").astype(np.float64) @ comp.T   # Spark: no centring
    scale = np.abs(want).max()
    checks.append(check("transform_max_err_over_scale",
                        np.abs(proj - want).max() / scale, 2e-2))

    path = os.path.join(OUT_DIR, "pca_model")
    model.write().overwrite().save(path)
    loaded = PCAModel.load(path)
    same = np.array_equal(loaded.components_, model.components_) and np.array_equal(
        np.asarray(loaded.transform(sub).column(loaded.getOrDefault("outputCol"))), proj
    )
    checks.append(check("save_load_round_trip_equal", float(same), 1.0, ">="))
    return {
        "shape": {"rows": len(X), "d": D, "k": PCA_K},
        "smoke_seconds": {"fit": t_fit, "transform_sub": t_tr, "reference_host_f64": t_ref},
        "checks": checks,
        # a row-major shard (this lane-aligned width) takes XLA's blocked pass
        # at HIGHEST by design: the kernel reads a rows-minor shard's transpose
        "kernel": {"gate": "linalg.gram_pallas_declined", "pallas": not declined, "declined": declined,
                   "ok": declined in ("", "rows_minor") or not ctx["on_tpu"]},
        "memory": mem,
    }


def phase_kmeans(ctx) -> dict:
    from spark_rapids_ml_tpu.ops.kmeans_pallas import kmeans_pallas_ok

    X, chips, k = ctx["X"], ctx["chips"], ctx["kmeans_k"]
    kw = dict(k=k, maxIter=KMEANS_ITERS, seed=ctx["seed"] + 1, num_workers=chips)
    t0 = time.perf_counter()
    model = KMeans(**kw).fit(ctx["df"])
    t_fit = time.perf_counter() - t0
    mem = memory(ctx["on_tpu"])
    # the same start: seeding is a deterministic function of (data, seed), so a
    # fit with maxIter=0 returns exactly the k-means|| start the fit above used
    t0 = time.perf_counter()
    start = KMeans(**{**kw, "maxIter": 0}).fit(ctx["df"]).cluster_centers_
    t_seed = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        Xd = jnp.asarray(X)                       # this script's copy, device 0
        C_ref, inertia_ref = ref_lloyd(Xd, jnp.asarray(start, jnp.float32), KMEANS_ITERS)
        inertia_ref = float(inertia_ref)
    del Xd, C_ref
    t_ref = time.perf_counter() - t0

    centres = np.asarray(model.cluster_centers_, np.float64)
    gen = ctx["centres"]
    d2 = (gen * gen).sum(1)[:, None] - 2.0 * gen @ centres.T + (centres * centres).sum(1)[None, :]
    nearest = np.sqrt(np.maximum(d2.min(axis=1), 0.0))
    # a recovered blob's centre is the mean of its >= 16 points: within
    # sqrt(d/16) = 4 sigma of the generating centre (sigma = 1); a missed blob is
    # off by about half the centre spacing, BLOB_SPREAD * sqrt(2d) / 2 = 113.
    # k-means++ seeding leaves a few percent of the blobs to a shared centre and
    # Lloyd does not repair that, hence a fraction and not all of them
    checks = [
        check("generating_centres_recovered_fraction", (nearest < 16.0).mean(), 0.9, ">="),
        # same start, same iterations, f32 both: inertia agrees to rounding ties
        check("inertia_rel_err_vs_plain_lloyd", abs(model.trainingCost / inertia_ref - 1.0), 1e-3),
    ]
    sub = DataFrame({"features": X[: min(len(X), SUB)]})
    t0 = time.perf_counter()
    pred = np.asarray(model.transform(sub).column("prediction"))
    t_tr = time.perf_counter() - t0
    xs = sub.column("features").astype(np.float64)[:8192]
    d2s = (xs * xs).sum(1)[:, None] - 2.0 * xs @ centres.T + (centres * centres).sum(1)[None, :]
    checks.append(check("transform_assignment_agreement", (pred[:8192] == d2s.argmin(1)).mean(), 0.99, ">="))
    n_local = -(-len(X) // chips)   # no padding at the default sizes
    return {
        "shape": {"rows": len(X), "d": D, "k": k, "maxIter": KMEANS_ITERS, "n_iter": model.numIter},
        "smoke_seconds": {"fit": t_fit, "seeding_fit_maxIter0": t_seed,
                          "reference_lloyd_device": t_ref, "transform_sub": t_tr},
        "checks": checks,
        "kernel": gate("kmeans_pallas_ok", kmeans_pallas_ok(n_local, D, k, jnp.float32, None), ctx["on_tpu"]),
        "memory": mem,
    }


def phase_linreg(ctx) -> dict:
    X = ctx["X"]
    t0 = time.perf_counter()
    model = LinearRegression(regParam=0.0, solver="normal", num_workers=ctx["chips"]).fit(ctx["df"].withColumn("label", ctx["y_lin"]))
    t_fit = time.perf_counter() - t0
    mem = memory(ctx["on_tpu"])
    beta, icpt = ref_linreg(*ctx["moments"])
    coef = np.asarray(model.coefficients, np.float64)
    # tolerance: f32 sufficient statistics and an f32 Cholesky with an
    # eps·trace jitter on a system of condition ~1e2, against f64 least squares
    checks = [
        check("coef_max_err_over_max_coef", np.abs(coef - beta).max() / np.abs(beta).max(), 5e-3),
        check("intercept_abs_err", abs(float(model.intercept) - icpt), 5e-3),
    ]
    sub = DataFrame({"features": X[: min(len(X), SUB)]})
    t0 = time.perf_counter()
    pred = np.asarray(model.transform(sub).column("prediction"), np.float64)
    t_tr = time.perf_counter() - t0
    want = sub.column("features").astype(np.float64) @ beta + icpt
    # predictions against the f64 model's, in units of the label noise (0.1)
    checks.append(check("prediction_rmse_vs_reference", np.sqrt(((pred - want) ** 2).mean()), 2e-2))
    return {
        "shape": {"rows": len(X), "d": D, "regParam": 0.0, "solver": "normal"},
        "smoke_seconds": {"fit": t_fit, "transform_sub": t_tr},
        "checks": checks,
        # by design: linreg_suffstats_chunked measured at parity with a Pallas
        # kernel and keeps the XLA scan; there is no gate to ask
        "kernel": {"gate": None, "pallas": False, "path": "xla_scan (no Pallas path exists)", "ok": True},
        "memory": mem,
    }


def phase_logreg(ctx) -> dict:
    from spark_rapids_ml_tpu.ops.logreg_pallas import logreg_pallas_ok

    X, y = ctx["X"], ctx["y_log"]
    t0 = time.perf_counter()
    model = LogisticRegression(maxIter=LOGREG_ITERS, num_workers=ctx["chips"]).fit(ctx["df"].withColumn("label", y))
    t_fit = time.perf_counter() - t0
    mem = memory(ctx["on_tpu"])
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        Xd, yd = jnp.asarray(X), jnp.asarray(y)
        w_ref, b_ref = ref_logreg(Xd, yd)
        obj_ref = logreg_objective(Xd, yd, w_ref, b_ref)
        obj_fit = logreg_objective(Xd, yd, np.ravel(model.coefficients), model.intercept)
    del Xd, yd
    t_ref = time.perf_counter() - t0
    sub = DataFrame({"features": X[: min(len(X), SUB)]})
    t0 = time.perf_counter()
    pred = np.asarray(model.transform(sub).column("prediction"))
    t_tr = time.perf_counter() - t0
    ys = y[: len(pred)]
    acc = float((pred == ys).mean())
    xs = sub.column("features").astype(np.float64)
    acc_ref = float((((xs @ w_ref + b_ref) > 0) == (ys > 0)).mean())
    checks = [
        # the reference is the converged optimum; maxIter=20 L-BFGS steps from
        # zero must come within 1% of it (objective on all rows, plain f32)
        check("objective_excess_over_newton_optimum", obj_fit / obj_ref - 1.0, 1e-2),
        check("accuracy_shortfall_vs_reference", acc_ref - acc, 5e-3),
    ]
    return {
        "shape": {"rows": len(X), "d": D, "classes": 2, "maxIter": LOGREG_ITERS, "n_iter": int(model.n_iter_)},
        "smoke_seconds": {"fit": t_fit, "reference_newton_device": t_ref, "transform_sub": t_tr},
        "objective": {"fit": obj_fit, "reference": obj_ref, "accuracy": acc, "accuracy_reference": acc_ref},
        "checks": checks,
        "kernel": gate("logreg_pallas_ok", logreg_pallas_ok(len(X) // ctx["chips"], D, 1, jnp.float32), ctx["on_tpu"]),
        "memory": mem,
    }


def phase_knn(ctx) -> dict:
    from spark_rapids_ml_tpu.ops.knn_pallas import _IB, _QB, knn_pallas_ok

    chips, seed = ctx["chips"], ctx["seed"]
    t0 = time.perf_counter()
    centres = blob_centres(seed, KMEANS_K, KNN_SPREAD)
    Xi, _ = blobs(seed, 2, ctx["knn_items"], centres)
    Xq, _ = blobs(seed, 3, ctx["knn_queries"], centres)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = NearestNeighbors(k=KNN_K, inputCol="features", num_workers=chips).fit(DataFrame({"features": Xi}))
    _, _, knn_df = model.kneighbors(DataFrame({"features": Xq}))
    t_knn = time.perf_counter() - t0
    mem = memory(ctx["on_tpu"])
    idx = np.asarray(knn_df.column("indices"))
    dist = np.asarray(knn_df.column("distances"), np.float64)
    nc = min(len(Xq), KNN_CHECK)
    t0 = time.perf_counter()
    ids_ref, dist_ref = ref_knn(Xq[:nc], Xi, KNN_K)
    t_ref = time.perf_counter() - t0
    same = np.asarray([set(idx[r]) == set(ids_ref[r]) for r in range(nc)])
    # exact up to f32 rounding of ||q||² + ||x||² - 2q·x (about 1e-6 of d² on
    # this data): the id sets agree except at near-ties, every returned
    # neighbour's TRUE distance is within 1e-4 of the true k-th distance, and
    # the returned distances are the true distances of the returned ids
    true_d = np.sqrt(((Xi[idx[:nc]].astype(np.float64) - Xq[:nc, None, :]) ** 2).sum(axis=2))
    checks = [
        check("index_set_agreement_fraction", same.mean(), 0.99, ">="),
        check("worst_returned_true_dist_over_true_kth", (true_d.max(axis=1) / dist_ref[:, -1]).max(), 1.0 + 1e-4),
        check("distance_max_rel_err", np.abs(np.sort(dist[:nc], axis=1) / np.sort(true_d, axis=1) - 1.0).max(), 1e-4),
        check("distances_ascending", float((np.diff(dist[:nc], axis=1) >= 0).all()), 1.0, ">="),
    ]

    def shard_rows_padded(n: int, block: int) -> int:
        """Rows per device, padded up to the kernel's block as ring_knn pads."""
        per_dev = -(-n // chips)
        return -(-per_dev // block) * block

    return {
        "shape": {"queries": len(Xq), "items": len(Xi), "d": D, "k": KNN_K, "checked_queries": nc},
        "smoke_seconds": {"generate": t_gen, "fit_kneighbors": t_knn, "reference_brute_force": t_ref},
        "checks": checks,
        "kernel": gate("knn_pallas_ok", knn_pallas_ok(shard_rows_padded(len(Xq), _QB), shard_rows_padded(len(Xi), _IB), D, KNN_K, jnp.float32), ctx["on_tpu"]),
        "memory": mem,
    }


PHASES = {"pca": phase_pca, "kmeans": phase_kmeans, "linreg": phase_linreg,
          "logreg": phase_logreg, "knn": phase_knn}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rows", type=int, help=f"default {DEFAULT_SIZES['rows']:,}")
    ap.add_argument("--knn-items", type=int, help=f"default {DEFAULT_SIZES['knn_items']:,}")
    ap.add_argument("--knn-queries", type=int, help=f"default {DEFAULT_SIZES['knn_queries']:,}")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip path (pca, kmeans, knn at num_workers=4)")
    args = ap.parse_args(argv)
    sized = any(getattr(args, k) is not None for k in DEFAULT_SIZES)   # a rehearsal names its sizes
    for k, v in DEFAULT_SIZES.items():
        if getattr(args, k) is None:
            setattr(args, k, v)

    cache_dir = enable_compile_cache()
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(jax.devices())}
    on_tpu = dev0.platform == "tpu"
    emit({"jax": jax.__version__, "device": device, "compile_cache": cache_dir,
          "memory": memory(on_tpu), "args": vars(args)})

    def finish(ok: bool, why: str = "") -> int:
        if why:
            emit({"refused": why})
        emit({"ok": bool(ok), "device": device})
        return 0 if ok else 1

    if not on_tpu and not sized:
        return finish(False, "no TPU, and the default shapes are chip-sized; rehearse off the chip with "
                             "--rows 8192 --knn-items 4096 --knn-queries 256 (that still ends ok=false)")
    if device["count"] != args.chips:
        return finish(False, f"--chips {args.chips} needs exactly {args.chips} device(s), jax sees {device['count']}")
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh_devices = {d.id for d in make_mesh(args.chips).devices.flat}
    if len(mesh_devices) != args.chips:
        return finish(False, f"make_mesh({args.chips}) holds {len(mesh_devices)} distinct devices")

    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    kmeans_k = min(KMEANS_K, max(2, args.rows // 16))
    centres = blob_centres(args.seed, kmeans_k)
    X, _ = blobs(args.seed, 1, args.rows, centres)
    y_lin, y_log = make_labels(args.seed, X)
    emit({"phase": "generate", "shape": {"rows": args.rows, "d": D, "blobs": kmeans_k},
          "smoke_seconds": {"host": time.perf_counter() - t0}})
    ctx = {"X": X, "df": DataFrame({"features": X}), "y_lin": y_lin, "y_log": y_log,
           "centres": centres, "kmeans_k": kmeans_k, "seed": args.seed, "chips": args.chips,
           "on_tpu": on_tpu, "knn_items": args.knn_items, "knn_queries": args.knn_queries}

    names = ["pca", "kmeans", "knn"] if args.chips == 4 else list(PHASES)
    all_ok = True
    for name in names:
        try:
            out = PHASES[name](ctx)
        except Exception as e:   # a phase that raises fails the run; later phases do not start
            traceback.print_exc()
            emit({"phase": name, "ok": False, "raised": f"{type(e).__name__}: {e}"[:2000]})
            return finish(False)
        out["ok"] = all(c["ok"] for c in out["checks"]) and out["kernel"]["ok"]
        all_ok &= out["ok"]
        emit({"phase": name, **out})
    return finish(all_ok and on_tpu)


if __name__ == "__main__":
    sys.exit(main())
