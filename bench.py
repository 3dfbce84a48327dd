"""Benchmark entry point — prints ONE JSON line with the headline metric.

Covers the three BASELINE.md fit workloads (PCA, KMeans, LogisticRegression;
reference methodology ``/root/reference/python/benchmark/databricks/run_benchmark.sh:44-135``)
at the 256-feature width of the 100M x 256 north-star, measuring per-chip fit
throughput so the number scales linearly to pod size.  Also reports an MFU
estimate per algorithm (FLOP model / chip peak).

``vs_baseline`` compares against an A10G cuML roofline estimate derived from
the reference's benchmark hardware (BASELINE.md: 2x g5.2xlarge, A10G 24 GB):

* PCA — Gram-bound, 2*n*d^2 FLOPs; A10G sustains ~15 TFLOP/s effective fp32
  on SYRK-shaped work -> 15e12 / (2*256^2) ~= 1.1e8 samples/sec/GPU.
* KMeans — distance-bound, 2*n*k*d FLOPs/iter (k=1024) ->
  15e12 / (2*1024*256) ~= 2.9e7 sample-iters/sec/GPU.
* LogReg — bandwidth-bound (matvec-shaped): ~2 passes over X per L-BFGS
  iter at 600 GB/s A10G HBM -> 600e9 / (2*256*4) ~= 2.9e8
  sample-iters/sec/GPU.

Measurement methodology (the chip is a plain local TPU; a run that finds
none fails unless ``--platform cpu`` asks for a host-only run, whose
entries are flagged ``host_only`` and are never device metrics):

* data is generated ON DEVICE with ``jax.random`` (no host-side multi-GB
  matrix to build and ship before the clock can start);
* every timed rep is exactly ONE jitted call returning ONE small array (a
  scalar checksum over all output leaves + an aux counter), so per-rep
  overhead is one dispatch and one fetch instead of one per output leaf;
* per-rep input perturbations are materialized BEFORE the clock starts, so
  no rep can be served from a memoized (executable, buffers) pair;
* the streaming (out-of-core) number measures host->device ingest as well
  as device math; the cell reports the two legs separately.

Headline metric stays ``pca_fit_throughput`` (round-1 continuity); the same
JSON line carries ``kmeans``/``logreg``/``pca_stream`` sub-objects and
per-algo MFU.

Robustness: any algo failing with a transient ``UNAVAILABLE`` TPU backend
error is retried once after a cooldown; partial results still produce a
JSON line and diagnostics go to stderr, but an entry that raised or tripped
its watchdog makes the exit code non-zero.
"""

import contextlib
import json
import math
import os
import sys
import time
import traceback

import numpy as np

# Apply a --platform cpu|tpu pin in-process BEFORE the first backend touch
# (without the option JAX reads JAX_PLATFORMS itself, else takes the TPU).
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from spark_rapids_ml_tpu.utils.platform import pin_platform  # noqa: E402

_platform = None
for _i, _a in enumerate(sys.argv[1:], start=1):
    if _a == "--platform":
        if _i + 1 >= len(sys.argv):
            sys.exit("--platform requires a value (cpu|tpu)")
        _platform = sys.argv[_i + 1]
    elif _a.startswith("--platform="):
        _platform = _a.split("=", 1)[1]
pin_platform(_platform)

N_ROWS = int(os.environ.get("BENCH_ROWS", 12_000_000))
N_COLS = int(os.environ.get("BENCH_COLS", 256))
KMEANS_K = int(os.environ.get("BENCH_KMEANS_K", 1024))
KMEANS_ITERS = 10
LOGREG_ITERS = 20


def _csize(n_rows: int) -> int:
    # 64k rows/chunk keeps the (chunk, k) distance + one-hot tiles ~0.5 GB
    # so a ~12 GB resident X still fits v5e HBM; tiles this tall keep the
    # MXU contraction saturated
    return min(65_536, max(256, n_rows // 8))


CSIZE = _csize(N_ROWS)

def _chip_peak_flops(device) -> float:
    """bf16 peak FLOP/s of one chip (MFU denominator), from the one peak
    table in ``runtime/roofline.py``; an unknown accelerator is an error."""
    from spark_rapids_ml_tpu.runtime.roofline import chip_peaks

    return chip_peaks(device.device_kind, device.platform)[0]


def _checksum(out, aux=None):
    """Reduce an output pytree to ONE tiny array (inside jit).

    Summing every leaf forces the whole computation; returning a single
    2-vector makes the host fetch a single round trip instead of one per
    output leaf.
    """
    import jax
    import jax.numpy as jnp

    acc = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(out):
        acc = acc + jnp.sum(jnp.asarray(leaf).astype(jnp.float32))
    return jnp.stack([acc, jnp.float32(0.0 if aux is None else aux)])


def _best_time(make_args, run, reps: int = 3):
    """(min wall time, aux from first rep) of ``run(*make_args(rep))``.

    Per-rep argument sets are materialized and blocked on BEFORE timing so
    the clock sees exactly one dispatch + one 2-scalar fetch per rep.
    """
    import jax

    argsets = [make_args(rep) for rep in range(reps)]
    for a in argsets:
        jax.block_until_ready(a)
    times, aux = [], 0.0
    for i, a in enumerate(argsets):
        t0 = time.perf_counter()
        out = np.asarray(run(*a))
        times.append(time.perf_counter() - t0)
        if i == 0:
            aux = float(out[1])
    return min(times), aux


INNER_FITS = max(1, int(os.environ.get("BENCH_INNER_FITS", 4)))


def _gen_dataset(mesh, n_rows, seed, dtype=None):
    """On-device chunked dataset generation -> (X, mask, y), row-sharded.

    Chunked because random.normal over the full matrix would hold the
    uint32 bit buffer AND the f32 output at once (2x matrix bytes — OOM
    for a ~12 GB X on a 16 GiB chip). Chunks land in a preallocated
    buffer via dynamic_update_slice (aliased in-place by XLA) — NOT a
    lax.scan stacked output, whose exotic layout forces downstream
    shard_map kernels to materialize a default-layout copy of the whole
    matrix (observed OOM at d=3000). ``dtype`` narrows the stored X
    (generation stays f32); labels come from a fixed seed-0 true weight
    vector so every caller labels consistently.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    x_dtype = jnp.float32 if dtype is None else dtype
    n_dp = mesh.shape["dp"]
    pad_unit = CSIZE * n_dp
    n_pad = ((n_rows + pad_unit - 1) // pad_unit) * pad_unit
    row_sharding = NamedSharding(mesh, P("dp"))
    w_true = jnp.asarray(
        np.random.default_rng(0).standard_normal(N_COLS, dtype=np.float32)
    )

    def _gen(key, w):
        def body(i, Xg):
            blk = jax.random.normal(
                jax.random.fold_in(key, i), (pad_unit, N_COLS), jnp.float32
            )
            return lax.dynamic_update_slice_in_dim(
                Xg, blk.astype(x_dtype), i * pad_unit, 0
            )

        Xg = lax.fori_loop(
            0, n_pad // pad_unit, body, jnp.zeros((n_pad, N_COLS), x_dtype)
        )
        m = (jnp.arange(n_pad) < n_rows).astype(jnp.float32)
        yg = (
            lax.dot_general(
                Xg, w.astype(x_dtype)[:, None],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )[:, 0]
            > 0
        ).astype(jnp.float32) * m
        return Xg, m, yg

    gen = jax.jit(
        _gen, out_shardings=(row_sharding, row_sharding, row_sharding)
    )
    X, m, y = gen(jax.random.key(seed), w_true)
    jax.block_until_ready(X)
    return X, m, y


def _time_scanned_fits(fit_body, args_for_rep):
    """Best per-fit time of INNER_FITS fits inside ONE dispatch.

    A single fit is ~20-50 ms on chip, the same order as one dispatch plus
    fetch — one fit per dispatch under-reports the chip.
    ``fit_body(eps, *args) -> checksum`` runs per inner fit; the eps scan
    perturbs each fit's inputs so XLA cannot CSE them into one."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def inner(*args):
        def body(acc, eps):
            return acc + fit_body(eps, *args), None

        acc, _ = lax.scan(
            body,
            jnp.zeros((2,), jnp.float32),
            jnp.arange(1, INNER_FITS + 1, dtype=jnp.float32) * 1e-7,
        )
        return acc

    timed = jax.jit(inner)
    np.asarray(timed(*args_for_rep(0)))  # compile (distinct rep-0 inputs
    # would be memoizable on remote backends; _best_time starts at rep 1)
    t, _ = _best_time(lambda rep: args_for_rep(rep + 1), timed)
    return t / INNER_FITS


def bench_pca(X, mask, mesh, n_chips):
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.feature import _pca_fit_kernel

    def fit_body(eps, X, m):
        return _checksum(
            _pca_fit_kernel(X, m * (1.0 + eps), 3, mesh=mesh, csize=CSIZE)
        )

    t = _time_scanned_fits(
        fit_body,
        lambda rep: (X, mask * jnp.float32(1.0 + rep * 1e-6)),
    )
    # transform path (reference reports fit AND transform per workload,
    # ``benchmark/base.py:241-270``): one centered projection sweep at
    # k=3 — the exact compute of PCAModel.transform
    W = jnp.asarray(
        np.random.default_rng(5).standard_normal((3, N_COLS)), jnp.float32
    )
    mu = jnp.asarray(
        np.random.default_rng(6).standard_normal(N_COLS), jnp.float32
    )

    def tr_body(eps, X, m):
        return _checksum((X - mu[None, :] * (1.0 + eps)) @ W.T)

    t_tr = _time_scanned_fits(
        tr_body, lambda rep: (X, mask * jnp.float32(1.0 + rep * 1e-6))
    )
    n = N_ROWS
    flops = 2.0 * n * N_COLS * N_COLS  # Gram dominates
    return {
        "samples_per_sec_per_chip": n / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_samples_per_sec_per_chip": n / t_tr / n_chips,
        "inner_fits_per_dispatch": INNER_FITS,
        "flops_model": flops,
        "baseline_samples_per_sec": 1.1e8,
        "baseline_inputs": {
            "formula": "a10g_syrk_flat_v1",
            "samples_per_sec": 1.1e8,
            "d": N_COLS,
        },
    }


def bench_kmeans(X, mask, mesh, n_chips):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans_kernels import kmeans_lloyd

    key = jax.random.key(1)
    centers0 = jax.random.normal(key, (KMEANS_K, N_COLS), dtype=jnp.float32)
    jax.block_until_ready(centers0)
    csize = CSIZE
    # bf16 matmul operands (f32 accumulation) on the two MXU contractions
    # — the TF32-tensor-core analog; see pairwise_sq_dists
    km_dtype = os.environ.get("BENCH_KMEANS_DTYPE", "bfloat16")
    if km_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"BENCH_KMEANS_DTYPE must be float32|bfloat16, got {km_dtype!r}"
        )
    mm = jnp.bfloat16 if km_dtype == "bfloat16" else None

    def timed_fn(X, m, c):
        out = kmeans_lloyd(
            X, m, c, mesh=mesh, csize=csize, max_iter=KMEANS_ITERS, tol=0.0,
            matmul_dtype=mm,
        )
        return _checksum(out, aux=out[2])

    timed = jax.jit(timed_fn)
    warm = np.asarray(timed(X, mask, centers0))  # compile + iteration count
    iters = int(warm[1]) + 1  # +1 final cost pass
    # rep-dependent center jitter -> distinct input buffers (see _best_time)
    t, _ = _best_time(
        lambda rep: (X, mask, centers0 + jnp.float32((rep + 1) * 1e-6)),
        timed,
    )
    # transform path: one chunked assignment pass (argmin over pairwise
    # distances) — the exact compute of KMeansModel.transform
    from spark_rapids_ml_tpu.ops.kmeans_kernels import pairwise_sq_dists

    def tr_body(eps, X, m, c):
        nchunks = X.shape[0] // csize

        def chunk(i, acc):
            xc = jax.lax.dynamic_slice(X, (i * csize, 0), (csize, N_COLS))
            d2 = pairwise_sq_dists(xc, c * (1.0 + eps), matmul_dtype=mm)
            return acc + jnp.argmin(d2, axis=1).astype(jnp.float32).sum()

        return jnp.stack(
            [jax.lax.fori_loop(0, nchunks, chunk, jnp.float32(0.0)),
             jnp.float32(0.0)]
        )

    t_tr = _time_scanned_fits(
        tr_body, lambda rep: (X, mask, centers0 + jnp.float32(rep * 1e-6))
    )
    # FLOPs are spent on padded rows; throughput counts real samples only
    flops = 2.0 * X.shape[0] * KMEANS_K * N_COLS * iters
    n = N_ROWS
    return {
        "samples_per_sec_per_chip": n * iters / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_samples_per_sec_per_chip": n / t_tr / n_chips,
        "iters": iters,
        "matmul_dtype": km_dtype,
        "flops_model": flops,
        "baseline_samples_per_sec": 2.9e7,
        "baseline_inputs": {
            "formula": "a10g_kmeans_flat_v1",
            "samples_per_sec": 2.9e7,
            "k": KMEANS_K,
            "d": N_COLS,
        },
    }


def bench_logreg(X, mask, y, mesh, n_chips):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.logreg_kernels import logreg_fit

    # bf16 objective reads (f32 stats/params/accumulation): halves the
    # HBM bytes of the bandwidth-bound eval — the TPU analog of the TF32
    # tensor-core reads cuML gets implicitly on Ampere-class GPUs
    # default float32: the bf16 objective needs a SEPARATE bf16-placed
    # dataset, and any extra resident next to the shared 12M x 256 f32 X
    # costs more in HBM-pressure slowdown than the halved reads buy
    # (measured: bf16 474M samples/s standalone vs 252M beside the f32 X,
    # f32 itself dropping 455->261M when a 3 GB bf16 sibling stays live).
    # The bf16 path earns its keep in the estimator, where X arrives
    # bf16-placed at ingestion (objective_dtype="bfloat16") and is the
    # ONLY resident.
    obj_dtype = os.environ.get("BENCH_LOGREG_DTYPE", "float32")

    n_rows = N_ROWS
    Xb, mb, yb = X, mask, y
    if obj_dtype == "bfloat16":
        # the fit must SEE a bf16 X: converting the shared f32 X inside the
        # program holds both copies live (observed 17.3 GB > 15.75 GB at
        # 12M x 256 on v5e). Generate a separate bf16 dataset instead —
        # at half the rows so it fits NEXT TO the f32 X the other entries
        # still need. The eval is bandwidth-bound, so samples/sec is
        # row-count-insensitive at these sizes; "rows" is recorded.
        n_rows = int(os.environ.get("BENCH_LOGREG_BF16_ROWS", N_ROWS // 2))
        try:
            Xb, mb, yb = _gen_dataset(mesh, n_rows, seed=7, dtype=jnp.bfloat16)
        except Exception as e:  # noqa: BLE001
            # the extra bf16 dataset may not fit next to the resident f32
            # X; deliver the f32 number rather than no logreg entry at all
            print(
                f"[bench] logreg bf16 dataset generation failed "
                f"({type(e).__name__}: {e}); falling back to float32",
                file=sys.stderr,
            )
            obj_dtype = "float32"
            n_rows = N_ROWS

    def make_timed(dt):
        def timed_fn(X, m, y, l2):
            out = logreg_fit(
                X, m, y,
                n_classes=2, multinomial=False, fit_intercept=True,
                standardization=False,
                l1=jnp.float32(0.0), l2=l2,
                use_l1=False, max_iter=LOGREG_ITERS, tol=jnp.float32(0.0),
                mesh=mesh, objective_dtype=dt,
            )
            return _checksum(out, aux=out["n_iter"])

        return jax.jit(timed_fn)

    timed = make_timed(obj_dtype)
    try:
        warm = np.asarray(timed(Xb, mb, yb, jnp.float32(1e-5)))  # compile
    except Exception as e:  # noqa: BLE001
        if obj_dtype == "float32":
            raise
        # narrow-dtype path failed on this backend (e.g. Mosaic lowering):
        # fall back to f32, record the dtype that actually ran, and keep
        # the original error visible for diagnosis
        print(
            f"[bench] logreg {obj_dtype} objective failed "
            f"({type(e).__name__}: {e}); falling back to float32",
            file=sys.stderr,
        )
        obj_dtype = "float32"
        n_rows = N_ROWS
        Xb, mb, yb = X, mask, y
        timed = make_timed(obj_dtype)
        warm = np.asarray(timed(Xb, mb, yb, jnp.float32(1e-5)))
    iters = max(int(warm[1]), 1)
    # rep-dependent l2 -> distinct scalar input buffer (see _best_time)
    t, _ = _best_time(
        lambda rep: (
            Xb, mb, yb, jnp.float32(1e-5 * (1.0 + (rep + 1) * 1e-3))
        ),
        timed,
    )
    # transform path: one decision sweep (X @ w > 0) — the compute of
    # LogisticRegressionModel.transform's prediction column
    w_t = jnp.asarray(
        np.random.default_rng(9).standard_normal(N_COLS), jnp.float32
    )

    def tr_body(eps, X, m, y):
        z = X @ (w_t * (1.0 + eps))
        return _checksum((z > 0).astype(jnp.float32) * m)

    t_tr = _time_scanned_fits(
        tr_body,
        lambda rep: (Xb, mb * jnp.float32(1.0 + rep * 1e-6), yb),
    )
    # ~2 objective evals/iter (step + line search), fwd+grad = 4*n*d each
    flops = 8.0 * n_rows * N_COLS * iters
    return {
        # throughput is PER ITERATION (samples x iters / s): the
        # reference benchmark runs maxIter=200 tol=1e-30
        # (run_benchmark.sh:126-135) while this leg runs 20 iterations —
        # per-iter normalization makes the numbers comparable, and
        # per_iter=true in the JSON says so explicitly
        "samples_per_sec_per_chip": n_rows * iters / t / n_chips,
        # end-to-end (un-normalized) rate alongside, so a consumer that
        # ignores per_iter cannot misread the 20x-inflated headline as
        # comparable with the other entries' end-to-end definition; the
        # vs_baseline ratio is consistent either way because the 2.9e8
        # baseline below is ALSO a per-iteration rate
        "samples_per_sec_per_chip_e2e": n_rows / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_samples_per_sec_per_chip": n_rows / t_tr / n_chips,
        "iters": iters,
        "per_iter": True,
        "rows": n_rows,
        "objective_dtype": obj_dtype,
        "gang_lanes": 1,
        "flops_model": flops,
        "baseline_samples_per_sec": 2.9e8,
        "baseline_inputs": {
            "formula": "a10g_logreg_flat_per_iter_v1",
            "samples_per_sec_per_iter": 2.9e8,
            "d": N_COLS,
        },
    }


LOGREG_MULTI_FOLDS = 3
LOGREG_MULTI_MAPS = 8


def bench_logreg_multi(X, mask, y, mesh, n_chips):
    """Gang-scheduled CV-shaped grid: numFolds=3 × 8 maps = 24 fold-masked
    L-BFGS lanes through ONE ``logreg_fit_batched`` dispatch over the
    shared resident X, against the same 24 solves run sequentially (solo
    ``logreg_fit`` with the fold mask folded into the row mask — exactly
    what the unganged CrossValidator dispatches). The gang leg reads X
    once per iteration for all 24 lanes; ``vs_sequential`` is the measured
    amortization."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.logreg_kernels import (
        logreg_fit,
        logreg_fit_batched,
    )
    from spark_rapids_ml_tpu.parallel.mesh import shard_aligned

    n_folds, n_maps = LOGREG_MULTI_FOLDS, LOGREG_MULTI_MAPS
    B = n_folds * n_maps
    fold_host = (
        np.random.default_rng(11).integers(0, n_folds, size=N_ROWS).astype(np.int32)
    )
    fid = shard_aligned(fold_host, mesh, X.shape[0])
    l2s = np.logspace(-6, -2, n_maps).astype(np.float32)
    lane_l2 = jnp.asarray(np.tile(l2s, n_folds))
    lane_fold = jnp.asarray(np.repeat(np.arange(n_folds, dtype=np.int32), n_maps))
    zeros_b = jnp.zeros((B,), jnp.float32)

    def gang_fn(X, m, y, l2v):
        out = logreg_fit_batched(
            X, m, y,
            n_classes=2, multinomial=False, fit_intercept=True,
            standardization=False,
            l1=zeros_b, l2=l2v, use_l1=False,
            max_iter=LOGREG_ITERS, tol=zeros_b,
            mesh=mesh, objective_dtype="float32",
            fold_id=fid, lane_fold=lane_fold, n_folds=n_folds,
        )
        return _checksum(out, aux=out["n_iter"].max())

    gang_timed = jax.jit(gang_fn)
    warm = np.asarray(gang_timed(X, mask, y, lane_l2))  # compile
    iters = max(int(warm[1]), 1)
    t, _ = _best_time(
        lambda rep: (X, mask, y, lane_l2 * jnp.float32(1.0 + (rep + 1) * 1e-3)),
        gang_timed,
    )

    # sequential leg: same 24 (fold, map) solves, one device program each
    def solo_fn(X, m, y, l2, fsel):
        m_f = m * (fid != fsel).astype(m.dtype)
        out = logreg_fit(
            X, m_f, y,
            n_classes=2, multinomial=False, fit_intercept=True,
            standardization=False,
            l1=jnp.float32(0.0), l2=l2,
            use_l1=False, max_iter=LOGREG_ITERS, tol=jnp.float32(0.0),
            mesh=mesh, objective_dtype="float32",
        )
        return _checksum(out, aux=out["n_iter"])

    solo_timed = jax.jit(solo_fn)
    warm_s = np.asarray(
        solo_timed(X, mask, y, jnp.float32(float(l2s[0])), jnp.int32(0))
    )  # compile
    t0 = time.perf_counter()
    out = None
    for f in range(n_folds):
        for j in range(n_maps):
            # perturbed l2 -> distinct scalar input buffer per solve
            out = solo_timed(
                X, mask, y,
                jnp.float32(float(l2s[j]) * 1.000123), jnp.int32(f),
            )
    np.asarray(out)  # block on the last solve: the device ran all 24
    t_seq = time.perf_counter() - t0

    # batched objective: ~2 evals/iter, fwd+grad = 4*n*d each, ×B lanes
    # riding ONE read of X per evaluation
    flops = 8.0 * N_ROWS * N_COLS * iters * B
    return {
        # lane-samples per second: B solves × rows × iters (per-iter
        # normalized, matching the logreg entry's convention) — against
        # the same solo per-iter baseline, so vs_baseline directly shows
        # the gang amortization over a one-lane solve
        "samples_per_sec_per_chip": N_ROWS * B * iters / t / n_chips,
        "fit_seconds": t,
        "seq_fit_seconds": t_seq,
        "solves_per_sec": B / t,
        "vs_sequential": t_seq / t,
        "gang_lanes": B,
        "iters": iters,
        "per_iter": True,
        "rows": N_ROWS,
        "flops_model": flops,
        "baseline_samples_per_sec": 2.9e8,
        "baseline_inputs": {
            "formula": "a10g_logreg_flat_per_iter_v1",
            "samples_per_sec_per_iter": 2.9e8,
            "d": N_COLS,
            "lanes": B,
        },
    }


def bench_linreg(X, mask, y, mesh, n_chips):
    """Normal-equation LinearRegression fit: suffstats (Gram + X'y) then a
    replicated solve — same roofline shape as PCA (A10G ~15 TFLOP/s on
    SYRK-shaped work -> 1.1e8 samples/sec/GPU at d=256)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.linreg_kernels import (
        linreg_suffstats_chunked,
        solve_normal,
    )

    def fit_body(eps, X, m, y):
        stats = linreg_suffstats_chunked(
            X, m * (1.0 + eps), y, mesh=mesh, csize=CSIZE
        )
        return _checksum(
            solve_normal(stats, jnp.float32(1e-5), standardization=True)
        )

    t = _time_scanned_fits(
        fit_body,
        lambda rep: (X, mask * jnp.float32(1.0 + rep * 1e-6), y),
    )
    # transform path: one prediction sweep (X @ w + b)
    w_t = jnp.asarray(
        np.random.default_rng(9).standard_normal(N_COLS), jnp.float32
    )

    def tr_body(eps, X, m, y):
        return _checksum(X @ (w_t * (1.0 + eps)))

    t_tr = _time_scanned_fits(
        tr_body,
        lambda rep: (X, mask * jnp.float32(1.0 + rep * 1e-6), y),
    )
    n = N_ROWS
    flops = 2.0 * n * N_COLS * N_COLS
    return {
        "samples_per_sec_per_chip": n / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_samples_per_sec_per_chip": n / t_tr / n_chips,
        "inner_fits_per_dispatch": INNER_FITS,
        "gang_lanes": 1,
        "flops_model": flops,
        "baseline_samples_per_sec": 1.1e8,
        "baseline_inputs": {
            "formula": "a10g_syrk_flat_v1",
            "samples_per_sec": 1.1e8,
            "d": N_COLS,
        },
    }


RF_TREES = int(os.environ.get("BENCH_RF_TREES", 50))
RF_ROWS = int(os.environ.get("BENCH_RF_ROWS", 131_072))
RF_DEPTH = int(os.environ.get("BENCH_RF_DEPTH", 13))
RF_BINS = 128


def bench_rf(X, mask, y, mesh, n_chips):
    """RandomForestClassifier at the reference forest config (50 trees,
    depth 13, 128 bins — ``databricks/run_benchmark.sh:102-112``) on a
    131k-row slice (the shape with a recorded round-2 datapoint: 426 s).

    Throughput unit is tree-samples/sec/chip (= rows x trees / seconds):
    trees are embarrassingly parallel with zero collectives, so the rate is
    invariant in tree count and scales linearly with chips.

    Baseline model: a histogram builder on A10G is bound by shared-memory
    atomics; cuML sustains ~1.8e9 histogram updates/s/GPU (consistent with
    the 2xA10G cluster finishing the 1Mx3000 50-tree benchmark inside its
    3600 s budget, ``databricks/README.md:37-40``). One tree-sample costs
    d x depth x n_stats updates, so at d=256/depth 13/S=2 the A10G model
    is 1.8e9 / 6656 ~= 2.7e5 tree-samples/sec/GPU."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.tree_kernels import (
        ForestConfig,
        binize,
        build_forest,
        next_pow2,
        resolve_contract_gather,
        resolve_hist_strategy,
        resolve_tree_batch,
    )

    n_dp = mesh.shape["dp"]
    n_rf = min(RF_ROWS, X.shape[0])
    n_rf = max(n_dp, (n_rf // n_dp) * n_dp)
    Xs = X[:n_rf]
    ys = y[:n_rf]
    ms = mask[:n_rf]
    d_pad = next_pow2(N_COLS)
    # quantile edges ON DEVICE (no ~67 MB host fetch of the subsample inside
    # the set-up); the estimator path sketches on host
    # because there the data starts on host
    qs = jnp.linspace(0.0, 1.0, RF_BINS + 1)[1:-1]
    # one-shot setup jit: this function runs once per bench invocation
    # tpuml: ignore[TPU003]
    edges = jax.jit(
        lambda Xs: jnp.quantile(Xs[: min(65536, n_rf)], qs, axis=0).T.astype(
            jnp.float32
        )
    )(Xs)
    bins = binize(Xs, edges, d_pad=d_pad)
    stats = jnp.stack([1.0 - ys, ys], axis=1) * ms[:, None]
    trees_per_dev = -(-RF_TREES // n_dp)
    from jax.sharding import NamedSharding, PartitionSpec as P
    # reference semantics: the benchmark config leaves featureSubsetStrategy
    # at Spark's default "auto", which cuML resolves to sqrt(d) per split
    # for classification (``/root/reference/python/src/spark_rapids_ml/
    # tree.py:380-386``). Resolution is shared with the estimator so the
    # bench can never drift from what the library fits. Override with
    # BENCH_RF_K=<n> or BENCH_RF_K=all (all-features variant).
    from spark_rapids_ml_tpu.models.tree import _resolve_k_features

    raw_k = os.environ.get("BENCH_RF_K", "auto")
    k_feat = _resolve_k_features(
        N_COLS if raw_k == "all" else (raw_k if raw_k == "auto" else int(raw_k)),
        N_COLS,
        True,
    )
    cfg = ForestConfig(
        max_depth=RF_DEPTH, n_bins=RF_BINS, n_features=N_COLS, n_stats=2,
        impurity="gini", k_features=k_feat, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True,
        hist_strategy=resolve_hist_strategy(),
        contract_gather=resolve_contract_gather(),
    )

    # trees build in groups of <= 8 per dispatch: a multi-minute single
    # device program outlives runtime health checks (the estimator groups
    # the same way). One compiled program serves every group (same size).
    group = min(8, trees_per_dev)
    trees_per_dev = -(-trees_per_dev // group) * group
    keys = jax.random.key_data(
        jax.random.split(jax.random.key(7), n_dp * trees_per_dev)
    ).reshape(n_dp, trees_per_dev, 2)
    keys = jax.device_put(np.asarray(keys), NamedSharding(mesh, P("dp")))
    # tree-batched growth (TPUML_RF_TREE_BATCH, default auto): the whole
    # dispatch group advances one level per device program instead of
    # lax.map-ing trees sequentially — same resolution the estimator uses,
    # so the bench measures exactly what the library ships
    rows_per_tree = n_rf // n_dp
    tree_batch = resolve_tree_batch(group, cfg, rows_per_tree)

    def timed_fn(bins, ms, stats, kg):
        return _checksum(
            build_forest(
                bins, ms, stats, kg, mesh=mesh, cfg=cfg,
                tree_batch=tree_batch,
            )
        )

    timed = jax.jit(timed_fn)
    # warm-up/compile on a DISTINCT key set: remote backends may memoize
    # (executable, input values) pairs, and the timed groups must be fresh
    warm_keys = jax.device_put(
        np.asarray(
            jax.random.key_data(
                jax.random.split(jax.random.key(99), n_dp * group)
            ).reshape(n_dp, group, 2)
        ),
        NamedSharding(mesh, P("dp")),
    )
    np.asarray(timed(bins, ms, stats, warm_keys))  # compile
    # best of BENCH_RF_REPS full passes: a transient host stall would
    # otherwise land in the single summed time (every rep perturbs stats
    # so no group dispatch repeats an earlier one exactly)
    reps = max(1, int(os.environ.get("BENCH_RF_REPS", 2)))
    # transient-stall filtering matters for sub-second dispatches; once a
    # full pass takes this long, a ~100 ms stall is noise and a second
    # pass would only burn the capture run's wall-clock budget
    rep_cap_s = float(os.environ.get("BENCH_RF_MAX_SECONDS_FOR_REPS", 90))
    # pre-slice and block every group's keys OUTSIDE the timed region
    # (the _best_time discipline). Inside it, groups stay host-synchronous
    # — one dispatch, one fetch — deliberately: the ~65 ms/group fetch is
    # <1% of a multi-second group build, and queueing many unfetched
    # multi-second programs is the long-occupancy shape that tripped
    # remote health checks in round 2.
    kgs = [keys[:, g0 : g0 + group] for g0 in range(0, trees_per_dev, group)]
    jax.block_until_ready(kgs)
    times = []
    for rep in range(reps):
        stats_r = stats * jnp.float32(1.0 + (rep + 1) * 1e-6)
        jax.block_until_ready(stats_r)
        t0 = time.perf_counter()
        for kg in kgs:
            np.asarray(timed(bins, ms, stats_r, kg))
        t_rep = time.perf_counter() - t0
        times.append(t_rep)
        if t_rep > rep_cap_s:
            break
    t = min(times)
    n_trees = trees_per_dev * n_dp
    # per-level cost: each group dispatch walks RF_DEPTH levels, groups
    # run back-to-back, so the derived average is t / (levels * groups).
    # BENCH_RF_LEVEL_TIMING=1 replaces the average with MEASURED marginal
    # level costs — depth-prefix builds of one group, differenced — at
    # the price of one compile per depth (tuning runs only).
    n_groups = len(kgs)
    seconds_per_level = t / (RF_DEPTH * n_groups)
    level_seconds = None
    if os.environ.get("BENCH_RF_LEVEL_TIMING") == "1":
        prefix_t = []
        for dep in range(1, RF_DEPTH + 1):
            cfg_l = cfg._replace(max_depth=dep)
            tb_l = resolve_tree_batch(group, cfg_l, rows_per_tree)
            # per-depth variant, compiled once and reused for the timed
            # call  # tpuml: ignore[TPU003]
            f_l = jax.jit(
                lambda b, m, s, kg, _c=cfg_l, _tb=tb_l: _checksum(
                    build_forest(
                        b, m, s, kg, mesh=mesh, cfg=_c, tree_batch=_tb
                    )
                )
            )
            np.asarray(f_l(bins, ms, stats, warm_keys))  # compile
            # perturb stats so a memoizing remote backend re-executes
            s_l = stats * jnp.float32(1.0 + dep * 1e-6)
            jax.block_until_ready(s_l)
            t0l = time.perf_counter()
            np.asarray(f_l(bins, ms, s_l, warm_keys))
            prefix_t.append(time.perf_counter() - t0l)
        level_seconds = [round(prefix_t[0], 4)] + [
            round(max(0.0, b - a), 4)
            for a, b in zip(prefix_t, prefix_t[1:])
        ]
    # transform path: the two-hop bin-space descent the model uses on TPU
    # (round 5; binize of the query batch is timed INSIDE, as the model
    # pays it per batch), over the FULL forest width (one built group's
    # trees tiled to n_trees — apply cost is content-independent).
    from spark_rapids_ml_tpu.ops.tree_kernels import binize, rf_classify_bins

    # one-shot warm build, outside the timed region  # tpuml: ignore[TPU003]
    grp = jax.jit(
        lambda b, m, s, kg: build_forest(
            b, m, s, kg, mesh=mesh, cfg=cfg, tree_batch=tree_batch
        )
    )(bins, ms, stats, warm_keys)
    feat_g = grp["feature"].reshape(-1, grp["feature"].shape[-1])
    thr_b = grp["threshold_bin"].reshape(feat_g.shape)
    leafs = grp["leaf_stats"].reshape(feat_g.shape + (2,))
    reps_t = -(-n_trees // feat_g.shape[0])

    def prep(feat_g, thr_b, leafs):
        prob = leafs / jnp.maximum(leafs.sum(-1, keepdims=True), 1e-12)
        tile = lambda a: jnp.tile(a, (reps_t,) + (1,) * (a.ndim - 1))[:n_trees]
        return tile(feat_g), tile(thr_b), tile(prob)

    # one-shot tiling, outside the timed region  # tpuml: ignore[TPU003]
    feat_t, thrb_t, prob_t = jax.jit(prep)(feat_g, thr_b, leafs)
    jax.block_until_ready((feat_t, thrb_t, prob_t))
    d_pad4 = -(-Xs.shape[1] // 4) * 4
    # row-chunked + group=4: the descent's per-tree-group transients must
    # coexist with the resident multi-GB design matrix here (a single
    # full-width pass RESOURCE_EXHAUSTed alongside it)
    n_half = n_rf // 2

    # packed-forest lockstep engine (round 6): pack OUTSIDE the timed fn
    # — the model pays it once and caches (models/tree._ensure_packed),
    # so the steady-state serving cost is traversal only. Falls back to
    # the per-tree bins descent when the traversal kernel can't lower
    # (CPU smoke runs, oversized feature words).
    from spark_rapids_ml_tpu.ops.rf_pallas import packed_traverse_ok
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        pack_forest, rf_classify_packed,
    )

    pf = pack_forest(
        np.asarray(feat_t), np.asarray(thrb_t), max_depth=RF_DEPTH
    )
    use_packed = pf.k2 == 0 or packed_traverse_ok(
        pf.feat1.shape[0], pf.k1, pf.k2, d_pad4 // 4
    )
    if use_packed:
        pk = tuple(
            jax.device_put(a) for a in (pf.feat1, pf.thr1, pf.feat2, pf.thr2)
        )
        jax.block_until_ready(pk)

        def tr_fn(Xq, edges, feat_t, thrb_t, prob_t):
            acc = jnp.float32(0.0)
            for lo in (0, n_rf - n_half):
                xbq = binize(Xq[lo : lo + n_half], edges, d_pad=d_pad4)
                acc = acc + _checksum(
                    rf_classify_packed(
                        xbq, *pk, prob_t,
                        k1=pf.k1, k2=pf.k2, max_depth=RF_DEPTH,
                    )[0]
                )
            return acc

    else:

        def tr_fn(Xq, edges, feat_t, thrb_t, prob_t):
            acc = jnp.float32(0.0)
            # second chunk is anchored to the END so odd n_rf still covers
            # every row (the one-row overlap double-counts a checksum term,
            # not timed work of any significance)
            for lo in (0, n_rf - n_half):
                xbq = binize(Xq[lo : lo + n_half], edges, d_pad=d_pad4)
                acc = acc + _checksum(
                    rf_classify_bins(
                        xbq, feat_t, thrb_t, prob_t, max_depth=RF_DEPTH, group=4
                    )[0]
                )
            return acc

    tr_timed = jax.jit(tr_fn)
    np.asarray(tr_timed(Xs, edges, feat_t, thrb_t, prob_t))  # compile
    t_tr, _ = _best_time(
        lambda rep: (
            Xs * jnp.float32(1.0 + (rep + 1) * 1e-6), edges, feat_t,
            thrb_t, prob_t,
        ),
        tr_timed,
    )
    # updates model: one histogram update per (row, sampled feature, stat,
    # level) — both sides of the comparison pay k_features per node, so
    # the A10G atomics baseline divides by the same per-sample cost
    updates = float(n_rf) * k_feat * 2 * RF_DEPTH * n_trees
    return {
        "samples_per_sec_per_chip": n_rf * n_trees / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_engine": "packed" if use_packed else "bins",
        "transform_samples_per_sec_per_chip": n_rf / t_tr / n_chips,
        # FIL/treelite serving roofline (reference tree.py:557-591): GPU
        # forest inference is bound by per-(row, tree, level) node fetches
        # hitting L1/SMEM at ~1e10 fetches/s/GPU — tens of millions of
        # rows/s at small forests, matching published FIL numbers
        "transform_baseline_samples_per_sec": 1e10 / (n_trees * RF_DEPTH),
        "trees": n_trees,
        "rows": n_rf,
        "k_features": k_feat,
        "hist_strategy": cfg.hist_strategy,
        "tree_batch": tree_batch,
        "seconds_per_level": round(seconds_per_level, 5),
        **({"level_seconds": level_seconds} if level_seconds else {}),
        "flops_model": updates,  # scatter-equivalent work, not MXU flops
        "baseline_samples_per_sec": 1.8e9 / (k_feat * RF_DEPTH * 2),
        "baseline_inputs": {
            "formula": "rf_hist_atomics_v1",
            "atomics_per_sec": 1.8e9,
            "k_features": k_feat,
            "depth": RF_DEPTH,
            "n_stats": 2,
            "transform_formula": "fil_node_fetch_v1",
            "node_fetches_per_sec": 1e10,
        },
    }


GBT_ROUNDS = int(os.environ.get("BENCH_GBT_ROUNDS", 20))
GBT_ROWS = int(os.environ.get("BENCH_GBT_ROWS", 131_072))
GBT_DEPTH = int(os.environ.get("BENCH_GBT_DEPTH", 8))


def bench_gbt(X, mask, y, mesh, n_chips):
    """Binary logistic gradient boosting (``ops/gbt_kernels.gbt_round``):
    sequential rounds, each a tree-batched level-wise build over the
    current gradient field plus an in-round margin advance. Rows stay
    data-parallel — every tree sees the full dataset through psum'd
    histograms — so unlike rf the round chain has collectives, and the
    fit rate measures the boosting-loop steady state (stats recompute,
    T-batched histograms, leaf Newton steps, margin update).

    Throughput unit matches rf: tree-samples/sec/chip (rows x trees /
    seconds).

    Baseline model (derived roofline like ann): XGBoost-class GPU hist
    boosting on the A10G is bound by the same shared-memory histogram
    atomics as the RF baseline (1.8e9 updates/s) but pays ALL d features
    per node (boosted trees don't subsample features per split) x depth
    levels x 2 stats (grad, hess) per tree-sample; the per-round
    gradient/margin streaming passes are charged at zero (they are HBM
    reads the histogram pass already pays)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.gbt_kernels import GBTConfig, gbt_round
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        ForestConfig,
        binize,
        next_pow2,
        resolve_contract_gather,
        resolve_hist_strategy,
    )

    n_dp = mesh.shape["dp"]
    n_g = min(GBT_ROWS, X.shape[0])
    n_g = max(n_dp, (n_g // n_dp) * n_dp)
    Xs, ys, ms = X[:n_g], y[:n_g], mask[:n_g]
    d_pad = next_pow2(N_COLS)
    qs = jnp.linspace(0.0, 1.0, RF_BINS + 1)[1:-1]
    # one-shot setup jit (same device-side sketch as rf)
    # tpuml: ignore[TPU003]
    edges = jax.jit(
        lambda Xs: jnp.quantile(Xs[: min(65536, n_g)], qs, axis=0).T.astype(
            jnp.float32
        )
    )(Xs)
    bins = binize(Xs, edges, d_pad=d_pad)
    cfg = GBTConfig(
        loss="logistic", n_out=1, learning_rate=0.1,
        tree=ForestConfig(
            max_depth=GBT_DEPTH, n_bins=RF_BINS, n_features=N_COLS,
            n_stats=4, impurity="variance", k_features=N_COLS,
            min_samples_leaf=1, min_info_gain=0.0, min_samples_split=2,
            bootstrap=False,
            hist_strategy=resolve_hist_strategy(),
            contract_gather=resolve_contract_gather(),
        ),
    )
    keys_np = np.asarray(jax.random.split(jax.random.PRNGKey(7), GBT_ROUNDS))
    zeros = jax.device_put(
        np.zeros((n_g, 1), np.float32), NamedSharding(mesh, P("dp"))
    )
    warm_key = jnp.asarray(np.asarray(jax.random.PRNGKey(99)))
    # compile on a distinct key (remote-memoization discipline, as in rf)
    out_w = gbt_round(bins, ms, ys, zeros, warm_key, mesh=mesh, cfg=cfg)
    jax.block_until_ready(out_w["margins"])

    reps = max(1, int(os.environ.get("BENCH_GBT_REPS", 2)))
    times = []
    last = None
    for rep in range(reps):
        # a fresh epsilon init perturbs every round's stats so a
        # memoizing remote backend cannot replay the chain
        margins = zeros + jnp.float32((rep + 1) * 1e-6)
        jax.block_until_ready(margins)
        t0 = time.perf_counter()
        outs = []
        for r in range(GBT_ROUNDS):
            out = gbt_round(
                bins, ms, ys, margins, jnp.asarray(keys_np[r]),
                mesh=mesh, cfg=cfg,
            )
            margins = out.pop("margins")
            outs.append(out)
        jax.block_until_ready(margins)
        times.append(time.perf_counter() - t0)
        last = outs
    t = min(times)

    # transform leg: the model's descent engines over the boosted forest
    # (summed leaf payloads; margin = init + sum), packed when the
    # traversal kernel lowers, else the two-hop bins descent — the same
    # engine split the rf entry reports
    feat_t = jnp.concatenate([o["feature"] for o in last], axis=0)
    thrb_t = jnp.concatenate([o["threshold_bin"] for o in last], axis=0)
    vals_t = jnp.concatenate([o["values"] for o in last], axis=0)[:, :, None]
    jax.block_until_ready((feat_t, thrb_t, vals_t))
    from spark_rapids_ml_tpu.ops.rf_pallas import packed_traverse_ok
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        pack_forest, rf_eval_bins, rf_eval_packed,
    )

    d_pad4 = -(-Xs.shape[1] // 4) * 4
    pf = pack_forest(
        np.asarray(feat_t), np.asarray(thrb_t), max_depth=GBT_DEPTH
    )
    use_packed = pf.k2 == 0 or packed_traverse_ok(
        pf.feat1.shape[0], pf.k1, pf.k2, d_pad4 // 4
    )
    n_half = n_g // 2
    if use_packed:
        pk = tuple(
            jax.device_put(a) for a in (pf.feat1, pf.thr1, pf.feat2, pf.thr2)
        )
        jax.block_until_ready(pk)

        def tr_fn(Xq, edges, feat_t, thrb_t, vals_t):
            acc = jnp.float32(0.0)
            for lo in (0, n_g - n_half):
                xbq = binize(Xq[lo : lo + n_half], edges, d_pad=d_pad4)
                acc = acc + _checksum(
                    rf_eval_packed(
                        xbq, *pk, vals_t,
                        k1=pf.k1, k2=pf.k2, max_depth=GBT_DEPTH,
                    )
                )
            return acc

    else:

        def tr_fn(Xq, edges, feat_t, thrb_t, vals_t):
            acc = jnp.float32(0.0)
            for lo in (0, n_g - n_half):
                xbq = binize(Xq[lo : lo + n_half], edges, d_pad=d_pad4)
                acc = acc + _checksum(
                    rf_eval_bins(
                        xbq, feat_t, thrb_t, vals_t,
                        max_depth=GBT_DEPTH, group=4,
                    )
                )
            return acc

    tr_timed = jax.jit(tr_fn)
    np.asarray(tr_timed(Xs, edges, feat_t, thrb_t, vals_t))  # compile
    t_tr, _ = _best_time(
        lambda rep: (
            Xs * jnp.float32(1.0 + (rep + 1) * 1e-6), edges, feat_t,
            thrb_t, vals_t,
        ),
        tr_timed,
    )
    n_trees = GBT_ROUNDS * cfg.n_out
    updates = float(n_g) * N_COLS * 2 * GBT_DEPTH * n_trees
    return {
        "samples_per_sec_per_chip": n_g * n_trees / t / n_chips,
        "fit_seconds": t,
        "transform_seconds": t_tr,
        "transform_engine": "packed" if use_packed else "bins",
        "transform_samples_per_sec_per_chip": n_g / t_tr / n_chips,
        "transform_baseline_samples_per_sec": 1e10 / (n_trees * GBT_DEPTH),
        "rounds": GBT_ROUNDS,
        "trees": n_trees,
        "rows": n_g,
        "depth": GBT_DEPTH,
        "hist_strategy": cfg.tree.hist_strategy,
        "seconds_per_round": round(t / GBT_ROUNDS, 5),
        "flops_model": updates,  # scatter-equivalent work, not MXU flops
        "baseline_samples_per_sec": 1.8e9 / (N_COLS * GBT_DEPTH * 2),
        "baseline_kind": "derived-roofline",
        "baseline_inputs": {
            "formula": "gbt_hist_atomics_v1",
            "atomics_per_sec": 1.8e9,
            "d": N_COLS,
            "depth": GBT_DEPTH,
            "n_stats": 2,
            "transform_formula": "fil_node_fetch_v1",
            "node_fetches_per_sec": 1e10,
        },
    }


KNN_QUERIES = int(os.environ.get("BENCH_KNN_QUERIES", 131_072))
KNN_ITEMS = int(os.environ.get("BENCH_KNN_ITEMS", 1_000_000))
KNN_K = 16


def bench_knn(X, mask, mesh, n_chips):
    """Exact brute-force kNN (the reference's NearestNeighbors workload):
    one ring pass over the item shards, distance matmul + running top-k.

    Baseline model (round 5, sharpened from the round-4 optimistic floor
    per that verdict): cuML brute kNN per query pays (a) the distance
    matmul, 2*ni*d FLOPs at A10G's ~15 TFLOP/s effective TF32, and (b)
    the k-selection pass over the ni-wide distance row — cuML's
    warp-select reads the materialized tile from L2/HBM, charged at half
    the 600 GB/s HBM rate (generous: tiles partially hit L2). UCX
    inter-GPU exchange is charged at zero (single-GPU roofline). At
    1M x 256 this lands ~9% below the old matmul-only floor."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.knn_kernels import ring_knn

    n_dp = mesh.shape["dp"]
    # clamp to REAL rows (N_ROWS), not the padded count: padding rows are
    # masked out of results but would inflate "rows" and the baseline's
    # workload credit
    ni = min(KNN_ITEMS, N_ROWS, X.shape[0])
    ni = max(n_dp, (ni // n_dp) * n_dp)
    nq = min(KNN_QUERIES, ni)
    nq = max(n_dp, (nq // n_dp) * n_dp)
    Xi, mi = X[:ni], mask[:ni]
    ids = jnp.arange(ni, dtype=jnp.int32)

    def timed_fn(Xq, Xi, mi, ids):
        return _checksum(ring_knn(Xq, Xi, mi, ids, mesh=mesh, k=KNN_K))

    timed = jax.jit(timed_fn)
    np.asarray(timed(X[:nq], Xi, mi, ids))  # compile
    t, _ = _best_time(
        lambda rep: (
            X[:nq] * jnp.float32(1.0 + (rep + 1) * 1e-6), Xi, mi, ids
        ),
        timed,
    )
    flops = 2.0 * nq * ni * N_COLS
    # per-query GPU cost: matmul + k-selection read (see docstring)
    base_q_s = 2.0 * ni * N_COLS / 15e12 + ni * 4.0 / (0.5 * 600e9)
    return {
        "samples_per_sec_per_chip": nq / t / n_chips,
        "fit_seconds": t,
        "rows": ni,
        "queries": nq,
        "flops_model": flops,
        "baseline_samples_per_sec": 1.0 / base_q_s,
        "baseline_kind": "derived-roofline",
        "baseline_inputs": {
            "formula": "knn_matmul_select_v1",
            "matmul_flops_per_sec": 15e12,
            "select_bytes_per_sec": 0.5 * 600e9,
            "items": ni,
            "d": N_COLS,
        },
    }


ANN_ROWS = int(os.environ.get("BENCH_ANN_ROWS", 131_072))
ANN_QUERIES = int(os.environ.get("BENCH_ANN_QUERIES", 65_536))
ANN_K = 16


def bench_ann(mesh, n_chips):
    """IVF-Flat approximate kNN serving (the reference's
    ``ApproximateNearestNeighbors`` ivfflat workload): k-means coarse
    quantizer + probe-list scan (``ops/ivf_kernels.py``). The timed
    quantity is the SEARCH rate; the one-off index build is reported
    separately (serving amortizes it away, exactly as cuML does).

    Data is host blobs (~128 MB at 128k x 256): IVF needs cluster
    structure — a uniform cloud has no identifiable cells and every ANN
    engine degrades toward brute force there (the reference benches ANN
    on ``gen_data.py`` blobs for the same reason).

    Baseline model: RAFT IVF-Flat on the A10G — the knn_matmul_select_v1
    constants applied per query to the PROBED candidate pool instead of
    all items: (a) coarse quantization, 2*nlist*d FLOPs at 15 TFLOP/s
    effective TF32; (b) candidate scan, 2*d FLOPs over the nprobe*cap
    gathered rows; (c) warp-select reading the nprobe*cap-wide distance
    row from L2/HBM at half the 600 GB/s HBM rate. Build is charged at
    zero. vs_baseline is only meaningful at matched approximation
    quality, so recall@k against the exact engine on a query subsample
    rides in the entry (docs/ann_performance.md has the trade-off
    curve)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.umap import knn_brute
    from spark_rapids_ml_tpu.ops.ivf_kernels import (
        build_ivf_index,
        ivf_search,
        resolve_ann_params,
    )
    from spark_rapids_ml_tpu.ops.knn_kernels import resolve_knn_topk

    n_dp = mesh.shape["dp"]
    ni = max(n_dp, (ANN_ROWS // n_dp) * n_dp)
    nq = min(ANN_QUERIES, ni)
    nq = max(n_dp, (nq // n_dp) * n_dp)
    d = N_COLS
    rng = np.random.default_rng(11)
    centers = rng.normal(size=(64, d)).astype(np.float32) * 4.0
    lab = rng.integers(0, 64, size=ni)
    Xh = (centers[lab] + rng.normal(size=(ni, d))).astype(np.float32)

    nlist, nprobe = resolve_ann_params(ni)
    t0 = time.perf_counter()
    index = build_ivf_index(Xh, nlist=nlist, seed=0, mesh=mesh)
    jax.block_until_ready(index.grouped_x)
    t_build = time.perf_counter() - t0

    topk = resolve_knn_topk()

    def timed(Xq):
        return np.asarray(
            _checksum(
                ivf_search(
                    Xq, index, k=ANN_K, nprobe=nprobe, topk_impl=topk,
                    mesh=mesh,
                )
            )
        )

    Q = Xh[:nq]
    timed(jnp.asarray(Q))  # compile + commit the index to the mesh
    t, _ = _best_time(
        lambda rep: (jnp.asarray(Q * np.float32(1.0 + (rep + 1) * 1e-6)),),
        timed,
    )

    # recall@k vs the exact sweep on a subsample — the quantity that makes
    # the throughput claim meaningful
    sub = min(1024, nq)
    _, aids = ivf_search(
        jnp.asarray(Xh[:sub]), index, k=ANN_K, nprobe=nprobe, topk_impl=topk
    )
    _, eids = knn_brute(jnp.asarray(Xh), jnp.asarray(Xh[:sub]), k=ANN_K)
    a, e = np.asarray(aids), np.asarray(eids)
    recall = float(
        np.mean([len(set(a[i]) & set(e[i])) / ANN_K for i in range(sub)])
    )

    cap = index.cap
    pool = nlist + nprobe * cap
    base_q_s = 2.0 * pool * d / 15e12 + nprobe * cap * 4.0 / (0.5 * 600e9)
    return {
        "samples_per_sec_per_chip": nq / t / n_chips,
        "fit_seconds": t,
        "build_seconds": round(t_build, 4),
        "rows": ni,
        "queries": nq,
        "nlist": nlist,
        "nprobe": nprobe,
        "recall": round(recall, 4),
        "flops_model": 2.0 * nq * pool * d,
        "baseline_samples_per_sec": 1.0 / base_q_s,
        "baseline_kind": "derived-roofline",
        "baseline_inputs": {
            "formula": "ann_ivf_probe_v1",
            "matmul_flops_per_sec": 15e12,
            "select_bytes_per_sec": 0.5 * 600e9,
            "nlist": nlist,
            "nprobe": nprobe,
            "cap": cap,
            "d": d,
        },
    }


UMAP_ROWS = int(os.environ.get("BENCH_UMAP_ROWS", 65_536))
UMAP_NEIGHBORS = 15


def bench_umap(mesh, n_chips):
    """UMAP end-to-end through the estimator (the reference benchmarks
    UMAP the same way and scores trustworthiness:
    ``python/benchmark/benchmark/bench_umap.py``).

    Pipeline timed: brute-force kNN graph (device) -> fuzzy simplicial
    set (host symmetrization of n*k entries) -> spectral init ->
    negative-sampling SGD (device). Data is host-side blobs (~64 MB at
    64k x 256) — the one entry where ingest rides inside fit, as it does
    in the reference's Spark flow; at these sizes the transfer is a few
    seconds of the multi-ten-second fit.

    Baseline model (round 5, replacing the round-4 1e4 proxy per that
    verdict): a derived cuML-on-A10G fit roofline —
      knn      2*n^2*d FLOPs at 15 TFLOP/s effective TF32;
      SGD      epochs * f_active*m_edges * (1+neg) head updates, c f32
               atomics each, at the 1.8e9 atomics/s constant the RF
               baseline uses (the 512 KB embedding is L2-resident);
      spectral 0.2 s flat credit for the GPU Lanczos init;
      fuzzy-set/transfer/launch overheads charged at ZERO.
    Constants measured at the bench shape: the symmetrized edge factor
    m/(n*k) = 1.74 and the mean Bernoulli activation f = 0.278
    (scripts/umap_profile.py lineage). At 65k x 256 this gives ~1.0 s,
    consistent with published cuML UMAP times (MNIST 70k in ~1-2 s) —
    i.e. a roofline, not a proxy. The transform baseline reuses the knn
    term plus one third of the SGD (the refine epochs).

    flops_model counts the brute kNN graph (2*n^2*d), the dominant
    device compute of this implementation; MFU is indicative only.
    """
    from sklearn.manifold import trustworthiness

    from spark_rapids_ml_tpu.data import DataFrame as TDF
    from spark_rapids_ml_tpu.umap import UMAP

    n, d = UMAP_ROWS, N_COLS
    rng = np.random.default_rng(3)
    centers = rng.normal(size=(32, d)).astype(np.float32) * 4.0
    lab = rng.integers(0, 32, size=n)
    Xh = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    df = TDF({"features": Xh})
    # warm-pass data is PERTURBED vs the timed pass: identical
    # (executable, buffers) pairs may be memoized by a remote backend
    # (module docstring; observed round 1) — the timed fit must see
    # fresh buffers
    df_warm = TDF({"features": Xh * np.float32(1.0 + 1e-6)})

    est = UMAP(n_neighbors=UMAP_NEIGHBORS, random_state=42)
    # graph engine: the bench runs the IVF-Flat approximate graph by
    # default (BENCH_UMAP_GRAPH=exact restores the old sweep) — set
    # explicitly because the estimator's own default gate keeps exact
    # below TPUML_ANN_GATE_ROWS (defaults-inert contract); scoped so the
    # process env is untouched for later entries
    graph_mode = os.environ.get("BENCH_UMAP_GRAPH", "ivf")
    prev_graph = os.environ.pop("TPUML_UMAP_GRAPH", None)
    os.environ["TPUML_UMAP_GRAPH"] = graph_mode
    try:
        # warm pass at FULL size first: the kNN-graph/SGD executables are
        # shape-specialized, so only a same-shape fit excludes compile time
        # from the timed pass (every other leg warms the same way);
        # BENCH_UMAP_WARM=0 skips when wall-clock budget is tight
        if os.environ.get("BENCH_UMAP_WARM", "1") != "0":
            est.fit(df_warm)
        t0 = time.perf_counter()
        model = est.fit(df)
        t_fit = time.perf_counter() - t0
        emb = np.asarray(model.embedding_)

        model.transform(df_warm)  # warm transform executables (fresh buffers)
        t0 = time.perf_counter()
        out = model.transform(df)
        emb_t = np.asarray(out["embedding"])
        t_tr = time.perf_counter() - t0
        assert emb_t.shape[0] == n
    finally:
        if prev_graph is None:
            os.environ.pop("TPUML_UMAP_GRAPH", None)
        else:
            os.environ["TPUML_UMAP_GRAPH"] = prev_graph

    # quality: trustworthiness on a subsample (the reference's score;
    # exact trust is O(sub^2) host work)
    sub = rng.choice(n, size=min(4096, n), replace=False)
    trust = float(
        trustworthiness(Xh[sub], emb[sub], n_neighbors=UMAP_NEIGHBORS)
    )

    # derived A10G roofline (docstring): knn + SGD atomics + spectral
    m_edges = n * UMAP_NEIGHBORS * 1.74   # measured symmetrized factor
    f_active = 0.278                      # measured mean(w)/max(w)
    epochs = 200 if n > 10000 else 500
    knn_s = 2.0 * n * n * d / 15e12
    sgd_s = epochs * f_active * m_edges * 6 * 2 / 1.8e9
    base_fit_s = knn_s + sgd_s + 0.2
    # stage decomposition + engine choice straight from the estimator's
    # fit/transform reports, so a drifting vs_baseline is attributable to
    # a stage (graph vs init vs sgd) without rerunning under a profiler
    rep = dict(getattr(model, "_fit_report", None) or {})
    trep = dict(getattr(model, "_transform_report", None) or {})

    # graph recall when the approximate engine ran: the fit's index is
    # deterministic in (X, nlist, seed), so rebuild it and score the probe
    # search against the exact sweep on a query subsample — graph_seconds
    # is only comparable across engines at matched recall
    graph_recall = None
    if rep.get("graph_engine") == "ivf":
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.umap import knn_brute
        from spark_rapids_ml_tpu.ops.ivf_kernels import (
            build_ivf_index,
            ivf_search,
        )

        gidx = build_ivf_index(
            Xh, nlist=rep["ann_nlist"], seed=42  # = random_state above
        )
        qs = jnp.asarray(Xh[: min(1024, n)])
        _, aids = ivf_search(
            qs, gidx, k=UMAP_NEIGHBORS + 1, nprobe=rep["ann_nprobe"]
        )
        _, eids = knn_brute(jnp.asarray(Xh), qs, k=UMAP_NEIGHBORS + 1)
        a, e = np.asarray(aids), np.asarray(eids)
        graph_recall = round(
            float(
                np.mean(
                    [
                        len(set(a[i]) & set(e[i])) / a.shape[1]
                        for i in range(a.shape[0])
                    ]
                )
            ),
            4,
        )
    return {
        "samples_per_sec_per_chip": n / t_fit / n_chips,
        "fit_seconds": t_fit,
        "transform_seconds": t_tr,
        "transform_samples_per_sec_per_chip": n / t_tr / n_chips,
        "transform_baseline_samples_per_sec": n / (knn_s + sgd_s / 3.0),
        "transform_engine": trep.get("sgd_engine"),
        "rows": n,
        "trustworthiness": round(trust, 4),
        "graph_seconds": rep.get("graph_seconds"),
        "graph_engine": rep.get("graph_engine"),
        "graph_recall": graph_recall,
        "ann_nlist": rep.get("ann_nlist"),
        "ann_nprobe": rep.get("ann_nprobe"),
        "init_seconds": rep.get("init_seconds"),
        "sgd_seconds": rep.get("sgd_seconds"),
        "epoch_ms": rep.get("epoch_ms"),
        "sgd_engine": rep.get("sgd_engine"),
        "flops_model": 2.0 * float(n) * n * d,
        "baseline_samples_per_sec": n / base_fit_s,
        "baseline_kind": "derived-roofline",
        "baseline_inputs": {
            "formula": "umap_roofline_v1",
            "knn_flops_per_sec": 15e12,
            "atomics_per_sec": 1.8e9,
            "edge_factor": 1.74,
            "f_active": f_active,
            "epochs": epochs,
            "n_neighbors": UMAP_NEIGHBORS,
            "spectral_flat_seconds": 0.2,
        },
    }


def bench_pca_stream(mesh, n_chips):
    """Out-of-core PCA: chunks stream through a bounded device buffer
    (``ops/streaming.py``), the path that handles beyond-HBM datasets
    (BASELINE.md 100M x 256 north-star). Self-calibrates the row count so a
    slow host->device link cannot blow the wall-clock budget; the reported
    rate is per-pass ingest+accumulate throughput (2 passes per fit).

    The ingest leg is the host->device link (PCIe/DMA on a local chip), not
    device math; the two legs are reported separately below."""
    import jax

    from spark_rapids_ml_tpu.data.chunks import GeneratorChunkSource
    from spark_rapids_ml_tpu.models.feature import _pca_from_cov
    from spark_rapids_ml_tpu.ops.streaming import streamed_suffstats

    d = N_COLS
    n_dp = mesh.shape["dp"]
    chunk_rows = int(os.environ.get("BENCH_STREAM_CHUNK", 1 << 18))
    chunk_rows = max(n_dp, (chunk_rows // n_dp) * n_dp)
    rng = np.random.default_rng(2)
    block = rng.standard_normal((chunk_rows, d), dtype=np.float32)

    def gen(start, count, seed):
        return block[:count], None

    def run(rows):
        src = GeneratorChunkSource(gen, rows, d)
        stats = streamed_suffstats(src, mesh, chunk_rows, np.float32, with_y=False)
        cov = stats["G"] / (stats["n"] - 1.0)
        out = _pca_from_cov(stats["mean_x"], cov, stats["n"], 3)
        # force a device->host fetch of every (small) leaf: block_until_ready
        # is not relied on here (the fetch is the fence), and the
        # calibration scales the real run's row
        # count off this timer
        for leaf in jax.tree_util.tree_leaves(out):
            np.asarray(leaf)
        return out

    # calibrate: compile + measure a 4-chunk fit, then size the real run
    calib_rows = 4 * chunk_rows
    run(calib_rows)  # compile
    t0 = time.perf_counter()
    run(calib_rows)
    t_calib = time.perf_counter() - t0
    budget_s = float(os.environ.get("BENCH_STREAM_SECONDS", 45))
    max_rows = int(os.environ.get("BENCH_STREAM_ROWS", 16_000_000))
    rows = int(min(max_rows, calib_rows * max(1.0, budget_s / max(t_calib, 1e-9))))
    rows = max(chunk_rows, (rows // chunk_rows) * chunk_rows)

    t0 = time.perf_counter()
    run(rows)
    t = time.perf_counter() - t0
    # the wire encoding the fit ACTUALLY used (env request resolved through
    # select_wire_format — "auto" lands here as the probed choice)
    from spark_rapids_ml_tpu.ops.streaming import last_ingest_report

    wire_kind = last_ingest_report().get("wire_dtype", "f32")

    # Decomposition (the artifact alone must distinguish "the link is
    # slow" from "streaming kernels are slow"):
    # (a) device math only — fold ONE device-resident chunk repeatedly
    #     through both passes' steps (no H2D inside the timed loop);
    # (b) ingest only — stream + transfer every chunk but fold it with a
    #     trivial (read-proving) step;
    # (c) decode only — run the chunk source with no transfer/fold at all
    #     (quantization cost shows up as ingest minus decode).
    # overlap_efficiency = (a + b - total) / min(a, b), clipped to [0, 1]:
    # 1.0 means the slower leg fully hides the faster one.
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.data.chunks import Chunk
    from spark_rapids_ml_tpu.ops.streaming import (
        StreamGuard, gram2_init, gram2_step, iter_device_chunks,
        moments1_init, moments1_step, put_chunk, wire_dense,
    )

    n_chunks = max(1, rows // chunk_rows)
    dev = put_chunk(
        Chunk(X=block, n_valid=chunk_rows), mesh, np.float32, wire=wire_kind
    )
    jax.block_until_ready(
        [v for k, v in dev.items() if v is not None and k != "_wire"]
    )
    mean0 = jnp.zeros((d,), jnp.float32)

    def math_pass():
        acc1 = moments1_init(d, jnp.float32, False)
        for _ in range(n_chunks):
            acc1 = moments1_step(acc1, dev["X"], dev["mask"])
        np.asarray(jnp.ravel(acc1["sum_x"])[:1])
        acc2 = gram2_init(d, jnp.float32, False)
        for _ in range(n_chunks):
            acc2 = gram2_step(acc2, dev["X"], dev["mask"], mean0)
        np.asarray(jnp.ravel(acc2["G"])[:1])

    math_pass()  # compile
    t0 = time.perf_counter()
    math_pass()
    t_math = time.perf_counter() - t0

    import functools

    import jax as _jax

    @functools.partial(_jax.jit, donate_argnums=(0,))
    def _touch(acc, Xc, m):
        Xc = wire_dense(Xc)
        return acc + (Xc[0, :8].astype(jnp.float32) * m[:8]).sum()

    def ingest_pass():
        # the LIBRARY path: decode/quantize/transfer ride the same
        # prefetch + staging ring as streamed_suffstats, so the measured
        # overlap_efficiency reflects the shipped machinery (round-4
        # verdict: the serial put_chunk loop here never exercised it)
        src = GeneratorChunkSource(gen, rows, d)
        for _pass in range(2):
            acc = jnp.float32(0.0)
            guard = StreamGuard()
            with contextlib.closing(
                iter_device_chunks(
                    src, mesh, chunk_rows, np.float32,
                    need_y=False, need_w=False,
                )
            ) as chunks:
                for _, devc in chunks:
                    acc = _touch(acc, devc["X"], devc["mask"])
                    guard.tick(devc, acc)
            guard.flush(acc)

    # warm: the first _touch call pays jit trace+compile — keep that out
    # of the measured ingest leg, matching
    # the math leg's warm pass
    src_w = GeneratorChunkSource(gen, chunk_rows, d)
    accw = jnp.float32(0.0)
    for chunk in src_w.iter_chunks(chunk_rows, np.float32):
        devw = put_chunk(chunk, mesh, np.float32, wire=wire_kind)
        accw = _touch(accw, devw["X"], devw["mask"])
    np.asarray(accw)
    t0 = time.perf_counter()
    ingest_pass()
    t_ingest = time.perf_counter() - t0

    def decode_pass():
        src = GeneratorChunkSource(gen, rows, d)
        for _pass in range(2):
            for _chunk in src.iter_chunks(chunk_rows, np.float32):
                pass

    t0 = time.perf_counter()
    decode_pass()
    t_decode = time.perf_counter() - t0
    overlap = max(
        0.0, min(1.0, (t_math + t_ingest - t) / max(min(t_math, t_ingest), 1e-9))
    )

    flops = 2.0 * rows * d * d  # pass-2 Gram dominates
    stream_gb = rows * d * 4 * 2 / 1e9  # 2 passes
    # The stream fit ingests host data every chunk; when the effective
    # ingest rate is far below PCIe-class (threshold 1 GB/s), the number
    # measures the link, not the chip, and is excluded from the geomean.
    ingest_gbps = stream_gb / max(t, 1e-9)
    return {
        "samples_per_sec_per_chip": rows / t / n_chips,
        "fit_seconds": t,
        "rows": rows,
        "stream_gb": round(stream_gb, 2),
        "ingest_gbps": round(ingest_gbps, 3),
        "device_math_seconds": round(t_math, 4),
        "device_math_samples_per_sec": round(rows / max(t_math, 1e-9), 1),
        "ingest_seconds": round(t_ingest, 4),
        "decode_seconds": round(t_decode, 4),
        "overlap_efficiency": round(overlap, 3),
        "wire_dtype": wire_kind,
        "flops_model": flops,
        "baseline_samples_per_sec": 1.1e8,
        "baseline_inputs": {
            "formula": "a10g_syrk_flat_v1",
            "samples_per_sec": 1.1e8,
            "d": d,
        },
    }


SERVE_TRAIN_ROWS = int(os.environ.get("BENCH_SERVE_ROWS", 4096))
SERVE_COLS = int(os.environ.get("BENCH_SERVE_COLS", 32))
SERVE_REQUESTS = int(os.environ.get("BENCH_SERVE_REQUESTS", 60))


def bench_serving(mesh, n_chips):
    """Online-serving latency bench: small rf/pca/umap models resident
    in a ServingRuntime, driven with a mixed-shape request stream.

    Reports (a) served micro-batched throughput vs the direct
    per-request ``model.transform`` loop — the A/B the registry +
    memoized closures exist to win (per-call closure rebuilds are what
    sank rf/umap transform in round 5); (b) client-observed p50/p99
    latency under an open-loop QPS sweep; (c) a batch-window sweep.
    Every phase runs inside telemetry spans so roofline attribution
    lands on the serving sites, and the retrace contract is enforced:
    ``retrace_storms`` must read 0 after the full load, else this entry
    raises (the bench-regression gate then sees the entry missing)."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.models.tree import RandomForestClassifier
    from spark_rapids_ml_tpu.models.umap import UMAP
    from spark_rapids_ml_tpu.runtime import telemetry as tele
    from spark_rapids_ml_tpu.serving import ServingRuntime

    rng = np.random.default_rng(41)
    n, d = SERVE_TRAIN_ROWS, SERVE_COLS
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.standard_normal(n) > 0).astype(np.float32)
    df = DataFrame({"features": X, "label": y})
    umap_rows = min(n, 2048)

    t0 = time.perf_counter()
    models = {
        "rf": RandomForestClassifier(
            numTrees=8, maxDepth=6, seed=3, num_workers=1
        ).fit(df),
        "pca": PCA(k=8).fit(df),
        "umap": UMAP(
            n_neighbors=8, n_epochs=30, random_state=3, num_workers=1
        ).fit(DataFrame({"features": X[:umap_rows]})),
    }
    fit_seconds = time.perf_counter() - t0

    # mixed-shape request stream: sizes that pad, share buckets, and
    # dispatch exact; umap stays small (never coalesced, each distinct
    # shape compiles once)
    sizes = {"rf": (1, 3, 8, 17, 33, 64), "pca": (2, 5, 16, 27), "umap": (3, 8)}
    stream = []
    for fam, szs in sizes.items():
        for i in range(SERVE_REQUESTS // (3 * len(szs)) or 1):
            for s in szs:
                q = rng.standard_normal((s, d)).astype(np.float32)
                stream.append((fam, q))
    rows_total = sum(q.shape[0] for _, q in stream)

    # A: direct per-request loop — one model.transform per request, the
    # path a naive deployment runs (and what the seed tree's last chip record measured)
    per_family_direct = {}
    t0 = time.perf_counter()
    for fam, model in models.items():
        reqs = [q for f, q in stream if f == fam]
        tf = time.perf_counter()
        for q in reqs:
            model.transform(DataFrame({"features": q}))
        per_family_direct[fam] = time.perf_counter() - tf
    direct_seconds = time.perf_counter() - t0

    # B: served — same requests through the micro-batched runtime, with
    # the live ops plane attached (ephemeral port): the scrape-under-
    # load criterion is measured against THIS mixed-shape stream
    import urllib.request as _urlreq

    from spark_rapids_ml_tpu.runtime import opsplane as ops

    os.environ["TPUML_OPS_PORT"] = "0"
    scrape_ms = {"/metrics": [], "/statusz": []}

    def _scrape(path):
        addr = ops.address()
        if addr is None:
            return
        t_s = time.perf_counter()
        with _urlreq.urlopen(
            f"http://{addr[0]}:{addr[1]}{path}", timeout=30
        ) as resp:
            resp.read()
        scrape_ms[path].append((time.perf_counter() - t_s) * 1e3)

    try:
        t0 = time.perf_counter()
        with ServingRuntime(batch_window_us=2000, max_bucket_rows=64) as rt:
            for fam, model in models.items():
                rt.register(fam, model)
            warm_seconds = time.perf_counter() - t0

            per_family_served = {}
            t0 = time.perf_counter()
            for fam in models:
                reqs = [q for f, q in stream if f == fam]
                tf = time.perf_counter()
                futs = [rt.predict_async(fam, q) for q in reqs]
                # scrape while this family's requests are in flight —
                # the live-ops latency under genuine dispatch load
                _scrape("/metrics")
                _scrape("/statusz")
                for f in futs:
                    f.result(600)
                per_family_served[fam] = time.perf_counter() - tf
            served_seconds = time.perf_counter() - t0

            # open-loop QPS sweep on the rf stream (bounded: 40 requests
            # per rate), client-observed latency
            qps_sweep = {}
            q8 = rng.standard_normal((8, d)).astype(np.float32)
            for qps in (64, 256, 1024):
                # latency recorded AT RESOLUTION (done-callback fires on
                # the dispatcher thread) — collecting after the submit
                # loop would charge early requests the remaining
                # open-loop sleep time
                lat = []
                with tele.span("serve.bench.qps", qps=qps):
                    futs = []
                    for _i in range(40):
                        t_req = time.perf_counter()
                        f = rt.predict_async("rf", q8)
                        f.add_done_callback(
                            lambda _f, t=t_req: lat.append(
                                (time.perf_counter() - t) * 1e3
                            )
                        )
                        futs.append(f)
                        time.sleep(1.0 / qps)
                    for f in futs:
                        f.result(600)
                qps_sweep[str(qps)] = {
                    "p50_ms": round(float(np.percentile(lat, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat, 99)), 3),
                }

        # batch-window sweep: burst of 48 rf requests per window setting
        window_sweep = {}
        for window_us in (0, 500, 2000, 8000):
            with ServingRuntime(
                batch_window_us=window_us, max_bucket_rows=64
            ) as rt:
                rt.register("rf", models["rf"])
                lat = []
                with tele.span("serve.bench.window", window_us=window_us):
                    t_burst = time.perf_counter()
                    futs = []
                    for s in (3, 5, 8, 17) * 12:
                        f = rt.predict_async(
                            "rf",
                            rng.standard_normal((s, d)).astype(np.float32),
                        )
                        f.add_done_callback(
                            lambda _f: lat.append(
                                (time.perf_counter() - t_burst) * 1e3
                            )
                        )
                        futs.append(f)
                    for f in futs:
                        f.result(600)
            window_sweep[str(window_us)] = {
                "p50_ms": round(float(np.percentile(lat, 50)), 3),
                "p99_ms": round(float(np.percentile(lat, 99)), 3),
            }

        # C: overload sweep — offered load past measured capacity into a
        # bounded-queue runtime with a per-request deadline: graceful
        # degradation means goodput PLATEAUS past capacity (admission
        # sheds absorb the excess, typed errors at submit) instead of
        # collapsing under unbounded queue growth
        deadline_ms = 250.0  # the serving_p99_ms SLO objective
        overload_sweep = {}
        q8 = rng.standard_normal((8, d)).astype(np.float32)
        with ServingRuntime(
            batch_window_us=2000, max_bucket_rows=64, queue_limit=32
        ) as rt:
            rt.register("rf", models["rf"])
            # measured capacity: closed-loop burst (stays under the
            # queue bound), no deadline — also primes the EWMA service
            # model the deadline_unmeetable shed decision uses
            t_c = time.perf_counter()
            futs = [rt.predict_async("rf", q8) for _ in range(24)]
            for f in futs:
                f.result(600)
            capacity_qps = 24 / max(time.perf_counter() - t_c, 1e-9)
            for mult in (1, 2, 4):
                offered = capacity_qps * mult
                n_req = 96
                shed = 0
                rec = []  # (latency_ms, resolved_ok) at resolution
                futs = []
                with tele.span("serve.bench.overload", mult=mult):
                    t_s = time.perf_counter()
                    for i in range(n_req):
                        # absolute schedule: sleep granularity must not
                        # silently lower the offered rate
                        lag = t_s + i / offered - time.perf_counter()
                        if lag > 0:
                            time.sleep(lag)
                        t_req = time.perf_counter()
                        try:
                            f = rt.predict_async(
                                "rf", q8, deadline_ms=deadline_ms
                            )
                        except Exception:
                            shed += 1  # typed Overloaded at admission
                            continue
                        f.add_done_callback(
                            lambda f_, t=t_req: rec.append((
                                (time.perf_counter() - t) * 1e3,
                                f_.exception() is None,
                            ))
                        )
                        futs.append(f)
                    for f in futs:
                        try:
                            f.result(600)
                        except Exception:
                            pass  # DeadlineExceeded while queued
                    elapsed = time.perf_counter() - t_s
                ok_lat = [l for l, good in rec if good]
                missed = len(rec) - len(ok_lat)
                overload_sweep[str(mult)] = {
                    "offered_qps": round(offered, 1),
                    "goodput_qps": round(len(ok_lat) / elapsed, 1),
                    "shed_frac": round(shed / n_req, 4),
                    "deadline_missed": missed,
                    "admitted_p99_ms": (
                        round(float(np.percentile(ok_lat, 99)), 3)
                        if ok_lat else None
                    ),
                }

        # degradation gates: past-capacity goodput must hold (plateau,
        # not collapse), and what IS served must honor the deadline
        top = overload_sweep[str(4)]
        base = overload_sweep[str(1)]
        if top["goodput_qps"] <= 0 or (
            base["goodput_qps"] > 0
            and top["goodput_qps"] < 0.35 * base["goodput_qps"]
        ):
            raise RuntimeError(
                f"overload goodput collapsed past capacity: {overload_sweep}"
            )
        for mult, row in overload_sweep.items():
            p99 = row["admitted_p99_ms"]
            if p99 is not None and p99 > 1.5 * deadline_ms:
                raise RuntimeError(
                    f"admitted-request p99 {p99} ms at {mult}x offered load "
                    f"is unbounded by the {deadline_ms} ms deadline"
                )
    finally:
        ops.stop()
        os.environ.pop("TPUML_OPS_PORT", None)

    # live-scrape contract: the plane must be ABLE to answer in <50 ms
    # while the dispatcher is under load (min-of-samples: a loaded CI
    # host may stall any single scrape, but a plane that can never
    # answer fast is a real regression)
    ops_scrape_ms = {
        path.lstrip("/"): {
            "count": len(v),
            "min_ms": round(min(v), 3),
            "max_ms": round(max(v), 3),
        }
        for path, v in scrape_ms.items()
        if v
    }
    for path, st in ops_scrape_ms.items():
        if st["min_ms"] >= 50.0:
            raise RuntimeError(
                f"ops-plane /{path} never answered under 50 ms during the "
                f"mixed-shape stream: {st}"
            )

    # the hard serving gate: the whole mixed load must not have scored a
    # single retrace storm (warmup sites absorb declared compiles)
    snap = tele.metrics_snapshot()
    storms = snap.get("retrace_storms")
    n_storms = sum(s["value"] for s in storms["series"]) if storms else 0
    if n_storms:
        raise RuntimeError(
            f"serving load swept {n_storms} retrace storm(s): "
            f"{storms['series']}"
        )
    p99_series = [
        s for s in snap.get("serve_p99_ms", {}).get("series", [])
    ]
    lat_all = qps_sweep["256"]
    # mean valid-row fraction across every dispatched bucket: the
    # micro-batching efficiency number the regression gate watches
    fill_series = snap.get("serve_batch_fill", {}).get("series", [])
    fill_count = sum(s["count"] for s in fill_series)
    serve_batch_fill = (
        round(sum(s["sum"] for s in fill_series) / fill_count, 4)
        if fill_count else 0.0
    )

    # FLOP model: pca projection + rf traversal compares + umap knn
    # against the resident training table (dominant term)
    n_trees, depth = 8, 6
    per_row = {
        "pca": 2.0 * d * 8,
        "rf": float(n_trees * depth),
        "umap": 2.0 * d * umap_rows,
    }
    flops = sum(
        per_row[fam] * sum(q.shape[0] for f, q in stream if f == fam)
        for fam in models
    )
    served_rps = rows_total / served_seconds
    direct_rps = rows_total / direct_seconds
    return {
        "samples_per_sec_per_chip": served_rps / n_chips,
        "fit_seconds": served_seconds,
        "setup_fit_seconds": round(fit_seconds, 4),
        "warm_seconds": round(warm_seconds, 4),
        "rows": rows_total,
        "requests": len(stream),
        "p50_ms": lat_all["p50_ms"],
        "p99_ms": lat_all["p99_ms"],
        "serve_batch_fill": serve_batch_fill,
        "qps_sweep": qps_sweep,
        "window_sweep": window_sweep,
        "ops_scrape_ms": ops_scrape_ms,
        "retrace_storms": n_storms,
        "serve_vs_direct": {
            fam: round(
                per_family_direct[fam] / max(per_family_served[fam], 1e-9), 3
            )
            for fam in models
        },
        "flops_model": flops,
        "baseline_samples_per_sec": direct_rps / n_chips,
        "baseline_kind": "direct_transform_per_request",
        "baseline_inputs": {
            "formula": "same_process_per_request_transform_loop_v1",
            "requests": len(stream),
            "rows": rows_total,
            "direct_seconds": round(direct_seconds, 4),
            "d": d,
        },
        "p99_series_models": sorted(
            {s["labels"].get("model") for s in p99_series}
        ),
        "capacity_qps": round(capacity_qps, 1),
        "overload_sweep": overload_sweep,
        "overload_deadline_ms": deadline_ms,
        "goodput_qps": overload_sweep[str(4)]["goodput_qps"],
        "shed_frac": overload_sweep[str(4)]["shed_frac"],
    }


def bench_router(mesh, n_chips):
    """Pod-scale router bench: one light resident model replicated over
    loopback replica fleets of 1/2/4, each fleet driven at the SAME
    fixed offered load, chosen above the 4-replica aggregate admission
    capacity so every fleet size is saturated.

    The single-replica fleet runs through the SAME ``Router`` front
    door, so the A/B isolates replica count, not router overhead.

    ``replica_scaling_efficiency`` is delivered-fraction against the
    offered-load-capped ideal: ``(g4/offered) / min(1, 4*g1/offered)``
    — at saturation (a chip host, where one replica's capacity is far
    under the offered load) this is exactly ``g4/(4*g1)``; when a
    single replica already absorbs most of the offered load (this
    1-core CI box: the dispatcher consumes the queue WHILE sleeping in
    its batch window, so one replica's admission capacity tracks the
    offered rate) the ideal is capped at 1 and the metric reads how
    close the fleet gets to delivering everything offered.

    Gates (raise = entry missing = regression): scaling efficiency
    >= 0.75; fleet goodput must never DEGRADE vs one replica
    (>= 0.9x); the 4-replica fleet must shed no more than the single
    replica; admitted p99 <= 1.5x the single-replica p99; zero retrace
    storms across the whole sweep. The ISSUE-17 absolute-scaling gates
    (2-rep >= 1.7x, 4-rep >= 3x single) arm only when the offered load
    exceeds the scaling target — i.e. when fleet-1 is genuinely
    saturated and N-replica goodput is physically expressible; a
    waived arm is logged to stderr, never silent. The reported
    ``fleet_p99_ms`` is read from the MERGED fleet snapshot
    (``Router.fleet_p99_ms`` -> ``telemetry.merge_metric_snapshots``,
    pooled reservoirs), not recomputed client-side."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.runtime import telemetry as tele
    from spark_rapids_ml_tpu.serving import Router

    rng = np.random.default_rng(47)
    d = 32
    X = rng.standard_normal((2048, d)).astype(np.float32)
    t0 = time.perf_counter()
    model = PCA(k=4).fit(DataFrame({"features": X}))
    setup_fit_seconds = time.perf_counter() - t0

    # per-replica admission capacity ~= queue_limit per (window +
    # compute) cycle; the fixed offered load sits 1.5x above the
    # 4-replica aggregate so goodput measures admitted capacity at
    # every fleet size and the excess sheds typed at the front door
    window_us = 40_000
    queue_limit = 12
    deadline_ms = 250.0  # the serving_p99_ms SLO objective
    per_replica_qps = queue_limit / (window_us / 1e6)
    offered = 1.5 * 4 * per_replica_qps
    duration_s = 2.0
    n_req = int(offered * duration_s)
    q2 = rng.standard_normal((2, d)).astype(np.float32)  # coalescable
    rt_kwargs = dict(
        batch_window_us=window_us, max_bucket_rows=32,
        queue_limit=queue_limit,
    )

    fleet_sweep = {}
    elapsed4 = 0.0
    for n_rep in (1, 2, 4):
        # distinct registry name per fleet: the merged serve_p99_ms
        # series stay separable by label across the sweep
        mname = f"pca{n_rep}"
        with Router(
            replicas=n_rep, policy="p2c", runtime_kwargs=rt_kwargs
        ) as router:
            router.register(mname, model)
            # prime dispatchers + the routing EWMA below the queue bound
            for _ in range(3):
                warm = [
                    router.predict_async(mname, q2)
                    for _ in range(4 * n_rep)
                ]
                for f in warm:
                    f.result(600)
            shed = 0
            rec = []  # (latency_ms, resolved_ok) at resolution
            futs = []
            with tele.span("serve.bench.router", replicas=n_rep):
                t_s = time.perf_counter()
                for i in range(n_req):
                    # absolute schedule: sleep granularity must not
                    # silently lower the offered rate
                    lag = t_s + i / offered - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    t_req = time.perf_counter()
                    try:
                        f = router.predict_async(
                            mname, q2, deadline_ms=deadline_ms
                        )
                    except Exception:
                        shed += 1  # typed Overloaded at the front door
                        continue
                    f.add_done_callback(
                        lambda f_, t=t_req: rec.append((
                            (time.perf_counter() - t) * 1e3,
                            f_.exception() is None,
                        ))
                    )
                    futs.append(f)
                for f in futs:
                    try:
                        f.result(600)
                    except Exception:
                        pass  # DeadlineExceeded while queued
                elapsed = time.perf_counter() - t_s
            fleet_p99 = router.fleet_p99_ms().get(mname)
            drained = router.drain(30.0)
        if n_rep == 4:
            elapsed4 = elapsed
        ok_lat = [l for l, good in rec if good]
        fleet_sweep[str(n_rep)] = {
            "offered_qps": round(n_req / elapsed, 1),
            "goodput_qps": round(len(ok_lat) / elapsed, 1),
            "shed_frac": round(shed / n_req, 4),
            "deadline_missed": len(rec) - len(ok_lat),
            "admitted_p99_ms": (
                round(float(np.percentile(ok_lat, 99)), 3)
                if ok_lat else None
            ),
            "fleet_p99_ms": (
                None if fleet_p99 is None else round(fleet_p99, 3)
            ),
            "drained": bool(drained["drained"]),
        }

    g1 = fleet_sweep["1"]["goodput_qps"]
    g2 = fleet_sweep["2"]["goodput_qps"]
    g4 = fleet_sweep["4"]["goodput_qps"]
    if g1 <= 0 or g2 < 0.9 * g1 or g4 < 0.9 * g1:
        raise RuntimeError(
            f"fleet goodput DEGRADED vs one replica at fixed "
            f"{offered:.0f} qps offered: 1->{g1} 2->{g2} 4->{g4} "
            f"(router spreading must never cost throughput): "
            f"{fleet_sweep}"
        )
    # absolute-scaling gates arm only where N-replica goodput is
    # physically expressible: the target must sit under the offered
    # load (on a saturated chip host it does; on this box one replica
    # absorbs most of the offered rate and the arm is logged, not
    # silently skipped)
    for n_rep, factor, g_n in (("2", 1.7, g2), ("4", 3.0, g4)):
        target = factor * g1
        if target <= offered:
            if g_n < target:
                raise RuntimeError(
                    f"replica scaling collapsed: {n_rep}-replica "
                    f"goodput {g_n} qps under the armed {factor}x "
                    f"single-replica target {target:.0f} qps: "
                    f"{fleet_sweep}"
                )
        else:
            print(
                f"[bench] router: {factor}x scaling gate waived — "
                f"target {target:.0f} qps exceeds the {offered:.0f} "
                f"qps offered load (single replica absorbs "
                f"{g1 / offered:.0%} of it on this host)",
                file=sys.stderr,
            )
    eff = (g4 / offered) / min(1.0, 4 * g1 / offered)
    if eff < 0.75:
        raise RuntimeError(
            f"replica scaling efficiency {eff:.3f} under 0.75 "
            f"(goodput vs the offered-load-capped 4-replica ideal): "
            f"{fleet_sweep}"
        )
    if fleet_sweep["4"]["shed_frac"] > fleet_sweep["1"]["shed_frac"]:
        raise RuntimeError(
            f"4-replica fleet shed MORE than one replica at the same "
            f"offered load: {fleet_sweep}"
        )
    p99_1 = fleet_sweep["1"]["admitted_p99_ms"]
    for n_rep in ("2", "4"):
        p99_n = fleet_sweep[n_rep]["admitted_p99_ms"]
        if p99_1 and p99_n and p99_n > 1.5 * p99_1:
            raise RuntimeError(
                f"admitted p99 at {n_rep} replicas ({p99_n} ms) blew "
                f"1.5x the single-replica p99 ({p99_1} ms): "
                f"{fleet_sweep}"
            )

    # the serving retrace contract holds fleet-wide: the whole sweep
    # (3 fleets x warmup + saturation) must not have scored one storm
    snap = tele.metrics_snapshot()
    storms = snap.get("retrace_storms")
    n_storms = sum(s["value"] for s in storms["series"]) if storms else 0
    if n_storms:
        raise RuntimeError(
            f"router load swept {n_storms} retrace storm(s): "
            f"{storms['series']}"
        )

    rows_per_req = int(q2.shape[0])
    ok4 = int(round(g4 * elapsed4))
    top = fleet_sweep["4"]
    return {
        "samples_per_sec_per_chip": g4 * rows_per_req / n_chips,
        "fit_seconds": elapsed4,
        "setup_fit_seconds": round(setup_fit_seconds, 4),
        "requests": n_req,
        "rows": ok4 * rows_per_req,
        "replicas": 4,
        "policy": "p2c",
        "offered_qps": round(offered, 1),
        "capacity_qps": g1,  # measured through the same front door
        "aggregate_goodput_qps": g4,
        "goodput_qps": g4,
        "shed_frac": top["shed_frac"],
        "replica_scaling_efficiency": round(eff, 4),
        "p99_ms": top["admitted_p99_ms"],
        "fleet_p99_ms": top["fleet_p99_ms"],
        "fleet_sweep": fleet_sweep,
        "retrace_storms": n_storms,
        # pca projection flops on the rows that actually served (4-rep)
        "flops_model": 2.0 * d * 4 * ok4 * rows_per_req,
        "baseline_samples_per_sec": g1 * rows_per_req / n_chips,
        "baseline_kind": "single_replica_router",
        "baseline_inputs": {
            "formula": "same_router_one_replica_fixed_offered_load_v1",
            "offered_qps": round(offered, 1),
            "queue_limit": queue_limit,
            "batch_window_us": window_us,
            "deadline_ms": deadline_ms,
            "rows_per_request": rows_per_req,
        },
    }


def bench_fit_sched(mesh, n_chips):
    """Multi-tenant fit-scheduler bench: many small same-shape KMeans
    fits driven through a :class:`FitScheduler`.

    Reports (a) scheduled closed-loop capacity (``fits_per_sec``)
    against the direct sequential ``.fit()`` loop — pack-compatible
    jobs gang through one coscheduled preprocess, so the scheduler
    should at worst break even and win once a backlog forms; (b) an
    open-loop arrival sweep at 1x/2x/4x measured capacity into a
    bounded queue with a per-fit deadline — graceful degradation means
    goodput plateaus past capacity (typed ``Overloaded`` sheds at
    submit, ``DeadlineExceeded`` in the backlog) while admitted fits
    keep a bounded client-observed p99. Hard gates: the swept load must
    score zero new retrace storms (same shapes => one compile), the 4x
    goodput must hold >= 35% of the 1x goodput, and every future must
    resolve (drain reports zero aborts)."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.runtime import FitScheduler, telemetry as tele

    rng = np.random.default_rng(47)
    n, d, k, iters = 1024, 8, 4, 4
    n_fits = int(os.environ.get("BENCH_SCHED_FITS", 12))
    X = rng.standard_normal((n, d)).astype(np.float32)
    df = DataFrame({"features": X})

    def make():
        return KMeans(k=k, maxIter=iters, seed=3, num_workers=n_chips)

    def _storms():
        s = tele.metrics_snapshot().get("retrace_storms")
        return sum(row["value"] for row in s["series"]) if s else 0

    make().fit(df)  # warm the compile cache outside every timed phase
    storms_base = _storms()

    # baseline: the direct sequential fit loop a naive tenant runs
    t0 = time.perf_counter()
    for _ in range(n_fits):
        make().fit(df)
    direct_seconds = time.perf_counter() - t0
    direct_fps = n_fits / direct_seconds

    # capacity: the same fits submitted at once — the backlog gangs
    # through one coscheduled preprocess; also primes the EWMA the
    # deadline shed decision uses
    with tele.span("sched.bench.capacity", fits=n_fits):
        with FitScheduler() as sched:
            t0 = time.perf_counter()
            futs = [
                sched.submit(make(), df, tenant=f"t{i % 4}")
                for i in range(n_fits)
            ]
            for f in futs:
                f.result(600)
            fit_seconds = time.perf_counter() - t0
            cap_stats = sched.stats()
    capacity_fps = n_fits / fit_seconds

    # open-loop arrival sweep: offered fit rate past capacity into a
    # bounded queue with a deadline; latency recorded AT RESOLUTION
    mean_fit_ms = 1e3 * fit_seconds / n_fits
    deadline_ms = max(8.0 * mean_fit_ms, 50.0)
    arrival_sweep = {}
    for mult in (1, 2, 4):
        offered = capacity_fps * mult
        n_req = max(2 * n_fits, 16)
        shed = 0
        rec = []  # (latency_ms, resolved_ok) at resolution
        with tele.span("sched.bench.arrival", mult=mult):
            with FitScheduler(queue_limit=8) as sched:
                futs = []
                t_s = time.perf_counter()
                for i in range(n_req):
                    # absolute schedule: sleep granularity must not
                    # silently lower the offered rate
                    lag = t_s + i / offered - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    t_req = time.perf_counter()
                    try:
                        f = sched.submit(
                            make(), df, tenant=f"t{i % 4}",
                            deadline_ms=deadline_ms,
                        )
                    except Exception:
                        shed += 1  # typed Overloaded at admission
                        continue
                    f.add_done_callback(
                        lambda f_, t=t_req: rec.append((
                            (time.perf_counter() - t) * 1e3,
                            f_.exception() is None,
                        ))
                    )
                    futs.append(f)
                for f in futs:
                    try:
                        f.result(600)
                    except Exception:
                        pass  # DeadlineExceeded while queued
                elapsed = time.perf_counter() - t_s
                report = sched.drain(timeout=60)
        if report["aborted"]:
            raise RuntimeError(
                f"fit_sched drain left {report['aborted']} future(s) "
                f"unresolved at {mult}x offered load"
            )
        ok_lat = [l for l, good in rec if good]
        arrival_sweep[str(mult)] = {
            "offered_fps": round(offered, 2),
            "goodput_fps": round(len(ok_lat) / elapsed, 2),
            "shed_frac": round(shed / n_req, 4),
            "deadline_missed": len(rec) - len(ok_lat),
            "fit_p50_ms": (
                round(float(np.percentile(ok_lat, 50)), 3) if ok_lat else None
            ),
            "fit_p99_ms": (
                round(float(np.percentile(ok_lat, 99)), 3) if ok_lat else None
            ),
        }

    # degradation gate: goodput past capacity must plateau, not collapse
    top, base = arrival_sweep["4"], arrival_sweep["1"]
    if top["goodput_fps"] <= 0 or (
        base["goodput_fps"] > 0
        and top["goodput_fps"] < 0.35 * base["goodput_fps"]
    ):
        raise RuntimeError(
            f"fit_sched goodput collapsed past capacity: {arrival_sweep}"
        )
    # retrace gate: same-shape fits through the scheduler must not have
    # swept a single NEW storm across the whole load
    new_storms = _storms() - storms_base
    if new_storms:
        raise RuntimeError(
            f"fit_sched load swept {new_storms} retrace storm(s)"
        )

    # FLOP model: lloyd assignment distances dominate each fit
    per_fit = 2.0 * n * d * k * iters
    rows_total = n * n_fits
    return {
        "samples_per_sec_per_chip": rows_total / fit_seconds / n_chips,
        "fit_seconds": fit_seconds,
        "rows": rows_total,
        "fits": n_fits,
        "fits_per_sec": round(capacity_fps, 3),
        "fit_p50_ms": arrival_sweep["1"]["fit_p50_ms"],
        "fit_p99_ms": arrival_sweep["1"]["fit_p99_ms"],
        "shed_frac": arrival_sweep["4"]["shed_frac"],
        "goodput_qps": arrival_sweep["4"]["goodput_fps"],
        "sched_occupancy": cap_stats["occupancy"],
        "arrival_sweep": arrival_sweep,
        "arrival_deadline_ms": round(deadline_ms, 1),
        "retrace_storms": new_storms,
        "flops_model": per_fit * n_fits,
        "baseline_samples_per_sec": rows_total / direct_seconds / n_chips,
        "baseline_kind": "direct_sequential_fit_loop",
        "baseline_inputs": {
            "formula": "same_process_sequential_fit_loop_v1",
            "fits": n_fits,
            "rows": rows_total,
            "direct_seconds": round(direct_seconds, 4),
            "direct_fits_per_sec": round(direct_fps, 3),
            "n": n, "d": d, "k": k, "iters": iters,
        },
    }


def bench_lifecycle(mesh, n_chips):
    """Continuous-training lifecycle bench: sustained closed-loop QPS
    through >= 3 consecutive versioned hot-swaps, plus the canary
    re-flip (rollback) latency.

    Reports the client-observed p99 during the swap windows against the
    steady-state p99 (``swap_p99_delta_ms``) and the time a rollback
    takes to re-flip the live version (``rollback_ms``). Hard gates —
    the zero-downtime contract: zero typed sheds and zero new retrace
    storms across every flip, every version lands (v4 resident at the
    end), and the during-swap p99 must stay within 15% of steady state
    (small absolute floor for sub-ms noise), else this entry raises and
    the bench-regression gate sees it missing."""
    import threading

    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.runtime import telemetry as tele
    from spark_rapids_ml_tpu.serving import ModelLifecycle, ServingRuntime

    rng = np.random.default_rng(53)
    n, d, k = 2048, 16, 8
    n_swaps = int(os.environ.get("BENCH_LIFECYCLE_SWAPS", 3))
    X = rng.standard_normal((n, d)).astype(np.float32)
    df = DataFrame({"features": X})

    t0 = time.perf_counter()
    # v1 + swap candidates fitted on the same data with the same params:
    # served outputs stay identical, so any latency delta is pure swap
    # machinery (stage+warm beside live, atomic flip, evict)
    versions = [PCA(k=k).fit(df) for _ in range(1 + n_swaps)]
    other = rng.standard_normal((n, d)).astype(np.float32)
    divergent = PCA(k=k).fit(DataFrame({"features": other}))
    fit_seconds = time.perf_counter() - t0

    def _metric_total(name):
        s = tele.metrics_snapshot().get(name)
        return sum(row["value"] for row in s["series"]) if s else 0

    storms_base = _metric_total("retrace_storms")
    sheds_base = _metric_total("serve_shed_total")

    sizes = (3, 8, 17, 33)
    queries = [
        rng.standard_normal((s, d)).astype(np.float32) for s in sizes
    ]

    # baseline: the direct per-request transform loop a deployment
    # without the resident registry runs (no hot-swap possible there
    # short of a process restart)
    t0 = time.perf_counter()
    for i in range(64):
        versions[0].transform(DataFrame({"features": queries[i % 4]}))
    direct_seconds = time.perf_counter() - t0
    direct_rows = sum(q.shape[0] for q in queries) * 16

    lat_steady, lat_swap = [], []  # (latency_ms, rows) at resolution
    phase = {"buf": lat_steady}
    stop = threading.Event()
    errors = []

    with ServingRuntime(batch_window_us=2000, max_bucket_rows=64) as rt:
        rt.register("pca", versions[0])
        lc = ModelLifecycle(rt)

        def client(tid):
            i = tid
            while not stop.is_set():
                q = queries[i % len(queries)]
                t_r = time.perf_counter()
                try:
                    rt.predict("pca", q, timeout=600)
                except Exception as e:  # typed shed = gate failure
                    errors.append(e)
                    return
                phase["buf"].append(
                    ((time.perf_counter() - t_r) * 1e3, q.shape[0])
                )
                i += 1

        swap_ms = []
        t_serve = time.perf_counter()
        threads = [
            threading.Thread(target=client, args=(t,)) for t in range(3)
        ]
        for t in threads:
            t.start()
        try:
            time.sleep(1.0)  # steady-state window
            for v, model in enumerate(versions[1:], start=2):
                phase["buf"] = lat_swap
                t_s = time.perf_counter()
                with tele.span("serve.bench.swap", version=v):
                    lc.swap("pca", model=model)
                swap_ms.append((time.perf_counter() - t_s) * 1e3)
                time.sleep(0.2)  # tail of the swap window
                phase["buf"] = lat_steady
                time.sleep(0.5)  # recover between consecutive swaps
        finally:
            stop.set()
            for t in threads:
                t.join(120)
        serve_seconds = time.perf_counter() - t_serve

        if errors:
            raise RuntimeError(
                f"lifecycle load took a typed shed under swap: {errors[0]!r}"
            )
        final = rt.registry.get("pca")
        if final.version != 1 + n_swaps or rt.registry.names() != ["pca"]:
            raise RuntimeError(
                f"swap ladder did not land a single consistent version: "
                f"v{final.version}, resident={rt.registry.names()}"
            )

        # rollback latency: a mirrored canary re-flipped to the live
        # version (shadow route cleared + candidate evicted + breaker)
        lc.start_canary(
            "pca", model=divergent, fraction=1.0, min_requests=10**6
        )
        rt.predict("pca", queries[1], timeout=600)
        t_r = time.perf_counter()
        lc.rollback("pca", reason="manual")
        rollback_ms = (time.perf_counter() - t_r) * 1e3
        lc.drain(timeout=30)

    new_storms = _metric_total("retrace_storms") - storms_base
    if new_storms:
        raise RuntimeError(
            f"lifecycle load swept {new_storms} retrace storm(s)"
        )
    new_sheds = _metric_total("serve_shed_total") - sheds_base
    if new_sheds:
        raise RuntimeError(
            f"lifecycle load shed {new_sheds} request(s) across the flips"
        )

    steady = np.array([ms for ms, _ in lat_steady])
    swapw = np.array([ms for ms, _ in lat_swap])
    if steady.size < 16 or swapw.size < 4:
        raise RuntimeError(
            f"lifecycle load under-sampled: steady={steady.size} "
            f"swap={swapw.size}"
        )
    steady_p99 = float(np.percentile(steady, 99))
    swap_p99 = float(np.percentile(swapw, 99))
    # the 15% zero-downtime latency gate; the absolute floor absorbs
    # host-side warm-compile CPU contention on the CPU backend, where
    # the bucket-ladder compiles and the serving compute share cores
    # (on an accelerator device compute is unaffected and the relative
    # bound is the binding one) — a retrace storm or a blocked flip
    # shows up as a 100ms+ delta and still trips it
    if swap_p99 > max(1.15 * steady_p99, steady_p99 + 10.0):
        raise RuntimeError(
            f"hot-swap disturbed the tail: during-swap p99 "
            f"{swap_p99:.3f}ms vs steady {steady_p99:.3f}ms (>15%)"
        )

    rows_served = int(
        sum(r for _, r in lat_steady) + sum(r for _, r in lat_swap)
    )
    return {
        "samples_per_sec_per_chip": rows_served / serve_seconds / n_chips,
        "fit_seconds": fit_seconds,
        "rows": rows_served,
        "swaps": len(swap_ms),
        "swap_ms": [round(m, 3) for m in swap_ms],
        "p50_ms": round(float(np.percentile(steady, 50)), 3),
        "p99_ms": round(steady_p99, 3),
        "swap_p99_ms": round(swap_p99, 3),
        "swap_p99_delta_ms": round(max(0.0, swap_p99 - steady_p99), 3),
        "rollback_ms": round(rollback_ms, 3),
        "retrace_storms": new_storms,
        "flops_model": 2.0 * rows_served * d * k,
        "baseline_samples_per_sec": direct_rows / direct_seconds / n_chips,
        "baseline_kind": "direct_transform_loop",
        "baseline_inputs": {
            "formula": "per_request_model_transform_loop_v1",
            "requests": 64,
            "rows": direct_rows,
            "direct_seconds": round(direct_seconds, 4),
            "n": n, "d": d, "k": k,
        },
    }


def bench_autotune(mesh, n_chips):
    """Measured-autotuner A/B: tuned-vs-default on three legs (rf tree
    batch, pca_stream stage depth, serving batch window).

    Per leg: (1) resolve the heuristic default and measure it, (2) run
    the probe search over the knob's candidate grid with
    ``autotune.probe`` — each candidate measured by a short dispatch of
    the real work — writing the winner into the tuning cache, (3)
    re-run with ``TPUML_AUTOTUNE=on`` (cache-warm: zero probes,
    asserted) and measure the tuned config. ``tuned_vs_default`` is
    tuned throughput over default throughput; when the search keeps the
    heuristic default the leg reports exactly 1.0 WITHOUT re-measuring
    (same config — a noisy re-measure would just launder timer jitter
    into a fake win/loss) and the provenance shows the tuner returning
    the default. The entry-level ``tuned_vs_default`` is the MINIMUM
    over legs — the regression gate bites on the worst knob, not an
    average that can hide one.

    On CPU the ratios measure the host (``host_only`` flags them);
    the search mechanics — default measured first, budget bound, warm
    cache answering with zero probes — are asserted here either way."""
    import shutil
    import tempfile

    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.data.chunks import GeneratorChunkSource
    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.models.tree import RandomForestClassifier
    from spark_rapids_ml_tpu.ops.streaming import streamed_suffstats
    from spark_rapids_ml_tpu.runtime import autotune, telemetry
    from spark_rapids_ml_tpu.serving import ServingRuntime

    @contextlib.contextmanager
    def env(**kv):
        old = {k: os.environ.get(k) for k in kv}
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        try:
            yield
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    cache_dir = os.environ.get("BENCH_AUTOTUNE_CACHE")
    tmp_cache = None
    if not cache_dir:
        tmp_cache = tempfile.mkdtemp(prefix="tpuml-autotune-bench-")
        cache_dir = tmp_cache
    reps = int(os.environ.get("BENCH_AUTOTUNE_REPS", 2))
    # the library default budget (2 s) is sized for in-situ micro-probes;
    # these legs dispatch whole fits per candidate, so give the search
    # room — it is still a hard wall-clock stop, just a bench-sized one
    budget_ms = float(os.environ.get("BENCH_AUTOTUNE_BUDGET_MS", 60_000))
    legs = {}
    t_total0 = time.perf_counter()

    def _timed(fn):
        """min-of-reps wall seconds (min: least-noise point estimate)."""
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    def _leg(name, knob, default_value, heuristic_key, candidates,
             run_default, run_tuned, measure, rows):
        """Shared leg harness: measure default, probe, measure tuned.

        ``run_tuned`` does one tuned pass and RETURNS the decision list
        that pass produced (fit reports for estimators, a collect()
        scope for direct calls) — the fit loop runs its own nested
        collector, so an outer collect() around an estimator fit sees
        nothing."""
        t_default = _timed(run_default)
        with env(TPUML_AUTOTUNE="on", TPUML_AUTOTUNE_CACHE=cache_dir):
            autotune.reset_autotune()
            decision = autotune.probe(
                knob, heuristic_key, candidates, measure,
                reps=reps, budget_ms=budget_ms,
            )
        if decision.value == default_value:
            t_tuned = t_default  # identical config: exactly 1.0
            ratio = 1.0
        else:
            with env(TPUML_AUTOTUNE="on", TPUML_AUTOTUNE_CACHE=cache_dir):
                autotune.reset_autotune()
                probes_before = _autotune_probe_count()
                t_tuned = None
                tuned_decisions = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    tuned_decisions = run_tuned()
                    dt = time.perf_counter() - t0
                    t_tuned = dt if t_tuned is None else min(t_tuned, dt)
                # warm-cache contract: the tuned run must answer from
                # the cache the probe just wrote — zero new searches
                if _autotune_probe_count() != probes_before:
                    raise RuntimeError(
                        f"{name}: tuned run probed on a warm cache"
                    )
                if not any(
                    d["knob"] == knob and d["provenance"] == "cache_hit"
                    for d in tuned_decisions
                ):
                    raise RuntimeError(
                        f"{name}: tuned run did not consult the cache "
                        f"(decisions: {tuned_decisions})"
                    )
            ratio = t_default / max(t_tuned, 1e-9)
        legs[name] = {
            "knob": knob,
            "default": default_value,
            "tuned": decision.value,
            "default_seconds": round(t_default, 4),
            "tuned_seconds": round(t_tuned, 4),
            "tuned_vs_default": round(ratio, 4),
            "probe_ms": round(decision.probe_ms or 0.0, 1),
            "candidates": len(candidates),
            "rows": rows,
        }
        return ratio

    def _autotune_probe_count():
        snap = telemetry.metrics_snapshot().get("autotune_probes_total")
        return sum(r["value"] for r in snap["series"]) if snap else 0

    # --- leg 1: rf tree batch (consult-only knob; bench is the prober) ---
    rng = np.random.default_rng(11)
    n_rf = int(os.environ.get("BENCH_AUTOTUNE_RF_ROWS", 4096))
    d_rf = 32
    X = rng.standard_normal((n_rf, d_rf)).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    df = DataFrame({"features": X, "label": y})
    n_trees = 8

    def rf_fit(width):
        with env(
            TPUML_AUTOTUNE=None,
            TPUML_RF_TREE_BATCH=(width if width is not None else "auto"),
        ):
            RandomForestClassifier(
                numTrees=n_trees, maxDepth=6, seed=3, num_workers=1
            ).fit(df)

    def rf_tuned():
        m = RandomForestClassifier(
            numTrees=n_trees, maxDepth=6, seed=3, num_workers=1
        ).fit(df)
        return (m._fit_report or {}).get("autotuned", [])

    rf_fit(None)  # warm the compile caches off the clock
    # the key + heuristic width exactly as the resolver derives them: a
    # cold tuned fit files a heuristic-provenance decision carrying both
    with env(TPUML_AUTOTUNE="on", TPUML_AUTOTUNE_CACHE=cache_dir):
        autotune.reset_autotune()
        cold = rf_tuned()
    rf_dec = next(d for d in cold if d["knob"] == "rf_tree_batch")
    rf_default = rf_dec["value"]
    group = n_trees  # single worker: the whole forest is one group
    widths = [rf_default] + [
        w for w in (1, 2, 4, 8) if group % w == 0 and w != rf_default
    ]

    def rf_measure(width):
        rf_fit(width)  # one compile per width rides the probe budget
        return _timed(lambda: rf_fit(width))

    r_rf = _leg(
        "rf", "rf_tree_batch", rf_default, rf_dec["key"], widths,
        lambda: rf_fit(None),
        rf_tuned,
        rf_measure, n_rf * n_trees,
    )

    # --- leg 2: pca_stream stage depth (consult-only; bench probes) ------
    n_dp = mesh.shape["dp"]
    chunk_rows = max(n_dp, (int(
        os.environ.get("BENCH_AUTOTUNE_STREAM_CHUNK", 8192)
    ) // n_dp) * n_dp)
    n_chunks = int(os.environ.get("BENCH_AUTOTUNE_STREAM_CHUNKS", 8))
    d_s = 64
    block = rng.standard_normal((chunk_rows, d_s), dtype=np.float32)

    def gen(start, count, seed):
        return block[:count], None

    def stream_run(depth):
        with env(
            TPUML_AUTOTUNE=None,
            TPUML_STREAM_STAGE_DEPTH=depth,
        ):
            src = GeneratorChunkSource(gen, n_chunks * chunk_rows, d_s)
            streamed_suffstats(
                src, mesh, chunk_rows, np.float32, with_y=False
            )

    def stream_tuned():
        # no env wrapper: runs under the caller's TPUML_AUTOTUNE=on so
        # the depth consult answers from the cache the probe wrote
        with autotune.collect() as ds:
            src = GeneratorChunkSource(gen, n_chunks * chunk_rows, d_s)
            streamed_suffstats(src, mesh, chunk_rows, np.float32, with_y=False)
        return ds

    stream_run(None)  # warm compile
    with env(TPUML_AUTOTUNE="on", TPUML_AUTOTUNE_CACHE=cache_dir):
        autotune.reset_autotune()
        cold = stream_tuned()
    sd_dec = next(d for d in cold if d["knob"] == "stream_stage_depth")
    sd_default = sd_dec["value"]
    depths = [sd_default] + [
        c for c in (0, 1, 2, 4) if c != sd_default
    ]

    r_stream = _leg(
        "pca_stream", "stream_stage_depth", sd_default, sd_dec["key"],
        depths,
        lambda: stream_run(None),
        stream_tuned,
        lambda c: _timed(lambda: stream_run(c)),
        n_chunks * chunk_rows,
    )

    # --- leg 3: serving batch window (consult-only; bench probes) --------
    n_sv, d_sv = 512, 16
    Xs = rng.standard_normal((n_sv, d_sv)).astype(np.float32)
    pca_model = PCA(k=4).fit(DataFrame({"features": Xs}))
    sizes = (1, 3, 8, 16)
    queries = [
        rng.standard_normal((s, d_sv)).astype(np.float32) for s in sizes
    ] * 8
    serve_rows = sum(q.shape[0] for q in queries)

    def serve_run(window):
        with env(
            TPUML_AUTOTUNE=None,
            TPUML_SERVE_BATCH_WINDOW_US=window,
        ):
            with ServingRuntime(
                batch_window_us=window, warmup=False
            ) as rt:
                rt.register("pca", pca_model)
                for q in queries:
                    rt.predict("pca", q, timeout=180)

    def serve_tuned():
        with autotune.collect() as ds:
            rt = ServingRuntime(warmup=False)
        with rt:
            rt.register("pca", pca_model)
            for q in queries:
                rt.predict("pca", q, timeout=180)
        return ds

    serve_run(None)  # warm compile
    with env(TPUML_AUTOTUNE="on", TPUML_AUTOTUNE_CACHE=cache_dir):
        autotune.reset_autotune()
        with autotune.collect() as cold:
            sv = ServingRuntime(warmup=False)
            sv.close()
    sv_dec = next(d for d in cold if d["knob"] == "serve_batch_window_us")
    sv_default = sv_dec["value"]
    windows = [sv_default] + [
        w for w in (0, 100, 500, 2000) if w != sv_default
    ]

    r_serving = _leg(
        "serving", "serve_batch_window_us", sv_default, sv_dec["key"],
        windows,
        lambda: serve_run(None),
        serve_tuned,
        lambda w: _timed(lambda: serve_run(w)),
        serve_rows,
    )

    if tmp_cache:
        shutil.rmtree(tmp_cache, ignore_errors=True)

    total_seconds = time.perf_counter() - t_total0
    ratios = [r_rf, r_stream, r_serving]
    # headline throughput: the tuned rf leg (rows x trees / tuned time);
    # baseline = the default config, so vs_baseline == the rf leg's ratio
    rf_leg = legs["rf"]
    return {
        "fit_seconds": rf_leg["tuned_seconds"],
        "samples_per_sec_per_chip": (
            rf_leg["rows"] / rf_leg["tuned_seconds"] / n_chips
        ),
        "baseline_samples_per_sec": (
            rf_leg["rows"] / rf_leg["default_seconds"] / n_chips
        ),
        "baseline_kind": "heuristic_default_config",
        "flops_model": float(n_rf) * d_rf * 6 * n_trees * 2,
        "tuned_vs_default": round(min(ratios), 4),
        "legs": legs,
        "total_seconds": round(total_seconds, 2),
        "budget_ms_per_search": budget_ms,
    }


def main() -> None:
    global N_ROWS, CSIZE
    import jax

    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    # persistent compile cache (the one rule in utils/platform.py): the RF
    # depth-13 program dominates compile time
    enable_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu" and _platform != "cpu":
        # a measuring run needs the chip: it does not carry on on the host
        sys.exit(
            f"[bench] no TPU found (jax sees {devices[0].platform!r}); pass "
            "--platform cpu for an explicit host-only run at scaled-down shapes"
        )
    n_chips = len(devices)
    peak = _chip_peak_flops(devices[0])
    if devices[0].platform == "cpu" and "BENCH_ROWS" not in os.environ:
        # an explicit --platform cpu run at the accelerator row count would
        # blow any time budget (kmeans k=1024 over millions of rows); scale
        # down unless the caller pinned a size explicitly
        N_ROWS = min(N_ROWS, 50_000)
        CSIZE = _csize(N_ROWS)
        global RF_ROWS, RF_TREES, RF_DEPTH, KNN_QUERIES, KNN_ITEMS, UMAP_ROWS
        global ANN_ROWS, ANN_QUERIES, GBT_ROWS, GBT_ROUNDS, GBT_DEPTH
        if "BENCH_UMAP_ROWS" not in os.environ:
            UMAP_ROWS = 2048
        if "BENCH_KNN_QUERIES" not in os.environ:
            KNN_QUERIES = 512
        if "BENCH_KNN_ITEMS" not in os.environ:
            KNN_ITEMS = 8192
        if "BENCH_ANN_ROWS" not in os.environ:
            ANN_ROWS = 8192
        if "BENCH_ANN_QUERIES" not in os.environ:
            ANN_QUERIES = 512
        if "BENCH_RF_ROWS" not in os.environ:
            RF_ROWS = 8192
        if "BENCH_RF_TREES" not in os.environ:
            RF_TREES = 4
        if "BENCH_RF_DEPTH" not in os.environ:
            RF_DEPTH = 8
        if "BENCH_GBT_ROWS" not in os.environ:
            GBT_ROWS = 8192
        if "BENCH_GBT_ROUNDS" not in os.environ:
            GBT_ROUNDS = 4
        if "BENCH_GBT_DEPTH" not in os.environ:
            GBT_DEPTH = 5
        print(
            f"[bench] cpu device: reducing N_ROWS to {N_ROWS}, "
            f"rf to {RF_TREES}x{RF_ROWS}x depth {RF_DEPTH} "
            "(set BENCH_ROWS / BENCH_RF_ROWS to override)",
            file=sys.stderr,
        )

    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_chips)

    # X-free entries run FIRST: umap and pca_stream never touch the
    # shared design matrix, and next to the resident ~12.3 GB X they
    # RESOURCE_EXHAUST the chip (observed round 4). Generation happens
    # lazily at the first entry that needs X — INSIDE that entry's
    # watchdog deadline, which the 1200 s default absorbs (~80 s gen).
    # Entries run on watchdog worker threads, so access is locked, the
    # triple is assigned atomically (an abandoned worker must never
    # expose a half-built dict), and a generation failure is cached so
    # later entries fail fast instead of re-running a doomed multi-
    # minute generation each.
    import threading

    _ds: dict = {}
    _ds_lock = threading.Lock()
    _ds_evt = threading.Event()

    def _X():
        # Claim-then-generate OUTSIDE the lock: the multi-minute generation
        # must not hold _ds_lock — if the watchdog abandons the generating
        # worker, later entries would block on the lock and trip their own
        # watchdogs too instead of failing fast; with the Event they wait
        # bounded-by-their-watchdog, and if the abandoned thread's
        # generation eventually completes they proceed normally.
        with _ds_lock:
            lead = not _ds.get("claimed")
            _ds["claimed"] = True
        if lead:
            try:
                # Generate the design matrix ON DEVICE (no host generation
                # and transfer of gigabytes before the first entry can
                # start). Padded rows get random values and a zero
                # mask — kernels mask them out.
                out = _gen_dataset(mesh, N_ROWS, seed=0)
                with _ds_lock:
                    _ds["all"] = out
            except Exception as e:  # noqa: BLE001
                with _ds_lock:
                    _ds["err"] = repr(e)
            finally:
                _ds_evt.set()
        else:
            _ds_evt.wait()
        with _ds_lock:
            if "err" in _ds:
                raise RuntimeError(
                    f"dataset generation already failed: {_ds['err']}"
                )
            return _ds["all"]

    runs = {
        "umap": lambda: bench_umap(mesh, n_chips),
        "ann": lambda: bench_ann(mesh, n_chips),
        "pca_stream": lambda: bench_pca_stream(mesh, n_chips),
        "serving": lambda: bench_serving(mesh, n_chips),
        "router": lambda: bench_router(mesh, n_chips),
        "fit_sched": lambda: bench_fit_sched(mesh, n_chips),
        "lifecycle": lambda: bench_lifecycle(mesh, n_chips),
        "autotune": lambda: bench_autotune(mesh, n_chips),
        "pca": lambda: bench_pca(*_X()[:2], mesh, n_chips),
        "kmeans": lambda: bench_kmeans(*_X()[:2], mesh, n_chips),
        "logreg": lambda: bench_logreg(*_X(), mesh, n_chips),
        "logreg_multi": lambda: bench_logreg_multi(*_X(), mesh, n_chips),
        "linreg": lambda: bench_linreg(*_X(), mesh, n_chips),
        "rf": lambda: bench_rf(*_X(), mesh, n_chips),
        "gbt": lambda: bench_gbt(*_X(), mesh, n_chips),
        "knn": lambda: bench_knn(*_X()[:2], mesh, n_chips),
    }
    # BENCH_ONLY=rf,kmeans : run a subset (tuning loops); full runs only
    # for the recorded metric
    only = os.environ.get("BENCH_ONLY")
    if only:
        keep = {s.strip() for s in only.split(",") if s.strip()}
        unknown = keep - set(runs)
        if unknown:
            sys.exit(f"BENCH_ONLY names unknown entries: {sorted(unknown)}")
        if not keep:
            sys.exit(f"BENCH_ONLY={only!r} selects no entries")
        runs = {k: v for k, v in runs.items() if k in keep}
    from spark_rapids_ml_tpu.utils.profiling import trace

    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    results = {}
    watchdog_tripped = []
    failed = []  # entries that raised: the run still reports, and exits 1
    meta = {
        "device": getattr(devices[0], "device_kind", "cpu"),
        "platform": devices[0].platform,
        # timings taken inside an active trace carry profiler overhead —
        # not comparable with unprofiled runs
        "profiled": bool(profile_dir),
        "n_chips": n_chips,
        "n_rows": N_ROWS,
        "n_cols": N_COLS,
    }
    # live references for the SIGTERM handler: an external timeout kill
    # mid-run still emits the entries that already finished
    _PARTIAL.update(
        results=results, meta=meta, tripped=watchdog_tripped, emitted=False
    )
    from spark_rapids_ml_tpu.runtime import counters as _res_counters
    from spark_rapids_ml_tpu.runtime import telemetry as _telemetry

    for name, fn in runs.items():
        for attempt in (0, 1):
            try:
                res_base = _res_counters.snapshot()
                tele_base = _telemetry.span_stats()
                # per-algo TensorBoard profile capture when requested
                with trace(
                    os.path.join(profile_dir, name) if profile_dir else None
                ):
                    res = _run_with_watchdog(name, fn, watchdog_tripped)
                # resilience-runtime provenance: robustness overhead must be
                # visible in the perf trajectory, and a clean run must prove
                # itself clean (both read 0 with no TPUML_* resilience env)
                res_delta = _res_counters.delta_since(res_base)
                res["retries"] = res_delta.get("retries", 0) + res_delta.get(
                    "chunk_halvings", 0
                )
                res["resumed_from"] = res_delta.get("resumed_from", 0)
                # span provenance when tracing is on: device seconds measured
                # by span fencing, and per-site span counts for this entry
                flops_measured = 0.0
                if _telemetry.enabled():
                    tele_now = _telemetry.span_stats()
                    dev = 0.0
                    spans = {}
                    for site, st in tele_now.items():
                        prev = tele_base.get(site, {})
                        dc = st["count"] - prev.get("count", 0)
                        if dc > 0:
                            spans[site] = dc
                            dev += st["device_seconds"] - prev.get(
                                "device_seconds", 0.0
                            )
                            flops_measured += st.get(
                                "flops_total", 0.0
                            ) - prev.get("flops_total", 0.0)
                    res["device_seconds"] = round(dev, 4)
                    res["spans"] = spans
                res["mfu"] = res["flops_model"] / (
                    res["fit_seconds"] * peak * n_chips
                )
                if flops_measured > 0:
                    # measured roofline position: XLA cost_analysis() FLOPs
                    # attributed to this entry's spans, replacing the
                    # hand-rolled flops_model estimate (kept as mfu_derived
                    # so trajectories across the swap stay comparable)
                    res["mfu_derived"] = round(res["mfu"], 4)
                    res["flops_measured"] = flops_measured
                    res["mfu"] = flops_measured / (
                        res["fit_seconds"] * peak * n_chips
                    )
                res["vs_baseline"] = (
                    res["samples_per_sec_per_chip"] / res["baseline_samples_per_sec"]
                )
                if "transform_baseline_samples_per_sec" in res:
                    res["transform_vs_baseline"] = (
                        res["transform_samples_per_sec_per_chip"]
                        / res["transform_baseline_samples_per_sec"]
                    )
                results[name] = res
                if devices[0].platform == "cpu":
                    # an explicit --platform cpu run measures the host, not
                    # the chip: flag every entry so bench_regress compares
                    # rounds as skip:host-only instead of gating on host
                    # noise, and no reader takes it for a device metric
                    res["host_only"] = True
                print(
                    f"[bench] {name}: {res['samples_per_sec_per_chip']:.3e} "
                    f"samples/sec/chip, mfu={res['mfu']:.3f}, "
                    f"vs_baseline={res['vs_baseline']:.2f}",
                    file=sys.stderr,
                )
                break
            except Exception as e:  # noqa: BLE001
                transient = "UNAVAILABLE" in str(e)
                print(
                    f"[bench] {name} attempt {attempt} failed"
                    f"{' (transient, will retry)' if transient and attempt == 0 else ''}:\n"
                    f"{traceback.format_exc()}",
                    file=sys.stderr,
                )
                if not (transient and attempt == 0):
                    failed.append(name)
                    break
                time.sleep(15)

    if not results:
        print("[bench] all algorithms failed; no metric to report", file=sys.stderr)
        if watchdog_tripped:
            # a parked worker thread can block interpreter teardown — see
            # the _hard_exit note below
            _hard_exit(1)
        sys.exit(1)

    # BENCH_REQUIRE_TRANSFORM=rf[,umap,...] — CI contract: the named
    # entries must have produced a transform_vs_baseline figure; a silent
    # fit-only result (transform path crashed, or an entry rename dropped
    # the metric) fails the run instead of shipping an artifact that
    # quietly lost the serving measurement.
    required = [
        s for s in os.environ.get("BENCH_REQUIRE_TRANSFORM", "").split(",") if s
    ]
    missing = [
        name
        for name in required
        if "transform_vs_baseline" not in results.get(name, {})
    ]
    if missing:
        print(
            f"[bench] BENCH_REQUIRE_TRANSFORM unmet: no transform_vs_baseline "
            f"for {missing} (have: {sorted(results)})",
            file=sys.stderr,
        )
        if watchdog_tripped:
            _hard_exit(1)
        sys.exit(1)

    # model-axis A/B columns for the mp-capable entries (subprocess probe;
    # skipped for subsets that exclude all four families)
    _merge_mp_ab(results)

    # flag BEFORE emitting: a SIGTERM landing mid-print must not re-enter
    # emission from the handler (interleaved/duplicate JSON lines)
    _PARTIAL["emitted"] = True
    _emit_line(results, meta, watchdog_tripped)
    if _telemetry.enabled():
        # Prometheus + JSON metric dump next to the trace files
        _telemetry.write_metrics()
    if watchdog_tripped:
        # a tripped watchdog means a worker thread is still parked inside
        # a device call that never returned; normal interpreter exit would
        # block on runtime teardown behind it, leaving this process alive
        # and holding the chip — the exact wedge the watchdog exists to
        # bound. Flush and leave; a tripped watchdog is a failed run.
        _hard_exit(1)
    if failed:
        print(f"[bench] entries failed: {failed}", file=sys.stderr)
        sys.exit(1)


# model-axis A/B: fit the four mp-capable families (pca/linreg/kmeans/ann)
# at TPUML_MESH_MP unset vs =2 in a clean subprocess on 8 virtual CPU
# devices, and attach {mp1,mp2} fit seconds + the measured per-shard HBM
# bytes from _fit_report/_ann_report to the matching bench entries. A
# subprocess because the main bench holds the real backend (and its own
# mesh) — the probe must not flip TPUML_MESH_MP under live entries.
_MP_AB_CHILD = r"""
import json, os, time
import numpy as np

os.environ.setdefault("TPUML_ANN_GATE_ROWS", "1")

from sklearn.datasets import make_blobs

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors
from spark_rapids_ml_tpu.regression import LinearRegression

rows, d, k = 4096, 64, 8
rng = np.random.default_rng(0)
X, _ = make_blobs(n_samples=rows, n_features=d, centers=k, random_state=0)
X = X.astype(np.float32)
y = (X @ rng.normal(size=d)).astype(np.float32)
df = DataFrame({"features": X})
df_lab = DataFrame({"features": X, "label": y})
qdf = DataFrame({"features": X[:128]})


def one_pass():
    out = {}
    t0 = time.perf_counter()
    m = PCA(k=4).setInputCol("features").fit(df)
    out["pca"] = (time.perf_counter() - t0, dict(m._fit_report))
    t0 = time.perf_counter()
    m = LinearRegression(regParam=1e-3).fit(df_lab)
    out["linreg"] = (time.perf_counter() - t0, dict(m._fit_report))
    t0 = time.perf_counter()
    m = KMeans(k=k, maxIter=10, seed=0).fit(df)
    out["kmeans"] = (time.perf_counter() - t0, dict(m._fit_report))
    t0 = time.perf_counter()
    m = ApproximateNearestNeighbors(k=10, num_workers=1).fit(df)
    m.kneighbors(qdf)
    out["ann"] = (time.perf_counter() - t0, dict(m._ann_report))
    return out


os.environ.pop("TPUML_MESH_MP", None)
base = one_pass()
os.environ["TPUML_MESH_MP"] = "2"
sharded = one_pass()

bkeys = {
    "pca": "gram_shard_bytes",
    "linreg": "gram_shard_bytes",
    "kmeans": "centroid_shard_bytes",
    "ann": "index_shard_bytes",
}
# replicated model-axis bytes for the gram/centroid families are exact
# analytically (f32, d aligned, k % mp == 0); the IVF index has capacity
# padding so only its measured shard bytes are reported
full = {"pca": d * d * 4, "linreg": d * d * 4, "kmeans": k * d * 4}
rep = {}
for name, bkey in bkeys.items():
    t1, _ = base[name]
    t2, r2 = sharded[name]
    entry = {
        "mp_degree": int(r2.get("mp_degree", 1)),
        "mp1_fit_seconds": round(t1, 4),
        "mp2_fit_seconds": round(t2, 4),
        "shard_bytes_mp2": int(r2.get(bkey, 0)),
    }
    if name in full:
        entry["replicated_bytes"] = full[name]
    rep[name] = entry
print("MPAB " + json.dumps(rep))
"""


def _mp_ab_probe() -> dict:
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.pop("TPUML_MESH_MP", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _MP_AB_CHILD],
            capture_output=True,
            text=True,
            timeout=900,
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except Exception as e:  # noqa: BLE001
        print(f"[bench] mp A/B probe failed to launch: {e!r}", file=sys.stderr)
        return {}
    for ln in proc.stdout.splitlines():
        if ln.startswith("MPAB "):
            try:
                return json.loads(ln[5:])
            except json.JSONDecodeError:
                break
    print(
        f"[bench] mp A/B probe produced no result (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}",
        file=sys.stderr,
    )
    return {}


def _merge_mp_ab(results) -> None:
    targets = [n for n in ("pca", "linreg", "kmeans", "ann") if n in results]
    if not targets or os.environ.get("BENCH_MP_AB", "1") == "0":
        return
    ab = _mp_ab_probe()
    for name in targets:
        if name in ab:
            results[name]["mp_degree"] = ab[name]["mp_degree"]
            results[name]["mp_ab"] = ab[name]


def _emit_line(results, meta, watchdog_tripped):
    """Assemble and print the one-line JSON metric. Pure-Python over
    already-fetched scalars — safe to call from the SIGTERM handler."""
    # host-only entries (an explicit --platform cpu run) measure the host,
    # not the chip — keep them out of the geomean
    vs = [
        r["vs_baseline"]
        for r in results.values()
        if not r.get("host_only")
    ] or [r["vs_baseline"] for r in results.values()]
    geomean_vs = math.exp(sum(math.log(max(v, 1e-12)) for v in vs) / len(vs))
    if "pca" in results:
        head_name, headline = "pca", results["pca"]
    else:  # BENCH_ONLY subset without pca: label honestly
        head_name, headline = next(iter(results.items()))
    line = {
        "metric": f"{head_name}_fit_throughput",
        "value": round(headline["samples_per_sec_per_chip"], 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(headline["vs_baseline"], 3),
        "vs_baseline_geomean": round(geomean_vs, 3),
        **meta,
    }
    # provenance scalars each entry may carry (configuration that actually
    # ran — dtype fallbacks, tree counts, dispatch amortization)
    _extras = (
        "iters", "per_iter", "trees", "rows", "queries", "objective_dtype",
        "matmul_dtype", "inner_fits_per_dispatch", "ingest_gbps",
        "stream_gb", "overlapped_abandoned", "k_features",
        "device_math_seconds", "device_math_samples_per_sec",
        "ingest_seconds", "overlap_efficiency",
        "transform_seconds", "transform_engine",
        "transform_samples_per_sec_per_chip",
        "transform_vs_baseline", "samples_per_sec_per_chip_e2e",
        "trustworthiness", "baseline_kind", "baseline_inputs",
        "graph_seconds", "graph_engine", "graph_recall", "ann_nlist",
        "ann_nprobe", "build_seconds", "nlist", "nprobe", "recall",
        "init_seconds", "sgd_seconds", "epoch_ms",
        "sgd_engine", "retries", "resumed_from",
        "wire_dtype", "decode_seconds", "device_seconds", "spans",
        "mfu_derived", "flops_measured",
        "hist_strategy", "tree_batch", "seconds_per_level",
        "level_seconds", "rounds", "depth", "seconds_per_round",
        "gang_lanes", "solves_per_sec", "vs_sequential", "seq_fit_seconds",
        "p50_ms", "p99_ms", "qps_sweep", "window_sweep", "retrace_storms",
        "serve_vs_direct", "setup_fit_seconds", "warm_seconds", "requests",
        "p99_series_models", "capacity_qps", "overload_sweep",
        "overload_deadline_ms", "goodput_qps", "shed_frac",
        "fits", "fits_per_sec", "fit_p50_ms", "fit_p99_ms",
        "sched_occupancy", "arrival_sweep", "arrival_deadline_ms",
        "ops_scrape_ms", "serve_batch_fill",
        "mp_degree", "mp_ab",
        "replicas", "policy", "offered_qps", "aggregate_goodput_qps",
        "replica_scaling_efficiency", "fleet_p99_ms", "fleet_sweep",
        "swaps", "swap_ms", "swap_p99_ms", "swap_p99_delta_ms",
        "rollback_ms",
        "tuned_vs_default", "legs", "total_seconds", "budget_ms_per_search",
    )
    for name, r in results.items():
        line[name] = {
            "samples_per_sec_per_chip": round(r["samples_per_sec_per_chip"], 1),
            "fit_seconds": round(r["fit_seconds"], 4),
            "mfu": round(r["mfu"], 4),
            "vs_baseline": round(r["vs_baseline"], 3),
        }
        for k in _extras:
            if k in r:
                line[name][k] = r[k]
        if r.get("host_only"):
            line[name]["host_only"] = True
    if watchdog_tripped:
        line["watchdog_tripped"] = watchdog_tripped
    print(json.dumps(line))


class _BenchTimeout(RuntimeError):
    pass


def _hard_exit(code):
    """Flush and leave WITHOUT interpreter unwind: with a worker thread
    parked in a dead device call, normal exit blocks on runtime teardown
    (keeping the process alive holding the chip), and an unwind
    with a dispatch mid-flight aborts in teardown anyway (observed)."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


def _algo_deadline():
    raw = os.environ.get("BENCH_ALGO_TIMEOUT", "1200")
    try:
        return float(raw)
    except ValueError:
        # one clear config error, not N phantom per-algorithm failures
        sys.exit(f"BENCH_ALGO_TIMEOUT must be a number of seconds, got {raw!r}")


_ABANDONED = []  # threads of tripped entries; may wake and run later


def _run_with_watchdog(name, fn, tripped):
    """Run one bench entry on a worker thread with a deadline.

    A dispatch can hang forever client-side (observed once: a compile
    fetch that never returned, eating an entire capture run). The worker
    is a daemon thread: on timeout the entry is abandoned (recorded in
    ``tripped``) and the loop moves on — later entries may still succeed
    if the backend recovers, and the final JSON line always prints.
    BENCH_ALGO_TIMEOUT=0 disables the deadline.

    An abandoned worker that UNBLOCKS later keeps issuing its entry's
    remaining device work until the entry finishes (a parked C call
    cannot be interrupted); its late result is discarded via the cancel
    flag. Entries that overlapped a live abandoned worker at START or
    END are flagged ``overlapped_abandoned`` (their timings shared the
    chip) — a worker that wakes and finishes strictly inside another
    entry's window can still evade the flag; treat entries after a trip
    with suspicion."""
    import threading

    deadline = _algo_deadline()
    if deadline <= 0:
        return fn()
    overlapped_at_start = any(a.is_alive() for a in _ABANDONED)
    box = {}
    cancelled = threading.Event()

    def work():
        try:
            res = fn()
            if not cancelled.is_set():
                box["res"] = res
        except BaseException as e:  # noqa: BLE001
            if not cancelled.is_set():
                box["err"] = e

    t = threading.Thread(target=work, name=f"bench-{name}", daemon=True)
    t.start()
    t.join(deadline)
    if t.is_alive():
        cancelled.set()
        tripped.append(name)
        _ABANDONED.append(t)
        raise _BenchTimeout(
            f"{name} exceeded BENCH_ALGO_TIMEOUT={deadline:.0f}s "
            "(device call never returned; entry abandoned)"
        )
    if "err" in box:
        err = box["err"]
        if not isinstance(err, Exception):
            # KeyboardInterrupt/SystemExit re-raised in the main thread
            # would escape the per-entry handler and unwind the whole run
            # (wedge-prone with parked workers); surface as a failure
            raise RuntimeError(f"{name} worker raised {type(err).__name__}: {err}")
        raise err
    res = box["res"]
    if overlapped_at_start or any(a.is_alive() for a in _ABANDONED):
        res["overlapped_abandoned"] = True
    return res


_PARTIAL = {"results": None, "meta": None, "tripped": None, "emitted": False}


def _install_signal_handlers():
    """External timeouts/cancellations send SIGTERM; the default handler
    kills the process mid-dispatch with nothing recorded. Instead: emit
    the JSON line for every entry that already finished (a partial
    capture beats none), then leave via os._exit — an interpreter unwind
    with a dispatch mid-flight aborts in runtime teardown anyway
    (observed), and a lingering process would keep holding the chip,
    which belongs to one process at a time."""
    import signal

    def _graceful(signum, frame):
        print(
            f"[bench] signal {signum}: emitting partial results and exiting",
            file=sys.stderr,
        )
        try:
            if (
                not _PARTIAL["emitted"]
                and _PARTIAL["results"]  # placed by main(), non-empty
            ):
                _PARTIAL["emitted"] = True
                _emit_line(
                    _PARTIAL["results"], _PARTIAL["meta"], _PARTIAL["tripped"]
                )
        except Exception:  # noqa: BLE001 — never mask the exit on a bug here
            traceback.print_exc()
        _hard_exit(128 + signum)

    def _interrupt(signum, frame):
        # Ctrl-C on a healthy run: default KeyboardInterrupt unwind (the
        # clean client teardown). After a watchdog trip the unwind would
        # block behind the parked worker — partial-emit and leave instead.
        if _PARTIAL["tripped"]:
            _graceful(signum, frame)
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, _graceful)
        signal.signal(signal.SIGINT, _interrupt)
    except (ValueError, OSError):
        pass  # non-main thread or unsupported platform


if __name__ == "__main__":
    _install_signal_handlers()
    main()
