"""RandomForest at the reference's FULL benchmark shape on one chip.

The reference runs RandomForestClassifier(numTrees=50, maxDepth=13,
maxBins=128) on 1M x 3000 on a 2x A10G cluster inside a 3600 s budget
(``/root/reference/python/benchmark/databricks/run_benchmark.sh:102-112``),
with featureSubsetStrategy at Spark's default "auto" -> sqrt(3000) = 55
features per split (``tree.py:380-386``). Before the subset-exploiting
histogram path (``ops/tree_kernels.py``), the all-features cost model put
this config at ~1-2 h per chip; with n*k*S updates it drops to minutes.

Memory design for one 16 GB v5e: the f32 design matrix (12 GB) never
materializes — rows are generated on device in chunks, binized to uint8
immediately, and only the (n, d_pad) binned matrix (~4 GB) plus labels
are kept.

Usage: python scripts/rf_reference_shape.py [--rows N] [--cols D]
       [--trees T] [--depth L] [--group G]
Prints one JSON line with wall-clock and config.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from spark_rapids_ml_tpu.utils.platform import pin_platform  # noqa: E402

pin_platform(os.environ.get("RFDEMO_PLATFORM"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--cols", type=int, default=3000)
    ap.add_argument("--trees", type=int, default=50)
    ap.add_argument("--depth", type=int, default=13)
    ap.add_argument("--bins", type=int, default=128)
    # trees per dispatch: a multi-minute single device program outlives
    # remote-runtime health checks (round-2 postmortem)
    ap.add_argument("--group", type=int, default=4)
    args = ap.parse_args()

    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.models.tree import _resolve_k_features
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        ForestConfig,
        build_forest,
        next_pow2,
        resolve_contract_gather,
        resolve_hist_strategy,
    )
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    n, d, nb = args.rows, args.cols, args.bins
    # lane-aligned (not pow2) feature padding: the compact+subset build
    # path only needs d_pad % 4 == 0 (word packing) and clipping room for
    # take_along_axis; 3000 -> 3072 instead of 4096 keeps the resident
    # binned matrix at 3.2 GB instead of 4.3 GB — the chip this was
    # written on exposed only ~8 GB HBM, and the pow2 pad OOMed the fit
    d_pad = -(-d // 256) * 256
    k = _resolve_k_features("auto", d, True)
    mesh = make_mesh(len(jax.devices()))
    n_dp = mesh.shape["dp"]
    sh = NamedSharding(mesh, P("dp"))

    # small generation chunks: the (chunk, 3000) f32 block plus the i32
    # searchsorted output are ~800 MB at 16k rows — transients must fit
    # beside the 3.2 GB binned matrix in ~8 GB visible HBM
    rows_per_chunk = 16_384
    gchunk = rows_per_chunk * n_dp
    n_pad = ((n + gchunk - 1) // gchunk) * gchunk
    w_true = jnp.asarray(
        np.random.default_rng(0).standard_normal(d, dtype=np.float32)
    )
    # data is synthetic i.i.d. N(0,1), so the exact standard-normal
    # quantiles serve as bin edges for every feature (the estimator path
    # sketches per-feature sample quantiles instead)
    from jax.scipy.special import ndtri

    edges = jnp.asarray(
        ndtri(np.linspace(0.0, 1.0, nb + 1)[1:-1]), jnp.float32
    )

    t0 = time.perf_counter()

    # Chunked generate -> binize -> place, as SEPARATE small programs
    # with a DONATED placement buffer. A single fori-loop program holds
    # the (n_pad, d_pad) carry double-buffered — at 1M x 3072 that is
    # 2 x 3.1 GB that backend then kept resident into the fit,
    # which OOMed the ~8 GB visible HBM (round-4 bisection; each stage
    # runs alone, gen-then-fit faulted). Donation keeps the peak at one
    # binned matrix + one 16k-row piece. NOTE: every device array the
    # jits touch rides as an ARGUMENT — a jit-captured device constant
    # (the original `edges` closure) deterministically faulted this
    # backend.
    import functools

    def _piece(key, i, w, edges):
        blk = jax.random.normal(
            jax.random.fold_in(key, i), (gchunk, d), jnp.float32
        )
        y = (blk @ w > 0).astype(jnp.float32)
        b = jnp.searchsorted(edges, blk, side="right").astype(jnp.uint8)
        b = jnp.pad(b, ((0, 0), (0, d_pad - d)))
        return b, jnp.stack([1.0 - y, y], axis=1)

    gen_piece = jax.jit(_piece, out_shardings=(sh, sh))

    @functools.partial(jax.jit, donate_argnums=(0,), out_shardings=sh)
    def place(ba, piece, i):
        return lax.dynamic_update_slice_in_dim(ba, piece, i * gchunk, 0)

    zeros_u8 = jax.jit(
        lambda: jnp.zeros((n_pad, d_pad), jnp.uint8), out_shardings=sh
    )
    zeros_f32 = jax.jit(
        lambda: jnp.zeros((n_pad, 2), jnp.float32), out_shardings=sh
    )
    bins, stats = zeros_u8(), zeros_f32()
    key0 = jax.random.key(11)
    for i in range(n_pad // gchunk):
        b, st = gen_piece(key0, jnp.int32(i), w_true, edges)
        bins = place(bins, b, jnp.int32(i))
        stats = place(stats, st, jnp.int32(i))
    mask_fn = jax.jit(
        lambda: (jnp.arange(n_pad) < n).astype(jnp.float32), out_shardings=sh
    )
    mask = mask_fn()
    # one-shot setup, runs once per demo invocation  # tpuml: ignore[TPU003]
    stats = jax.jit(
        lambda s, m: s * m[:, None], donate_argnums=(0,), out_shardings=sh
    )(stats, mask)
    jax.block_until_ready(bins)
    t_gen = time.perf_counter() - t0
    print(f"[rf-demo] binned data ready in {t_gen:.1f}s "
          f"({n}x{d} -> uint8 {n_pad}x{d_pad})", file=sys.stderr)

    cfg = ForestConfig(
        max_depth=args.depth, n_bins=nb, n_features=d, n_stats=2,
        impurity="gini", k_features=k, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True,
        hist_strategy=resolve_hist_strategy(),
        contract_gather=resolve_contract_gather(),
    )
    trees_per_dev = -(-args.trees // n_dp)
    group = min(args.group, trees_per_dev)
    trees_per_dev = -(-trees_per_dev // group) * group
    keys = jax.random.key_data(
        jax.random.split(jax.random.key(5), n_dp * trees_per_dev)
    ).reshape(n_dp, trees_per_dev, 2)
    keys = jax.device_put(np.asarray(keys), sh)

    fit = jax.jit(
        lambda b, m, s, kg: build_forest(b, m, s, kg, mesh=mesh, cfg=cfg)
    )
    t1 = time.perf_counter()
    depths = []
    for gi, g0 in enumerate(range(0, trees_per_dev, group)):
        out = fit(bins, mask, stats, keys[:, g0 : g0 + group])
        feat = np.asarray(out["feature"])  # (n_dp*group, M) fetch = sync
        depths.append(int((feat >= 0).sum()))
        print(
            f"[rf-demo] group {gi}: trees {g0}..{g0 + group - 1} done, "
            f"{time.perf_counter() - t1:.1f}s elapsed, "
            f"splits so far {sum(depths)}",
            file=sys.stderr,
        )
    t_fit = time.perf_counter() - t1
    n_trees = trees_per_dev * n_dp

    print(json.dumps({
        "metric": "rf_reference_shape_fit",
        "rows": n, "cols": d, "trees": n_trees, "max_depth": args.depth,
        "n_bins": nb, "k_features": k,
        "gen_binize_seconds": round(t_gen, 1),
        "fit_seconds": round(t_fit, 1),
        "seconds_per_tree": round(t_fit / n_trees, 2),
        "total_splits": sum(depths),
        "device": getattr(jax.devices()[0], "device_kind", "cpu"),
        "n_chips": n_dp,
        "reference_envelope_seconds": 3600,
        "reference_hardware": "2x A10G",
    }))


if __name__ == "__main__":
    main()
