"""100M x 256 north-star, grouped-subprocess edition.

Two in-process 100M attempts were OOM-killed on the HOST (~130 GB RSS,
growing at exactly the ingest rate): that installation's client retained a
host-side copy of each TRANSFERRED buffer until that exact buffer is
deleted, and the early mitigations (reference drops; deleting only the
derived f32 upcast of the f16 wire chunk) released nothing.
``ops.streaming.StreamGuard`` now deletes the raw wire buffers at proven
sync points, which bounds in-process retention — but a multi-hour
flagship run should not bet on the client's retention semantics staying
fixed across backend versions. The streaming two-pass algebra is additive
over file groups, so this driver additionally bounds retention by process
lifetime:

* pass 1 (weighted first moments) runs as one SUBPROCESS per file group,
  each writing its partials (n, Σx, Σy) to an npz and exiting — freeing
  everything the client retained for that group;
* the driver combines partials, fixes the global means, and fans out
  pass 2 (centered Gram/Xy/yy) the same way;
* ONE set of passes feeds BOTH models: PCA finalizes from G via
  ``_pca_from_cov``, LinearRegression solves from (G, Xy, yy) via
  ``_solve_from_stats`` — the exact code paths the in-process streaming
  fit uses, so results are identical by construction. Two dataset passes
  total instead of the naive four.

Per-group retention = group bytes shipped (~2 GB/pass at 5 files/group),
device memory = one chunk slab + O(d^2) accumulators throughout.

Usage:
    python scripts/run_100m_northstar_grouped.py [--data DIR]
        [--group-files 5] [--chunk-rows 524288] [--max-files N]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _worker(args) -> None:
    """Run one pass over one file group; write partials npz; exit."""
    from spark_rapids_ml_tpu.utils.platform import pin_platform

    pin_platform(args.platform)
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.data.chunks import ParquetChunkSource
    from spark_rapids_ml_tpu.ops.streaming import (
        StreamGuard, gram2_init, gram2_step, moments1_init, moments1_step,
        put_chunk,
    )
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    files = args.files.split(",")
    src = ParquetChunkSource(args.data, label_col="label", _files=files)
    mesh = make_mesh()
    dtype = jnp.float32
    np_dtype = np.float32

    if args.phase == "pass1":
        acc = moments1_init(src.n_features, dtype, with_y=True)
        guard = StreamGuard()
        for chunk in src.iter_chunks(args.chunk_rows, np_dtype):
            dev = put_chunk(chunk, mesh, dtype)
            acc = moments1_step(acc, dev["X"], dev["mask"], dev["y"])
            guard.tick(dev, acc)
        guard.flush(acc)
        np.savez(
            args.out,
            n=np.asarray(acc["n"], np.float64),
            sum_x=np.asarray(acc["sum_x"], np.float64),
            sum_y=np.asarray(acc["sum_y"], np.float64),
        )
    else:
        means = np.load(args.means)
        mean_x = jnp.asarray(means["mean_x"], dtype)
        mean_y = jnp.asarray(means["mean_y"], dtype)
        acc = gram2_init(src.n_features, dtype, with_y=True)
        guard = StreamGuard()
        for chunk in src.iter_chunks(args.chunk_rows, np_dtype):
            dev = put_chunk(chunk, mesh, dtype)
            acc = gram2_step(acc, dev["X"], dev["mask"], mean_x, dev["y"], mean_y)
            guard.tick(dev, acc)
        guard.flush(acc)
        np.savez(
            args.out,
            G=np.asarray(acc["G"], np.float64),
            Xy=np.asarray(acc["Xy"], np.float64),
            yy=np.asarray(acc["yy"], np.float64),
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=os.path.join(_REPO, ".data", "blobs100m"))
    ap.add_argument("--platform", default=None)
    ap.add_argument("--group-files", type=int, default=5)
    ap.add_argument("--chunk-rows", type=int, default=1 << 19)
    ap.add_argument("--max-files", type=int, default=None)
    ap.add_argument("--sub-rows", type=int, default=500_000)
    # worker-mode internals
    ap.add_argument("--phase", choices=["pass1", "pass2"], default=None)
    ap.add_argument("--files", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--means", default=None)
    args = ap.parse_args()

    if args.phase:
        _worker(args)
        return

    files = sorted(glob.glob(os.path.join(args.data, "part-*.parquet")))
    if args.max_files:
        files = files[: args.max_files]
    groups = [
        files[i : i + args.group_files]
        for i in range(0, len(files), args.group_files)
    ]
    tmp = tempfile.mkdtemp(prefix="northstar_grouped_")

    def run_phase(phase: str, means_path: str | None):
        outs = []
        for gi, g in enumerate(groups):
            out = os.path.join(tmp, f"{phase}-{gi:03d}.npz")
            cmd = [
                sys.executable, os.path.abspath(__file__),
                "--phase", phase, "--data", args.data,
                "--files", ",".join(g), "--out", out,
                "--chunk-rows", str(args.chunk_rows),
            ]
            if means_path:
                cmd += ["--means", means_path]
            if args.platform:
                cmd += ["--platform", args.platform]
            t0 = time.perf_counter()
            subprocess.run(cmd, check=True)
            print(
                f"[northstar-grouped] {phase} group {gi + 1}/{len(groups)} "
                f"({len(g)} files) in {time.perf_counter() - t0:.0f}s",
                file=sys.stderr, flush=True,
            )
            outs.append(out)
        return outs

    t_start = time.perf_counter()
    p1 = run_phase("pass1", None)
    n = sum(float(np.load(o)["n"]) for o in p1)
    sum_x = np.sum([np.load(o)["sum_x"] for o in p1], axis=0)
    sum_y = sum(float(np.load(o)["sum_y"]) for o in p1)
    mean_x = sum_x / n
    mean_y = sum_y / n
    means_path = os.path.join(tmp, "means.npz")
    np.savez(means_path, mean_x=mean_x, mean_y=np.float64(mean_y))
    t_pass1 = time.perf_counter() - t_start

    t0 = time.perf_counter()
    p2 = run_phase("pass2", means_path)
    G = np.sum([np.load(o)["G"] for o in p2], axis=0)
    Xy = np.sum([np.load(o)["Xy"] for o in p2], axis=0)
    yy = sum(float(np.load(o)["yy"]) for o in p2)
    t_pass2 = time.perf_counter() - t0

    # finalize BOTH models through the library's own solver paths
    from spark_rapids_ml_tpu.utils.platform import pin_platform

    pin_platform("cpu")  # d x d finalization; no need to re-grab the chip
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.feature import PCA, _pca_from_cov
    from spark_rapids_ml_tpu.models.regression import LinearRegression

    d = mean_x.shape[0]
    dtype = jnp.float64
    cov = jnp.asarray(G, dtype) / (n - 1.0)
    pca_out = {
        k: np.asarray(v)
        for k, v in _pca_from_cov(
            jnp.asarray(mean_x, dtype), cov, jnp.asarray(n, dtype), 3
        ).items()
    }
    stats = {
        "n": jnp.asarray(n, dtype),
        "mean_x": jnp.asarray(mean_x, dtype),
        "mean_all": jnp.asarray(mean_x, dtype),
        "mean_y": jnp.asarray(mean_y, dtype),
        "G": jnp.asarray(G, dtype),
        "Xy": jnp.asarray(Xy, dtype),
        "yy": jnp.asarray(yy, dtype),
        "var": jnp.asarray(np.diagonal(G) / n, dtype),
    }
    lin_out = LinearRegression._solve_from_stats(
        stats,
        {
            "alpha": 1e-5, "l1_ratio": 0.0, "standardization": True,
            "fit_intercept": True, "max_iter": 100, "tol": 1e-6,
        },
        dtype,
    )

    # parity: resident PCA on a strided subsample of the first file
    import pyarrow.parquet as pq

    from spark_rapids_ml_tpu.data import DataFrame

    t = pq.read_table(files[0], columns=["features"])
    sub_rows = min(len(t), args.sub_rows)
    stride = max(1, len(t) // sub_rows)
    t = t.take(np.arange(0, len(t), stride)[:sub_rows])
    fc = t.column("features").combine_chunks()
    Xs = (
        fc.flatten().to_numpy(zero_copy_only=False)
        .reshape(-1, fc.type.list_size).astype(np.float32)
    )
    resident = PCA(k=3).fit(DataFrame({"features": Xs}))
    cos = np.abs(
        np.sum(pca_out["components"] * np.asarray(resident.components_), axis=1)
    )

    wall = time.perf_counter() - t_start
    dataset_f32_gb = n * d * 4 / 1e9
    ingest_gbps = (dataset_f32_gb / 2) * 2 / max(wall, 1e-9)  # f16 wire, 2 passes
    line = {
        "metric": "northstar_100m_pca_fit",
        "rows": int(n),
        "cols": int(d),
        "pass1_seconds": round(t_pass1, 1),
        "pass2_seconds": round(t_pass2, 1),
        "wall_seconds": round(wall, 1),
        "groups": len(groups),
        "group_files": args.group_files,
        "link_bound": ingest_gbps < 1.0,
        "dataset_f32_gb": round(dataset_f32_gb, 1),
        "wire_f16_gb_total": round(dataset_f32_gb, 1),  # 2 passes x f16
        "chunk_device_mb": round(args.chunk_rows * d * 4 / 1e6, 1),
        "subsample_component_cosines": [round(float(c), 5) for c in cos],
        "explained_variance_ratio": [
            round(float(v), 5) for v in pca_out["explained_variance_ratio"]
        ],
        "linreg_n_iter": int(lin_out.get("n_iter", 1)),
    }
    print(json.dumps(line))


if __name__ == "__main__":
    main()
