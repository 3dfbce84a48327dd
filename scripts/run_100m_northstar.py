"""100M x 256 north-star demonstration (BASELINE.md target).

Streams the 100M-row parquet dataset (generated by
``benchmark/gen_data_distributed.py`` at float16 storage) through the
out-of-core fit path: streaming PCA and streaming LinearRegression via
``DataFrame.scan_parquet`` — the dataset never materializes on host or
device; device memory holds one chunk slab plus the suffstats
accumulators.

Prints ONE JSON line with wall-clock (flagged ``link_bound`` when ingest
runs below 1 GB/s — the time then measures the host->device link, not the
chip), bounded-memory evidence (per-chunk device bytes), and a
parity check of the streamed components against a resident fit on a
subsample.

Usage:
    python scripts/run_100m_northstar.py [--data DIR] [--platform cpu|tpu]
        [--max-files N]   # N parquet files only (smoke mode)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_ml_tpu.utils.platform import pin_platform  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".data", "blobs100m"))
    ap.add_argument("--platform", default=None)
    ap.add_argument("--max-files", type=int, default=None)
    ap.add_argument("--chunk-rows", type=int, default=1 << 19)
    ap.add_argument("--sub-rows", type=int, default=500_000,
                    help="resident parity-check subsample size")
    args = ap.parse_args()

    pin_platform(args.platform)

    import jax

    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.regression import LinearRegression

    data_dir = args.data
    if args.max_files:
        import glob
        import tempfile

        files = sorted(glob.glob(os.path.join(data_dir, "part-*.parquet")))
        sub = tempfile.mkdtemp(prefix="northstar_sub_")
        for f in files[: args.max_files]:
            os.symlink(f, os.path.join(sub, os.path.basename(f)))
        data_dir = sub

    scan = DataFrame.scan_parquet(data_dir)
    n_rows, n_cols = scan.count(), 256
    dev = jax.devices()[0]
    chunk_bytes = args.chunk_rows * n_cols * 4  # f32 on device after upcast
    print(f"[northstar] {n_rows} rows, device {dev.device_kind}, "
          f"chunk {args.chunk_rows} rows ({chunk_bytes/1e6:.0f} MB device slab)",
          file=sys.stderr)

    t0 = time.perf_counter()
    pca = PCA(k=3, streaming=True, stream_chunk_rows=args.chunk_rows).fit(scan)
    t_pca = time.perf_counter() - t0

    t0 = time.perf_counter()
    lin = LinearRegression(
        regParam=1e-5, streaming=True, stream_chunk_rows=args.chunk_rows
    ).fit(DataFrame.scan_parquet(data_dir))
    t_lin = time.perf_counter() - t0

    # device-memory boundedness: the accumulators + one chunk slab must be
    # a tiny fraction of the dataset
    mem_stats = {}
    try:
        ms = dev.memory_stats() or {}
        mem_stats = {
            "device_bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "device_peak_bytes": int(ms.get("peak_bytes_in_use", 0)),
        }
    except Exception:
        pass
    dataset_f32_bytes = n_rows * n_cols * 4

    # parity: resident fit on a row-strided subsample of the first file
    # must agree on the dominant principal directions. Strided (not the
    # leading block) so any future row ordering cannot bias it; the table
    # is sliced via take() before any python-object conversion.
    import glob as _g

    import pyarrow.parquet as pq

    first = sorted(_g.glob(os.path.join(data_dir, "part-*.parquet")))[0]
    t = pq.read_table(first, columns=["features"])
    sub_rows = min(len(t), args.sub_rows)
    stride = max(1, len(t) // sub_rows)
    t = t.take(np.arange(0, len(t), stride)[:sub_rows])
    fc = t.column("features").combine_chunks()
    if hasattr(fc.type, "list_size"):
        Xs = (
            fc.flatten().to_numpy(zero_copy_only=False)
            .reshape(-1, fc.type.list_size).astype(np.float32)
        )
    else:
        Xs = np.stack([np.asarray(v, np.float32) for v in fc.to_pylist()])
    resident = PCA(k=3).fit(DataFrame({"features": Xs}))
    cos = np.abs(
        np.sum(
            np.asarray(pca.components_) * np.asarray(resident.components_),
            axis=1,
        )
    )

    ingest_gbps = dataset_f32_bytes / 2 * 2 / max(t_pca, 1e-9) / 1e9  # f16 wire x 2 passes
    line = {
        "metric": "northstar_100m_pca_fit",
        "rows": n_rows,
        "cols": n_cols,
        "pca_fit_seconds": round(t_pca, 1),
        "linreg_fit_seconds": round(t_lin, 1),
        "device": dev.device_kind,
        "link_bound": ingest_gbps < 1.0,
        "dataset_f32_gb": round(dataset_f32_bytes / 1e9, 1),
        "wire_f16_gb_per_pass": round(dataset_f32_bytes / 2 / 1e9, 1),
        "chunk_device_mb": round(chunk_bytes / 1e6, 1),
        "subsample_component_cosines": [round(float(c), 5) for c in cos],
        "explained_variance_ratio": [
            round(float(v), 5) for v in np.asarray(pca.explained_variance_ratio_)
        ],
        **mem_stats,
    }
    print(json.dumps(line))


if __name__ == "__main__":
    main()
