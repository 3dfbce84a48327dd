"""The upstream benchmark's ``blobs`` data set, which ``bench_kmeans.py`` feeds
its estimator.

Copied from ``benchmark/gen_data.py`` (``_blobs_struct`` / ``_blobs_chunk``,
the repo's port of the reference's ``python/benchmark/gen_data.py``): the same
structure and the same distributions, parameter for parameter — ``centers``
generating centres drawn N(0, 10²) a column (``rng.normal(size=(centers,
cols)) * 10`` in float32 from ``default_rng(seed)``), each row one uniformly
drawn centre plus ``cluster_std``·N(0, 1) noise on every column. What differs
is how the bytes are drawn, as in ``gen_data.py`` beside this file: rows come
in chunks of 2¹⁴ from independent seeded float32 streams, filled by a few
threads, so a 500,000 × 3000 frame takes seconds and the thread count never
changes the data. Host numpy only: the program under test receives a
host-resident frame, as it does from Spark.

``make(seed, rows, cols, params)`` returns the frame's columns: ``features``
(rows × cols float32) and ``label`` (rows float64: the index of the
generating blob, as ``_blobs_chunk`` hands it over; KMeans reads ``features``
alone, the generator ``closed_loop`` wants a label column in the frame).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_GEN_ROWS = 1 << 14     # rows per generation chunk (own seeded stream each)


def make(seed: int, rows: int, cols: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    centres = (rng.normal(size=(int(params["centers"]), cols)) * 10).astype(np.float32)
    std = np.float32(params["cluster_std"])
    X = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float64)

    def fill(ci: int) -> None:
        lo, hi = ci * _GEN_ROWS, min((ci + 1) * _GEN_ROWS, rows)
        chunk_rng = np.random.default_rng([seed, 0, ci])
        lab = chunk_rng.integers(0, len(centres), hi - lo)
        x = X[lo:hi]
        chunk_rng.standard_normal(out=x, dtype=np.float32)
        x *= std
        x += centres[lab]
        y[lo:hi] = lab

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(-(-rows // _GEN_ROWS))))
    return {"features": X, "label": y}
