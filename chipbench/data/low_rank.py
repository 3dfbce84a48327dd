"""The upstream benchmark's ``low_rank_matrix`` data set, which ``bench_pca.py``
feeds its estimator.

Copied from ``benchmark/gen_data.py:65-77`` (``_low_rank_struct`` /
``_low_rank_chunk``, the repo's port of the reference's
``python/benchmark/gen_data.py``, itself sklearn's ``make_low_rank_matrix``
without the QR of U): the same structure, parameter for parameter. With
n = min(rows, cols) the singular profile is

    s_i = (1 − tail_strength)·exp(−(i / effective_rank)²) + tail_strength·exp(−0.1·i / effective_rank)

for i < n; V is the Q of a QR of ``default_rng(seed).normal(size=(cols, n))``;
a row is ``(u·s)·Vᵀ`` with u standard normal over √rows. So the columns have
mean nought, the population covariance is ``V·diag(s²)·Vᵀ / rows``, and with
the defaults (``effective_rank=10``, ``tail_strength=0.5``) its leading
eigenvalues are 1, 0.980, 0.941, 0.888, ... of the first: 2–6% apart.

What differs is how the bytes are drawn, as in ``gen_data.py`` beside this
file: u comes in chunks of 2¹⁴ rows from independent seeded float32 streams,
filled by a few threads, and the product is float32 (one ``sgemm`` a chunk,
2·rows·n·cols operations in all: 9e12 at 500,000 × 3000), so the thread
count never changes the data. Host numpy only: the program under test
receives a host-resident frame, as it does from Spark.

``make(seed, rows, cols, params)`` returns the frame's columns: ``features``
(rows × cols float32) and ``label`` (rows float64, all nought: PCA reads
``features`` alone, the generator ``closed_loop`` wants a label column in the
frame).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_GEN_ROWS = 1 << 14     # rows per generation chunk (own seeded stream each)


def profile(n: int, effective_rank: float, tail_strength: float) -> np.ndarray:
    """The singular values s_0 … s_{n-1} (float64)."""
    sv = np.arange(n, dtype=np.float64) / effective_rank
    return (1 - tail_strength) * np.exp(-(sv**2)) + tail_strength * np.exp(-0.1 * sv)


def make(seed: int, rows: int, cols: int, params: dict, threads: int | None = None) -> dict:
    n = min(rows, cols)
    s = profile(n, float(params.get("effective_rank", 10)), float(params.get("tail_strength", 0.5)))
    V, _ = np.linalg.qr(np.random.default_rng(seed).normal(size=(cols, n)))
    sVt = np.ascontiguousarray((V * (s / np.sqrt(rows))).T, dtype=np.float32)   # diag(s)·Vᵀ / √rows
    X = np.empty((rows, cols), np.float32)

    def fill(ci: int) -> None:
        lo, hi = ci * _GEN_ROWS, min((ci + 1) * _GEN_ROWS, rows)
        u = np.random.default_rng([seed, 0, ci]).standard_normal((hi - lo, n), dtype=np.float32)
        np.matmul(u, sVt, out=X[lo:hi])

    with ThreadPoolExecutor(max_workers=threads or min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(-(-rows // _GEN_ROWS))))
    return {"features": X, "label": np.zeros((rows,), np.float64)}
