"""Seeded blob data with planted classification labels.

Copied from ``chip_smoke.py`` (``blob_centres``, ``blobs``, ``make_labels``;
proven on the chip in PR 22) with the width taken from the configuration
instead of a module constant. Host numpy only: the program under test
receives a host-resident frame, as it does from Spark.

``make(seed, rows, cols, params)`` returns the frame's columns. Chunks are
drawn from independent seeded streams, so the thread count never changes the
data; the same seed gives the same bytes.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_GEN_ROWS = 1 << 14     # rows per generation chunk (own seeded stream each)
STRONG = (5.0, 4.0, 3.0)  # three planted directions (chip_smoke's)


def blob_centres(seed: int, kb: int, cols: int, spread: float) -> np.ndarray:
    """(kb, cols) f64 generating centres: isotropic N(0, spread²) plus three
    planted orthonormal directions scaled by STRONG."""
    rng = np.random.default_rng([seed, 0])
    q3, _ = np.linalg.qr(rng.standard_normal((cols, len(STRONG))))
    c = rng.standard_normal((kb, cols))
    c += (rng.standard_normal((kb, len(STRONG))) * np.asarray(STRONG)) @ q3.T
    return spread * c


def blobs(seed: int, rows: int, centres: np.ndarray, threads: int) -> np.ndarray:
    """rows × cols f32: centre of a uniformly drawn blob + N(0, 1) noise."""
    cols = centres.shape[1]
    X = np.empty((rows, cols), np.float32)
    c32 = centres.astype(np.float32)

    def fill(ci: int) -> None:
        lo, hi = ci * _GEN_ROWS, min((ci + 1) * _GEN_ROWS, rows)
        rng = np.random.default_rng([seed, 1, ci])
        b = rng.integers(0, len(c32), hi - lo, dtype=np.int32)
        x = rng.standard_normal((hi - lo, cols), dtype=np.float32)
        x += c32[b]
        X[lo:hi] = x

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(fill, range(-(-rows // _GEN_ROWS))))
    return X


def _matvec(X: np.ndarray, w: np.ndarray, threads: int) -> np.ndarray:
    out = np.empty((len(X),), np.float32)

    def part(ci: int) -> None:
        lo = ci * _GEN_ROWS
        out[lo : lo + _GEN_ROWS] = X[lo : lo + _GEN_ROWS] @ w

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(part, range(-(-len(X) // _GEN_ROWS))))
    return out


def make(seed: int, rows: int, cols: int, params: dict) -> dict:
    """Columns ``features`` (rows × cols f32) and ``label`` (rows f32).

    ``params``: ``centres`` (blob count), ``spread`` (centre scale over the
    within-blob sigma of 1), ``label``: ``"logistic"`` → y ~
    Bernoulli(sigmoid(Xv − 0.25)) with logits of about unit scale, so the
    classes are not separable.
    """
    threads = min(8, os.cpu_count() or 1)
    spread = float(params["spread"])
    centres = blob_centres(seed, int(params["centres"]), cols, spread)
    X = blobs(seed, rows, centres, threads)
    rng = np.random.default_rng([seed, 7])
    if params["label"] != "logistic":
        raise ValueError(f"unknown label kind {params['label']!r}")
    scale = np.sqrt(cols) * np.sqrt(1.0 + spread * spread)
    v = (rng.standard_normal(cols) / scale).astype(np.float32)
    logits = _matvec(X, v, threads) - np.float32(0.25)
    u = rng.random(rows, dtype=np.float32)
    y = (u < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return {"features": X, "label": y}
