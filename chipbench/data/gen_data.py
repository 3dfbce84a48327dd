"""The upstream benchmark's ``regression`` and ``classification`` data sets.

Copied from ``benchmark/gen_data.py`` (the repo's port of the reference's
``python/benchmark/gen_data.py``; ``bench_linear_regression.py`` and
``bench_logistic_regression.py`` feed their estimators these two, with the
generators' default parameters): the same structures and the same
distributions, parameter for parameter. What differs is how the bytes are
drawn: rows come in chunks of 2¹⁴ from independent seeded float32 streams,
filled by a few threads, so a 500,000 × 3000 frame takes seconds and the
thread count never changes the data. Host numpy only: the program under test
receives a host-resident frame, as it does from Spark.

``make(seed, rows, cols, params)`` returns the frame's columns: ``features``
(rows × cols float32) and ``label`` (rows float64, as ``make_dataframe``
hands it over).

* ``regression``: X iid N(0, 1); ``n_informative`` (default cols // 10)
  coefficients drawn uniformly from [0, 100), the rest nought;
  y = Xw + ``bias`` + ``noise``·N(0, 1).
* ``classification``: ``n_classes`` Gaussian clusters whose centres are
  N(0, (2·``class_sep``)²) on the first ``n_informative`` (default cols // 10)
  columns, unit noise on every column; the label is the cluster.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_GEN_ROWS = 1 << 14     # rows per generation chunk (own seeded stream each)


def _regression_struct(cols: int, seed: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    n_informative = int(params.get("n_informative") or max(1, cols // 10))
    w = np.zeros((cols,), np.float64)
    idx = rng.permutation(cols)[:n_informative]
    w[idx] = 100.0 * rng.random(n_informative)
    return {"w": w, "noise": float(params.get("noise", 1.0)), "bias": float(params.get("bias", 0.0))}


def _regression_chunk(s: dict, x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    rng.standard_normal(out=x, dtype=np.float32)
    y = np.zeros((len(x),), np.float64)
    for lo in range(0, len(x), 2048):       # float64 products without a float64 copy of the chunk
        y[lo : lo + 2048] = x[lo : lo + 2048].astype(np.float64) @ s["w"]
    return y + s["bias"] + s["noise"] * rng.standard_normal(len(x))


def _classification_struct(cols: int, seed: int, params: dict) -> dict:
    rng = np.random.default_rng(seed)
    k = int(params.get("n_classes", 2))
    n_informative = int(params.get("n_informative") or max(2, cols // 10))
    sep = float(params.get("class_sep", 1.0))
    centres = (rng.normal(size=(k, n_informative)) * 2 * sep).astype(np.float32)
    return {"centres": centres, "ni": n_informative, "k": k}


def _classification_chunk(s: dict, x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    lab = rng.integers(0, s["k"], len(x))
    rng.standard_normal(out=x, dtype=np.float32)
    x[:, : s["ni"]] += s["centres"][lab]
    return lab.astype(np.float64)


KINDS = {
    "regression": (_regression_struct, _regression_chunk),
    "classification": (_classification_struct, _classification_chunk),
}


def make(seed: int, rows: int, cols: int, params: dict) -> dict:
    struct_fn, chunk_fn = KINDS[params["kind"]]
    struct = struct_fn(cols, seed, params)
    X = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float64)

    def fill(ci: int) -> None:
        lo, hi = ci * _GEN_ROWS, min((ci + 1) * _GEN_ROWS, rows)
        y[lo:hi] = chunk_fn(struct, X[lo:hi], np.random.default_rng([seed, 0, ci]))

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(-(-rows // _GEN_ROWS))))
    return {"features": X, "label": y}
