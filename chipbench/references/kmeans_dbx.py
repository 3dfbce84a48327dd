"""Plain reference of ``kmeans_dbx``: Lloyd's algorithm as Spark states it.

From k distinct rows of the frame, ``maxIter`` times: every row goes to its
nearest centre (squared euclidean distance), every centre becomes the mean of
its rows, and **an empty cluster keeps its centre** (Spark's rule). ``tol`` is
1e-20, so iterations end before ``maxIter`` only at a fixed point, where no
centre moves at all (on well-separated blobs that is reached in 10 to 15
iterations). The cost of a set of centres is F(c) = Σ_i ‖x_i − c_a(i)‖², a(i)
the nearest centre.

The reference imports nothing of the program and takes nothing it made: it is
given the frame's columns, the configuration, and what the timed jobs
returned, and it answers with numbers, each beside its limit. **One thing is
taken from the program's contract, copied here and not imported:** which rows
``initMode="random"`` starts from for the configuration's ``seed`` —
``numpy.random.default_rng(seed).choice(rows, k, replace=False)``, in
ascending row order (:func:`initial_rows`). Without it no two Lloyd runs from
random rows could be compared at all.

Everything runs in row blocks (``_blocks.py``) in plain ``jax.numpy``: f32
products at ``highest`` precision; the nearest centre is found through the
expansion ‖c‖² − 2x·c (an argmin needs no more), but a distance that is
*summed* is always ‖x − c‖² from the differences, never the expansion; sums
over blocks (the centres' sums and counts, F) are float64 on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _blocks
from ._blocks import f64


def _xc(xb, c, control: bool):
    """x·cᵀ of one block: f32 at ``highest``; the control's is one bf16 pass."""
    if control:
        return jnp.matmul(xb.astype(jnp.bfloat16), c.T.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.matmul(xb, c.T, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("control",))
def _lloyd_block(xb, c, c_sq, control: bool):
    """One block's share of a Lloyd iteration: Σ x and the row count per
    nearest centre (f32; the one-hot is exact in any precision)."""
    a = jnp.argmin(c_sq[None, :] - 2.0 * _xc(xb, c, control), axis=1)
    onehot = jax.nn.one_hot(a, c.shape[0], dtype=xb.dtype)
    if control:
        sums = jnp.matmul(onehot.T, xb, preferred_element_type=jnp.float32)
    else:
        sums = jnp.matmul(onehot.T, xb.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    return sums, onehot.astype(jnp.float32).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("control",))
def _nearest_block(xb, c, c_sq, pred, control: bool):
    """Per row: the nearest centre, ‖x − c‖² to it from the differences, how
    much farther (by the expansion) the centre ``pred`` names lies than the
    nearest (inf where ``pred`` names none), and ‖x‖²."""
    x = xb.astype(jnp.float32)
    part = c_sq[None, :] - 2.0 * _xc(xb, c, control)
    a = jnp.argmin(part, axis=1)
    named = jnp.take_along_axis(part, jnp.clip(pred, 0, c.shape[0] - 1)[:, None], axis=1)[:, 0]
    farther = jnp.where((pred >= 0) & (pred < c.shape[0]), named - jnp.min(part, axis=1), jnp.inf)
    diff = x - c[a]
    return a.astype(jnp.int32), (diff * diff).sum(axis=1), farther, (x * x).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("control",))
def _expansion_cost_block(xb, c, c_sq, control: bool):
    """Σ over the block of min_j max(‖x‖² − 2x·c_j + ‖c_j‖², 0): the cost as a
    program reports it, from the expansion (``reference_job`` only)."""
    x = xb.astype(jnp.float32)
    part = c_sq[None, :] - 2.0 * _xc(xb, c, control)
    return jnp.maximum(jnp.min(part, axis=1) + (x * x).sum(axis=1), 0.0).sum()


def initial_rows(config: dict, rows: int) -> np.ndarray:
    """The program's contract for ``initMode="random"``, copied: k distinct
    rows drawn by ``default_rng(seed).choice(rows, k, replace=False)``,
    gathered in ascending order."""
    params = config["estimator"]["params"]
    if params.get("initMode") != "random":
        raise ValueError("the reference states initMode='random' only")
    return np.sort(np.random.default_rng(int(params["seed"])).choice(rows, int(params["k"]), replace=False))


class Frame:
    """The frame on the device in blocks."""

    def __init__(self, X: np.ndarray, control: bool = False):
        self.control = control
        self.n, self.d = X.shape
        self.blocks = _blocks.place(X, control)

    def _centres(self, c: np.ndarray):
        c32 = jnp.asarray(c, jnp.float32)
        return c32, (c32 * c32).sum(axis=1)

    def step(self, c: np.ndarray) -> np.ndarray:
        """One Lloyd iteration over all rows from centres ``c`` (float64, host)."""
        c32, c_sq = self._centres(c)
        parts = [_lloyd_block(xb, c32, c_sq, self.control) for xb in self.blocks]
        sums, counts = np.zeros_like(c), np.zeros(len(c))
        for s, n in parts:
            sums, counts = sums + f64(s), counts + f64(n)
        return np.where(counts[:, None] > 0, sums / np.maximum(counts, 1.0)[:, None], c)

    def lloyd(self, c: np.ndarray, iterations: int) -> np.ndarray:
        """``iterations`` Lloyd iterations from centres ``c``."""
        c = f64(c)
        for _ in range(iterations):
            moved = self.step(c)
            if np.array_equal(moved, c):   # a fixed point: every further iteration returns it again
                break
            c = moved
        return c

    def nearest(self, c: np.ndarray, pred=None):
        """All rows against centres ``c``: assignment; F (float64 sum of the
        per-row ‖x − c‖²); how much farther than the nearest the centre
        ``pred`` names lies, per row; ‖x‖²."""
        c32, c_sq = self._centres(c)
        pred = np.zeros(self.n, np.int32) if pred is None else np.asarray(pred).astype(np.int32)
        parts, lo = [], 0
        for xb in self.blocks:
            parts.append(_nearest_block(xb, c32, c_sq, jnp.asarray(pred[lo : lo + len(xb)]), self.control))
            lo += len(xb)
        a, d2, farther, x_sq = (np.concatenate([np.asarray(p[i]) for p in parts]) for i in range(4))
        return a, float(d2.astype(np.float64).sum()), farther, x_sq

    def expansion_cost(self, c: np.ndarray) -> float:
        c32, c_sq = self._centres(c)
        return float(sum(float(_expansion_cost_block(xb, c32, c_sq, self.control)) for xb in self.blocks))


def _served_centres(job: dict, k: int, d: int):
    c = np.asarray(job["model"].get("cluster_centers"), np.float64)
    return c if c.shape == (k, d) and np.isfinite(c).all() else None


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    ``cost_err``: |trainingCost − F(served centres)| / F. ``out_err``: share
    of rows whose served prediction is not the nearest served centre, a row
    not counted where the centre it names lies within ``tie_margin``·‖x‖² of
    the nearest (two centres inside one blob: the expansion in float32 cannot
    tell them apart, and no product of the MXU can). ``obj_excess``: (F(served) − F(reference's centres
    after maxIter iterations from the same rows)) / that. ``repeat_err``:
    largest difference between the centres of any two jobs, over the centres'
    rms. Jobs whose centres and predictions are bit-identical are judged
    once. ``step_err``: one more Lloyd iteration over all rows from the served
    centres moves the median centre by this much, ‖Δc‖² over the columns — a
    model fitted over all rows is a fixed point but for the rows a reduced
    product assigns otherwise; one fitted on a part of the rows is not."""
    X = columns["features"]
    params = config["estimator"]["params"]
    k, margin = int(params["k"]), float(config["tie_margin"])
    pred_col = config["outputs"]["prediction"]
    bad = [(name, float("inf")) for name in ("cost_err", "out_err", "obj_excess", "repeat_err", "step_err")]
    served = [_served_centres(job, k, X.shape[1]) for job in jobs]
    if any(c is None for c in served):
        return bad
    frame = Frame(X)
    c_ref = frame.lloyd(f64(X[initial_rows(config, len(X))]), int(params["maxIter"]))
    F_ref = frame.nearest(c_ref)[1]
    judged: dict = {}
    moves: dict = {}
    cost_err = out_err = repeat_err = step_err = 0.0
    obj_excess = -float("inf")
    scale = float(np.sqrt((served[0] ** 2).mean()))
    for job, c in zip(jobs, served):
        pred = np.asarray(job["outputs"][pred_col])
        if pred.shape != (len(X),):
            return bad
        key = (c.tobytes(), pred.tobytes())
        if key not in judged:
            judged[key] = frame.nearest(c, pred)
        a, F, farther, x_sq = judged[key]
        cost_err = max(cost_err, abs(float(job["model"]["training_cost"]) - F) / F)
        obj_excess = max(obj_excess, (F - F_ref) / F_ref)
        out_err = max(out_err, float(((pred != a) & (farther >= margin * x_sq)).mean()))
        repeat_err = max(repeat_err, float(np.abs(c - served[0]).max()) / scale)
        if key[0] not in moves:
            moves[key[0]] = float(np.median(((frame.step(c) - c) ** 2).sum(axis=1))) / X.shape[1]
        step_err = max(step_err, moves[key[0]])
    return [("cost_err", cost_err), ("out_err", out_err), ("obj_excess", obj_excess), ("repeat_err", repeat_err), ("step_err", step_err)]


def reference_job(config: dict, columns: dict, control: bool = False, fit_rows=None) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, output columns), made by the reference alone — the
    cost from the expansion, as a program reports it. With ``control`` every
    product is one bf16 pass (X rounded to bf16 on its way up). With
    ``fit_rows`` the Lloyd iterations see only the first rows (the fault
    "half of the rows left out"; the starting rows are drawn among them); the
    cost and the transform are over all rows either way. The fault "stopped
    early" is this function under a configuration with a smaller ``maxIter``."""
    X = columns["features"]
    params = config["estimator"]["params"]
    frame = Frame(X, control=control)
    fit = Frame(X[:fit_rows], control=control) if fit_rows else frame
    c = fit.lloyd(f64(X[initial_rows(config, fit.n)]), int(params["maxIter"]))
    del fit
    c32 = c.astype(np.float32)
    a = frame.nearest(c32)[0]
    return {
        "model": {
            "cluster_centers": c32,
            "training_cost": np.float32(frame.expansion_cost(c32)),
            "n_iter": np.asarray(int(params["maxIter"])),
        },
        "outputs": {config["outputs"]["prediction"]: a.astype(np.int32)},
    }
