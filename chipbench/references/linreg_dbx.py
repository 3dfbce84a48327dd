"""Plain reference of ``linreg_dbx``: ordinary least squares with an
intercept (regParam = 0), and its transform.

Copied from ``chip_smoke.py`` (``host_moments_f64`` + ``ref_linreg``): the
moments Σ[x|y] and [x|y]ᵀ[x|y] over all rows, then float64 least squares on
the centred normal equations. The float64 host pass of chip_smoke would take
minutes at 500,000 × 3001 (9 × 10¹² FLOP), so the Gram is taken per row block
on the device at ``highest`` precision — each block's sum in float32 over
2¹⁴ rows — and the blocks are added in float64 on the host.

The reference imports nothing of the program and takes nothing it made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _blocks
from ._blocks import f64


@functools.partial(jax.jit, static_argnames=("control",))
def _moments_block(xb, yb, control: bool):
    """Σ[x|y] and [x|y]ᵀ[x|y] of one block."""
    if control:
        z = jnp.concatenate([xb, yb.astype(jnp.bfloat16)[:, None]], axis=1)
        G = jnp.matmul(z.T, z, preferred_element_type=jnp.float32)
    else:
        z = jnp.concatenate([xb, yb[:, None]], axis=1)
        G = jnp.matmul(z.T, z, precision=jax.lax.Precision.HIGHEST)
    return z.astype(jnp.float32).sum(axis=0), G


@functools.partial(jax.jit, static_argnames=("control",))
def _predict_block(xb, w, b, control: bool):
    if control:
        return jnp.matmul(xb, w.astype(jnp.bfloat16), preferred_element_type=jnp.float32) + b
    return jnp.matmul(xb, w, precision=jax.lax.Precision.HIGHEST) + b


class Problem:
    """The frame on the device in blocks, with its moments."""

    def __init__(self, columns: dict, config: dict, control: bool = False):
        X, y = columns["features"], columns["label"]
        self.control = control
        self.n, self.d = X.shape
        self.blocks = _blocks.place(X, control)
        self.y = [jnp.asarray(y[lo : lo + _blocks.BLOCK]) for lo in range(0, self.n, _blocks.BLOCK)]
        s, G = np.zeros(self.d + 1), np.zeros((self.d + 1, self.d + 1))
        for xb, yb in zip(self.blocks, self.y):
            sb, Gb = _moments_block(xb, yb, control)
            s, G = s + f64(sb), G + f64(Gb)
        m = s / self.n
        self.Gc = (G - self.n * np.outer(m, m)) / self.n     # centred, per row
        self.m = m

    def solve(self):
        """float64 least squares on the centred normal equations."""
        d = self.d
        beta = np.linalg.lstsq(self.Gc[:d, :d], self.Gc[:d, d], rcond=None)[0]
        return beta, float(self.m[d] - self.m[:d] @ beta)

    def rss(self, beta: np.ndarray) -> float:
        """Mean squared residual of the centred fit, from the moments."""
        d = self.d
        return float(self.Gc[d, d] - 2.0 * beta @ self.Gc[:d, d] + beta @ self.Gc[:d, :d] @ beta)

    def predict(self, beta: np.ndarray, icpt: float) -> np.ndarray:
        w32, b32 = jnp.asarray(beta, jnp.float32), jnp.asarray(icpt, jnp.float32)
        return np.concatenate([np.asarray(_predict_block(xb, w32, b32, self.control)) for xb in self.blocks])


def _gap(pred: np.ndarray, want: np.ndarray) -> float:
    want = want.astype(np.float64)
    return float(np.abs(pred - want).max() / np.sqrt((want * want).mean()))


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    ``rss_excess``: (RSS(served model) − RSS*) / RSS*, the mean squared
    residual over all rows from the float64 moments (a served intercept off
    the one its coefficients imply adds its square) and RSS* at the
    reference's least-squares fit. ``out_err``: widest gap of the served
    predictions to the reference's transform of the served model, over the
    predictions' rms."""
    prob = Problem(columns, config)
    name = config["outputs"]["prediction"]
    rss_ref = prob.rss(prob.solve()[0])
    rss_excess, out_err = 0.0, 0.0
    for job in jobs:
        beta = f64(job["model"]["coefficients"]).reshape(-1)
        icpt = float(np.asarray(job["model"]["intercept"]))
        pred = np.asarray(job["outputs"][name], np.float64)
        if beta.shape != (prob.d,) or pred.shape != (prob.n,) or not np.isfinite(beta).all():
            return [("rss_excess", float("inf")), ("out_err", float("inf"))]
        icpt_gap = icpt - (prob.m[prob.d] - prob.m[: prob.d] @ beta)
        rss_excess = max(rss_excess, (prob.rss(beta) + icpt_gap**2 - rss_ref) / rss_ref)
        out_err = max(out_err, _gap(pred, prob.predict(beta, icpt)))
    return [("rss_excess", rss_excess), ("out_err", out_err)]


def reference_job(config: dict, columns: dict, control: bool = False, fit_rows=None) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, output columns), made by the reference alone. With
    ``control`` in bfloat16: X rounded to bf16 on its way up, Gram and
    predictions one bf16 pass with f32 accumulation. With ``fit_rows`` the fit
    sees only the first rows (the fault "half of the rows left out"); the
    transform is of all rows either way."""
    prob = Problem(columns, config, control=control)
    fit = Problem({k: v[:fit_rows] for k, v in columns.items()}, config, control=control) if fit_rows else prob
    beta, icpt = fit.solve()
    del fit
    return {
        "model": {"coefficients": beta.astype(np.float32), "intercept": np.float32(icpt)},
        "outputs": {config["outputs"]["prediction"]: prob.predict(beta, icpt)},
    }
