"""Plain reference of ``logreg_dbx``: binary logistic regression as Spark
states it, solved by Newton's method, and its transform.

Objective (Spark ML / the upstream estimator, standardization on):

    F(w, b) = 1/n · Σ_i [softplus(z_i) − y_i z_i] + λ/2 · Σ_j (s_j w_j)²,
    z_i = x_i·w + b,  s_j the unbiased (n−1) standard deviation of column j,

the penalty on the standardized coefficients and never on the intercept.
Widened from ``chip_smoke.py``'s ``ref_logreg`` (which had no penalty). The
reference imports nothing of the program and takes nothing it made: it is
given the frame's columns, the configuration, and what the timed jobs
returned, and it answers with numbers, each beside its limit.

Everything runs in row blocks (``_blocks.py``) at ``highest`` precision;
sums over blocks and the (cols+1)² solves are float64 on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _blocks
from ._blocks import f64

NEWTON_STEPS = 4    # full Newton steps (Hessian rebuilt)
CHORD_STEPS = 12    # further steps on the last Hessian (gradient passes only)


def _mv(xb, v, control: bool):
    if control:
        return jnp.matmul(xb, v.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.matmul(xb, v, precision=jax.lax.Precision.HIGHEST)


def _tmv(xb, r, control: bool):
    if control:
        return jnp.matmul(r.astype(jnp.bfloat16), xb, preferred_element_type=jnp.float32)
    return jnp.matmul(r, xb, precision=jax.lax.Precision.HIGHEST)


@jax.jit
def _col_sums(xb):
    return xb.astype(jnp.float32).sum(axis=0)


@jax.jit
def _col_sq(xb, mean):
    x = xb.astype(jnp.float32) - mean[None, :]
    return (x * x).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("control",))
def _grad_block(xb, yb, w, b, control: bool):
    """Σ x(p−y), Σ (p−y) and the logits of one block."""
    z = _mv(xb, w, control) + b
    r = jax.nn.sigmoid(z) - yb
    return _tmv(xb, r, control), r.sum(), z


@functools.partial(jax.jit, static_argnames=("control",))
def _hess_block(xb, w, b, H, control: bool):
    """H += [x|1]ᵀ diag(p(1−p)) [x|1] of one block (f32 accumulate)."""
    z = _mv(xb, w, control) + b
    p = jax.nn.sigmoid(z)
    q = (p * (1.0 - p))[:, None]
    xa = jnp.concatenate([xb.astype(jnp.float32), jnp.ones((xb.shape[0], 1), jnp.float32)], axis=1)
    prec = None if control else jax.lax.Precision.HIGHEST
    return H + jnp.matmul((xa * q).T, xa, precision=prec)


class Problem:
    """The frame on the device in blocks, with its column moments."""

    def __init__(self, columns: dict, config: dict, control: bool = False):
        X, y = columns["features"], columns["label"]
        self.control = control
        self.n, self.d = X.shape
        self.lam = float(config["estimator"]["params"]["regParam"])
        self.blocks = _blocks.place(X, control)
        self.y = [jnp.asarray(y[lo : lo + _blocks.BLOCK]) for lo in range(0, self.n, _blocks.BLOCK)]
        self.y_host = np.asarray(y, np.float64)
        mean = sum(f64(_col_sums(xb)) for xb in self.blocks) / self.n
        m32 = jnp.asarray(mean, jnp.float32)
        sq = sum(f64(_col_sq(xb, m32)) for xb in self.blocks)
        self.mean = mean
        self.std = np.sqrt(sq / (self.n - 1.0))

    def grad(self, w: np.ndarray, b: float):
        """F, ∇F in (w, b) and all logits at (w, b); float64 on the host."""
        w32, b32 = jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32)
        gw, gb, zs = np.zeros(self.d), 0.0, []
        for xb, yb in zip(self.blocks, self.y):
            g, r, z = _grad_block(xb, yb, w32, b32, self.control)
            gw, gb = gw + f64(g), gb + float(r)
            zs.append(np.asarray(z))
        s2 = self.std**2
        z = np.concatenate(zs)
        z64, y64 = z.astype(np.float64), self.y_host
        F = float((np.logaddexp(0.0, z64) - y64 * z64).mean()) + 0.5 * self.lam * float((s2 * w * w).sum())
        g = np.concatenate([gw / self.n + self.lam * s2 * w, [gb / self.n]])
        return F, g, z

    def hessian(self, w: np.ndarray, b: float) -> np.ndarray:
        w32, b32 = jnp.asarray(w, jnp.float32), jnp.asarray(b, jnp.float32)
        H = jnp.zeros((self.d + 1, self.d + 1), jnp.float32)
        for xb in self.blocks:
            H = _hess_block(xb, w32, b32, H, self.control)
        H = f64(H) / self.n
        H[np.arange(self.d), np.arange(self.d)] += self.lam * self.std**2
        return H

    def std_norm(self, g: np.ndarray) -> float:
        """‖∇F‖ in the standardized parametrization the penalty is stated in."""
        return float(np.sqrt(((g[:-1] / self.std) ** 2).sum() + g[-1] ** 2))

    def solve(self):
        """Newton from zero, then chord steps while the gradient still falls."""
        theta = np.zeros(self.d + 1)
        best, H = None, None
        for step in range(NEWTON_STEPS + CHORD_STEPS):
            F, g, _ = self.grad(theta[:-1], theta[-1])
            gn = self.std_norm(g)
            if best is not None and gn >= best[0]:
                break
            best = (gn, theta.copy(), F)
            if step < NEWTON_STEPS:
                H = self.hessian(theta[:-1], theta[-1])
            theta = theta - np.linalg.solve(H, g)
        return best[1][:-1], float(best[1][-1]), best[0], best[2]


def _out_err(outputs: dict, z_ref: np.ndarray, names: dict) -> float:
    """Widest gap of the served columns to the reference's at the same model:
    rawPrediction over the logits' rms, probability as it is, and a prediction
    on the wrong side counts 1 where the reference is not within 0.01 of 0.5."""
    raw = np.asarray(outputs[names["raw"]], np.float64)
    prob = np.asarray(outputs[names["probability"]], np.float64)
    pred = np.asarray(outputs[names["prediction"]], np.float64)
    n = len(z_ref)
    if raw.shape != (n, 2) or prob.shape != (n, 2) or pred.shape != (n,):
        return float("inf")
    z = z_ref.astype(np.float64)
    rms = float(np.sqrt((z * z).mean()))
    p1 = 1.0 / (1.0 + np.exp(-z))
    e_raw = max(np.abs(raw[:, 1] - z).max(), np.abs(raw[:, 0] + z).max()) / rms
    e_prob = max(np.abs(prob[:, 1] - p1).max(), np.abs(prob[:, 0] - (1.0 - p1)).max())
    sure = np.abs(p1 - 0.5) > 0.01
    e_pred = float(((pred != (p1 > 0.5)) & sure).any())
    return float(max(e_raw, e_prob, e_pred))


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    ``obj_excess``: (F(served model) − F*) / F*, F the stated objective over
    all rows (float64 on the host from float32 logits) and F* its value at the
    reference's Newton optimum. ``out_err``: the served columns against the
    reference's transform of the served model."""
    prob = Problem(columns, config)
    names = config["outputs"]
    F_ref = prob.solve()[3]
    obj_excess, out_err = 0.0, 0.0
    for job in jobs:
        w = f64(job["model"]["coef_"]).reshape(-1)
        b = float(f64(job["model"]["intercept_"]).reshape(-1)[0])
        if w.shape != (prob.d,) or not np.isfinite(w).all():
            return [("obj_excess", float("inf")), ("out_err", float("inf"))]
        F, _, z = prob.grad(w, b)
        obj_excess = max(obj_excess, (F - F_ref) / F_ref)
        out_err = max(out_err, _out_err(job["outputs"], z, names))
    return [("obj_excess", obj_excess), ("out_err", out_err)]


def reference_job(config: dict, columns: dict, control: bool = False, fit_rows=None) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, output columns), made by the reference alone. With
    ``control`` in bfloat16: X rounded to bf16 on its way up, every product one
    bf16 pass with f32 accumulation. With ``fit_rows`` the fit sees only the
    first rows (the fault "half of the rows left out"); the transform is of
    all rows either way."""
    prob = Problem(columns, config, control=control)
    fit = Problem({k: v[:fit_rows] for k, v in columns.items()}, config, control=control) if fit_rows else prob
    w, b, _, _ = fit.solve()
    del fit
    _, _, z = prob.grad(w, b)
    z = z.astype(np.float32)
    p1 = 1.0 / (1.0 + np.exp(-z))
    names = config["outputs"]
    return {
        "model": {"coef_": w[None, :].astype(np.float32), "intercept_": np.asarray([b], np.float32)},
        "outputs": {
            names["raw"]: np.stack([-z, z], axis=1),
            names["probability"]: np.stack([1.0 - p1, p1], axis=1),
            names["prediction"]: (p1 > 0.5).astype(np.float32),
        },
    }
