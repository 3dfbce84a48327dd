"""Plain reference of ``pca_dbx``: principal components as Spark states them.

The model of ``PCA(k)`` over n rows is the k leading eigenpairs (λ_i, v_i) of
the sample covariance C = Σ (x − μ)(x − μ)ᵀ / (n − 1), μ the column means;
``pc`` is the d × k matrix of the v_i, each signed so that its entry of
largest magnitude is positive; ``explainedVariance`` is λ_i / trace(C); and
``transform`` returns ``x·pc`` for every row, **without** removing the mean
(Spark's ``PCAModel``; the upstream estimator adds the projected mean back to
cuML's centred output to say the same).

The reference imports nothing of the program and takes nothing it made: it is
given the frame's columns, the configuration, and what the timed jobs
returned, and it answers with numbers, each beside its limit.

Everything runs in row blocks (``_blocks.py``): the mean is a float64 sum on
the host; the centred Gram of a block is one float32 product at ``highest``
precision on the device; blocks are summed in float64 on the host, where the
rank-one term of the mean's own rounding to float32 is taken off again; the
eigen-decomposition is ``numpy.linalg.eigh`` in float64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import _blocks
from ._blocks import f64

NAMES = ("resid_err", "ortho_err", "top_err", "evr_err", "sign_err", "out_err", "repeat_err")


@functools.partial(jax.jit, static_argnames=("control",))
def _gram_block(xb, mean, control: bool):
    """Σ (x − mean)(x − mean)ᵀ of one block: f32 at ``highest``; the control's
    is one bf16 pass."""
    xc = xb.astype(jnp.float32) - mean[None, :]
    if control:
        xc = xc.astype(jnp.bfloat16)
        return jnp.matmul(xc.T, xc, preferred_element_type=jnp.float32)
    return jnp.matmul(xc.T, xc, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("control",))
def _project_block(xb, pc, control: bool):
    """x·pc of one block, no mean removed."""
    if control:
        return jnp.matmul(xb, pc.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    return jnp.matmul(xb, pc, precision=jax.lax.Precision.HIGHEST)


def signed(V: np.ndarray) -> np.ndarray:
    """Columns of ``V`` (d × k), each with its entry of largest magnitude positive."""
    picked = V[np.abs(V).argmax(axis=0), np.arange(V.shape[1])]
    return V * np.where(picked < 0, -1.0, 1.0)[None, :]


class Frame:
    """The frame on the device in blocks."""

    def __init__(self, X: np.ndarray, control: bool = False):
        self.control = control
        self.n, self.d = X.shape
        self.blocks = _blocks.place(X, control)

    def covariance(self, X: np.ndarray, rows=None):
        """(mean, C) in float64 over the first ``rows`` rows (default: all)."""
        n = int(rows or self.n)
        mean = np.zeros(self.d)
        for lo in range(0, n, _blocks.BLOCK):
            mean += X[lo : min(lo + _blocks.BLOCK, n)].sum(axis=0, dtype=np.float64)
        mean /= n
        m32 = mean.astype(np.float32)
        G, lo = np.zeros((self.d, self.d)), 0
        for xb in self.blocks:
            take = min(len(xb), n - lo)
            if take <= 0:
                break
            G += f64(_gram_block(xb[:take], jnp.asarray(m32), self.control))
            lo += take
        delta = mean - m32.astype(np.float64)          # Σ(x−m32)(x−m32)ᵀ = Σ(x−μ)(x−μ)ᵀ + n·δδᵀ
        return mean, (G - n * np.outer(delta, delta)) / (n - 1.0)

    def project(self, pc: np.ndarray) -> np.ndarray:
        """X·pc, all rows (float32 products, as the guarantee states the output)."""
        p = jnp.asarray(pc, jnp.float32)
        return np.concatenate([np.asarray(_project_block(xb, p, self.control)) for xb in self.blocks])


def leading(C: np.ndarray, k: int, skip: int = 0):
    """The k leading eigenpairs of C after the first ``skip``, descending, signed."""
    w, V = np.linalg.eigh(C)
    order = np.argsort(w)[::-1][skip : skip + k]
    return w[order], signed(V[:, order])


def _served(job: dict, k: int, d: int):
    m = job["model"]
    try:
        pc = f64(m["components"]).T
        lam, evr = f64(m["explained_variance"]).ravel(), f64(m["explained_variance_ratio"]).ravel()
    except (KeyError, ValueError):
        return None
    ok = pc.shape == (d, k) and lam.shape == (k,) and evr.shape == (k,) and all(np.isfinite(a).all() for a in (pc, lam, evr))
    return (pc, lam, evr) if ok else None


def check(config: dict, columns: dict, jobs: list) -> list:
    """Numbers compared, worst over the window's jobs: ``[(name, value), ...]``.

    With C the reference's covariance, (λ*, V*) its k leading pairs and
    (λ_i, v_i) as served. ``resid_err``: max_i ‖C·v_i − λ_i·v_i‖ / λ*_1 — a
    served pair is an eigenpair of the covariance of ALL rows, to float32.
    ``ortho_err``: largest entry of |VᵀV − I|. ``top_err``: (Σλ* − Σ v_iᵀ·C·v_i)
    / Σλ* — the served subspace carries the most variance k directions can (a
    wrong or lesser eigenpair has no residual, and shows here). ``evr_err``:
    largest relative gap of ``explainedVariance`` to λ*_i / trace(C).
    ``sign_err``: components whose entry of largest magnitude is not
    positive. ``out_err``: widest gap of a served output column to the
    reference's ``X·pc`` of the SERVED pc (no mean removed), over that
    column's rms; all rows. ``repeat_err``: largest difference between the
    components of any two jobs, over their rms. Jobs whose model and output
    are bit-identical are judged once."""
    X = columns["features"]
    k, d = int(config["estimator"]["params"]["k"]), X.shape[1]
    out_col = config["outputs"]["pca_features"]
    bad = [(name, float("inf")) for name in NAMES]
    served = [_served(job, k, d) for job in jobs]
    if any(s is None for s in served):
        return bad
    frame = Frame(X)
    _, C = frame.covariance(X)
    lam_ref, _ = leading(C, k)
    evr_ref = lam_ref / np.trace(C)
    worst = dict.fromkeys(NAMES, 0.0)
    judged: dict = {}
    pc0 = served[0][0]
    for job, (pc, lam, evr) in zip(jobs, served):
        out = np.asarray(job["outputs"][out_col])
        if out.shape != (len(X), k):
            return bad
        key = (pc.tobytes(), lam.tobytes(), evr.tobytes(), out.tobytes())
        if key not in judged:
            CV = C @ pc
            ref_out = frame.project(pc)
            judged[key] = {
                "resid_err": float(np.linalg.norm(CV - pc * lam[None, :], axis=0).max() / lam_ref[0]),
                "ortho_err": float(np.abs(pc.T @ pc - np.eye(k)).max()),
                "top_err": float((lam_ref.sum() - np.einsum("ij,ij->", pc, CV)) / lam_ref.sum()),
                "evr_err": float(np.abs(evr / evr_ref - 1.0).max()),
                "sign_err": float((pc[np.abs(pc).argmax(axis=0), np.arange(k)] <= 0).sum()),
                "out_err": float((np.abs(out - ref_out).max(axis=0) / np.sqrt((ref_out.astype(np.float64) ** 2).mean(axis=0))).max()),
            }
        for name, value in judged[key].items():
            worst[name] = max(worst[name], value)
        worst["repeat_err"] = max(worst["repeat_err"], float(np.abs(pc - pc0).max() / np.sqrt((pc0**2).mean())))
    return [(name, worst[name]) for name in NAMES]


def reference_job(config: dict, columns: dict, control: bool = False, fit_rows=None, skip: int = 0) -> dict:
    """The reference put in the program's place: what a timed job returns
    (model attributes, output columns), made by the reference alone. With
    ``control`` every product of the fit and of the transform is one bf16
    pass (X rounded to bf16 on its way up). With ``fit_rows`` the covariance
    sees only the first rows (the fault "half of the rows left out"); the
    transform is over all rows either way. With ``skip=1`` the last served
    pair is the (k+1)-th of the covariance, not the k-th (the fault "k−1
    right pairs and one wrong")."""
    X = columns["features"]
    k = int(config["estimator"]["params"]["k"])
    frame = Frame(X, control=control)
    mean, C = frame.covariance(X, fit_rows)
    lam, V = leading(C, k)
    if skip:
        lam_w, V_w = leading(C, 1, skip=k - 1 + skip)
        lam, V = np.concatenate([lam[:-1], lam_w]), np.concatenate([V[:, :-1], V_w], axis=1)
    n = int(fit_rows or frame.n)
    pc = V.astype(np.float32)
    return {
        "model": {
            "mean": mean.astype(np.float32),
            "components": np.ascontiguousarray(pc.T),
            "explained_variance": lam.astype(np.float32),
            "explained_variance_ratio": (lam / np.trace(C)).astype(np.float32),
            "singular_values": np.sqrt(lam * (n - 1.0)).astype(np.float32),
        },
        "outputs": {config["outputs"]["pca_features"]: frame.project(pc)},
    }
