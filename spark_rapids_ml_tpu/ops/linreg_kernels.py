"""Linear-regression device kernels: sufficient statistics + solvers.

TPU-native replacement for the reference's three cuML solver classes
(``/root/reference/python/src/spark_rapids_ml/regression.py:502-559``:
``LinearRegressionMG`` eig for OLS, ``RidgeMG`` with the alpha×M Spark
scaling, ``CDMG`` coordinate descent for elasticnet).

Design: ONE distributed pass over the dp-sharded design matrix computes the
weighted centered sufficient statistics (Gram d×d, X'y, y'y, moments). A
resident unweighted fit in one process folds them block by block under the
frame's crossing with PCA's Gram (``ops.linalg.GramFold``, the label beside
it) and :func:`_linreg_finish` turns the folds into them; every other fit
takes one pass over the placed frame (:func:`linreg_suffstats`,
:func:`linreg_suffstats_chunked`). Every product of X is float32
(``Precision.HIGHEST``): the statistics are the result. Every solver then
works on the replicated d×d system: OLS/ridge are a Cholesky solve with no
added ridge (a least-norm eigen-solve where the system is singular),
elasticnet is FISTA on the quadratic form — O(d²) per iteration with NO
further data passes or collectives (cuML's CD re-reads the data every
iteration; for the reference's d≈3000 benchmark shape this is strictly less
communication).

Spark objective parity: 1/(2n)·Σ wᵢ(yᵢ - x·β - b)² + λ[(1-α)/2‖β‖₂² + α‖β‖₁]
with the penalty applied to standardized coefficients when
``standardization=True`` (Spark MLlib semantics the reference matches via
the alpha×M rescale, ``regression.py:530-537``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .linalg import moments_from_gram_folds

_HIGHEST = lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("fit_intercept",))
def linreg_suffstats(
    X: jax.Array,
    mask: jax.Array,
    y: jax.Array,
    row_w: Optional[jax.Array] = None,
    *,
    fit_intercept: bool = True,
) -> Dict[str, jax.Array]:
    """Weighted centered sufficient statistics in one pass.

    Returns dict with n (Σw), mean_x, mean_y, G=(Xc√w)'(Xc√w), Xy, yy, var.
    Centering before the Gram keeps f32 stable (see ops/linalg.py).
    """
    w = mask if row_w is None else mask * row_w
    n = w.sum()
    mean_all = (X * w[:, None]).sum(axis=0) / n  # true feature means
    if fit_intercept:
        mean_x = mean_all
        mean_y = (y * w).sum() / n
    else:
        mean_x = jnp.zeros((X.shape[1],), X.dtype)
        mean_y = jnp.asarray(0.0, X.dtype)
    sw = jnp.sqrt(w)
    Xc = (X - mean_x[None, :]) * sw[:, None]
    yc = (y - mean_y) * sw
    G = jnp.matmul(Xc.T, Xc, precision=_HIGHEST)
    Xy = jnp.matmul(Xc.T, yc, precision=_HIGHEST)
    yy = (yc * yc).sum()
    # penalty scaling always uses the true (centered) variance, even when
    # fit_intercept=False leaves G uncentered: diag(G)/n is then E[x²], so
    # subtract mean² (matches Spark's std-based penalty semantics)
    var = jnp.diagonal(G) / n
    if not fit_intercept:
        var = var - mean_all * mean_all
    return {
        "n": n, "mean_x": mean_x, "mean_y": mean_y,
        "G": G, "Xy": Xy, "yy": yy, "var": var,
    }


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "csize", "fit_intercept", "weighted", "mp_blocks"),
)
def linreg_suffstats_chunked(
    X: jax.Array,
    mask: jax.Array,
    y: jax.Array,
    row_w: Optional[jax.Array] = None,
    *,
    mesh,
    csize: int,
    fit_intercept: bool = True,
    weighted: bool = False,
    mp_blocks: bool = False,
) -> Dict[str, jax.Array]:
    """:func:`linreg_suffstats` with O(csize·d) temporaries and one pass.

    Same memory/stability design as ``ops.linalg.mean_and_cov_chunked``: the
    fused form can materialize the centered √w-scaled copy of X at
    double-digit-GB row counts and OOM; here each device scans fixed
    ``csize`` row chunks, accumulating statistics shifted by a mean
    *estimate* (from the device's leading rows, one cheap psum), and exact
    rank-1 corrections re-center at the true weighted means. With
    ``fit_intercept=False`` the solver statistics (G, Xy, yy) accumulate
    uncentered for parity with the resident path, while the penalty
    variance still uses the shifted accumulator — stable where the
    resident ``E[x²] - mean²`` form cancels catastrophically for |μ| ≫ σ.

    Requires per-device rows divisible by ``csize``; rows sharded over dp.

    Which fits take it: a weighted fit, one of several processes, or one
    whose Gram is column-blocked over mp; a resident unweighted fit in one
    process folds its statistics under the frame's crossing through PCA's
    Gram instead (``models.regression``). No chip reading of this scan at
    ``Precision.HIGHEST`` exists; the benchmark's cell runs the fold.

    With ``mp_blocks`` (gate via ``ops.linalg.mp_gram_blocks`` — env read
    outside jit) the d×d Gram accumulates as each device's own (d, d/mp)
    column block, psum over dp only, returned column-sharded over mp
    (``LAYOUT.cols()``) — same SUMMA panel product as the blocked
    covariance. The d-vector statistics (Xy, sums, variance) stay
    replicated: they are O(d), not O(d²).
    """
    from jax import shard_map
    from ..parallel.layout import LAYOUT
    from ..parallel.mesh import DP_AXIS, MP_AXIS
    from .linalg import check_row_chunking, row_chunk

    if not weighted:
        row_w = None

    n_mp = int(mesh.shape.get(MP_AXIS, 1)) if mp_blocks else 1
    if n_mp > 1 and X.shape[1] % n_mp != 0:
        raise ValueError(
            f"blocked Gram requires feature width ({X.shape[1]}) divisible "
            f"by the mp extent ({n_mp}); gate with mp_gram_blocks"
        )
    bw = X.shape[1] // n_mp

    def per_device(Xl, ml, yl, *rw):
        d = Xl.shape[1]
        wl = ml if not rw else ml * rw[0]
        # column-block start of THIS device's Gram panel (0 at mp=1)
        blk0 = lax.axis_index(MP_AXIS) * bw if n_mp > 1 else 0

        # mean estimate from each device's leading rows — shifts the
        # sum/variance accumulators ALWAYS (stable var even in the
        # uncentered fit), and the G/Xy/yy accumulators only when the fit
        # centers (fit_intercept); uncentered solver statistics must stay
        # uncentered for parity
        e = min(csize, Xl.shape[0])
        w0 = wl[:e]
        sx0 = lax.psum((Xl[:e] * w0[:, None]).sum(axis=0), DP_AXIS)
        sy0 = lax.psum((yl[:e] * w0).sum(), DP_AXIS)
        c0 = jnp.maximum(lax.psum(w0.sum(), DP_AXIS), 1.0)
        mu_x, mu_y = sx0 / c0, sy0 / c0

        nc = check_row_chunking(Xl.shape[0], csize)

        def body(i, carry):
            sx, sy, vs, W, G, Xy, yy = carry
            x, w, yv = row_chunk(i, csize, Xl, wl, yl)
            sqw = jnp.sqrt(w)
            xd = x - mu_x[None, :]
            xs = (xd if fit_intercept else x) * sqw[:, None]
            ys = ((yv - mu_y) if fit_intercept else yv) * sqw
            xdw = xd * sqw[:, None]
            xb = (
                lax.dynamic_slice_in_dim(xs, blk0, bw, 1)
                if n_mp > 1
                else xs
            )
            return (
                sx + (xdw * sqw[:, None]).sum(axis=0),  # Σ w (x-μ̂x)
                sy + ((yv - mu_y) * w).sum(),           # Σ w (y-μ̂y)
                vs + (xdw * xdw).sum(axis=0),           # Σ w (x-μ̂x)²
                W + w.sum(),
                G + jnp.matmul(xs.T, xb, precision=_HIGHEST),
                Xy + jnp.matmul(xs.T, ys, precision=_HIGHEST),
                yy + (ys * ys).sum(),
            )

        zero = functools.partial(jnp.zeros, dtype=Xl.dtype)
        sx, sy, vs, W, G, Xy, yy = lax.fori_loop(
            0,
            nc,
            body,
            (
                zero((d,)), zero(()), zero((d,)), zero(()),
                zero((d, bw)), zero((d,)), zero(()),
            ),
        )
        sx = lax.psum(sx, DP_AXIS)
        sy = lax.psum(sy, DP_AXIS)
        vs = lax.psum(vs, DP_AXIS)
        n = lax.psum(W, DP_AXIS)
        G = lax.psum(G, DP_AXIS)
        Xy = lax.psum(Xy, DP_AXIS)
        yy = lax.psum(yy, DP_AXIS)

        dx, dy = sx / n, sy / n
        var = vs / n - dx * dx             # shifted: stable for any |μ|
        dx_b = (
            lax.dynamic_slice_in_dim(dx, blk0, bw, 0) if n_mp > 1 else dx
        )
        if fit_intercept:
            # re-center the shifted statistics at the true weighted means
            G = G - n * jnp.outer(dx, dx_b)
            Xy = Xy - n * dx * dy
            yy = yy - n * dy * dy
            mean_x, mean_y = mu_x + dx, mu_y + dy
        else:
            mean_x = jnp.zeros((d,), Xl.dtype)
            mean_y = jnp.asarray(0.0, Xl.dtype)
        return n, mean_x, mean_y, G, Xy, yy, var

    args = (X, mask, y) + ((row_w,) if row_w is not None else ())
    in_specs = (LAYOUT.rows(),) * len(args)
    g_spec = LAYOUT.cols() if n_mp > 1 else LAYOUT.replicated()
    n, mean_x, mean_y, G, Xy, yy, var = shard_map(
        per_device,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated(), g_spec, LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated()),
        check_vma=False,
    )(*args)
    return {
        "n": n, "mean_x": mean_x, "mean_y": mean_y,
        "G": G, "Xy": Xy, "yy": yy, "var": var,
    }


def _to_standardized(stats: Dict[str, jax.Array], standardization: bool):
    """Scale the quadratic system into standardized-coefficient space."""
    std = jnp.sqrt(jnp.maximum(stats["var"], 0.0))
    safe = jnp.where(std > 0, std, 1.0)
    if standardization:
        G = stats["G"] / jnp.outer(safe, safe)
        Xy = stats["Xy"] / safe
    else:
        G = stats["G"]
        Xy = stats["Xy"]
    return G, Xy, std, safe


def _least_norm_host(A, b):
    """The least-norm solution of ``A β = b`` (A symmetric positive
    semi-definite) by LAPACK's ``eigh`` in float64 on the host, dropping the
    eigenvalues under ``d·eps·λ_max`` (eps of A's dtype): cuML's ``eig``
    algorithm, which the reference's ``LinearRegressionMG`` runs."""
    a = np.asarray(A, np.float64)
    w, V = np.linalg.eigh(0.5 * (a + a.T))
    keep = w > a.shape[0] * np.finfo(np.asarray(A).dtype).eps * max(float(w.max()), 0.0)
    Vk = V[:, keep]
    return (Vk @ ((Vk.T @ np.asarray(b, np.float64)) / w[keep])).astype(np.asarray(b).dtype)


def _normal_system(stats: Dict[str, jax.Array], l2: jax.Array, standardization: bool):
    """``(A, b, std, safe)``: the normal equations ``A β = b`` with ``A =
    G/n + λ₂I`` and ``b = Xy/n`` exactly as Spark states them — no ridge is
    added. A column whose (centred) Gram entry is exactly zero is constant:
    its row of the system is replaced by ``β_i = 0``, its least-norm value."""
    n = stats["n"]
    G, Xy, std, safe = _to_standardized(stats, standardization)
    zero = jnp.where(jnp.diagonal(G) == 0, 1.0, 0.0).astype(G.dtype)
    return G / n + jnp.diag(l2 + zero), Xy / n, std, safe


def _coefficients(stats, beta, std, safe, standardization: bool):
    """(coefficients in original scale, intercept) from the standardized solution."""
    if standardization:
        beta = jnp.where(std > 0, beta / safe, 0.0)
    return beta, stats["mean_y"] - jnp.matmul(stats["mean_x"], beta, precision=_HIGHEST)


def _normal_solve(
    stats: Dict[str, jax.Array], l2: jax.Array, standardization: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Closed-form OLS/ridge by Cholesky of :func:`_normal_system` on the
    device; XLA's factor and triangular solves, their products at HIGHEST.
    Returns (coefficients, intercept, declined): ``declined`` where the
    factor is not finite or a pivot² falls under ``d·eps·max(diag)``
    (exactly collinear columns) — the caller then runs
    :func:`solve_least_norm` on the same statistics, and the coefficients
    here are not to be used."""
    A, b, std, safe = _normal_system(stats, l2, standardization)
    L = jnp.linalg.cholesky(A)
    floor = A.shape[0] * jnp.finfo(A.dtype).eps * jnp.diagonal(A).max()
    declined = ~(jnp.isfinite(L).all() & (jnp.diagonal(L).min() ** 2 > floor))
    beta = jax.scipy.linalg.cho_solve((L, True), b)
    return _coefficients(stats, beta, std, safe, standardization) + (declined,)


@functools.partial(jax.jit, static_argnames=("standardization",))
def solve_normal(
    stats: Dict[str, jax.Array], l2: jax.Array, *, standardization: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`_normal_solve` as its own program, on statistics already made
    (a ``fitMultiple``'s later lanes, the streaming and weighted fits).
    Replaces the reference's eig solver path (``regression.py:502-559``)."""
    return _normal_solve(stats, l2, standardization)


@functools.partial(jax.jit, static_argnames=("standardization",))
def solve_least_norm(
    stats: Dict[str, jax.Array], l2: jax.Array, *, standardization: bool
) -> Tuple[jax.Array, jax.Array]:
    """The same system's least-norm solution, where the Cholesky declined:
    :func:`_least_norm_host`, LAPACK on the host. A program of its own,
    dispatched only then: a program with a host callback is never written
    to JAX's persistent compilation cache, so inside the finish it would be
    compiled again in every process, and a TPU compiles
    ``jnp.linalg.eigh`` at d = 3000 for minutes (PERF.md section 6)."""
    A, b, std, safe = _normal_system(stats, l2, standardization)
    beta = jax.pure_callback(_least_norm_host, jax.ShapeDtypeStruct(b.shape, b.dtype), A, b)
    return _coefficients(stats, beta, std, safe, standardization)


def _stats_from_moments(n, mean_x, mean_y, Gc, Xyc, yyc, fit_intercept: bool) -> Dict[str, jax.Array]:
    """The solvers' statistics from the centred moments: centred where the
    fit has an intercept, else uncentred by the exact rank-one identity
    ``Σ x xᵀ = Σ (x-x̄)(x-x̄)ᵀ + n·x̄x̄ᵀ``; the penalty's variance is the
    centred one either way (Spark's)."""
    var = jnp.diagonal(Gc) / n
    if fit_intercept:
        return {"n": n, "mean_x": mean_x, "mean_y": mean_y, "G": Gc, "Xy": Xyc, "yy": yyc, "var": var}
    return {
        "n": n, "mean_x": jnp.zeros_like(mean_x), "mean_y": jnp.zeros_like(mean_y),
        "G": Gc + n * jnp.outer(mean_x, mean_x), "Xy": Xyc + n * mean_x * mean_y,
        "yy": yyc + n * mean_y * mean_y, "var": var,
    }


@functools.partial(
    jax.jit, static_argnames=("mesh", "d", "pallas", "fit_intercept", "standardization", "solve")
)
def _linreg_finish(
    folds, mean_hat, mean_y_hat, l2, *, mesh, d: int, pallas: bool,
    fit_intercept: bool, standardization: bool, solve: bool,
):
    """What is left of a least-squares fit when the last row block has been
    folded (``ops.linalg.GramFold`` with a label): the psum over dp, the
    mirror and the rank-one re-centring (``moments_from_gram_folds``, PCA's
    steps), the statistics, and with ``solve`` :func:`_normal_solve`. Takes
    the accumulators, not the frame. Returns ``(statistics, (coefficients,
    intercept, declined) or None)``: the statistics serve a ``fitMultiple``'s
    later lanes, the elastic-net solver and :func:`solve_least_norm`."""
    moments = moments_from_gram_folds(*folds, mean_hat, mean_y_hat, mesh, d, pallas)
    stats = _stats_from_moments(*moments, fit_intercept)
    return stats, (_normal_solve(stats, l2, standardization) if solve else None)


@functools.partial(jax.jit, static_argnames=("standardization", "max_iter"))
def solve_elasticnet(
    stats: Dict[str, jax.Array],
    l1: jax.Array,
    l2: jax.Array,
    *,
    standardization: bool,
    max_iter: int,
    tol: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """FISTA on the precomputed quadratic form — replaces cuML ``CDMG``.

    grad f(β) = (Gβ - Xy)/n + λ₂β ; prox = soft-threshold at λ₁/L.
    L is bounded by power iteration on G/n. Entirely replicated d×d math:
    zero data passes, zero collectives per iteration.
    Returns (coefficients, intercept, n_iter).
    """
    n = stats["n"]
    G, Xy, std, safe = _to_standardized(stats, standardization)
    d = G.shape[0]
    Gn = G / n
    b = Xy / n

    # Lipschitz constant: power iteration for λmax(G/n). The start vector is
    # pseudo-random (an all-ones start can be exactly orthogonal to the top
    # eigenvector, e.g. for a feature and its negation, collapsing L to ~0
    # and blowing up the first FISTA step); if the iterate still collapses,
    # fall back to the Frobenius norm, a guaranteed λmax upper bound.
    def power_body(_, v):
        v = Gn @ v
        return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

    v0 = jnp.cos(jnp.arange(d, dtype=G.dtype) * 1.61803398875 + 0.5)
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)
    v = lax.fori_loop(0, 16, power_body, v0)
    fro = jnp.sqrt((Gn * Gn).sum())
    L_pow = (v @ (Gn @ v)) / jnp.maximum(v @ v, 1e-30)
    L_smooth = jnp.where(L_pow > 1e-6 * fro, L_pow * 1.01, fro)
    L = L_smooth + l2 + 1e-12

    def soft(x, t):
        return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)

    def cond(state):
        _, _, _, it, delta = state
        return jnp.logical_and(it < max_iter, delta > tol)

    def body(state):
        beta, z, t, it, _ = state
        grad = Gn @ z - b + l2 * z
        beta_new = soft(z - grad / L, l1 / L)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = beta_new + ((t - 1.0) / t_new) * (beta_new - beta)
        delta = jnp.abs(beta_new - beta).max()
        return (beta_new, z_new, t_new, it + 1, delta)

    beta0 = jnp.zeros((d,), G.dtype)
    state = (beta0, beta0, jnp.asarray(1.0, G.dtype), jnp.asarray(0), jnp.asarray(jnp.inf, G.dtype))
    beta, _, _, it, _ = lax.while_loop(cond, body, state)
    if standardization:
        beta = jnp.where(std > 0, beta / safe, 0.0)
    intercept = stats["mean_y"] - jnp.matmul(stats["mean_x"], beta, precision=_HIGHEST)
    return beta, intercept, it


@functools.partial(jax.jit, static_argnames=("standardization", "max_iter"))
def solve_elasticnet_batched(
    stats: Dict[str, jax.Array],
    l1: jax.Array,
    l2: jax.Array,
    *,
    standardization: bool,
    max_iter: int,
    tol: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gang-lane FISTA: B elastic-net solves over ONE shared quadratic form.

    ``l1``/``l2``/``tol`` are traced ``(B,)`` lane arrays; the power
    iteration for the smooth Lipschitz bound runs once (it only depends on
    G/n) and each lane gets ``L = L_smooth + l2[b]``. One ``lax.while_loop``
    runs until every lane meets its own tol, with converged lanes frozen by
    ``jnp.where(active, new, old)`` — the same freeze contract as
    ``minimize_lbfgs_batched``. Returns (coefficients ``(B, d)``,
    intercepts ``(B,)``, n_iter ``(B,)``).
    """
    n = stats["n"]
    G, Xy, std, safe = _to_standardized(stats, standardization)
    d = G.shape[0]
    B = l1.shape[0]
    Gn = G / n
    b = Xy / n

    def power_body(_, v):
        v = Gn @ v
        return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

    v0 = jnp.cos(jnp.arange(d, dtype=G.dtype) * 1.61803398875 + 0.5)
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)
    v = lax.fori_loop(0, 16, power_body, v0)
    fro = jnp.sqrt((Gn * Gn).sum())
    L_pow = (v @ (Gn @ v)) / jnp.maximum(v @ v, 1e-30)
    L_smooth = jnp.where(L_pow > 1e-6 * fro, L_pow * 1.01, fro)
    L = L_smooth + l2 + 1e-12  # (B,)

    def soft(x, t):
        return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)

    def cond(state):
        _, _, _, it, delta = state
        return jnp.any(jnp.logical_and(it < max_iter, delta > tol))

    def body(state):
        beta, z, t, it, delta = state
        active = jnp.logical_and(it < max_iter, delta > tol)  # (B,)
        grad = jnp.einsum("de,be->bd", Gn, z) + l2[:, None] * z - b[None, :]
        beta_new = soft(z - grad / L[:, None], (l1 / L)[:, None])
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = beta_new + ((t - 1.0) / t_new)[:, None] * (beta_new - beta)
        delta_new = jnp.abs(beta_new - beta).max(axis=1)
        beta = jnp.where(active[:, None], beta_new, beta)
        z = jnp.where(active[:, None], z_new, z)
        t = jnp.where(active, t_new, t)
        delta = jnp.where(active, delta_new, delta)
        it = it + active.astype(jnp.int32)
        return (beta, z, t, it, delta)

    beta0 = jnp.zeros((B, d), G.dtype)
    state = (
        beta0,
        beta0,
        jnp.ones((B,), G.dtype),
        jnp.zeros((B,), jnp.int32),
        jnp.full((B,), jnp.inf, G.dtype),
    )
    beta, _, _, it, _ = lax.while_loop(cond, body, state)
    if standardization:
        beta = jnp.where((std > 0)[None, :], beta / safe[None, :], 0.0)
    intercept = stats["mean_y"] - jnp.matmul(beta, stats["mean_x"], precision=_HIGHEST)
    return beta, intercept, it
