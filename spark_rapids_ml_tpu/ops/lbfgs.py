"""Jitted L-BFGS / OWL-QN minimizer — the framework's quasi-Newton engine.

TPU-native replacement for the solver inside cuML's ``LogisticRegressionMG``
(the reference dispatches to cuML's C++ QN solver with ``lbfgs_memory=10``,
``/root/reference/python/src/spark_rapids_ml/classification.py:1062-1064``).
Here the whole optimization is ONE jitted ``lax.while_loop``: each iteration
evaluates the caller's loss/gradient (a masked data pass over the dp-sharded
design matrix — XLA inserts the psum collectives), then does replicated
O(m·p) two-loop-recursion math on fixed-size history buffers. No Python in
the loop, no host round-trips, no dynamic shapes.

L1 regularization uses OWL-QN (the same algorithm Spark/cuML use for
elasticnet): pseudo-gradient in place of the gradient, search-direction
sign alignment, and orthant projection inside the line search.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class LbfgsResult(NamedTuple):
    w: jax.Array          # (p,) solution
    f: jax.Array          # final objective (incl. L1 term)
    n_iter: jax.Array     # iterations taken
    converged: jax.Array  # bool
    # loss+gradient evaluations: one at w0, one for the first trial of each
    # iteration, one per backtracking trial — what the device's work (data
    # passes) is proportional to
    n_evals: jax.Array


def _pseudo_gradient(w: jax.Array, g: jax.Array, l1w: jax.Array) -> jax.Array:
    """OWL-QN pseudo-gradient of f(w) + ||l1w * w||_1.

    For w_i != 0 the subgradient is g_i + l1w_i*sign(w_i); at w_i == 0 pick
    the one-sided derivative if it is negative in either direction, else 0.
    """
    nonzero = g + l1w * jnp.sign(w)
    lo = g - l1w  # right derivative
    hi = g + l1w  # left derivative
    at_zero = jnp.where(lo > 0.0, lo, jnp.where(hi < 0.0, hi, 0.0))
    return jnp.where(w != 0.0, nonzero, at_zero)


@jax.named_scope("lbfgs.two_loop")
def _two_loop(
    g: jax.Array, S: jax.Array, Y: jax.Array, k: jax.Array
) -> jax.Array:
    """Standard L-BFGS two-loop recursion H·g on circular buffers.

    ``S``/``Y`` are (m, p); entry i is valid iff i < min(k, m). ``k`` is the
    number of (s, y) pairs ever stored; the newest lives at (k-1) % m.
    """
    m = S.shape[0]
    dtype = g.dtype
    tiny = jnp.asarray(1e-30, dtype)
    n_valid = jnp.minimum(k, m)

    def bwd(i, carry):
        q, alphas = carry
        idx = (k - 1 - i) % m
        valid = (i < n_valid).astype(dtype)
        s, y = S[idx], Y[idx]
        rho = 1.0 / jnp.maximum(jnp.vdot(y, s), tiny)
        alpha = rho * jnp.vdot(s, q) * valid
        q = q - alpha * y
        return q, alphas.at[idx].set(alpha)

    q, alphas = lax.fori_loop(0, m, bwd, (g, jnp.zeros((m,), dtype)))

    recent = (k - 1) % m
    s_r, y_r = S[recent], Y[recent]
    gamma = jnp.where(
        k > 0,
        jnp.vdot(s_r, y_r) / jnp.maximum(jnp.vdot(y_r, y_r), tiny),
        jnp.asarray(1.0, dtype),
    )
    r = gamma * q

    def fwd(i, r):
        idx = (k - n_valid + i) % m  # oldest -> newest
        valid = (i < n_valid).astype(dtype)
        s, y = S[idx], Y[idx]
        rho = 1.0 / jnp.maximum(jnp.vdot(y, s), tiny)
        beta = rho * jnp.vdot(y, r)
        r = r + s * (alphas[idx] - beta) * valid
        return r

    return lax.fori_loop(0, m, fwd, r)


def minimize_lbfgs(
    fun: Callable[[jax.Array], jax.Array],
    w0: jax.Array,
    *,
    max_iter: int,
    tol: float,
    l1_weights: Optional[jax.Array] = None,
    history: int = 10,
    max_ls: int = 30,
) -> LbfgsResult:
    """Minimize ``fun(w) + ||l1_weights * w||_1`` from ``w0``.

    ``fun`` must be a smooth, jit-traceable scalar loss (it may close over
    dp-sharded arrays; every call is a distributed data pass). When
    ``l1_weights`` is None or all-zero the algorithm is plain L-BFGS with
    Armijo backtracking; otherwise OWL-QN. Call under ``jit``.
    """
    dtype = w0.dtype
    p = w0.shape[0]
    vg = jax.value_and_grad(fun)
    use_l1 = l1_weights is not None
    l1w = l1_weights if use_l1 else jnp.zeros((p,), dtype)

    @jax.named_scope("lbfgs.loss_grad")
    def full_obj_parts(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(L1-inclusive objective, smooth gradient) in one fwd+bwd pass."""
        f, g = vg(w)
        return f + jnp.abs(l1w * w).sum(), g

    f0, g0 = full_obj_parts(w0)

    # state: (w, f, g, S, Y, k, it, converged, n_evals)
    S0 = jnp.zeros((history, p), dtype)
    Y0 = jnp.zeros((history, p), dtype)
    state0 = (
        w0, f0, g0, S0, Y0, jnp.asarray(0), jnp.asarray(0), jnp.asarray(False),
        jnp.asarray(1, jnp.int32),
    )

    c1 = jnp.asarray(1e-4, dtype)

    def cond(state):
        _, _, _, _, _, _, it, converged, _ = state
        return jnp.logical_and(it < max_iter, jnp.logical_not(converged))

    def body(state):
        w, f, g, S, Y, k, it, _, n_evals = state
        pg = _pseudo_gradient(w, g, l1w) if use_l1 else g
        d = -_two_loop(pg, S, Y, k)
        if use_l1:
            # align direction with -pg (OWL-QN sign fix)
            d = jnp.where(d * pg < 0.0, d, 0.0)
            # orthant for the projected line search
            xi = jnp.where(w != 0.0, jnp.sign(w), -jnp.sign(pg))
        dir_deriv = jnp.vdot(pg, d)

        d_norm = jnp.sqrt(jnp.vdot(d, d))
        t0 = jnp.where(
            k == 0, 1.0 / jnp.maximum(d_norm, 1.0), jnp.asarray(1.0, dtype)
        )

        def trial_point(t):
            w_t = w + t * d
            if use_l1:
                w_t = jnp.where(w_t * xi < 0.0, 0.0, w_t)  # orthant projection
            return w_t

        # Armijo backtracking on the full (L1-inclusive) objective. Each
        # trial evaluates value AND gradient in one fused fwd+bwd data pass:
        # the accepted trial's gradient feeds the curvature update directly,
        # so no extra pass is spent re-evaluating the accepted point.
        def ls_cond(carry):
            t, f_t, _, n_try = carry
            ok = f_t <= f + c1 * t * dir_deriv
            return jnp.logical_and(jnp.logical_not(ok), n_try < max_ls)

        def ls_body(carry):
            t, _, _, n_try = carry
            t = t * 0.5
            f_t, g_t = full_obj_parts(trial_point(t))
            return t, f_t, g_t, n_try + 1

        f_t0, g_t0 = full_obj_parts(trial_point(t0))
        with jax.named_scope("lbfgs.line_search"):
            t, f_new, g_new, n_try = lax.while_loop(
                ls_cond, ls_body, (t0, f_t0, g_t0, jnp.asarray(0))
            )
        w_new = trial_point(t)

        s = w_new - w
        yv = g_new - g
        curv = jnp.vdot(s, yv)
        store = curv > jnp.asarray(1e-10, dtype)
        idx = k % history
        S = jnp.where(store, S.at[idx].set(s), S)
        Y = jnp.where(store, Y.at[idx].set(yv), Y)
        k = jnp.where(store, k + 1, k)

        denom = jnp.maximum(jnp.maximum(jnp.abs(f), jnp.abs(f_new)), 1.0)
        rel_impr = (f - f_new) / denom
        # stop on stall (no descent direction / line-search failure) or tol
        converged = jnp.logical_or(rel_impr <= tol, dir_deriv >= 0.0)

        n_evals = n_evals + 1 + n_try.astype(jnp.int32)
        return (w_new, f_new, g_new, S, Y, k, it + 1, converged, n_evals)

    w, f, g, S, Y, k, it, converged, n_evals = lax.while_loop(cond, body, state0)
    return LbfgsResult(w=w, f=f, n_iter=it, converged=converged, n_evals=n_evals)


class LbfgsBatchedResult(NamedTuple):
    w: jax.Array          # (B, p) per-lane solutions
    f: jax.Array          # (B,) final objectives (incl. L1 term)
    n_iter: jax.Array     # (B,) iterations each lane took
    converged: jax.Array  # (B,) bool
    # (B,) evaluations a solo solve of each lane would have made (the gang
    # shares its data passes; a lane counts its own trials)
    n_evals: jax.Array


# vmapping the SAME two-loop the solo solver runs (rather than rewriting
# the reductions with a batch axis) keeps the per-lane op sequence —
# dot_general contractions, scatter updates, index arithmetic — identical
# to a solo solve, which is what the lane/solo bit-parity contract rests on
_two_loop_batched = jax.vmap(_two_loop)
_vdot_batched = jax.vmap(jnp.vdot)


def minimize_lbfgs_batched(
    fun: Callable[[jax.Array], jax.Array],
    w0: jax.Array,
    *,
    max_iter: int,
    tol: jax.Array,
    l1_weights: Optional[jax.Array] = None,
    history: int = 10,
    max_ls: int = 30,
) -> LbfgsBatchedResult:
    """Gang-scheduled :func:`minimize_lbfgs`: B independent lanes, one loop.

    ``fun`` is the *batched* smooth loss ``(B, p) -> (B,)`` — lane b's value
    may only depend on row b of the argument (per-lane gradients come from
    one vjp with a ones cotangent, i.e. one fused fwd+bwd data pass for all
    lanes). ``w0`` is ``(B, p)``; ``tol`` is per-lane ``(B,)``;
    ``l1_weights`` (optional) is per-lane ``(B, p)`` and switches the whole
    group to OWL-QN (lanes wanting plain L-BFGS must go in a separate call —
    OWL-QN's direction sign-fix is not the identity even at l1=0).

    The ``lax.while_loop`` runs until every lane is done. Correctness core:
    a lane that converges (or exhausts ``max_iter``) is FROZEN — every state
    update is guarded by ``jnp.where(active, new, old)`` — so its final
    state is bit-identical to a solo :func:`minimize_lbfgs` run of the same
    problem, no matter how long the slowest lane keeps the gang looping.
    (A plain vmap-of-while has no such guarantee: it keeps executing the
    body for finished lanes, and OWL-QN's orthant projection can move a
    converged iterate again.) The line search is per-lane: each lane halves
    its own step until its own Armijo test passes, riding the shared data
    pass of the lanes still searching.
    """
    dtype = w0.dtype
    B, p = w0.shape
    use_l1 = l1_weights is not None
    l1w = l1_weights if use_l1 else jnp.zeros((B, p), dtype)

    @jax.named_scope("lbfgs.loss_grad")
    def full_obj_parts(W: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Per-lane (L1-inclusive objective, smooth gradient), ONE shared
        fwd+bwd data pass. The ones-cotangent vjp is exact per-lane: lane
        b's loss depends only on lane b's params, so rows of the vjp output
        are the per-lane gradients."""
        f, vjp = jax.vjp(fun, W)
        (g,) = vjp(jnp.ones_like(f))
        return f + jnp.abs(l1w * W).sum(axis=-1), g

    f0, g0 = full_obj_parts(w0)

    S0 = jnp.zeros((B, history, p), dtype)
    Y0 = jnp.zeros((B, history, p), dtype)
    zi = jnp.zeros((B,), jnp.int32)
    state0 = (w0, f0, g0, S0, Y0, zi, zi, jnp.zeros((B,), bool), zi + 1)

    c1 = jnp.asarray(1e-4, dtype)

    def cond(state):
        _, _, _, _, _, _, it, converged, _ = state
        return jnp.any(jnp.logical_and(jnp.logical_not(converged), it < max_iter))

    def body(state):
        w, f, g, S, Y, k, it, converged, n_evals = state
        # lanes still running this iteration; everything a frozen lane
        # "computes" below is discarded by the where-guards at the bottom
        active = jnp.logical_and(jnp.logical_not(converged), it < max_iter)

        pg = _pseudo_gradient(w, g, l1w) if use_l1 else g
        d = -_two_loop_batched(pg, S, Y, k)
        if use_l1:
            d = jnp.where(d * pg < 0.0, d, 0.0)
            xi = jnp.where(w != 0.0, jnp.sign(w), -jnp.sign(pg))
        dir_deriv = _vdot_batched(pg, d)

        d_norm = jnp.sqrt(_vdot_batched(d, d))
        t0 = jnp.where(
            k == 0, 1.0 / jnp.maximum(d_norm, 1.0), jnp.asarray(1.0, dtype)
        )

        def trial_point(t):
            w_t = w + t[:, None] * d
            if use_l1:
                w_t = jnp.where(w_t * xi < 0.0, 0.0, w_t)
            return w_t

        # Per-lane Armijo backtracking. One batched data pass per halving
        # round serves every lane still searching; lanes already accepted
        # (and frozen lanes) keep their (t, f, g) via the need-guard, so
        # each lane sees exactly the solo solver's trial sequence.
        def ls_cond(carry):
            _, _, _, n_try, ok = carry
            return jnp.any(active & ~ok & (n_try < max_ls))

        def ls_body(carry):
            t, f_t, g_t, n_try, ok = carry
            need = active & ~ok & (n_try < max_ls)
            t_new = jnp.where(need, t * 0.5, t)
            f_n, g_n = full_obj_parts(trial_point(t_new))
            f_t = jnp.where(need, f_n, f_t)
            g_t = jnp.where(need[:, None], g_n, g_t)
            ok = jnp.where(need, f_t <= f + c1 * t_new * dir_deriv, ok)
            return t_new, f_t, g_t, n_try + need.astype(jnp.int32), ok

        f_t0, g_t0 = full_obj_parts(trial_point(t0))
        ok0 = f_t0 <= f + c1 * t0 * dir_deriv
        with jax.named_scope("lbfgs.line_search"):
            t, f_new, g_new, n_try, _ = lax.while_loop(
                ls_cond, ls_body,
                (t0, f_t0, g_t0, jnp.zeros((B,), jnp.int32), ok0),
            )
        w_new = trial_point(t)

        s = w_new - w
        yv = g_new - g
        curv = _vdot_batched(s, yv)
        store = active & (curv > jnp.asarray(1e-10, dtype))
        idx = k % history
        S_set = jax.vmap(lambda Sb, i, sb: Sb.at[i].set(sb))(S, idx, s)
        Y_set = jax.vmap(lambda Yb, i, yb: Yb.at[i].set(yb))(Y, idx, yv)
        S = jnp.where(store[:, None, None], S_set, S)
        Y = jnp.where(store[:, None, None], Y_set, Y)
        k = jnp.where(store, k + 1, k)

        denom = jnp.maximum(jnp.maximum(jnp.abs(f), jnp.abs(f_new)), 1.0)
        rel_impr = (f - f_new) / denom
        conv_now = jnp.logical_or(rel_impr <= tol, dir_deriv >= 0.0)

        # the freeze: frozen lanes keep w/f/g (and S/Y/k via the store
        # guard above, which requires `active`) bit-exactly
        w = jnp.where(active[:, None], w_new, w)
        f = jnp.where(active, f_new, f)
        g = jnp.where(active[:, None], g_new, g)
        converged = jnp.where(active, conv_now, converged)
        it = it + active.astype(jnp.int32)
        n_evals = n_evals + jnp.where(active, 1 + n_try, 0)
        return (w, f, g, S, Y, k, it, converged, n_evals)

    w, f, g, S, Y, k, it, converged, n_evals = lax.while_loop(cond, body, state0)
    return LbfgsBatchedResult(
        w=w, f=f, n_iter=it, converged=converged, n_evals=n_evals
    )


def minimize_lbfgs_host(
    value_grad: Callable,
    w0,
    *,
    max_iter: int,
    tol: float,
    l1_weights=None,
    history: int = 10,
    max_ls: int = 30,
    checkpointer=None,
) -> LbfgsResult:
    """Host-driven L-BFGS/OWL-QN for out-of-core objectives.

    Same algorithm as :func:`minimize_lbfgs` (Armijo backtracking on the
    L1-inclusive objective, pseudo-gradient + orthant projection for L1,
    curvature-guarded history) but the loop runs in Python: each
    ``value_grad(w)`` call is free to stream the dataset through the device
    in chunks (a full distributed data pass), which a ``lax.while_loop``
    cannot express. The O(m·p) two-loop math runs in float64 on host —
    negligible next to the data passes.

    ``value_grad`` must return the SMOOTH (f, g) pair; the L1 term is added
    here, mirroring ``full_obj_parts`` in the jitted solver.

    ``checkpointer`` (a ``runtime.FitCheckpointer``, or None) snapshots the
    full carry — ``w/f/g`` and the ``S/Y`` history — after each iteration
    and resumes from the last committed one on refit. The algorithm is
    deterministic given the carry, so an interrupted-then-resumed run walks
    the identical iterate sequence as an uninterrupted one.
    """
    import numpy as np

    from ..runtime import counters
    from ..runtime.faults import fault_site
    from ..runtime.scheduler import preempt_point

    w = np.asarray(w0, dtype=np.float64)
    p = w.shape[0]
    use_l1 = l1_weights is not None
    l1w = np.asarray(l1_weights, np.float64) if use_l1 else np.zeros((p,))

    # evaluations made by THIS call: a Python count beside the checkpointed
    # carry (a resumed fit counts from its resume, the format is unchanged)
    n_evals = 0

    def full_obj(wv):
        nonlocal n_evals
        n_evals += 1
        f, g = value_grad(wv)
        return float(f) + float(np.abs(l1w * wv).sum()), np.asarray(g, np.float64)

    def pseudo_grad(wv, g):
        nonzero = g + l1w * np.sign(wv)
        lo = g - l1w
        hi = g + l1w
        at_zero = np.where(lo > 0.0, lo, np.where(hi < 0.0, hi, 0.0))
        return np.where(wv != 0.0, nonzero, at_zero)

    S: list = []
    Y: list = []
    c1 = 1e-4
    it = 0
    converged = False
    resumed = checkpointer.load() if checkpointer is not None else None
    if resumed is not None:
        it, arrays, extra = resumed
        w = np.asarray(arrays["w"], np.float64)
        g = np.asarray(arrays["g"], np.float64)
        S = [np.asarray(row, np.float64) for row in arrays["S"]]
        Y = [np.asarray(row, np.float64) for row in arrays["Y"]]
        f = float(extra["f"])
        converged = bool(extra.get("converged", False))
        counters.bump("resumed_fits")
        counters.note("resumed_from", it)
    else:
        f, g = full_obj(w)
    while it < max_iter and not converged:
        fault_site("sgd:epoch")
        pg = pseudo_grad(w, g) if use_l1 else g
        # two-loop recursion over the (oldest -> newest) history
        q = pg.copy()
        alphas = []
        for s, yv in reversed(list(zip(S, Y))):
            rho = 1.0 / max(float(yv @ s), 1e-30)
            a = rho * float(s @ q)
            q -= a * yv
            alphas.append((a, rho))
        if S:
            s_r, y_r = S[-1], Y[-1]
            gamma = float(s_r @ y_r) / max(float(y_r @ y_r), 1e-30)
        else:
            gamma = 1.0
        r = gamma * q
        for (a, rho), (s, yv) in zip(reversed(alphas), zip(S, Y)):
            beta = rho * float(yv @ r)
            r += s * (a - beta)
        d = -r
        if use_l1:
            d = np.where(d * pg < 0.0, d, 0.0)
            xi = np.where(w != 0.0, np.sign(w), -np.sign(pg))
        dir_deriv = float(pg @ d)

        d_norm = float(np.sqrt(d @ d))
        t = 1.0 / max(d_norm, 1.0) if not S else 1.0

        def trial(tv):
            wt = w + tv * d
            if use_l1:
                wt = np.where(wt * xi < 0.0, 0.0, wt)
            return wt

        f_t, g_t = full_obj(trial(t))
        n_try = 0
        while f_t > f + c1 * t * dir_deriv and n_try < max_ls:
            t *= 0.5
            f_t, g_t = full_obj(trial(t))
            n_try += 1
        w_new = trial(t)

        s = w_new - w
        yv = g_t - g
        if float(s @ yv) > 1e-10:
            S.append(s)
            Y.append(yv)
            if len(S) > history:
                S.pop(0)
                Y.pop(0)

        denom = max(abs(f), abs(f_t), 1.0)
        rel_impr = (f - f_t) / denom
        converged = rel_impr <= tol or dir_deriv >= 0.0
        w, f, g = w_new, f_t, g_t
        it += 1
        if checkpointer is not None:
            state = lambda: {
                "w": w,
                "g": g,
                "S": np.stack(S) if S else np.zeros((0, p)),
                "Y": np.stack(Y) if Y else np.zeros((0, p)),
            }
            checkpointer.maybe_save(
                it, state(), {"f": f, "converged": bool(converged)}
            )
            preempt_point(
                checkpointer, it, state, {"f": f, "converged": bool(converged)}
            )

    if checkpointer is not None:
        checkpointer.clear()

    import jax.numpy as _jnp

    return LbfgsResult(
        w=w, f=_jnp.asarray(f), n_iter=_jnp.asarray(it),
        converged=_jnp.asarray(converged), n_evals=_jnp.asarray(n_evals),
    )
