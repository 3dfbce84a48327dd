"""LogisticRegression device kernels — distributed L-BFGS/OWL-QN fit.

TPU-native replacement for cuML ``LogisticRegressionMG``
(reference: ``/root/reference/python/src/spark_rapids_ml/classification.py:955-1140``).

Design notes:

* **One jitted program.** The whole fit — standardization moments, the
  L-BFGS loop, the coefficient back-transform — is a single jit over the
  dp-sharded design matrix; XLA inserts the psum for every masked reduction
  (the role NCCL allreduce played inside cuML's QN solver).
* **Standardization without a data copy.** The reference materializes a
  standardized copy of the dataset with cupy and allGathers mean/var
  (``classification.py:989-1038``). Here standardization is a
  *reparametrization*: optimize W in standardized-coefficient space and
  fold the (mean, 1/std) affine map into the logits,
  ``logits = X @ (W·inv_std)ᵀ + (b − (W·inv_std)·mean)`` — zero extra HBM,
  identical objective. The final back-transform (coef/std, intercept
  −coef·mean, multinomial intercept centering) matches the reference's
  post-processing at ``classification.py:1073-1094``.
* **Spark objective**: (1/n)·Σ logloss + λ[(1−α)/2‖β‖₂² + α‖β‖₁] with the
  penalty applied to standardized coefficients when standardization=True
  and never to intercepts. Feature variance uses the unbiased (n−1)
  denominator exactly like the reference (``classification.py:1024-1026``).
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from ..parallel.mesh import DP_AXIS
from .lbfgs import minimize_lbfgs, minimize_lbfgs_batched


def objective_read_dtype(X, mesh, objective_dtype: str):
    """dtype of the X copy ``logreg_fit``'s objective reads: X's own, or bf16
    where ``objective_dtype="bfloat16"`` asks for it over an f32 X small
    enough to convert in the program. The near-HBM-capacity guard: the
    convert holds the f32 argument AND the bf16 copy live — per chip, so the
    budget is the PER-DEVICE shard (global bytes / dp size on a mesh). Past
    ~1 GB per device callers must pass X in bf16 instead (zero-copy; the
    estimator's ``_x_placement_dtype`` hook does exactly that)."""
    n_dp = dict(mesh.shape).get(DP_AXIS, 1) if mesh is not None else 1
    narrow = (
        objective_dtype == "bfloat16"
        and X.dtype == jnp.float32
        and X.size * X.dtype.itemsize // max(n_dp, 1) <= (1 << 30)
    )
    return jnp.dtype(jnp.bfloat16) if narrow else X.dtype


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_classes",
        "multinomial",
        "fit_intercept",
        "standardization",
        "use_l1",
        "max_iter",
        "history",
        "mesh",
        "objective_dtype",
    ),
)
def logreg_fit(
    X: jax.Array,
    mask: jax.Array,
    y: jax.Array,
    *,
    n_classes: int,
    multinomial: bool,
    fit_intercept: bool,
    standardization: bool,
    l1: jax.Array,
    l2: jax.Array,
    use_l1: bool,
    max_iter: int,
    tol: jax.Array,
    history: int = 10,
    mesh=None,
    objective_dtype: str = "float32",
) -> Dict[str, jax.Array]:
    """Fit logistic regression; returns coef_ (K,d), intercept_ (K,), n_iter,
    n_evals (loss+gradient evaluations, ``ops/lbfgs.LbfgsResult``),
    objective. K=1 for the binomial (sigmoid) formulation, else n_classes.

    With ``mesh`` (rows dp-sharded over it) on a TPU the data term of every
    L-BFGS evaluation is ONE read of X through a fused Pallas loss+gradient
    pass (``ops/logreg_pallas.py``) instead of autodiff's forward and
    backward pass, chosen from K, the dtype of X and the backend:

    * binomial (K = 1), f32 X, any width: float32 multiplies and adds on
      the VPU — as exact as the XLA path below, which it replaces;
    * multinomial (K >= 3), f32/bf16 X, lane-aligned d <= 2048: two MXU
      products at default precision (one bf16 pass with f32 accumulation);
    * anything else (no mesh, no TPU, a bf16-placed X with K = 1, a wide
      multinomial): XLA's two passes — for K = 1 a float32 multiply-reduce
      on the VPU, for K >= 3 a ``dot`` at default precision.

    ``objective_dtype="bfloat16"`` stores the X copy the objective reads
    in bf16 (statistics, parameters and accumulation stay f32): the
    bandwidth-bound eval reads half the HBM bytes. Per-element
    rounding is ~1e-2 relative but i.i.d. across rows, so gradient sums
    see it averaged down by sqrt(n); solution drift at bench scales is
    well inside the solver tolerance.

    X may itself arrive in bf16 (with any ``objective_dtype``): solver
    state, statistics and reductions still run f32 — the upcast fuses
    into the reduction/matmul loops, so no f32 copy of X is ever
    materialized. Passing bf16 X is the memory-safe route at near-HBM
    scales: an in-program ``astype`` of an f32 argument would hold both
    copies live (observed 17.3 GB > 15.75 GB on a 12M x 256 bench fit)."""
    dtype = jnp.float32 if X.dtype == jnp.bfloat16 else X.dtype
    d = X.shape[1]
    n = mask.sum()
    yi = y.astype(jnp.int32)
    yf = y.astype(dtype)

    with jax.named_scope("logreg.moments"):
        mean = (X.astype(dtype) * mask[:, None]).sum(axis=0) / n
        if standardization:
            sq = ((X.astype(dtype) - mean[None, :]) ** 2 * mask[:, None]).sum(
                axis=0
            )
            var = sq / jnp.maximum(n - 1.0, 1.0)
            std = jnp.sqrt(jnp.maximum(var, 0.0))
            inv_std = jnp.where(std > 0, 1.0 / std, 1.0)
        else:
            inv_std = jnp.ones((d,), dtype)
    # the reference skips centering when fit_intercept=False (adds the mean
    # back before scaling, ``classification.py:1036-1037``)
    use_center = standardization and fit_intercept

    K = n_classes if multinomial else 1
    n_coef = K * d
    p = n_coef + (K if fit_intercept else 0)

    def unpack(wflat: jax.Array):
        A = wflat[:n_coef].reshape(K, d)
        b = wflat[n_coef:] if fit_intercept else jnp.zeros((K,), dtype)
        return A, b

    def to_original(A: jax.Array, b: jax.Array):
        Aeff = A * inv_std[None, :]
        beff = b - (Aeff @ mean if use_center else jnp.zeros((), dtype))
        return Aeff, beff

    coef_mask = jnp.concatenate(
        [jnp.ones((n_coef,), dtype), jnp.zeros((p - n_coef,), dtype)]
    )

    from .logreg_pallas import logreg_pallas_ok, make_fused_data_loss

    # the objective's X copy: mean/std above come from X as it arrived
    # (exact-f32 moments for f32 input; bf16-rounded-then-f32-accumulated
    # for a bf16-placed X); only the per-iteration data passes read the
    # narrow copy
    if objective_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"objective_dtype must be float32|bfloat16, got {objective_dtype!r}"
        )
    X_obj = X
    if objective_dtype == "bfloat16" and X.dtype == jnp.float32:
        if objective_read_dtype(X, mesh, objective_dtype) == jnp.bfloat16:
            X_obj = X.astype(jnp.bfloat16)
        else:
            # trace-time, so the warning fires once per shape
            from ..utils.logging import get_logger

            get_logger("logreg_fit").warning(
                "objective_dtype=bfloat16 requested for a %.1f GB f32 X: "
                "running f32 reads instead (an in-program convert would "
                "double X's residency). Pass X placed in bf16 to get bf16 "
                "reads at this scale.",
                X.size * X.dtype.itemsize / 2**30,
            )

    fused_data = None
    if mesh is not None and logreg_pallas_ok(
        X.shape[0] // mesh.shape[DP_AXIS], d, K, X_obj.dtype, mesh.devices.flat[0]
    ):
        fused_data = make_fused_data_loss(
            X_obj, yf, mask, mesh, K, multinomial
        )

    def smooth_loss(wflat: jax.Array) -> jax.Array:
        A, b = unpack(wflat)
        Aeff, beff = to_original(A, b)
        if fused_data is not None:
            data_loss = fused_data(Aeff, beff) / n
        else:
            # weights stay f32 (rounding A to bf16 would bias every row
            # identically — no sqrt(n) averaging); the X upcast feeds the
            # dot and XLA fuses it into operand loading where it can.
            logits = X_obj.astype(dtype) @ Aeff.T + beff[None, :]  # (n, K)
            if multinomial:
                ll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
                    logits, yi[:, None], axis=1
                )[:, 0]
            else:
                z = logits[:, 0]
                ll = jax.nn.softplus(z) - yf * z
            data_loss = (ll * mask).sum() / n
        coefs = wflat * coef_mask  # penalty never touches intercepts
        return data_loss + 0.5 * l2 * jnp.vdot(coefs, coefs)

    w0 = jnp.zeros((p,), dtype)
    res = minimize_lbfgs(
        smooth_loss,
        w0,
        max_iter=max_iter,
        tol=tol,
        # None keeps the solver on plain L-BFGS; OWL-QN's direction
        # sign-alignment and orthant projection only pay off when L1 > 0
        l1_weights=l1 * coef_mask if use_l1 else None,
        history=history,
    )

    A, b = unpack(res.w)
    coef, intercept = to_original(A, b)
    if fit_intercept and K > 1:
        # Spark centers multinomial intercepts (reference
        # ``classification.py:1082-1094``)
        intercept = intercept - intercept.mean()
    return {
        "coef_": coef,
        "intercept_": intercept,
        "n_iter": res.n_iter,
        "n_evals": res.n_evals,
        "objective": res.f,
    }


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_classes",
        "multinomial",
        "fit_intercept",
        "standardization",
        "use_l1",
        "max_iter",
        "history",
        "mesh",
        "objective_dtype",
        "n_folds",
    ),
)
def logreg_fit_batched(
    X: jax.Array,
    mask: jax.Array,
    y: jax.Array,
    *,
    n_classes: int,
    multinomial: bool,
    fit_intercept: bool,
    standardization: bool,
    l1: jax.Array,
    l2: jax.Array,
    use_l1: bool,
    max_iter: int,
    tol: jax.Array,
    history: int = 10,
    mesh=None,
    objective_dtype: str = "float32",
    fold_id=None,
    lane_fold=None,
    n_folds: int = 0,
) -> Dict[str, jax.Array]:
    """Gang-scheduled :func:`logreg_fit`: B solves share every data pass.

    ``l1``/``l2``/``tol`` are per-lane ``(B,)`` traced arrays (continuous
    params ride the lane axis — no recompile across reg grids); everything
    in ``static_argnames`` must be uniform across the gang, which is why the
    estimator partitions param maps into static-bucket dispatch groups.

    The objective is ONE batched loss over the shared dp-sharded X: per
    L-BFGS evaluation the design matrix is read once for all B lanes
    (``logits = einsum('nd,bkd->nbk', X, Aeff)``) and the masked reduction
    over rows is one psum — amortizing the bandwidth-bound data pass B ways
    is where the MFU win over B sequential solves comes from. The fused
    Pallas solo path is deliberately not used here: the batched einsum
    already feeds the MXU B·K output columns per X tile, which is the same
    amortization the fused kernel buys the solo solve.

    Fold-masked CV lanes: with ``fold_id`` (per-row int fold assignment,
    sharded like ``mask``) and ``lane_fold`` ``(B,)``, lane b's objective
    sees only rows with ``fold_id != lane_fold[b]`` — the mask is computed
    on the fly inside the loss (it fuses into the row reduction; no (B, n)
    weight matrix is ever materialized), and standardization moments are
    computed per FOLD (``n_folds`` static, one extra masked pass per fold
    at setup) then gathered per lane. Without folds the moments are the
    same shared scalars as the solo kernel.

    Returns per-lane ``coef_`` (B, K, d), ``intercept_`` (B, K),
    ``n_iter``/``objective``/``converged`` (B,).
    """
    dtype = jnp.float32 if X.dtype == jnp.bfloat16 else X.dtype
    d = X.shape[1]
    B = l1.shape[0]
    yi = y.astype(jnp.int32)
    yf = y.astype(dtype)
    folds = fold_id is not None
    if folds:
        assert lane_fold is not None and n_folds >= 2

    if folds:
        # per-fold training moments: fold f's lanes train on rows with
        # fold_id != f. One masked pass per fold (static unroll, n_folds is
        # small) keeps the centered-variance numerics of the solo kernel.
        fid = fold_id.astype(jnp.int32)
        means, inv_stds, ns = [], [], []
        for f in range(n_folds):
            wf = mask * (fid != f).astype(dtype)
            nf = wf.sum()
            mean_f = (X.astype(dtype) * wf[:, None]).sum(axis=0) / nf
            if standardization:
                sq = ((X.astype(dtype) - mean_f[None, :]) ** 2 * wf[:, None]).sum(axis=0)
                var = sq / jnp.maximum(nf - 1.0, 1.0)
                std = jnp.sqrt(jnp.maximum(var, 0.0))
                inv_std_f = jnp.where(std > 0, 1.0 / std, 1.0)
            else:
                inv_std_f = jnp.ones((d,), dtype)
            means.append(mean_f)
            inv_stds.append(inv_std_f)
            ns.append(nf)
        lane_mean = jnp.stack(means)[lane_fold]        # (B, d)
        lane_inv_std = jnp.stack(inv_stds)[lane_fold]  # (B, d)
        lane_n = jnp.stack(ns)[lane_fold]              # (B,)
    else:
        n = mask.sum()
        mean = (X.astype(dtype) * mask[:, None]).sum(axis=0) / n
        if standardization:
            sq = ((X.astype(dtype) - mean[None, :]) ** 2 * mask[:, None]).sum(axis=0)
            var = sq / jnp.maximum(n - 1.0, 1.0)
            std = jnp.sqrt(jnp.maximum(var, 0.0))
            inv_std = jnp.where(std > 0, 1.0 / std, 1.0)
        else:
            inv_std = jnp.ones((d,), dtype)
        lane_mean = jnp.broadcast_to(mean, (B, d))
        lane_inv_std = jnp.broadcast_to(inv_std, (B, d))
        lane_n = jnp.broadcast_to(n, (B,))
    use_center = standardization and fit_intercept

    K = n_classes if multinomial else 1
    n_coef = K * d
    p = n_coef + (K if fit_intercept else 0)

    def unpack(W: jax.Array):
        A = W[:, :n_coef].reshape(B, K, d)
        b = W[:, n_coef:] if fit_intercept else jnp.zeros((B, K), dtype)
        return A, b

    def to_original(A: jax.Array, b: jax.Array):
        Aeff = A * lane_inv_std[:, None, :]
        if use_center:
            beff = b - jnp.einsum("bkd,bd->bk", Aeff, lane_mean)
        else:
            beff = b
        return Aeff, beff

    coef_mask = jnp.concatenate(
        [jnp.ones((n_coef,), dtype), jnp.zeros((p - n_coef,), dtype)]
    )

    if objective_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"objective_dtype must be float32|bfloat16, got {objective_dtype!r}"
        )
    # same residency guard as the solo kernel
    X_obj = X.astype(objective_read_dtype(X, mesh, objective_dtype))

    def smooth_loss(W: jax.Array) -> jax.Array:
        A, b = unpack(W)
        Aeff, beff = to_original(A, b)
        # the shared data pass: one X read feeds all B lanes' logits
        logits = (
            jnp.einsum("nd,bkd->nbk", X_obj.astype(dtype), Aeff)
            + beff[None, :, :]
        )  # (n, B, K)
        if multinomial:
            ysel = jnp.take_along_axis(
                logits, jnp.broadcast_to(yi[:, None, None], (yi.shape[0], B, 1)), axis=2
            )[:, :, 0]
            ll = jax.nn.logsumexp(logits, axis=2) - ysel  # (n, B)
        else:
            z = logits[:, :, 0]
            ll = jax.nn.softplus(z) - yf[:, None] * z
        if folds:
            # on-the-fly per-lane row mask — fuses into the reduction, so
            # no (B, n) weight matrix resides in HBM
            wrow = mask[:, None] * (fid[:, None] != lane_fold[None, :]).astype(dtype)
        else:
            wrow = mask[:, None]
        data_loss = (ll * wrow).sum(axis=0) / lane_n  # (B,)
        coefs = W * coef_mask[None, :]
        return data_loss + 0.5 * l2 * jnp.einsum("bp,bp->b", coefs, coefs)

    W0 = jnp.zeros((B, p), dtype)
    res = minimize_lbfgs_batched(
        smooth_loss,
        W0,
        max_iter=max_iter,
        tol=tol,
        l1_weights=l1[:, None] * coef_mask[None, :] if use_l1 else None,
        history=history,
    )

    A, b = unpack(res.w)
    coef, intercept = to_original(A, b)
    if fit_intercept and K > 1:
        intercept = intercept - intercept.mean(axis=1, keepdims=True)
    return {
        "coef_": coef,
        "intercept_": intercept,
        "n_iter": res.n_iter,
        "objective": res.f,
        "converged": res.converged,
    }


@functools.partial(jax.jit, static_argnames=("multinomial",))
def logreg_predict(
    Xb: jax.Array, coef: jax.Array, intercept: jax.Array, *, multinomial: bool
):
    """Batch inference -> (prediction, probability, rawPrediction).

    Binomial rawPrediction follows Spark's [-m, m] convention; multinomial
    rawPrediction is the margins vector (reference transform computes the
    same scores then local sigmoid/softmax, ``classification.py:1410-1433``).
    """
    scores = Xb @ coef.T + intercept[None, :]
    if multinomial:
        raw = scores
        prob = jax.nn.softmax(scores, axis=1)
        pred = jnp.argmax(scores, axis=1).astype(Xb.dtype)
    else:
        z = scores[:, 0]
        raw = jnp.stack([-z, z], axis=1)
        p1 = jax.nn.sigmoid(z)
        prob = jnp.stack([1.0 - p1, p1], axis=1)
        pred = (p1 > 0.5).astype(Xb.dtype)
    return pred, prob, raw
