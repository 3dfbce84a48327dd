"""Fused logistic loss+gradient Pallas kernel — one X pass per L-BFGS eval.

``jax.value_and_grad`` of the logistic data term reads the design matrix
twice per objective evaluation: once forward (``X @ Aᵀ``) and once backward
(``Rᵀ @ X``). For the bandwidth-bound L-BFGS fit that is the entire cost.
This kernel computes the masked loss **and** the gradient in a single
HBM pass: per row tile, logits → per-row loss → residuals → the tile's
``Rᵀ x`` contribution, with the (K, d) gradient accumulator resident in
VMEM. A ``jax.custom_vjp`` wrapper computes both in the forward pass and
makes the backward pass free, so the solver's value-and-grad costs one
data read instead of two.

Used by ``logreg_fit`` (``ops/logreg_kernels.py``) when a dp-only mesh is
supplied and the shapes qualify (TPU backend, f32, lane-aligned d); the
portable XLA path is unchanged otherwise. cuML reference this replaces:
the QN solver's fused objective inside ``LogisticRegressionMG``
(``/root/reference/python/src/spark_rapids_ml/classification.py:1062-1064``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS

_LANES = 128

# Test hook: when True, logreg_pallas_ok ignores the backend check and the
# kernel runs through the Pallas interpreter — lets CPU CI exercise the
# REAL fused branch inside logreg_fit (gate → custom_vjp → L-BFGS), not
# just the standalone kernel.
FORCE_INTERPRET = False


from .linalg import _pallas_gram_tile


def _row_tile(d: int, Kp: int) -> int:
    """Row-tile size: the gram kernel's sizing, shrunk when the padded
    class count is large — multinomial materializes several (tile, Kp)
    intermediates (logits, softmax, residuals, one-hot, the packed
    loss/residual block), which at small d and many classes would
    otherwise dominate scoped VMEM.

    Dtype does NOT change the tile: measured on v5e, the kernel runs at
    the same ~2.2 ns/row for f32 and bf16 X alike (pipeline-bound, not
    HBM-bound), so bf16's value is halved residency — a full-speed fit
    from an X that occupies half the HBM — not throughput. Doubling the
    bf16 tile was measured a wash, and the validity-guard where-copy it
    would evict is load-bearing: without it the input window feeds the
    MXU directly and the kernel drops to ~1.7x slower (the guard's
    select decouples the window from the dots, letting the DMA
    double-buffer run ahead)."""
    return _pallas_gram_tile(max(d, 6 * Kp))


def logreg_pallas_declined(d: int, n_classes: int, dtype) -> str:
    """The terms of the fused kernel's gate that fail, comma-joined (empty:
    the kernel is admitted). TPU, f32/bf16 X, lane-aligned d, and few enough
    classes that the sublane-padded class block plus the loss lane pack
    into one 128-lane row (ceil(K/8)*8 + 1 <= 128, i.e. K <= 120). bf16 X
    feeds both dots directly (f32 accumulation) — no VMEM upcast. A pure
    function of its arguments and the backend: the estimator evaluates it
    again on the host to say on its ``solver.launch`` span why a fit ran
    XLA's two passes."""
    terms = (
        ("backend", jax.default_backend() == "tpu" or FORCE_INTERPRET),
        ("d%128", d % _LANES == 0),
        ("d<=2048", d <= 2048),
        ("n_classes<=120", -(-n_classes // 8) * 8 + 1 <= _LANES),
        ("dtype", dtype in (jnp.float32, jnp.bfloat16)),
    )
    return ",".join(name for name, ok in terms if not ok)


def logreg_pallas_ok(d: int, n_classes: int, dtype) -> bool:
    """Trace-time gate of the fused kernel (:func:`logreg_pallas_declined`)."""
    return not logreg_pallas_declined(d, n_classes, dtype)


def _loss_grad_pallas(Xl, yl, ml, A, b_row, *, multinomial: bool,
                      n_valid_classes: int, tile: int, interpret: bool):
    """Per-device fused pass.

    ``A`` is (Kp, d) with Kp a sublane multiple (rows >= n_valid_classes are
    zero); ``b_row`` is (1, 128) with the first K lanes holding intercepts.
    Returns (gA (Kp, d), acc (1, 128) = [loss_sum, grad_b_0..K-1, ...]).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = Xl.shape
    Kp = A.shape[0]
    K = n_valid_classes

    def kern(x_ref, y_ref, m_ref, a_ref, b_ref, gA_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            gA_ref[:] = jnp.zeros_like(gA_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # x stays in its storage dtype: a materialized f32 upcast of a bf16
        # tile doubles VMEM pressure and caps the tile size — instead both
        # dots below take the narrow operands directly with f32
        # accumulation (the MXU-native mixed-precision path; the TF32
        # analog cuML gets implicitly on Ampere). Parameters/residuals are
        # rounded to the operand dtype per dot; with objective_dtype=bf16
        # the data itself already carries that rounding.
        row = i * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        valid = row < n
        x = jnp.where(valid, x_ref[:], jnp.zeros((), x_ref.dtype))
        m = jnp.where(valid[:, 0], m_ref[:], 0.0)
        yv = jnp.where(valid[:, 0], y_ref[:], 0.0)

        A_t = a_ref[:].astype(x.dtype)       # (Kp, d)
        b = b_ref[0, :Kp]                    # (Kp,) f32
        z = lax.dot_general(                 # (tile, Kp) logits, f32
            x, A_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b[None, :]

        if multinomial:
            lane_k = lax.broadcasted_iota(jnp.int32, (tile, Kp), 1)
            # padded classes must not contribute to softmax/logsumexp
            z = jnp.where(lane_k < K, z, -1e30)
            zmax = jnp.max(z, axis=1, keepdims=True)
            ez = jnp.exp(z - zmax)
            sez = jnp.sum(ez, axis=1, keepdims=True)
            lse = jnp.log(sez[:, 0]) + zmax[:, 0]
            oh = (lane_k == yv.astype(jnp.int32)[:, None]).astype(jnp.float32)
            ll = lse - jnp.sum(z * oh, axis=1)
            R = (ez / sez - oh) * m[:, None]          # (tile, Kp)
        else:
            z1 = z[:, 0]
            ll = jax.nn.softplus(z1) - yv * z1
            r = (jax.nn.sigmoid(z1) - yv) * m          # (tile,)
            lane_k = lax.broadcasted_iota(jnp.int32, (tile, Kp), 1)
            R = jnp.where(lane_k == 0, r[:, None], 0.0)

        gA_ref[:] += lax.dot_general(                  # (Kp, d), f32 acc
            R.astype(x.dtype), x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        S = jnp.concatenate(
            [
                (ll * m)[:, None],
                R,
                jnp.zeros((tile, _LANES - 1 - Kp), jnp.float32),
            ],
            axis=1,
        )
        acc_ref[:] += jnp.sum(S, axis=0, keepdims=True)

    gA, acc = pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((Kp, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((Kp, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(Xl, yl, ml, A, b_row)
    return gA, acc


def make_fused_data_loss(X, y, mask, mesh, K: int, multinomial: bool,
                         interpret: bool | None = None):
    """Build ``f(Aeff, beff) -> Σ m·logloss`` whose value-and-grad is ONE
    data pass (custom_vjp: the forward pallas pass also yields the
    gradients; backward is a couple of multiplies).

    ``X``/``y``/``mask`` must be dp-sharded over ``mesh``; the (K, d)
    parameters are replicated. Gradients flow only to ``Aeff``/``beff``.
    """
    if interpret is None:
        interpret = FORCE_INTERPRET
    d = X.shape[1]
    Kp = max(8, -(-K // 8) * 8)
    tile = _row_tile(d, Kp)

    def run(Aeff, beff):
        A = jnp.zeros((Kp, d), jnp.float32).at[:K].set(Aeff)
        b_row = jnp.zeros((1, _LANES), jnp.float32).at[0, :K].set(beff)

        def per_device(Xl, yl, ml, A, b_row):
            gA, acc = _loss_grad_pallas(
                Xl, yl, ml, A, b_row,
                multinomial=multinomial, n_valid_classes=K,
                tile=tile, interpret=interpret,
            )
            gA = lax.psum(gA, DP_AXIS)
            acc = lax.psum(acc, DP_AXIS)
            return gA, acc

        gA, acc = shard_map(
            per_device,
            mesh=mesh,
            in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated(), LAYOUT.replicated()),
            out_specs=(LAYOUT.replicated(), LAYOUT.replicated()),
            check_vma=False,
        )(X, y, mask, A, b_row)
        return acc[0, 0], gA[:K], acc[0, 1:1 + K]

    @jax.custom_vjp
    def f(Aeff, beff):
        loss, _, _ = run(Aeff, beff)
        return loss

    def f_fwd(Aeff, beff):
        loss, gA, gb = run(Aeff, beff)
        return loss, (gA, gb)

    def f_bwd(res, g):
        gA, gb = res
        return (g * gA, g * gb)

    f.defvjp(f_fwd, f_bwd)
    return f
