"""Fused logistic loss+gradient Pallas kernels — one X pass per L-BFGS eval.

``jax.value_and_grad`` of the logistic data term reads the design matrix
twice per objective evaluation: once forward (``X @ Aᵀ``) and once backward
(``Rᵀ @ X``). For the bandwidth-bound L-BFGS fit that is the entire cost.
These kernels compute the masked loss **and** the gradient in a single HBM
pass, and a ``jax.custom_vjp`` wrapper (:func:`make_fused_data_loss`) hands
both to the solver, so its value-and-grad costs one data read instead of two.

Two cases that share no arithmetic, so two kernels, chosen from what the
code can see (K, the dtype of X, the backend, the layout the device keeps
the shard in):

* **Binomial (K = 1), f32 X, any width: the VPU, exact to float32.** A
  matvec fills 1 of the MXU's 128 columns and an f32 ``dot`` at default
  precision is one bf16 pass, so ``z = Σ_j x_ij·a_j`` and ``g_j = Σ_i
  r_i·x_ij`` are float32 multiplies and adds on the VPU. The frame is read
  as the device keeps it (:func:`rows_minor`): a shard whose width is no
  lane multiple lives with its ROWS minor (``f32[500000,3000]{0,1}``) and
  is read as its transpose, a bitcast, with 128 samples to a vreg
  (:func:`_binary_pass_rows_minor`); a lane-aligned width lives row-major
  (:func:`_binary_pass_cols_minor`). Either way no copy of X is made. The
  sums keep many independent partials: ``g`` in a (d, 128) / (1, d)
  accumulator that takes one tile sum a grid step, loss and ``Σr`` as one
  (8, 128) block of partials a grid step, all reduced once in XLA.
* **Multinomial (K >= 3), lane-aligned d <= 2048: the MXU, default
  precision** (:func:`_loss_grad_pallas`). Both ``dot_general``s take the
  operands in their storage dtype with f32 accumulation — one bf16 pass for
  f32 X, as XLA's own ``dot`` at default precision.

Used by ``logreg_fit`` (``ops/logreg_kernels.py``) when a dp-only mesh is
supplied and the gate admits the shape; the portable XLA path is unchanged
otherwise. cuML reference this replaces: the QN solver's fused objective
inside ``LogisticRegressionMG``
(``/root/reference/python/src/spark_rapids_ml/classification.py:1062-1064``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS
from .linalg import rows_minor  # noqa: F401  (the layout question is linalg's; asked here and by tests under this name)

_LANES = 128
_SUBLANES = 8

# Test hook: when True, logreg_pallas_ok ignores the backend check and the
# kernel runs through the Pallas interpreter — lets CPU CI exercise the
# REAL fused branch inside logreg_fit (gate → custom_vjp → L-BFGS), not
# just the standalone kernel.
FORCE_INTERPRET = False

# Handed to Mosaic as its limit, and what the counted residents must fit.
_VMEM_LIMIT = 100 * 1024 * 1024



def _row_tile(d: int, Kp: int) -> int:
    """Row-tile size of the multinomial kernel: ~16 MB of f32 per block
    (double-buffered by the pipeline) regardless of feature width, in
    VPU-sublane multiples (measured on v5e at 12M×256: 8 MB blocks sustain
    ~670 GB/s, 16 MB ~715 GB/s against ~735 achievable), shrunk when the
    padded class count is large — it materializes several
    (tile, Kp) intermediates (logits, softmax, residuals, one-hot, the
    packed loss/residual block), which at small d and many classes would
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
    return max(256, (4_194_304 // max(d, 6 * Kp)) // 8 * 8)


def binary_tile(d: int, minor_rows: bool) -> Tuple[int, int]:
    """``(tile, vmem bytes)`` of the binary pass: samples a grid step. The
    ONE rule the gate, the compile test and the kernels read.

    Rows minor: a ``(d, tile)`` block of the transposed frame, two buffers
    (the pipeline's), beside the (d, 128) coefficients and gradient
    accumulator, two buffers each: the power of two that makes the block
    about 8 MB, between 128 and 2048 lanes (512 at d=3000; on a v5e the pass
    runs at the HBM's rate at 512, 1024 and 2048 alike, and the kernel's code,
    so what tracing and lowering it cost every process, grows with the tile).
    Columns minor: a ``(tile, d)`` block, two buffers and two temporaries its
    size (the masked block and a product): the power of two that makes the
    block about 4 MB, at least 1024 rows (``y`` and the mask ride in as 1-D
    blocks, which XLA tiles by 1024). Either is halved while its residents do
    not fit; ``(0, bytes at the smallest tile)`` where none fits."""
    pow2_floor = lambda v: 1 << (max(1, v).bit_length() - 1)
    if minor_rows:
        d8 = -(-d // _SUBLANES) * _SUBLANES
        need = lambda t: 2 * d8 * t * 4 + 4 * d8 * _LANES * 4 + 2 * _SUBLANES * t * 4
        smallest = _LANES
        tile = min(2048, max(smallest, pow2_floor((8 << 20) // (d8 * 4))))
    else:
        dp = -(-d // _LANES) * _LANES
        need = lambda t: 4 * t * dp * 4 + 4 * _SUBLANES * dp * 4 + 4 * t * 4
        smallest = 1024
        tile = max(smallest, pow2_floor((4 << 20) // (dp * 4)))
    while need(tile) > _VMEM_LIMIT and tile // 2 >= smallest:
        tile //= 2
    return (tile if need(tile) <= _VMEM_LIMIT else 0), need(tile)


def logreg_pallas_declined(n_local: int, d: int, n_classes: int, dtype, device=None) -> str:
    """The terms of the fused kernels' gate that fail, comma-joined (empty:
    a kernel is admitted). Always: a TPU. ``n_classes`` is K of the
    formulation. K = 1 (binomial): f32 X (a bf16-placed X keeps XLA's path)
    and a tile (:func:`binary_tile`) whose residents fit VMEM, at any width.
    K >= 2 (multinomial): f32/bf16 X, lane-aligned d <= 2048, and few enough
    classes that the sublane-padded class block plus the loss lane pack into
    one 128-lane row (ceil(K/8)*8 + 1 <= 128, i.e. K <= 120). ``device`` is
    one device of the shard's mesh (default: the first of the backend). A
    pure function of its arguments and the backend: the estimator evaluates
    it again on the host to say on its ``solver.launch`` span why a fit ran
    XLA's two passes."""
    terms = [("backend", jax.default_backend() == "tpu" or FORCE_INTERPRET)]
    if n_classes == 1:
        terms += [
            ("dtype", dtype == jnp.float32),
            ("tile", binary_pass_tile(n_local, d, device)[0] > 0),
        ]
    else:
        terms += [
            ("d%128", d % _LANES == 0),
            ("d<=2048", d <= 2048),
            ("n_classes<=120", -(-n_classes // 8) * 8 + 1 <= _LANES),
            ("dtype", dtype in (jnp.float32, jnp.bfloat16)),
        ]
    return ",".join(name for name, ok in terms if not ok)


def logreg_pallas_ok(n_local: int, d: int, n_classes: int, dtype, device=None) -> bool:
    """Trace-time gate of the fused kernels (:func:`logreg_pallas_declined`)."""
    return not logreg_pallas_declined(n_local, d, n_classes, dtype, device)


def binary_pass_tile(n_local: int, d: int, device=None) -> Tuple[int, bool]:
    """``(tile, rows minor)`` of the binary pass over an f32 ``(n_local, d)``
    shard on ``device``: what the estimator's span reports and the pass runs."""
    minor_rows = rows_minor(device or jax.devices()[0], n_local, d)
    return binary_tile(d, minor_rows)[0], minor_rows


def _logistic_terms(z, y, m):
    """Masked per-sample loss and residual of the binomial model at logit z."""
    return (jax.nn.softplus(z) - y * z) * m, (jax.nn.sigmoid(z) - y) * m


def _partials_block(loss_lanes, r_lanes):
    """The (1, 8, 128) block a grid step writes: row 0 the loss partials,
    row 1 those of ``Σr``, the rest zero. Each argument is (1, 128)."""
    row = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
    return jnp.where(row == 0, loss_lanes, jnp.where(row == 1, r_lanes, 0.0))[None]


def _binary_pass_rows_minor(Xt, ym, a_lanes, b, *, tile: int, interpret: bool):
    """The binary pass over a shard kept with its rows minor.

    ``Xt`` is the (d, n) transpose of the shard — the same bytes — so a
    ``(d, tile)`` block holds ``tile`` samples along the lanes. ``ym`` is
    (8, n): row 0 the labels, row 1 the mask. ``a_lanes`` is the (d, 128)
    coefficient column broadcast along the lanes, ``b`` the (1, 1) intercept.
    Per grid step, all on the VPU in float32: ``z`` as d/8 multiply-adds of
    (8, 128) vregs per lane tile and one sublane sum; loss and residual on
    the lane-dense (1, tile) logits; ``g += x·r`` summed over the step's
    lane tiles into the (d, 128) accumulator. Lanes past n (the last block
    only) are masked. Returns (g (d, 128), partials (steps, 8, 128))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, n = Xt.shape
    steps = pl.cdiv(n, tile)
    lane_tiles = tile // _LANES
    d8 = d // _SUBLANES * _SUBLANES          # rows covered by whole sublane groups
    lanes = [slice(t * _LANES, (t + 1) * _LANES) for t in range(lane_tiles)]

    def step(x_ref, ym_ref, a_ref, b_ref, g_ref, part_ref, valid):
        """``valid``: None, or the (1, tile) mask of the lanes that are samples."""

        def x_at(rows, t):
            x = x_ref[rows, lanes[t]]
            return x if valid is None else jnp.where(valid[:, lanes[t]], x, 0.0)

        def z_group(rows, acc):
            a = a_ref[rows, :]
            return tuple(acc[t] + x_at(rows, t) * a for t in range(lane_tiles))

        def group(c):
            return pl.ds(pl.multiple_of(c * _SUBLANES, _SUBLANES), _SUBLANES)

        acc = (jnp.zeros((_SUBLANES, _LANES), jnp.float32),) * lane_tiles
        if d8:
            acc = lax.fori_loop(0, d8 // _SUBLANES, lambda c, acc: z_group(group(c), acc), acc)
        z = jnp.concatenate([jnp.sum(p, axis=0, keepdims=True) for p in acc], axis=1)
        if d8 < d:                            # the width's last rows, fewer than 8
            a = a_ref[d8:d, :]
            z = z + jnp.concatenate(
                [jnp.sum(x_at(slice(d8, d), t) * a, axis=0, keepdims=True) for t in range(lane_tiles)],
                axis=1,
            )
        z = z + b_ref[0, 0]
        y, m = ym_ref[0:1, :], ym_ref[1:2, :]
        if valid is not None:
            y, m = jnp.where(valid, y, 0.0), jnp.where(valid, m, 0.0)
        ll, r = _logistic_terms(z, y, m)                        # (1, tile)
        part_ref[:] = _partials_block(
            sum(ll[:, s] for s in lanes), sum(r[:, s] for s in lanes)
        )
        r_rows = [jnp.broadcast_to(r[:, s], (_SUBLANES, _LANES)) for s in lanes]

        def g_rows(rows, r_of):
            p = x_at(rows, 0) * r_of[0]
            for t in range(1, lane_tiles):
                p = p + x_at(rows, t) * r_of[t]
            g_ref[rows, :] += p

        if d8:
            lax.fori_loop(0, d8 // _SUBLANES, lambda c, _: g_rows(group(c), r_rows), None)
        if d8 < d:
            g_rows(slice(d8, d), [rr[: d - d8] for rr in r_rows])

    def kern(x_ref, ym_ref, a_ref, b_ref, g_ref, part_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            g_ref[:] = jnp.zeros_like(g_ref)

        refs = (x_ref, ym_ref, a_ref, b_ref, g_ref, part_ref)
        if n % tile == 0:
            step(*refs, None)
        else:
            @pl.when(i < steps - 1)
            def _():
                step(*refs, None)

            @pl.when(i == steps - 1)
            def _():
                lane = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                step(*refs, (steps - 1) * tile + lane < n)

    return pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((d, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((_SUBLANES, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((d, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((d, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _SUBLANES, _LANES), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((steps, _SUBLANES, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name="logreg_binary_pass",
        interpret=interpret,
    )(Xt, ym, a_lanes, b)


def _binary_pass_cols_minor(Xl, yl, ml, a_row, b, *, tile: int, interpret: bool):
    """The binary pass over a shard kept row-major (a lane-aligned width):
    a ``(tile, d)`` block holds ``tile`` samples along the sublanes. Float32
    on the VPU: ``z`` a multiply by the (1, d) coefficients and a lane sum,
    ``g`` a multiply by the residuals and a sum over the tile's rows, added
    to the (1, d) accumulator. Rows past n are zeroed. Returns
    (g (1, d), partials (steps, 8, 128))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = Xl.shape
    steps = pl.cdiv(n, tile)

    def kern(x_ref, y_ref, m_ref, a_ref, b_ref, g_ref, part_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            g_ref[:] = jnp.zeros_like(g_ref)

        valid = i * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0) < n
        x = jnp.where(valid, x_ref[:], 0.0)
        m = jnp.where(valid, m_ref[:][:, None], 0.0)            # (tile, 1)
        y = jnp.where(valid, y_ref[:][:, None], 0.0)
        z = jnp.sum(x * a_ref[:], axis=1, keepdims=True) + b_ref[0, 0]
        ll, r = _logistic_terms(z, y, m)
        g_ref[:] += jnp.sum(x * r, axis=0, keepdims=True)
        first = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) == 0
        part_ref[:] = _partials_block(
            jnp.where(first, jnp.sum(ll, axis=0, keepdims=True), 0.0),
            jnp.where(first, jnp.sum(r, axis=0, keepdims=True), 0.0),
        )

    return pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _SUBLANES, _LANES), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((steps, _SUBLANES, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        name="logreg_binary_pass",
        interpret=interpret,
    )(Xl, yl, ml, a_row, b)


@functools.partial(jax.jit, static_argnames=("minor_rows", "interpret"))
def binary_loss_grad(Xl, yl, ml, a, b, *, minor_rows: bool, interpret: bool):
    """One pass over an f32 shard ``Xl`` (n, d): the masked loss sum of the
    binomial model at coefficients ``a`` (d,) and intercept ``b`` (scalar),
    ``Xᵀr`` (d,) and ``Σr``. ``minor_rows`` says how the device keeps the
    shard (:func:`rows_minor`) and so which way it is read. Jitted, so the
    L-BFGS's three evaluation sites trace and lower the kernel once."""
    n, d = Xl.shape
    tile, _ = binary_tile(d, minor_rows)
    b = jnp.reshape(b, (1, 1)).astype(jnp.float32)
    if minor_rows:
        ym = jnp.zeros((_SUBLANES, n), jnp.float32).at[0].set(yl).at[1].set(ml)
        g, parts = _binary_pass_rows_minor(
            Xl.T, ym, jnp.broadcast_to(a[:, None], (d, _LANES)), b,
            tile=tile, interpret=interpret,
        )
        g = g.sum(axis=1)
    else:
        g, parts = _binary_pass_cols_minor(
            Xl, yl, ml, a[None, :], b, tile=tile, interpret=interpret
        )
        g = g[0]
    return parts[:, 0].sum(), g, parts[:, 1].sum()


def _loss_grad_pallas(Xl, yl, ml, A, b_row, *, n_valid_classes: int,
                      tile: int, interpret: bool):
    """Per-device fused multinomial pass (MXU, default precision).

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
        # accumulation (the MXU-native mixed-precision path). Parameters and
        # residuals are rounded to the operand dtype per dot; with
        # objective_dtype=bf16 the data itself already carries that rounding.
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
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(Xl, yl, ml, A, b_row)
    return gA, acc


def make_fused_data_loss(X, y, mask, mesh, K: int, multinomial: bool,
                         interpret: bool | None = None):
    """Build ``f(Aeff, beff) -> Σ m·logloss`` whose value-and-grad is ONE
    data pass (custom_vjp: the forward pallas pass also yields the
    gradients; backward is a couple of multiplies). Binomial (K = 1, not
    ``multinomial``): the float32 VPU pass; else the multinomial MXU pass.

    ``X``/``y``/``mask`` must be dp-sharded over ``mesh``; the (K, d)
    parameters are replicated. Gradients flow only to ``Aeff``/``beff``.
    """
    if interpret is None:
        interpret = FORCE_INTERPRET
    d = X.shape[1]
    rows = LAYOUT.rows()
    rep = LAYOUT.replicated()

    if not multinomial:
        minor_rows = rows_minor(mesh.devices.flat[0], X.shape[0] // mesh.shape[DP_AXIS], d)

        def per_device(Xl, yl, ml, a, b):
            out = binary_loss_grad(Xl, yl, ml, a, b, minor_rows=minor_rows, interpret=interpret)
            return tuple(lax.psum(o, DP_AXIS) for o in out)

        def run(Aeff, beff):
            loss, g, gb = shard_map(
                per_device, mesh=mesh, in_specs=(rows, rows, rows, rep, rep),
                out_specs=(rep, rep, rep), check_vma=False,
            )(X, y, mask, Aeff[0], beff[0])
            return loss, g[None, :], gb[None]
    else:
        Kp = max(8, -(-K // 8) * 8)
        tile = _row_tile(d, Kp)

        def per_device(Xl, yl, ml, A, b_row):
            gA, acc = _loss_grad_pallas(
                Xl, yl, ml, A, b_row, n_valid_classes=K, tile=tile, interpret=interpret,
            )
            return lax.psum(gA, DP_AXIS), lax.psum(acc, DP_AXIS)

        def run(Aeff, beff):
            A = jnp.zeros((Kp, d), jnp.float32).at[:K].set(Aeff)
            b_row = jnp.zeros((1, _LANES), jnp.float32).at[0, :K].set(beff)
            gA, acc = shard_map(
                per_device, mesh=mesh, in_specs=(rows, rows, rows, rep, rep),
                out_specs=(rep, rep), check_vma=False,
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
