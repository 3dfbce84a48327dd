"""Shared dense linear-algebra kernels (jit-friendly global math).

These are the TPU-native equivalents of the reference's native CUDA kernels
(``/root/reference/jvm/native/src/rapidsml_jni.cu``): ``dgemmCov`` (Gram /
covariance, :109-127), ``calSVD`` (eigendecomposition of the covariance,
:215-268) and ``signFlip`` (deterministic eigenvector sign, :35-60).
Written as global math over row-sharded arrays: under ``jit`` XLA's SPMD
partitioner turns the row reductions into ``psum`` over the dp axis — the
role NCCL allreduce played for cuML.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental.layout import Layout

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS, MP_AXIS, allreduce_sum_host, padded_rows, row_sharding


def masked_mean(X: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(column means, valid count) under a row-validity mask."""
    n = mask.sum()
    s = (X * mask[:, None]).sum(axis=0)
    return s / n, n


def mean_and_cov(X: jax.Array, mask: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Column mean and sample covariance (n-1 normalized) with masking.

    Computed as a single Gram pass: cov = (XᵀX - n·μμᵀ) / (n-1). The XᵀX
    contraction is the MXU hot loop; rows are dp-sharded so XLA emits one
    psum of the d×d partial Gram per device — identical communication
    volume to the reference's cuML allreduce of cov partials.
    """
    mean, n = masked_mean(X, mask)
    # Center BEFORE the Gram: the one-pass (X'X - n μμ')/(n-1) form
    # catastrophically cancels in f32 when |μ| >> σ. The subtraction fuses
    # into the matmul's operand read, so the extra pass is ~free on TPU.
    Xc = (X - mean[None, :]) * mask[:, None]
    # HIGHEST: at default precision an f32 dot on the MXU is one bf16 pass
    cov = jnp.matmul(Xc.T, Xc, precision=lax.Precision.HIGHEST) / (n - 1.0)
    return mean, cov, n


# Test hook (mirrors ops.logreg_pallas.FORCE_INTERPRET): when True, the Gram
# kernel's gate ignores the backend and layout terms and the kernel runs
# through the Pallas interpreter, letting CPU CI exercise the real kernel
# branch inside the fit paths.
FORCE_INTERPRET = False

_LANES = 128
# Handed to Mosaic as its limit, and what the Gram kernel's counted residents
# must fit (a v5e has 128 MiB of VMEM).
_GRAM_VMEM_LIMIT = 110 * 1024 * 1024
# XLA's blocked Gram: bytes of one row block (8,192 rows at d = 3000).
_GRAM_BLOCK_BYTES = 96 << 20


def row_chunk(i, csize: int, *arrays):
    """Rows ``[i*csize, (i+1)*csize)`` of each array, sliced along axis 0.

    The canonical chunk access for every chunked-scan kernel. Slice with
    ``dynamic_slice`` — do NOT ``lax.scan`` over a reshaped X: scan
    materializes its xs operand as a second (chunks, csize, d) array, a full
    copy of the design matrix beside the resident one. The slice reads the
    original buffer in place whichever way the device keeps it: a TPU lays
    ``f32[500000,3000]`` out with its rows minor and the slice of a whole
    number of 128-row lane tiles comes out rows minor too (PERF.md section 6,
    PR 33: the parent's 6.9 GB temporary was the strided mean sample's
    gather, not this slice).

    Use :func:`check_row_chunking` at kernel entry so a non-divisible row
    count fails loudly at trace time instead of silently dropping the tail.
    """
    return tuple(
        lax.dynamic_slice_in_dim(a, i * csize, csize, 0) for a in arrays
    )


def check_row_chunking(n_rows: int, csize: int) -> int:
    """Trace-time guard: rows must split into whole ``csize`` chunks
    (``shard_rows`` pads to this). Returns the chunk count."""
    if n_rows % csize != 0:
        raise ValueError(
            f"chunked kernel requires rows ({n_rows}) divisible by the "
            f"chunk size ({csize}); pad with shard_rows first"
        )
    return n_rows // csize


def rows_minor(device, n_local: int, d: int, dtype=jnp.float32) -> bool:
    """Whether ``device`` keeps a ``(n_local, d)`` array with its rows minor
    (the samples along the lanes). A TPU lays a 2-D array out whichever way
    pads less to its (8, 128) tiles: ``f32[500000,3000]`` has its rows minor
    (3000 x 500,096), ``f32[4194304,256]`` its columns. Asked of the runtime
    (a described device answers too), not reckoned: the answer decides which
    way the binary pass reads the frame, and a wrong one would put a relayout
    of the whole frame in front of the kernel."""
    layout = device.client.get_default_layout(np.dtype(dtype), (n_local, d), device)
    return tuple(Layout.from_pjrt_layout(layout).major_to_minor) == (1, 0)


def gram_tile(d: int) -> Tuple[int, int, int]:
    """``(tile, block, vmem bytes)`` of :func:`_shifted_gram_pallas` at width
    ``d``: samples a grid step, rows of one accumulator block, and the bytes
    the kernel keeps in VMEM. The ONE rule the gate, the compile test and the
    kernel read.

    The accumulator is cut into square blocks of ``block`` = 512 rows (the
    lane-padded width where that is narrower), of which the kernel fills the
    upper triangle. ``tile`` is the power of two that makes the (padded d,
    tile) block of the transposed frame about 3 MB, between 128 and 2048
    lanes (256 at d = 3000: on a v5e the pass takes 0.178 s there, 0.185 s
    at 512 and 0.196 s at 1024, and Mosaic 6, 11 and 14 s to compile it).
    Resident: the accumulator once (its block never moves, so it is
    single-buffered), the frame's block twice (the pipeline's) and once more
    centred, the (padded d, 128) row-sum partials and μ̂ along the lanes (two
    buffers each), and the operands of one block product split three ways in
    bf16."""
    block = min(512, -(-d // _LANES) * _LANES)
    dp = -(-d // block) * block
    tile = min(2048, max(_LANES, 1 << (max(1, (3 << 20) // (dp * 4)).bit_length() - 1)))
    need = dp * dp * 4 + 3 * dp * tile * 4 + 4 * dp * _LANES * 4 + 2 * 3 * block * tile * 2 + block * block * 4
    return tile, block, need


def _gram_triangle_pallas(
    Xt: jax.Array,
    ml: jax.Array,
    mean_hat: jax.Array,
    *,
    tile: int | None = None,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas TPU kernel: one pass over a shard (or one row block of it) kept
    with its ROWS minor, accumulating the shifted Gram ``Σ m·(x-μ̂)(x-μ̂)ᵀ``
    and row-sum ``Σ m·(x-μ̂)`` with float32-exact products. Returns them as
    the kernel leaves them — the upper block triangle ``(nb, block, padded
    d)`` and the row sum's ``(padded d, 128)`` lane partials — which add up
    over row blocks as they are; :func:`_mirror_gram_triangle` finishes them.

    ``Xt`` is the (d, n) transpose of the shard — the same bytes (a TPU keeps
    ``f32[500000,3000]`` as 3000 × 500,096: whichever way pads less) — so the
    Gram is ``A·Aᵀ`` of that view and a ``(d, tile)`` block holds ``tile``
    samples along the lanes. The whole (d, d) accumulator stays in VMEM
    (37.7 MB at d = 3000 → 3072), so X is read from HBM once. Per grid step
    the block is centred and masked into a scratch, and for every pair of
    ``block``-row slabs (i ≤ j) one ``dot_general`` over the lanes at
    ``Precision.HIGHEST`` (six bf16 passes: float32 to the last bit that an
    f32 accumulator keeps) adds to accumulator block (i, j): the upper block
    triangle, 21 of 36 blocks at d = 3000. The block of the frame is taken
    ``padded d`` rows tall: the rows past ``d`` (the overhang of the one block
    along that axis) are zeroed, as are lanes past ``n`` and masked samples.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, n = Xt.shape
    rule_tile, bs, _ = gram_tile(d)
    tile = rule_tile if tile is None else tile
    nb = -(-d // bs)
    dp = nb * bs
    if interpret is None:
        interpret = FORCE_INTERPRET

    def kern(x_ref, m_ref, mu_ref, G_ref, s_ref, xs_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _():
            G_ref[:] = jnp.zeros_like(G_ref)
            s_ref[:] = jnp.zeros_like(s_ref)

        # lanes past n and rows past d: the block is fetched beyond the
        # array — zero them explicitly (where, not multiply: the fill
        # could be non-finite)
        lane = t * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        m = jnp.where(lane < n, m_ref[:], 0.0)                      # (1, tile)
        keep = (lax.broadcasted_iota(jnp.int32, (dp, 1), 0) < d) & (m != 0.0)
        mu = mu_ref[:]                                              # (dp, 128): μ̂ along the lanes
        part = jnp.zeros((dp, _LANES), jnp.float32)
        for q in range(tile // _LANES):
            sl = slice(q * _LANES, (q + 1) * _LANES)
            xq = jnp.where(keep[:, sl], (x_ref[:, sl] - mu) * m[:, sl], 0.0)
            xs_ref[:, sl] = xq
            part = part + xq
        s_ref[:] += part
        for j in range(nb):
            xj = xs_ref[j * bs:(j + 1) * bs, :]

            def slab(i, _, j=j, xj=xj):
                xi = xs_ref[pl.ds(pl.multiple_of(i * bs, bs), bs), :]
                G_ref[i, :, j * bs:(j + 1) * bs] += lax.dot_general(
                    xi, xj, (((1,), (1,)), ((), ())),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
                return 0

            lax.fori_loop(0, j + 1, slab, 0)

    once = pl.Buffered(1)
    G, s = pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((dp, tile), lambda t: (0, t)),
            pl.BlockSpec((1, tile), lambda t: (0, t)),
            pl.BlockSpec((dp, _LANES), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, bs, dp), lambda t: (0, 0, 0), pipeline_mode=once),
            pl.BlockSpec((dp, _LANES), lambda t: (0, 0), pipeline_mode=once),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bs, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dp, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_GRAM_VMEM_LIMIT
        ),
        name="pca_gram_pass",
        interpret=interpret,
    )(Xt, ml.reshape(1, n), jnp.broadcast_to(jnp.pad(mean_hat, (0, dp - d))[:, None], (dp, _LANES)))
    return G, s


def _mirror_gram_triangle(G: jax.Array, s: jax.Array, d: int) -> Tuple[jax.Array, jax.Array]:
    """``(Gram (d, d), row sum (d,))`` from :func:`_gram_triangle_pallas`'s
    outputs, or from sums of them over row blocks and devices: the lower
    block triangle is the mirror of the upper, written once in XLA; the
    diagonal blocks are symmetrized."""
    nb, bs, dp = G.shape
    U = G.reshape(dp, dp)
    bi = lax.broadcasted_iota(jnp.int32, (dp, dp), 0) // bs
    bj = lax.broadcasted_iota(jnp.int32, (dp, dp), 1) // bs
    G = jnp.where(bi < bj, U, jnp.where(bi > bj, U.T, 0.5 * (U + U.T)))
    return G[:d, :d], s.sum(axis=1)[:d]


def _shifted_gram_pallas(
    Xt: jax.Array,
    ml: jax.Array,
    mean_hat: jax.Array,
    *,
    tile: int | None = None,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """``(Σ m·(x-μ̂)(x-μ̂)ᵀ, Σ m·(x-μ̂))`` of a whole rows-minor shard in one
    call: :func:`_gram_triangle_pallas`, mirrored."""
    G, s = _gram_triangle_pallas(Xt, ml, mean_hat, tile=tile, interpret=interpret)
    return _mirror_gram_triangle(G, s, Xt.shape[0])


def _shifted_gram_xla(
    Xl: jax.Array, ml: jax.Array, mean_hat: jax.Array, *, block: int, c0=0, bw: int | None = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA's blocked pass: ``(Σ m·(x-μ̂)(x-μ̂[c0:c0+bw])ᵀ, Σ m·(x-μ̂), Σ m)``
    over row blocks of ``block`` rows, each one ``dot_general`` at
    ``Precision.HIGHEST``, in a ``fori_loop`` whose ``dynamic_slice`` reads
    the frame in place whichever way the device keeps it. Any row count: the
    last block is moved back to end on the last row, and the rows it shares
    with the block before are masked out of it."""
    n, d = Xl.shape
    bw = d if bw is None else bw
    block = min(block, n)

    def body(i, carry):
        s, cnt, G = carry
        start = jnp.minimum(i * block, n - block)
        x = lax.dynamic_slice_in_dim(Xl, start, block, 0)
        m = lax.dynamic_slice_in_dim(ml, start, block, 0)
        m = jnp.where(start + lax.iota(jnp.int32, block) >= i * block, m, 0.0)
        xs = (x - mean_hat[None, :]) * m[:, None]
        xb = xs if bw == d else lax.dynamic_slice_in_dim(xs, c0, bw, 1)
        G = G + lax.dot_general(
            xs, xb, (((0,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST, preferred_element_type=Xl.dtype,
        )
        return s + xs.sum(axis=0), cnt + m.sum(), G

    s, cnt, G = lax.fori_loop(
        0,
        -(-n // block),
        body,
        (jnp.zeros((d,), Xl.dtype), jnp.zeros((), Xl.dtype), jnp.zeros((d, bw), Xl.dtype)),
    )
    return G, s, cnt


def mp_gram_blocks(mesh, d: int) -> int:
    """Resolved model-axis degree for the blocked (feature-sharded) Gram
    accumulators: the mesh's mp extent when ``TPUML_MP_GRAM`` is on and the
    (padded) feature width splits evenly across it, else 1. Reads the env
    OUTSIDE jit — callers pass the result in as a static arg so retraces
    track the knob."""
    from ..runtime import envspec

    n_mp = int(mesh.shape.get(MP_AXIS, 1))
    if n_mp <= 1 or d % n_mp != 0:
        return 1
    if str(envspec.get("TPUML_MP_GRAM")) == "off":
        return 1
    return n_mp


def gram_pallas_declined(n_local: int, d: int, dtype, device=None, mp_blocks: bool = False) -> str:
    """The terms of the Gram kernel's gate that fail, comma-joined (empty:
    :func:`_shifted_gram_pallas` is admitted; otherwise XLA's blocked pass
    runs, :func:`_shifted_gram_xla`). A TPU; f32 X (the kernel's products
    and accumulator are float32: an f64 fit keeps XLA's); a shard the device
    keeps with its ROWS minor (``rows_minor``: a width that is no multiple of
    128 under many rows), which the kernel reads as its transpose without a
    copy — a row-major shard (a lane-aligned width) would get a relayout of
    the whole frame in front of it; a (d, d) accumulator that fits VMEM
    beside the frame's blocks (:func:`gram_tile`: up to d ≈ 4,000); and no
    column-blocked accumulator (``mp_blocks``). ``device`` is one device of
    the shard's mesh (default: the first of the backend). A pure function of
    its arguments and the backend: the estimator evaluates it again on the
    host to say on its ``solver.launch`` span which pass a fit ran, and why."""
    terms = [("dtype", dtype == jnp.float32), ("vmem", gram_tile(d)[2] <= _GRAM_VMEM_LIMIT), ("mp", not mp_blocks)]
    if not FORCE_INTERPRET:
        on_tpu = jax.default_backend() == "tpu"
        terms.insert(0, ("backend", on_tpu))
        terms.append(("rows_minor", on_tpu and rows_minor(device or jax.devices()[0], n_local, d)))
    return ",".join(name for name, ok in terms if not ok)


def gram_block_rows(n_local: int, d: int, csize: int, itemsize: int = 4) -> int:
    """Rows of one block of XLA's pass: at most ``csize`` (the caller's bound
    on temporaries) and ``_GRAM_BLOCK_BYTES``, in whole 128-row lane tiles
    where that many rows are there."""
    rows = min(csize, max(_LANES, _GRAM_BLOCK_BYTES // (d * itemsize)), n_local)
    return rows - rows % _LANES if rows >= _LANES else rows


def _mean_sample(Xl: jax.Array, ml: jax.Array, rows: int) -> Tuple[jax.Array, jax.Array]:
    """(Σ m·x, Σ m) over about ``rows`` rows taken as up to 64 runs of
    consecutive rows spread evenly over the shard. Runs, not a stride: a
    strided gather along the minor dimension of a rows-minor frame put a
    row-major copy of the WHOLE frame in front of it (6.9 GB at 500,000 x
    3000: PERF.md section 6, PR 33); a run of whole lane tiles is a slice."""
    n = Xl.shape[0]
    runs = max(1, min(64, rows))
    width = rows // runs
    if width >= _LANES:
        width -= width % _LANES
    s = jnp.zeros((Xl.shape[1],), Xl.dtype)
    c = jnp.zeros((), Xl.dtype)
    for r in range(runs):
        lo = r * n // runs
        lo = min(lo - lo % _LANES if width >= _LANES else lo, n - width)
        x, m = Xl[lo:lo + width], ml[lo:lo + width]
        s, c = s + (x * m[:, None]).sum(axis=0), c + m.sum()
    return s, c


def host_mean_sample(x: np.ndarray, rows: int = 4096) -> Tuple[np.ndarray, float]:
    """``(Σ x, count)`` in float64 over about ``rows`` rows of the HOST array,
    taken as 64 runs of consecutive rows spread evenly from its first row to
    its last (an array of at most ``rows`` rows: all of it). What
    :func:`_mean_sample` is to a shard on the device, for a caller that needs
    μ̂ before the first row block is there (:func:`gram_fold`): 49 MB of host
    reads at 3000 f32 columns, a few milliseconds. From all over the array
    and not its leading rows, so that sorted or drifting data still gives
    δ = O(σ/√rows)."""
    n = x.shape[0]
    if n <= rows:
        return x.sum(axis=0, dtype=np.float64), float(n)
    runs = 64
    width = max(1, rows // runs)
    s = np.zeros(x.shape[1:], np.float64)
    for r in range(runs):
        lo = r * (n - width) // (runs - 1)
        s += x[lo:lo + width].sum(axis=0, dtype=np.float64)
    return s, float(runs * width)


def _recentred(G, s, n, mean_hat, cols=None):
    """``(mean, Σ m·(x-x̄)(x-x̄)ᵀ)`` from the shifted sums ``G = Σ m·(x-μ̂)
    (x-μ̂)ᵀ``, ``s = Σ m·(x-μ̂)`` and the count: with ``δ = s/n`` the exact
    mean minus μ̂, ``G − n·δδᵀ`` exactly, whatever μ̂ — only the rounding of
    the correction depends on how small δ is. ``cols`` = (start, width) of
    the column block that ``G`` holds, where it is not the whole."""
    delta = s / n
    delta_b = delta if cols is None else lax.dynamic_slice_in_dim(delta, cols[0], cols[1], 0)
    return mean_hat + delta, G - n * jnp.outer(delta, delta_b)


def _recentre(G, s, n, mean_hat, cols=None):
    """``(mean, covariance over n-1)`` from the shifted sums: :func:`_recentred`."""
    mean, Gc = _recentred(G, s, n, mean_hat, cols)
    return mean, Gc / (n - 1.0)


def mean_and_cov_chunked(
    X: jax.Array, mask: jax.Array, mesh, csize: int, *, mp_blocks: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`mean_and_cov` with bounded temporaries, ONE pass over X and
    float32-exact products — over a frame that is already WHOLE on the
    devices: one program, which starts when the last row block has landed.
    ``PCA.fit`` runs it (inside ``_pca_fit_kernel``) only with ``mp_blocks``;
    its other resident fits run the same passes block by block under the
    frame's crossing (:func:`gram_fold`, :func:`cov_from_gram_folds`), with
    μ̂ from the host (:func:`host_mean_sample`) instead of the device sample.

    The fused form relies on XLA folding the ``(X - μ)·mask`` centering into
    the Gram matmul's operand read; at double-digit-GB row counts the
    compiler can instead materialize the centered copy and OOM a chip whose
    HBM the resident matrix already half-fills. Here each device reads its
    rows block by block, so peak extra memory is one block (at most
    ``csize`` rows, and 96 MB), and no product is a single bf16 pass: an f32
    ``dot`` on the MXU at default precision is, and the covariance of a fit
    that states float32 is not to be.

    Numerics: the naive one-pass ``(XᵀX - n·μμᵀ)/(n-1)`` catastrophically
    cancels in f32 when |μ| >> σ, and a full two-pass centering reads X
    twice from HBM. Instead the mean is *estimated* from a sample (one cheap
    psum), the main pass accumulates shifted sums ``Σ m·(x-μ̂)`` and Gram
    ``Σ m·(x-μ̂)(x-μ̂)ᵀ``, and a final rank-1 correction re-centers exactly:
    with ``δ = mean - μ̂`` small, the cancellation term is harmless —
    two-pass stability at one-pass bandwidth. The sample is ``csize`` rows
    in runs spread *across the whole device shard* (:func:`_mean_sample`,
    not the leading chunk), so data sorted or drifting in magnitude still
    yields δ = O(σ/√csize); only then does the f32 rank-1 correction stay
    clear of the cancellation the shift avoids. Partials combine with one
    ``psum`` over dp — the same communication volume as the fused form.

    Which pass (*separated*, by what the code can observe): a shard the TPU
    keeps with its rows minor takes the Pallas kernel over its transposed
    view (:func:`_shifted_gram_pallas`; gate :func:`gram_pallas_declined`);
    every other shard — row-major, f64, another backend, a column-blocked
    accumulator, a width past the kernel's VMEM — takes XLA's blocked pass
    (:func:`_shifted_gram_xla`). Rows must be sharded over dp only; any row
    count.

    With ``mp_blocks`` (resolve via :func:`mp_gram_blocks` — env is read
    outside jit) each device accumulates only its OWN column block of the
    shifted Gram, ``Σ m·(x-μ̂)(x-μ̂[blk])ᵀ`` of shape (d, d/mp): the d²
    accumulator — the structure that bounds feature width on a chip —
    shrinks by 1/mp, the SUMMA-style row-panel × column-panel product. The
    psum stays over dp only (mp peers hold *different* blocks, dp peers the
    same block) and the returned covariance is column-sharded over mp
    (``LAYOUT.cols()``). Per-element reduction order matches the full-width
    pass, so parity with the 1-D path is tight (see docs/mesh.md tolerance
    contract).
    """

    n_mp = int(mesh.shape.get(MP_AXIS, 1)) if mp_blocks else 1
    if n_mp > 1 and X.shape[1] % n_mp != 0:
        raise ValueError(
            f"blocked Gram requires feature width ({X.shape[1]}) divisible "
            f"by the mp extent ({n_mp}); gate with mp_gram_blocks"
        )
    bw = X.shape[1] // n_mp
    n_local = X.shape[0] // int(mesh.shape[DP_AXIS])
    use_pallas = not gram_pallas_declined(
        n_local, X.shape[1], X.dtype, mesh.devices.flat[0], mp_blocks=n_mp > 1
    )

    def per_device(Xl, ml):
        s0, c0 = _mean_sample(Xl, ml, min(csize, Xl.shape[0]))
        mean_hat = lax.psum(s0, DP_AXIS) / jnp.maximum(lax.psum(c0, DP_AXIS), 1.0)

        with jax.named_scope("pca.gram"):
            if use_pallas:
                G, s = _shifted_gram_pallas(Xl.T, ml, mean_hat)
                cnt = ml.sum()
            else:
                G, s, cnt = _shifted_gram_xla(
                    Xl, ml, mean_hat,
                    block=gram_block_rows(Xl.shape[0], Xl.shape[1], csize, Xl.dtype.itemsize),
                    # column-block start of THIS device's Gram panel (0 at mp=1)
                    c0=lax.axis_index(MP_AXIS) * bw if n_mp > 1 else 0, bw=bw,
                )
        n = lax.psum(cnt, DP_AXIS)
        s = lax.psum(s, DP_AXIS)
        G = lax.psum(G, DP_AXIS)
        mean, cov = _recentre(
            G, s, n, mean_hat, cols=(lax.axis_index(MP_AXIS) * bw, bw) if n_mp > 1 else None
        )
        return mean, cov, n

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows()),
        out_specs=(LAYOUT.replicated(), LAYOUT.cols() if n_mp > 1 else LAYOUT.replicated(), LAYOUT.replicated()),
        check_vma=False,
    )(X, mask)


def gram_fold_zeros(d: int, dtype, pallas: bool, device, label: bool = False) -> Tuple[jax.Array, ...]:
    """The state :func:`gram_fold` starts from on ``device``: zero ``(Gram,
    row sum, count)`` in the form its pass leaves them — the Pallas kernel's
    upper block triangle and lane partials, or XLA's (d, d) and (d,) — and,
    with a ``label``, zero ``(Σ (x-μ̂)(y-μ̂_y), Σ (y-μ̂_y), Σ (y-μ̂_y)²)``."""
    if pallas:
        _, bs, _ = gram_tile(d)
        dp = -(-d // bs) * bs
        shapes = ((dp // bs, bs, dp), (dp, _LANES), (1,))
    else:
        shapes = ((d, d), (d,), (1,))
    if label:
        shapes += ((d,), (1,), (1,))
    return tuple(jnp.zeros(shape, dtype, device=device) for shape in shapes)


@functools.partial(jax.jit, static_argnames=("pallas", "block"), donate_argnums=0)
def gram_fold(acc, rows: jax.Array, mean_hat: jax.Array, valid, label=None, *, pallas: bool, block: int):
    """``acc`` (one device's running ``(Gram, row sum, count)``, donated) plus
    the shifted sums of one row block: ``Σ (x-μ̂)(x-μ̂)ᵀ``, ``Σ (x-μ̂)`` and
    the count over the ``valid`` leading rows of ``rows``. The covariance is
    a sum over rows, so over row blocks: handed to ``shard_rows`` as its fold,
    this program runs on block *i* while block *i+1* crosses the link, and
    :func:`cov_from_gram_folds` finishes what the blocks left.

    The pass is the one :func:`mean_and_cov_chunked` runs on a whole shard,
    chosen the same way (``pallas``: :func:`gram_pallas_declined` of the
    block's shape): the Pallas kernel over the block's transposed view where
    the device keeps the block rows minor — its upper block triangle is added
    as it is, and mirrored once, in the finish — XLA's blocked pass (``block``
    rows a product) otherwise. Both at ``Precision.HIGHEST``; the sums over
    blocks are float32 adds of float32 partials, eight an entry at
    ``pca_dbx``'s frame. ``valid`` is traced: one program a block shape.

    ``label`` = ``(y, μ̂_y)``, the block's labels (rows past ``valid`` are
    masked) and their shift: the Gram as above, and beside it
    ``Σ (x-μ̂)(y-μ̂_y)``, ``Σ (y-μ̂_y)`` and ``Σ (y-μ̂_y)²`` in ``acc[3:]`` —
    a least-squares fit's statistics. Their products are float32 ones on the
    VPU, one more read of the block. Without a label the program is the
    Gram's alone."""
    m = (lax.iota(jnp.int32, rows.shape[0]) < valid).astype(rows.dtype)
    with jax.named_scope("pca.gram"):
        if pallas:
            G, s = _gram_triangle_pallas(rows.T, m, mean_hat)
            cnt = m.sum()
        else:
            G, s, cnt = _shifted_gram_xla(rows, m, mean_hat, block=block)
    out = (acc[0] + G, acc[1] + s, acc[2] + cnt)
    if label is None:
        return out
    y, mean_y_hat = label
    ys = (y - mean_y_hat) * m
    xy = ((rows - mean_hat[None, :]) * ys[:, None]).sum(axis=0)
    return out + (acc[3] + xy, acc[4] + ys.sum(), acc[5] + (ys * ys).sum())


class GramFold:
    """The fold over the row blocks of a frame (``parallel.mesh.RowFold``)
    that PCA and LinearRegression place their frames with: each block's
    shifted Gram, row sum and count — with a label, also its shifted label
    sums — are added into its device's running float32 accumulators by one
    :func:`gram_fold` program, dispatched when the block's put has been
    issued, so it runs while the next block crosses the link.

    μ̂ (and μ̂_y), which every block is shifted by, has to exist before block
    0 is folded: it comes from the HOST arrays (:func:`host_mean_sample`:
    runs of rows from all over them, summed over the process world), taken
    inside the first call, while block 0 is on its way. Which pass folds (the
    Pallas kernel or XLA's blocked one) is the gate's answer for the FIRST
    block's shape, asked once a fit: the accumulators have one form, and a
    short tail block that the device keeps the other way round costs a
    relayout of that tail alone.

    ``y_host`` is the label of every host row, single process only: a
    block's labels are its rows' slice of it, found from where the block's
    device shard starts (``mesh``, ``csize``: the padding ``shard_rows``
    gives the frame), put beside the block."""

    def __init__(self, x_host: np.ndarray, mesh, csize: int, y_host: np.ndarray | None = None):
        self.x_host, self.mesh, self.csize, self.y_host = x_host, mesh, csize, y_host
        self.d, self.dtype = x_host.shape[1], x_host.dtype
        self.mean_hat: np.ndarray | None = None
        self.mean_y_hat: np.ndarray | None = None
        self._on_device: dict = {}
        self.attrs: dict = {}

    def _first_call(self, rows: jax.Array, device) -> None:
        s, c = allreduce_sum_host(*host_mean_sample(self.x_host))
        self.mean_hat = (s / max(float(c), 1.0)).astype(self.dtype)
        if self.y_host is not None:
            s, c = allreduce_sum_host(*host_mean_sample(self.y_host))
            self.mean_y_hat = np.asarray(s / max(float(c), 1.0), self.dtype)
            shape = (padded_rows(len(self.y_host), self.mesh, self.csize),)
            self.row_start = {
                dev: idx[0].indices(shape[0])[0]
                for dev, idx in row_sharding(self.mesh).addressable_devices_indices_map(shape).items()
            }
        n, d = rows.shape
        declined = gram_pallas_declined(n, d, self.dtype, device)
        self.pallas = not declined
        self.block = gram_block_rows(n, d, self.csize, self.dtype.itemsize)
        self.attrs = {
            "precision": "highest",
            "rows_minor": jax.default_backend() == "tpu" and rows_minor(device, n, d, self.dtype),
            "gram": "pallas" if self.pallas else "xla",
            "tile": gram_tile(d)[0] if self.pallas else self.block,
        }
        if declined:
            self.attrs["declined"] = declined

    def _zeros(self, device):
        return gram_fold_zeros(self.d, self.dtype, self.pallas, device, label=self.y_host is not None)

    def __call__(self, state, rows: jax.Array, row0: int, valid: int):
        (device,) = rows.devices()
        if self.mean_hat is None:
            self._first_call(rows, device)
        if device not in self._on_device:
            shifts = (self.mean_hat,) if self.y_host is None else (self.mean_hat, self.mean_y_hat)
            self._on_device[device] = tuple(jax.device_put(a, device) for a in shifts)
        if state is None:
            state = self._zeros(device)
        label = None
        if self.y_host is not None:
            lo = self.row_start[device] + row0
            y = np.zeros((rows.shape[0],), self.dtype)
            y[:valid] = self.y_host[lo:lo + valid]
            label = (jax.device_put(y, device), self._on_device[device][1])
        return gram_fold(
            state, rows, self._on_device[device][0], np.int32(valid), label, pallas=self.pallas, block=self.block
        )

    def sums(self, states: dict) -> tuple:
        """Every device's state as global arrays, stacked along axis 0 and
        sharded over dp (a device that got no block: zeros)."""
        mesh = self.mesh
        sh = row_sharding(mesh)
        per_device = [
            states[dev] or self._zeros(dev)
            for dev in sh.addressable_devices_indices_map((mesh.shape[DP_AXIS],))
        ]
        n_dp = mesh.shape[DP_AXIS]
        return tuple(
            jax.make_array_from_single_device_arrays(
                (n_dp * parts[0].shape[0],) + parts[0].shape[1:], sh, list(parts)
            )
            for parts in zip(*per_device)
        )


def _fold_totals(Gl, sl, cl, d: int, pallas: bool):
    """Inside a ``shard_map`` over dp: one device's :func:`gram_fold` state
    summed over dp (a ``psum``, the communication of
    :func:`mean_and_cov_chunked`) — ``(Gram (d, d), row sum (d,), count)``,
    the triangle mirrored where the Pallas kernel left one."""
    G, s, n = lax.psum(Gl, DP_AXIS), lax.psum(sl, DP_AXIS), lax.psum(cl[0], DP_AXIS)
    if pallas:
        G, s = _mirror_gram_triangle(G, s, d)
    return G, s, n


def cov_from_gram_folds(
    G: jax.Array, s: jax.Array, cnt: jax.Array, mean_hat: jax.Array, mesh, d: int, pallas: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(mean, covariance, count)``, replicated, from every device's
    :func:`gram_fold` state stacked along axis 0 and sharded over dp:
    :func:`_fold_totals` and the rank-one correction. The frame is no
    operand of it."""

    def per_device(Gl, sl, cl, mu):
        G, s, n = _fold_totals(Gl, sl, cl, d, pallas)
        return _recentre(G, s, n, mu) + (n,)

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(),) * 3 + (LAYOUT.replicated(),),
        out_specs=(LAYOUT.replicated(),) * 3,
        check_vma=False,
    )(G, s, cnt, mean_hat)


def moments_from_gram_folds(
    G, s, cnt, xy, sy, yy, mean_hat, mean_y_hat, mesh, d: int, pallas: bool
) -> Tuple[jax.Array, ...]:
    """What :func:`cov_from_gram_folds` is to PCA, for least squares: from
    every device's :func:`gram_fold` state WITH a label, replicated, ``(n,
    mean of x, mean of y, Σ (x-x̄)(x-x̄)ᵀ, Σ (x-x̄)(y-ȳ), Σ (y-ȳ)²)`` — the
    same psum, mirror and rank-one correction (:func:`_recentred`), and the
    label's sums re-centred by the same exact identity."""

    def per_device(Gl, sl, cl, xyl, syl, yyl, mu, mu_y):
        G, s, n = _fold_totals(Gl, sl, cl, d, pallas)
        xy, sy, yy = lax.psum(xyl, DP_AXIS), lax.psum(syl[0], DP_AXIS), lax.psum(yyl[0], DP_AXIS)
        mean, Gc = _recentred(G, s, n, mu)
        dx, dy = s / n, sy / n
        return n, mean, mu_y + dy, Gc, xy - n * dx * dy, yy - n * dy * dy

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(),) * 6 + (LAYOUT.replicated(),) * 2,
        out_specs=(LAYOUT.replicated(),) * 6,
        check_vma=False,
    )(G, s, cnt, xy, sy, yy, mean_hat, mean_y_hat)


def sign_flip(vectors: jax.Array) -> jax.Array:
    """Deterministic eigenvector sign convention: make the max-|.| entry of
    each column positive (reference thrust kernel ``signFlip``,
    ``rapidsml_jni.cu:35-60``; same convention as cuML / sklearn's svd_flip).

    ``vectors``: (d, k) — columns are eigenvectors.
    """
    idx = jnp.argmax(jnp.abs(vectors), axis=0)
    picked = vectors[idx, jnp.arange(vectors.shape[1])]
    signs = jnp.where(picked < 0, -1.0, 1.0).astype(vectors.dtype)
    return vectors * signs[None, :]


# Widest matrix handed to ``jnp.linalg.eigh`` whole. On a TPU that routine is
# Jacobi up to 256 columns (compiles in a second) and QDWH above (20 s at 512,
# 283 s at 3000: PERF.md section 6, PR 33), so a wider matrix is projected
# onto a block of at most this many columns first.
_EIGH_DIRECT = 256
# Subspace iteration: the least columns of the block, the most steps, and the
# residual (over λ₁, in units of the dtype's epsilon) under which a step that
# no longer halves it ends the loop.
_EIG_BLOCK = 64
_EIG_MAX_STEPS = 256
_EIG_TOL_EPS = 64.0


def _host_topk_eigh(cov, k: int):
    """The k leading pairs by LAPACK in float64 on the host: what
    :func:`topk_eigh` answers with where its iteration did not converge."""
    a = np.asarray(cov, np.float64)
    try:
        w, v = np.linalg.eigh(0.5 * (a + a.T))
    except np.linalg.LinAlgError:
        w, v = np.full((a.shape[0],), np.nan), np.full(a.shape, np.nan)
    dt = np.asarray(cov).dtype
    return w[::-1][:k].astype(dt), np.ascontiguousarray(v[:, ::-1][:, :k]).astype(dt)


def _subspace_topk(cov: jax.Array, k: int):
    """(eigenvalues (k,), eigenvectors (d, k), steps, converged) of the k
    leading pairs of a wide symmetric ``cov`` by subspace iteration.

    A block Q of ``b = max(_EIG_BLOCK, 2k)`` orthonormal columns (from a fixed
    key: the same matrix gives the same pairs) is multiplied by ``cov`` and
    orthonormalized again, step after step; every step the Rayleigh–Ritz
    pairs of ``Qᵀ·cov·Q`` (a b × b ``eigh``) give the k leading candidates
    (θ_i, x_i) and their residual ``max_i ‖cov·x_i − θ_i·x_i‖ / θ_1`` exactly,
    from the product the step has made anyway. The error falls by
    λ_{b+1}/λ_k a step, so a covariance with a decaying spectrum needs a
    handful. The loop ends at the float32 floor, not at a tolerance: on the
    first step whose residual is under ``_EIG_TOL_EPS`` epsilons (3.8e-6 in
    float32) and no longer half the step's before. Every product is ``Precision.HIGHEST``."""
    d = cov.shape[0]
    b = min(d, max(_EIG_BLOCK, 2 * k))
    mm = lambda a, c: jnp.matmul(a, c, precision=lax.Precision.HIGHEST)
    q0, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (d, b), cov.dtype))
    tiny = jnp.finfo(cov.dtype).tiny
    tol = _EIG_TOL_EPS * float(jnp.finfo(cov.dtype).eps)

    def step(state):
        it, q, _, _, prev, _ = state
        z = mm(cov, q)
        t = mm(q.T, z)
        theta, w = jnp.linalg.eigh(0.5 * (t + t.T))
        theta, w = theta[::-1][:k], w[:, ::-1][:, :k]
        x = mm(q, w)
        r = mm(z, w) - x * theta[None, :]
        res = jnp.sqrt((r * r).sum(axis=0)).max() / jnp.maximum(jnp.abs(theta[0]), tiny)
        done = (res <= tol) & (res >= 0.5 * prev)
        return it + 1, jnp.linalg.qr(z)[0], theta, x, res, done

    def unfinished(state):
        it, *_, done = state
        return (it < _EIG_MAX_STEPS) & ~done

    init = (
        jnp.int32(0), q0, jnp.zeros((k,), cov.dtype), jnp.zeros((d, k), cov.dtype),
        jnp.asarray(jnp.inf, cov.dtype), jnp.asarray(False),
    )
    it, _, theta, x, _, done = lax.while_loop(unfinished, step, init)
    return theta, x, it, done


def topk_eigh(cov: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k eigenpairs of a symmetric matrix, descending, sign-fixed.

    Returns (eigenvalues (k,), eigenvectors (d, k)). The reference does this
    on one GPU via ``raft::linalg::eigDC`` + column/row reversal
    (``rapidsml_jni.cu:215-268``); here it runs replicated on every chip
    (d is small relative to HBM; replication avoids a gather).

    Up to ``_EIGH_DIRECT`` columns: ``jnp.linalg.eigh`` of the whole matrix.
    Wider: :func:`_subspace_topk`, named ``pca.eig`` among the program's
    operations, and where its loop ran out of steps before its residual
    reached the float32 floor (a spectrum that does not decay: λ_{b+1}/λ_k
    above ≈ 0.95) the pairs come from LAPACK on the host instead, in float64
    (:func:`_host_topk_eigh`, seconds at d = 3000) — never an unconverged
    block. Either way the k leading pairs to float32.
    """
    d = cov.shape[0]
    if d <= _EIGH_DIRECT:
        evals, evecs = jnp.linalg.eigh(cov)        # ascending
        return evals[::-1][:k], sign_flip(evecs[:, ::-1][:, :k])
    with jax.named_scope("pca.eig"):
        theta, x, _, done = _subspace_topk(cov, k)
        shapes = (jax.ShapeDtypeStruct((k,), cov.dtype), jax.ShapeDtypeStruct((d, k), cov.dtype))
        evals, evecs = lax.cond(
            done,
            lambda: (theta, x),
            lambda: tuple(jax.pure_callback(lambda a: _host_topk_eigh(a, k), shapes, cov)),
        )
    return evals, sign_flip(evecs)


def standardize_moments(
    X: jax.Array, mask: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(mean, std (population), n) for feature standardization.

    Reference reimplements Spark's standardization with cupy partials +
    allGather (``classification.py:989-1038``); here one masked pass with
    XLA-inserted psum.
    """
    mean, n = masked_mean(X, mask)
    # centered second pass — same f32-cancellation rationale as mean_and_cov
    d = (X - mean[None, :]) * mask[:, None]
    var = (d * d).sum(axis=0) / n
    return mean, jnp.sqrt(var), n


class PallasLoweringError(RuntimeError):
    """A Pallas kernel whose static gate said yes was refused by the TPU
    compiler. Carries the kernel's name and the compiler's message."""


class _naming_refusal:
    """Context manager: an exception leaving the block is re-raised as
    :class:`PallasLoweringError` with the kernel's name in front of it."""

    def __init__(self, name: str, key) -> None:
        self.name, self.key = name, key

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        if isinstance(exc, Exception):
            raise PallasLoweringError(
                f"{self.name} Pallas kernel failed to lower for config "
                f"{self.key}: {exc_type.__name__}: {exc}"
            ) from exc
        return False


def probe_pallas_lowering(cache: dict, key, compile_fn, name: str) -> bool:
    """Shared hardware-lowering probe for Pallas kernels.

    Interpret-mode tests exercise kernel bodies but not Mosaic lowering (a
    scalar VMEM store traced and interpreted fine yet failed only on the
    real chip). Before first real use of a config, ``compile_fn``
    AOT-compiles a tiny instance. The static shape gates decide which path
    a shape takes; a kernel they admitted and the compiler then refuses is
    a defect, so the refusal **raises** :class:`PallasLoweringError` naming
    the kernel — a fit never carries on in silence on the XLA path.
    ``cache`` memoises successes only. Always returns True.
    """
    if key not in cache:
        with _naming_refusal(name, key):
            compile_fn()
        cache[key] = True
    return True
