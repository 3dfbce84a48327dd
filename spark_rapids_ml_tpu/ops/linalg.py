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

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS, MP_AXIS


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
    cov = (Xc.T @ Xc) / (n - 1.0)
    return mean, cov, n

# Test hook (mirrors ops.logreg_pallas.FORCE_INTERPRET): when True,
# _pallas_gram_ok ignores the backend check and the kernels run through the
# Pallas interpreter, letting CPU CI exercise the real kernel branches
# inside the fit paths.
FORCE_INTERPRET = False


def row_chunk(i, csize: int, *arrays):
    """Rows ``[i*csize, (i+1)*csize)`` of each array, sliced along axis 0.

    The canonical chunk access for every chunked-scan kernel. Slice with
    ``dynamic_slice`` — do NOT ``lax.scan`` over a reshaped X: scan
    materializes its xs operand in the layout the loop body's matmuls
    prefer, which at lane-unaligned d (e.g. 3000) is a full transposed
    copy of the design matrix — doubling memory and OOMing resident fits
    that otherwise fit (observed at 1M×3000 on v5e). Slicing reads the
    original buffer in place.

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


def _pallas_gram_tile(d: int) -> int:
    """Row-tile size for :func:`_shifted_gram_pallas`: ~16 MB of f32 per
    block (double-buffered by the pipeline) regardless of feature width,
    in VPU-sublane multiples. Measured on v5e at 12M×256: 8 MB blocks
    sustain ~670 GB/s, 16 MB ~715 GB/s (against ~735 achievable)."""
    return max(256, (4_194_304 // d) // 8 * 8)


def _shifted_gram_pallas(
    Xl: jax.Array,
    ml: jax.Array,
    mean_hat: jax.Array,
    *,
    tile: int | None = None,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pallas TPU kernel: one pass over local rows accumulating the shifted
    Gram ``Σ m·(x-μ̂)(x-μ̂)ᵀ`` and row-sum ``Σ m·(x-μ̂)``.

    XLA's fused ``(X-μ̂)ᵀ(X-μ̂)`` on a skinny (d≈256) design matrix sustains
    only ~half the chip's HBM bandwidth (measured 385 GB/s vs 735 GB/s
    achievable on v5e); this kernel streams row tiles HBM→VMEM with the
    d×d accumulator resident in VMEM and reaches ~715 GB/s. Rows beyond
    ``n`` (the last partial tile) are zeroed by an index-validity guard, so
    any row count works. f32 end to end.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = Xl.shape
    if tile is None:
        tile = _pallas_gram_tile(d)
    if interpret is None:
        interpret = FORCE_INTERPRET

    def kern(x_ref, m_ref, mu_ref, G_ref, s_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            G_ref[:] = jnp.zeros_like(G_ref)
            s_ref[:] = jnp.zeros_like(s_ref)

        # rows past n: the block is fetched beyond the array — zero them
        # explicitly (jnp.where, not multiply: OOB fill could be non-finite)
        row = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        valid = row < n
        x = jnp.where(valid, x_ref[:], 0.0)
        m = jnp.where(valid[:, 0], m_ref[:], 0.0)
        xs = (x - mu_ref[:]) * m[:, None]
        G_ref[:] += jax.lax.dot_general(
            xs, xs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s_ref[:] += jnp.sum(xs, axis=0, keepdims=True)

    G, s = pl.pallas_call(
        kern,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((d, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # 16 MB double-buffered row tiles + centering temporaries + the
            # d×d accumulator (16 MB at d=2048) need headroom past the
            # 64 MB default (v5e has 128 MB VMEM)
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(Xl, ml, mean_hat.reshape(1, d))
    return G, s[0]


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


def _pallas_gram_ok(d: int, dtype) -> bool:
    """Trace-time gate for the Pallas gram path: TPU backend, lane-aligned
    feature width, f32 (the kernel accumulates in f32; f64 fits keep the
    scan path). d is capped so the d×d VMEM accumulator plus double-buffered
    16 MB row blocks stay under the kernel's 100 MB VMEM budget — wider
    fits route to the scan path, which handles any d."""
    return (
        (jax.default_backend() == "tpu" or FORCE_INTERPRET)
        and d % 128 == 0
        and d <= 2048
        and dtype == jnp.float32
    )


def mean_and_cov_chunked(
    X: jax.Array, mask: jax.Array, mesh, csize: int, *, mp_blocks: bool = False
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`mean_and_cov` with O(csize·d) temporaries and ~1 pass over X.

    The fused form relies on XLA folding the ``(X - μ)·mask`` centering into
    the Gram matmul's operand read; at double-digit-GB row counts the
    compiler can instead materialize the centered copy and OOM a chip whose
    HBM the resident matrix already half-fills. Here each device scans its
    rows in fixed ``csize`` chunks (same pattern as the KMeans Lloyd kernel)
    so peak extra memory is one chunk.

    Numerics: the naive one-pass ``(XᵀX - n·μμᵀ)/(n-1)`` catastrophically
    cancels in f32 when |μ| >> σ, and a full two-pass centering reads X
    twice from HBM. Instead the mean is *estimated* from each device's
    first chunk (one cheap psum), the main pass accumulates shifted sums
    ``Σ m·(x-μ̂)`` and Gram ``Σ m·(x-μ̂)(x-μ̂)ᵀ``, and a final rank-1
    correction re-centers exactly: with ``δ = mean - μ̂`` small, the
    cancellation term is harmless — two-pass stability at one-pass
    bandwidth. The estimate samples ``csize`` rows *strided across the
    whole device shard* (not the leading chunk), so data sorted or
    drifting in magnitude still yields δ = O(σ/√csize); only then does
    the f32 rank-1 correction stay clear of the cancellation the shift
    avoids. Partials combine with one ``psum`` over dp — the same
    communication volume as the fused form.

    Requires per-device rows divisible by ``csize`` (``shard_rows`` pads to
    this); rows must be sharded over dp only.

    With ``mp_blocks`` (resolve via :func:`mp_gram_blocks` — env is read
    outside jit) each device accumulates only its OWN column block of the
    shifted Gram, ``Σ m·(x-μ̂)(x-μ̂[blk])ᵀ`` of shape (d, d/mp): the d²
    accumulator — the structure that bounds feature width on a chip —
    shrinks by 1/mp, the SUMMA-style row-panel × column-panel product. The
    psum stays over dp only (mp peers hold *different* blocks, dp peers the
    same block) and the returned covariance is column-sharded over mp
    (``LAYOUT.cols()``). Per-element reduction order matches the full-width
    scan, so parity with the 1-D path is tight (see docs/mesh.md tolerance
    contract).
    """

    n_mp = int(mesh.shape.get(MP_AXIS, 1)) if mp_blocks else 1
    if n_mp > 1 and X.shape[1] % n_mp != 0:
        raise ValueError(
            f"blocked Gram requires feature width ({X.shape[1]}) divisible "
            f"by the mp extent ({n_mp}); gate with mp_gram_blocks"
        )
    bw = X.shape[1] // n_mp
    use_pallas = n_mp == 1 and _pallas_gram_ok(X.shape[1], X.dtype)

    def per_device(Xl, ml):
        d = Xl.shape[1]

        # mean estimate from rows strided across the whole shard — a
        # leading-chunk sample misestimates μ̂ on sorted/drifting data
        # and the rank-1 correction then reintroduces cancellation; the
        # mask weights out any padding rows the stride lands on
        e = min(csize, Xl.shape[0])
        stride = max(1, Xl.shape[0] // e)
        x0, m0 = Xl[::stride][:e], ml[::stride][:e]
        s0 = lax.psum((x0 * m0[:, None]).sum(axis=0), DP_AXIS)
        c0 = lax.psum(m0.sum(), DP_AXIS)
        mean_hat = s0 / jnp.maximum(c0, 1.0)

        if use_pallas:
            G, s = _shifted_gram_pallas(Xl, ml, mean_hat)
            cnt = ml.sum()
        else:
            nc = check_row_chunking(Xl.shape[0], csize)
            # column-block start of THIS device's Gram panel (0 at mp=1)
            c0 = lax.axis_index(MP_AXIS) * bw if n_mp > 1 else 0

            def body(i, carry):
                s, cnt, G = carry
                x, m = row_chunk(i, csize, Xl, ml)
                xs = (x - mean_hat[None, :]) * m[:, None]
                xb = (
                    lax.dynamic_slice_in_dim(xs, c0, bw, 1)
                    if n_mp > 1
                    else xs
                )
                return (s + xs.sum(axis=0), cnt + m.sum(), G + xs.T @ xb)

            s, cnt, G = lax.fori_loop(
                0,
                nc,
                body,
                (
                    jnp.zeros((d,), Xl.dtype),
                    jnp.zeros((), Xl.dtype),
                    jnp.zeros((d, bw), Xl.dtype),
                ),
            )
        n = lax.psum(cnt, DP_AXIS)
        s = lax.psum(s, DP_AXIS)
        G = lax.psum(G, DP_AXIS)
        delta = s / n                      # exact mean minus μ̂
        mean = mean_hat + delta
        if n_mp > 1:
            delta_b = lax.dynamic_slice_in_dim(
                delta, lax.axis_index(MP_AXIS) * bw, bw, 0
            )
            cov = (G - n * jnp.outer(delta, delta_b)) / (n - 1.0)
        else:
            cov = (G - n * jnp.outer(delta, delta)) / (n - 1.0)
        return mean, cov, n

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows()),
        out_specs=(LAYOUT.replicated(), LAYOUT.cols() if n_mp > 1 else LAYOUT.replicated(), LAYOUT.replicated()),
        check_vma=False,
    )(X, mask)


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


def topk_eigh(cov: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Top-k eigenpairs of a symmetric matrix, descending, sign-fixed.

    Returns (eigenvalues (k,), eigenvectors (d, k)). The reference does this
    on one GPU via ``raft::linalg::eigDC`` + column/row reversal
    (``rapidsml_jni.cu:215-268``); here it runs replicated on every chip
    (d is small relative to HBM; replication avoids a gather).
    """
    evals, evecs = jnp.linalg.eigh(cov)        # ascending
    evals = evals[::-1][:k]
    evecs = evecs[:, ::-1][:, :k]
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
