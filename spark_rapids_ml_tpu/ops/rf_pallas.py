"""Pallas sub-block histogram kernel for the RandomForest deep levels.

The round-3 measurement campaign (docs/rf_performance.md) established
that every histogram formulation expressible in XLA converges to the same
~1.2e8 updates/s scatter wall on v5e — including the one-hot matmul
forms, because XLA pattern-matches dot(one-hot-compare, X) and rewrites
it back into scatter/select chains. This kernel is the counter-move the
compiler cannot undo: with rows pre-sorted into node-contiguous order and
each node's segment padded to a multiple of ``r_sub``, every aligned
``r_sub``-row sub-block is node-pure, so the node dimension VANISHES from
the one-hot — the kernel builds per-sub-block histograms with a bin-only
one-hot and two MXU dots per block, and a cheap segment reduce over
sub-blocks (they arrive sorted by node) finishes the per-node histogram.

Per block of R rows the kernel does exactly:

  bl  = binq @ E          (R, k*nb)   E[f, f*nb+j] = 1   (static, MXU)
  oh  = (bl == lane%nb)   (R, k*nb)   bin one-hot        (one VPU compare)
  out = A @ oh            (L*S, k*nb)                    (MXU)

where A[j*S+s, r] = (r in sub-block j) * sw[r, s] folds the sub-block
selector (a static band) and the per-row stat weights into the dot's LHS.
Total per-level cost is one compare + ~3 matmul-equivalents over the
data — no scatters anywhere.

Numerics: identical to the scatter path for classification (one-hots,
bin values <= 255 and small-integer bootstrap weights are exact in bf16
multiplies with f32 accumulation). Variance stats (regression) carry
real-valued y/y^2 and use Precision.HIGHEST, mirroring
``tree_kernels._hist_matmul``.

Reference role: replaces the shared-memory atomic histogram kernels cuML's
decision-tree builder launches per level (the builder behind
``/root/reference/python/src/spark_rapids_ml/tree.py:269-402``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Test hook (mirrors ops.linalg.FORCE_INTERPRET): run the kernel through
# the Pallas interpreter on CPU so tests cover the real kernel body.
FORCE_INTERPRET = False

# Hardware-lowering probe results keyed by (k, nb, S, r_sub, R, variance);
# policy in ops.linalg.probe_pallas_lowering. The probed instance matches
# the production call exactly: int32 bins (callers cast before the kernel)
# and the same variance flag (it switches both dots to HIGHEST emulation,
# a different Mosaic lowering).
_LOWERING_OK: dict = {}


# Rows per grid block — FIXED so callers can size padded row counts
# independently of the (chunked) feature width. The (R, k*nb) one-hot and
# bl residents cap at ~48 MB at the max supported W (k*nb <= 8192,
# enforced by rf_hist_pallas_ok; wider levels must feature-chunk), inside
# the 100 MB vmem budget; the probe has the final word per shape.
BLOCK_ROWS = 512


def _block_rows(k: int, nb: int) -> int:
    return BLOCK_ROWS


def rf_hist_pallas_declined(
    n_pad: int, k: int, nb: int, S: int, r_sub: int
) -> str:
    """The terms of the sub-block kernel's gate that fail, comma-joined
    (empty: the kernel is admitted): TPU (or interpret), lane-aligned
    one-hot width, power-of-two sub-blocks dividing the block, block-aligned
    row count. A pure function of its arguments and the backend: the level
    plan evaluates it again on the host to say on the grow group's span
    which levels took which histogram, and why."""
    R = _block_rows(k, nb)
    terms = (
        ("backend", jax.default_backend() == "tpu" or FORCE_INTERPRET),
        ("width%128", (k * nb) % 128 == 0),
        ("bins<=256", nb <= 256),
        ("stats<=16", 1 <= S <= 16),
        ("r_sub", r_sub >= 1 and (r_sub & (r_sub - 1)) == 0 and R % r_sub == 0),
        ("rows%block", n_pad % R == 0),
        # Mosaic block rule: the (L*S, W) output block's sublane dim must
        # be a multiple of 8 once the grid has more than one block
        ("sublanes%8", (R // max(r_sub, 1)) * S % 8 == 0),
        # one-hot width cap: wider levels feature-chunk down to this
        ("width<=8192", k * nb <= 8192),
    )
    return ",".join(name for name, ok in terms if not ok)


def rf_hist_pallas_ok(
    n_pad: int, k: int, nb: int, S: int, r_sub: int, variance: bool = False
) -> bool:
    """Trace-time gate (:func:`rf_hist_pallas_declined`) and a probed
    lowering."""
    ok = not rf_hist_pallas_declined(n_pad, k, nb, S, r_sub)
    if ok and not FORCE_INTERPRET:
        ok = _probe_lowering(k, nb, S, r_sub, _block_rows(k, nb), variance)
    return ok


def _probe_lowering(
    k: int, nb: int, S: int, r_sub: int, R: int, variance: bool
) -> bool:
    from .linalg import probe_pallas_lowering

    key = (k, nb, S, r_sub, R, variance)

    def compile_fn():
        # two grid blocks: a single-block probe would let Mosaic accept
        # output block shapes merely because they EQUAL the array shape,
        # masking sublane-divisibility rejections the real multi-block
        # call then hits
        binq = jax.ShapeDtypeStruct((2 * R, k), jnp.int32)
        swT = jax.ShapeDtypeStruct((S, 2 * R), jnp.float32)
        subblock_hist.lower(
            binq, swT, n_bins=nb, r_sub=r_sub, variance=variance,
            transposed_sw=True,
        ).compile()

    return probe_pallas_lowering(
        _LOWERING_OK, key, compile_fn, "RF sub-block histogram"
    )


def _live_index(i, live_ref):
    """Block index of grid step ``i`` under a live-block count: a step past
    the count names the LAST live block again, so the pipeline fetches no
    new input block for it and writes no new output block (its body is
    skipped by ``pl.when``). Output blocks past the count are therefore
    never written; with a count of 0 not even block 0 is."""
    return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0))


def _live_operand(live_blocks, n_blocks: int) -> jax.Array:
    if live_blocks is None:
        return jnp.full((1,), n_blocks, jnp.int32)
    return jnp.asarray(live_blocks, jnp.int32).reshape(1)


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "r_sub", "variance", "interpret", "transposed_sw"),
)
def subblock_hist(
    binq: jax.Array,   # (n_pad, k) int32 bins in node-contiguous order
    sw: jax.Array,     # (n_pad, S) f32 stats*weight (0 on padding rows)
    live_blocks: jax.Array | None = None,  # (1,) int32; None = every block
    *,
    n_bins: int,
    r_sub: int,
    variance: bool = False,
    interpret: bool | None = None,
    transposed_sw: bool = False,
) -> jax.Array:
    """Per-sub-block histograms: (n_pad//r_sub, S, k*n_bins) float32.

    Rows must be node-contiguous with every node's segment padded to a
    multiple of ``r_sub`` (padding rows carry sw == 0, bins arbitrary).
    Sub-block j covers rows [j*r_sub, (j+1)*r_sub); summing the
    sub-blocks of one node — they are consecutive — yields that node's
    (S, k, n_bins) histogram.

    ``live_blocks`` (scalar prefetch): only the first ``live_blocks[0]``
    grid blocks hold rows (:func:`_live_index`); the partials of the others
    are NOT written and the caller must not read them.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n_pad, k = binq.shape
    nb = n_bins
    W = k * nb
    swT = sw if transposed_sw else sw.T  # (S, n_pad): lane-major rows per stat
    S = swT.shape[0]
    R = _block_rows(k, nb)
    L = R // r_sub
    n_blocks = n_pad // R
    prec = lax.Precision.HIGHEST if variance else None

    def kern(live_ref, b_ref, s_ref, out_ref):
        pl.when(pl.program_id(0) < live_ref[0])(
            lambda: body(b_ref, s_ref, out_ref)
        )

    def body(b_ref, s_ref, out_ref):
        # static lane-expansion matrix: E[f, f*nb + j] = 1 (built from
        # iotas in-kernel; Pallas forbids captured array constants)
        fi = lax.broadcasted_iota(jnp.int32, (k, W), 0)
        li = lax.broadcasted_iota(jnp.int32, (k, W), 1)
        E = (li // nb == fi).astype(jnp.float32)
        b = b_ref[:].astype(jnp.float32)                   # (R, k)
        bl = jnp.dot(b, E, precision=prec,
                     preferred_element_type=jnp.float32)   # (R, W)
        lane_bin = (
            lax.broadcasted_iota(jnp.int32, (1, W), 1) % nb
        ).astype(jnp.float32)
        oh = (bl == lane_bin).astype(jnp.float32)          # (R, W)
        # A[j*S+s, r] = (r // r_sub == j) * sw[r, s]
        a0 = lax.broadcasted_iota(jnp.int32, (L * S, R), 0)
        r0 = lax.broadcasted_iota(jnp.int32, (L * S, R), 1)
        band = ((a0 // S) == (r0 // r_sub)).astype(jnp.float32)
        sw_sel = jnp.zeros((L * S, R), jnp.float32)
        for s in range(S):
            sw_sel = sw_sel + jnp.where(
                a0 % S == s, s_ref[s : s + 1, :], 0.0
            )
        A = band * sw_sel
        out_ref[:] = jnp.dot(
            A, oh, precision=prec, preferred_element_type=jnp.float32
        )                                                  # (L*S, W)

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(
                    (R, k), lambda i, live: (_live_index(i, live), 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (S, R), lambda i, live: (0, _live_index(i, live)),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (L * S, W), lambda i, live: (_live_index(i, live), 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks * L * S, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="rf_hist_pass",
    )(_live_operand(live_blocks, n_blocks), binq, swT)
    return out.reshape(n_pad // r_sub, S, W)


# ---------------------------------------------------------------------------
# fused-selection variant: per-node feature subset selected IN KERNEL
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "r_sub", "variance", "interpret"),
)
def subblock_hist_sel(
    bq: jax.Array,      # (n_pad, d_pad / 4) int32 FULL bins rows, four to a
                        # word (tree_kernels._pack_bins), node-sorted
    featsq: jax.Array,  # (n_sb, k) int32 selected feature ids per sub-block
    swT: jax.Array,     # (S, n_pad) f32 stats*weight (0 on padding rows)
    live_blocks: jax.Array | None = None,  # (1,) int32; None = every block
    *,
    n_bins: int,
    r_sub: int,
    variance: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-sub-block histograms with IN-KERNEL feature-subset selection:
    (n_pad//r_sub, S, k*n_bins) float32. ``live_blocks`` as in
    :func:`subblock_hist`: partials past it are unwritten, never zeros.

    The pre-gathered variant (``subblock_hist``) needs hist_src =
    bins[row, feats[node[row]]] built OUTSIDE the kernel — a per-row
    k-column gather that costs ~780 ms/level at the reference's
    1M x 3000 shape (measured round 4; TPU element gathers run ~1e8/s).
    Node-contiguous rows turn that gather into dense MXU work: every
    ``r_sub``-aligned sub-block is node-pure, so its k selected columns
    are ONE static set — a (d_pad, k) one-hot built from the sub-block's
    feature-id row and contracted against the rows' bins, a byte plane of
    the words at a time (plane ``p`` holds columns ``p*W .. p*W + W - 1``):

        selected = sum_p plane_p(r_sub, W) @ sel_p(W, k)  (MXU)
        bl       = selected @ E(k, k*nb)                  (lane expand)
        oh       = (bl == lane % nb)                      (bin one-hot)
        out_j    = swT_j(S, r_sub) @ oh                   (stat reduce)

    The full-bins operand arrives by ONE row gather of whole rows of
    words (a row is ``W = d_pad / 4`` words: a quarter of the tiles of its
    bytes). Sentinel feature ids (== n_features) hit a zero-padded or
    absent column and produce bin 0, the same invariant the gather paths
    keep. Exact for classification: bins and one-hots are bf16-exact, a
    slot's column lies in one plane, f32 accumulation; variance stats
    force Precision.HIGHEST.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n_pad, words = bq.shape
    d_pad = 4 * words
    n_sb, k = featsq.shape
    S = swT.shape[0]
    nb = n_bins
    W = k * nb
    R = BLOCK_ROWS
    L = R // r_sub
    n_blocks = n_pad // R
    prec = lax.Precision.HIGHEST if variance else None
    # feature ids are lane-padded to a 128 multiple (padding value d_pad
    # matches no d-iota, so padded slots select nothing and die in E —
    # their fi >= k), and the block keeps L >= 8 sublanes (the gate caps
    # r_sub at R/8) so the (L, k_lanes) block satisfies Mosaic's (8, 128)
    # block rule, which rejected the raw (L, k) shape on every real
    # configuration.
    k_lanes = -(-k // 128) * 128
    fq = jnp.pad(
        featsq, ((0, 0), (0, k_lanes - k)), constant_values=d_pad
    )                                                      # (n_sb, k_lanes)

    def kern(live_ref, b_ref, f_ref, s_ref, out_ref):
        pl.when(pl.program_id(0) < live_ref[0])(
            lambda: body(b_ref, f_ref, s_ref, out_ref)
        )

    def body(b_ref, f_ref, s_ref, out_ref):
        lane_bin = (
            lax.broadcasted_iota(jnp.int32, (1, W), 1) % nb
        ).astype(jnp.float32)
        # E maps selection slot f (< k) to its nb output lanes; padded
        # slots f >= k match no output lane
        fi = lax.broadcasted_iota(jnp.int32, (k_lanes, W), 0)
        li = lax.broadcasted_iota(jnp.int32, (k_lanes, W), 1)
        E = (li // nb == fi).astype(jnp.float32)
        w_iota = lax.broadcasted_iota(jnp.int32, (words, k_lanes), 0)
        for j in range(L):
            wj = b_ref[j * r_sub : (j + 1) * r_sub, :]     # (r_sub, words)
            f_row = f_ref[j : j + 1, :]                    # (1, k_lanes)
            selected = None
            for p in range(4):
                plane = ((wj >> (8 * p)) & 0xFF).astype(jnp.float32)
                sel = (w_iota + p * words == f_row).astype(jnp.float32)
                part = jnp.dot(
                    plane, sel, precision=prec,
                    preferred_element_type=jnp.float32,
                )                                          # (r_sub, k_lanes)
                selected = part if selected is None else selected + part
            bl = jnp.dot(
                selected, E, precision=prec,
                preferred_element_type=jnp.float32,
            )                                              # (r_sub, W)
            oh = (bl == lane_bin).astype(jnp.float32)      # (r_sub, W)
            swj = s_ref[:, j * r_sub : (j + 1) * r_sub]    # (S, r_sub)
            out_ref[j * S : (j + 1) * S, :] = jnp.dot(
                swj, oh, precision=prec,
                preferred_element_type=jnp.float32,
            )                                              # (S, W)

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(
                    (R, words), lambda i, live: (_live_index(i, live), 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (L, k_lanes), lambda i, live: (_live_index(i, live), 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (S, R), lambda i, live: (0, _live_index(i, live)),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (L * S, W), lambda i, live: (_live_index(i, live), 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks * L * S, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="rf_hist_sel_pass",
    )(_live_operand(live_blocks, n_blocks), bq, fq, swT)
    return out.reshape(n_pad // r_sub, S, W)


# probe results for the fused-selection variant, keyed by
# (d_pad, k, nb, S, r_sub, variance)
_SEL_LOWERING_OK: dict = {}


def rf_hist_sel_declined(
    n_pad: int, d_pad: int, k: int, nb: int, S: int, r_sub: int
) -> str:
    """The failing terms of the fused-selection kernel's gate, comma-joined:
    :func:`rf_hist_pallas_declined`'s rules plus a lane-aligned full-bins
    width, eight sub-blocks a block and the block's VMEM residency."""
    R = BLOCK_ROWS
    base = rf_hist_pallas_declined(n_pad, k, nb, S, r_sub)
    terms = (
        # the (L, k_lanes) feature-id block needs >= 8 sublanes
        ("sub-blocks>=8", R // max(r_sub, 1) >= 8),
        ("d_pad%128", d_pad % 128 == 0),
        # the block's bins as f32 + (r_sub, W) transients + sel, x2 buffers
        (
            "vmem",
            (R * d_pad * 4 + r_sub * k * nb * 4 + d_pad * k * 4) * 2
            <= 80 * 1024 * 1024,
        ),
    )
    return ",".join(
        ([base] if base else []) + [name for name, ok in terms if not ok]
    )


def rf_hist_sel_ok(
    n_pad: int, d_pad: int, k: int, nb: int, S: int, r_sub: int,
    variance: bool = False,
) -> bool:
    """Gate for the fused-selection kernel (:func:`rf_hist_sel_declined`)
    and a probed lowering."""
    R = BLOCK_ROWS
    ok = not rf_hist_sel_declined(n_pad, d_pad, k, nb, S, r_sub)
    if ok and not FORCE_INTERPRET:
        key = (d_pad, k, nb, S, r_sub, variance)

        def compile_fn():
            bq = jax.ShapeDtypeStruct((2 * R, d_pad // 4), jnp.int32)
            fq = jax.ShapeDtypeStruct((2 * (R // r_sub), k), jnp.int32)
            sT = jax.ShapeDtypeStruct((S, 2 * R), jnp.float32)
            subblock_hist_sel.lower(
                bq, fq, sT, n_bins=nb, r_sub=r_sub, variance=variance
            ).compile()

        from .linalg import probe_pallas_lowering

        ok = probe_pallas_lowering(
            _SEL_LOWERING_OK, key, compile_fn, "RF fused-selection histogram"
        )
    return ok


# ---------------------------------------------------------------------------
# wide fused selection: a grid over feature-slot tiles, per-node sums in place
# ---------------------------------------------------------------------------
#
# ``subblock_hist_sel`` holds the one-hot of ALL a node's slots at once: k*nb
# lanes, capped at 8192, and its lane expansion ``selected @ E`` costs k^2*nb
# products a row. A regressor's subset (a third of the columns: 1000 of 3000,
# 131,072 lanes) is past both. This variant tiles the slots over a GRID axis,
# 128 a step, so every term of it is a tile's: the selection is an exact
# bf16 product of the block's columns in the tile's window with the tile's
# one-hot of them,
# the bin one-hot is a lane REPEAT of the 128 selected columns compared with
# the lane's bin (bin-major lanes: no expansion product), and the statistics
# ride a 16-row bf16 operand that holds their exact three-way split.

# rows a grid block: ONE node's (the caller pads a node's run to a multiple)
WIDE_BLOCK_ROWS = 512
# feature slots a grid step
WIDE_SLOTS = 128
# rows of the statistics' operand: S stats x 3 bf16 parts, sublane-padded
WIDE_STAT_ROWS = 16
# bins whose one-hot is held at once: (512, 32*128) bf16 = 4 MB
_WIDE_BIN_GROUP = 32


def split_f32_exact(swT: jax.Array) -> jax.Array:
    """(S, n) float32 -> (WIDE_STAT_ROWS, n) bfloat16 whose rows ``s``,
    ``S + s`` and ``2*S + s`` sum to ``swT[s]`` EXACTLY: a float32 has 24
    mantissa bits, a bfloat16 8, and each part takes the next eight. A product
    of a part with a 0/1 one-hot is exact in one bf16 MXU pass with float32
    accumulation, so three rows buy what ``Precision.HIGHEST`` spends six
    passes on (it splits the one-hot operand too). ``lax.reduce_precision``,
    not a cast there and back: XLA may drop a convert pair as excess
    precision, and the remainder would then read 0."""
    S = swT.shape[0]
    assert 3 * S <= WIDE_STAT_ROWS, S
    x = swT.astype(jnp.float32)
    hi = lax.reduce_precision(x, 8, 7)
    r1 = x - hi
    mid = lax.reduce_precision(r1, 8, 7)
    lo = lax.reduce_precision(r1 - mid, 8, 7)
    parts = jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16)
    return jnp.pad(parts, ((0, WIDE_STAT_ROWS - 3 * S), (0, 0)))


def rf_hist_wide_declined(
    n_pad: int, d_pad: int, k: int, nb: int, S: int
) -> str:
    """The failing terms of the wide fused-selection kernel's gate,
    comma-joined (empty: admitted)."""
    R = WIDE_BLOCK_ROWS
    g = min(_WIDE_BIN_GROUP, nb)
    vmem = (
        2 * R * d_pad                              # the block's rows of words, x2
        + 128 * WIDE_SLOTS * (4 + 4 + 2)           # a window tile's iota, compare, one-hot
        + 2 * R * WIDE_SLOTS * 4                   # the selected columns and a tile's product
        + R * g * WIDE_SLOTS * (4 + 4 + 2)         # repeat, compare, one-hot
        + 4 * WIDE_STAT_ROWS * nb * WIDE_SLOTS * 4  # a tile's sums in and out, x2
    )
    terms = (
        ("backend", jax.default_backend() == "tpu" or FORCE_INTERPRET),
        ("slots%128", k >= WIDE_SLOTS and k % WIDE_SLOTS == 0),
        # a column tile is a byte plane of a whole lane tile of words
        ("words%128", d_pad % 512 == 0),
        # a bin is an exact bfloat16 up to 256; the lane groups divide nb
        ("bins<=256", nb <= 256 and nb % g == 0),
        ("stats*3<=16", 1 <= 3 * S <= WIDE_STAT_ROWS),
        ("rows%block", n_pad % R == 0),
        ("vmem", vmem <= 80 * 1024 * 1024),
    )
    return ",".join(name for name, ok in terms if not ok)


_WIDE_LOWERING_OK: dict = {}


def rf_hist_wide_ok(n_pad: int, d_pad: int, k: int, nb: int, S: int) -> bool:
    """Gate (:func:`rf_hist_wide_declined`) and a probed lowering."""
    ok = not rf_hist_wide_declined(n_pad, d_pad, k, nb, S)
    if ok and not FORCE_INTERPRET:
        R = WIDE_BLOCK_ROWS

        def compile_fn():
            sds = jax.ShapeDtypeStruct
            subblock_hist_sel_wide.lower(
                sds((2 * R, d_pad // 4), jnp.int32), sds((2, k), jnp.int32),
                sds((WIDE_STAT_ROWS, 2 * R), jnp.bfloat16),
                sds((2,), jnp.int32), sds((1,), jnp.int32),
                sds((2, WIDE_STAT_ROWS, k * nb), jnp.float32), n_bins=nb,
            ).compile()

        from .linalg import probe_pallas_lowering

        ok = probe_pallas_lowering(
            _WIDE_LOWERING_OK, (d_pad, k, nb), compile_fn,
            "RF wide fused-selection histogram",
        )
    return ok


def wide_windows(feats: jax.Array, d_pad: int) -> tuple[jax.Array, jax.Array]:
    """The columns each slot tile's selection reads: ``(first, count)`` of its
    128-lane column tiles, ``(..., k // 128)`` int32 each, from the tile's own
    feature ids (``feats``: ``(..., k)``). A tile reads every column tile from
    its least id's to its greatest's, so the window is exact whatever the ids;
    a node's ids sorted ascending make each tile's run of them contiguous, and
    the window of a third of the columns about four tiles wide, not
    ``d_pad // 128``. A sentinel id at or past ``d_pad`` counts as the last
    column (it selects nothing there, as in the full width)."""
    f = jnp.clip(feats, 0, d_pad - 1).reshape(*feats.shape[:-1], -1, WIDE_SLOTS)
    lo = f.min(axis=-1) // 128
    return lo, f.max(axis=-1) // 128 - lo + 1


@functools.partial(
    jax.jit, static_argnames=("n_bins", "interpret"), donate_argnums=(5,)
)
def subblock_hist_sel_wide(
    bq: jax.Array,        # (n_pad, d_pad / 4) int32 FULL bins rows, four to a
                          # word (tree_kernels._pack_bins), node-sorted
    feats_b: jax.Array,   # (n_blocks, k) int32 feature ids of a block's node
    parts: jax.Array,     # (WIDE_STAT_ROWS, n_pad) bf16: split_f32_exact
    nodes_b: jax.Array,   # (n_blocks,) int32 node of a block, ascending
    live_blocks: jax.Array,  # (1,) int32 blocks that hold a live row
    acc: jax.Array,       # (n_nodes, WIDE_STAT_ROWS, k*n_bins) f32 running sums
    *,
    n_bins: int,
    interpret: bool | None = None,
) -> jax.Array:
    """``acc`` with the live blocks' histograms added IN PLACE (the operand is
    donated and aliased to the result): cell ``[g, p, (t*n_bins + b)*128 + j]``
    gains, over the rows of the blocks of node ``g``, part ``p`` of the
    statistics of the rows whose feature ``feats[g, t*128 + j]`` lies in bin
    ``b``. Summing a node's rows ``s``, ``S + s``, ``2*S + s`` gives stat
    ``s``; the lanes are tile-major, then BIN-major, then the slot in its tile.

    Grid ``(k // 128, n_blocks)``, the slot tile outermost: along a tile the
    blocks of one node follow each other, so a node's (16, n_bins*128) sums
    stay in VMEM from its first block (which takes them from ``acc``) to its
    last. A tile's selection reads only its window of the block's columns
    (:func:`wide_windows`, by scalar prefetch): a loop over those 128-column
    tiles, each an exact bf16 product with the tile's one-hot of them — a
    node's ids ascending make the window about four tiles wide, where the
    whole width is ``d_pad // 128``. Column tile ``c`` is byte plane
    ``c // (W / 128)`` of word tile ``c % (W / 128)`` (the words' planar
    layout), so a tile's bins are one lane tile of words shifted and cast in
    VMEM: the rows cross HBM as words, a quarter of the tiles of their
    bytes and half the bytes of bf16. Every block is node-pure
    (``WIDE_BLOCK_ROWS`` rows; padding rows carry zero statistics). Blocks
    past ``live_blocks`` are neither fetched nor computed (:func:`_live_index`);
    a node with no block in this call keeps its sums untouched. Exact: bins
    and one-hots are bf16 integers, the statistics' parts are bf16 by
    construction, accumulation is float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n_pad, words = bq.shape
    d_pad = 4 * words
    word_tiles = words // 128
    n_blocks, k = feats_b.shape
    nb, R, T, P = n_bins, WIDE_BLOCK_ROWS, WIDE_SLOTS, WIDE_STAT_ROWS
    G = min(_WIDE_BIN_GROUP, nb)
    n_tiles = k // T
    assert n_pad == n_blocks * R and k % T == 0 and nb % G == 0 and words % 128 == 0
    lo, cnt = wide_windows(feats_b, d_pad)              # (n_blocks, n_tiles)
    # flat (SMEM pads a 2-D table's rows): [(block * n_tiles + tile) * 2 + 0/1]
    win = jnp.stack([lo, cnt], axis=-1).reshape(-1).astype(jnp.int32)

    def kern(live_ref, nodes_ref, win_ref, b_ref, f_ref, p_ref, acc_ref, out_ref):
        t, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i < live_ref[0])
        def _():
            first = (i == 0) | (
                nodes_ref[i] != nodes_ref[jnp.maximum(i - 1, 0)]
            )
            f_row = f_ref[0]                                   # (1, T)

            def select(c, selected):
                ids = lax.broadcasted_iota(jnp.int32, (128, T), 0) + c * 128
                sel = (ids == f_row).astype(jnp.bfloat16)      # (128, T)
                w = b_ref[:, pl.ds(pl.multiple_of((c % word_tiles) * 128, 128), 128)]
                bins = ((w >> (8 * (c // word_tiles))) & 0xFF).astype(jnp.float32)
                return selected + jnp.dot(
                    bins.astype(jnp.bfloat16), sel,
                    preferred_element_type=jnp.float32,
                )

            w = 2 * (i * n_tiles + t)
            c0 = win_ref[w]
            selected = lax.fori_loop(
                c0, c0 + win_ref[w + 1], select, jnp.zeros((R, T), jnp.float32)
            )                                                  # (R, T)
            lane_bin = (
                lax.broadcasted_iota(jnp.int32, (1, G * T), 1) // T
            ).astype(jnp.float32)
            for g in range(nb // G):
                oh = (
                    pltpu.repeat(selected, G, axis=1) == lane_bin + float(g * G)
                ).astype(jnp.bfloat16)                         # (R, G*T)
                sl = slice(g * G * T, (g + 1) * G * T)
                base = jnp.where(first, acc_ref[0, :, sl], out_ref[0, :, sl])
                out_ref[0, :, sl] = base + jnp.dot(
                    p_ref[:], oh, preferred_element_type=jnp.float32
                )                                              # (P, G*T)

        # no live block at all: the one block the pipeline still visits (and
        # writes back) a tile carries its sums through
        @pl.when((live_ref[0] == 0) & (i == 0))
        def _():
            out_ref[:] = acc_ref[:]

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, n_blocks),
            in_specs=[
                pl.BlockSpec(
                    (R, words),
                    lambda t, i, live, nodes, win: (_live_index(i, live), 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, T),
                    lambda t, i, live, nodes, win: (_live_index(i, live), 0, t),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (P, R),
                    lambda t, i, live, nodes, win: (0, _live_index(i, live)),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, P, nb * T),
                    lambda t, i, live, nodes, win: (
                        nodes[_live_index(i, live)], 0, t
                    ),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, P, nb * T),
                lambda t, i, live, nodes, win: (nodes[_live_index(i, live)], 0, t),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
        name="rf_hist_sel_pass_wide",
    )(
        jnp.asarray(live_blocks, jnp.int32).reshape(1),
        nodes_b.astype(jnp.int32), win, bq, feats_b.reshape(n_blocks, 1, k),
        parts, acc,
    )


def wide_hist_nodes(
    acc: jax.Array, S: int, k: int, nb: int, draw: jax.Array | None = None
) -> jax.Array:
    """The kernel's sums as (n_nodes, S, k, nb): the three parts added, the
    tile-major / bin-major lanes back in slot order. ``draw`` (n_nodes, k):
    the ids as each node drew them, where the kernel took them ascending —
    the slots then go back to the draw's order (a row gather of the
    (S, nb) blocks), so the gain search sees the draw's slots, first to
    last, and breaks its ties as it would without the sort."""
    n_nodes, T = acc.shape[0], WIDE_SLOTS
    a = acc[:, : 3 * S].reshape(n_nodes, 3, S, k // T, nb, T)
    a = a[:, 0] + a[:, 1] + a[:, 2]
    if draw is None:
        return a.transpose(0, 1, 2, 4, 3).reshape(n_nodes, S, k, nb)
    # the draw's slot j holds the id at ascending place ``place[g, j]``
    place = jnp.argsort(jnp.argsort(draw, axis=1), axis=1).astype(jnp.int32)
    rows = a.transpose(0, 2, 4, 1, 3).reshape(n_nodes * k, S * nb)
    idx = place + (k * jnp.arange(n_nodes, dtype=jnp.int32))[:, None]
    return rows[idx.reshape(-1)].reshape(n_nodes, k, S, nb).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# T-batched wrappers: one kernel call over a whole tree batch
# ---------------------------------------------------------------------------
#
# The sub-block kernels process independent BLOCK_ROWS-row grid blocks, so
# a batch of T trees flattens its (T, n_pad, ...) operands to (T*n_pad, ...)
# rows and runs ONE kernel call: when n_pad % BLOCK_ROWS == 0 (already a
# rf_hist_*_ok gate condition), every grid block lies inside one tree and
# block j of tree t is computed exactly as in a per-tree call — the batched
# partials are bitwise identical to T separate calls, while the grid gets
# T times the blocks to pipeline through the MXU per dispatch.


def subblock_hist_batched(
    binq: jax.Array,   # (T, n_pad, k) int32 bins, node-contiguous per tree
    sw: jax.Array,     # (T, n_pad, S) f32 stats*weight (0 on padding rows)
    *,
    n_bins: int,
    r_sub: int,
    variance: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-tree sub-block histograms: (T, n_pad//r_sub, S, k*n_bins)."""
    T, n_pad, k = binq.shape
    S = sw.shape[-1]
    assert n_pad % BLOCK_ROWS == 0, n_pad
    out = subblock_hist(
        binq.reshape(T * n_pad, k),
        sw.reshape(T * n_pad, S),
        n_bins=n_bins, r_sub=r_sub, variance=variance, interpret=interpret,
    )
    return out.reshape(T, n_pad // r_sub, S, k * n_bins)


def subblock_hist_sel_batched(
    bq: jax.Array,      # (T, n_pad, d_pad / 4) int32 FULL bins words, node-sorted
    featsq: jax.Array,  # (T, n_sb, k) int32 selected feature ids
    swT: jax.Array,     # (T, S, n_pad) f32 stats*weight
    *,
    n_bins: int,
    r_sub: int,
    variance: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused-selection variant: (T, n_pad//r_sub, S, k*n_bins)."""
    T, n_pad, words = bq.shape
    n_sb, k = featsq.shape[-2:]
    S = swT.shape[-2]
    assert n_pad % BLOCK_ROWS == 0, n_pad
    out = subblock_hist_sel(
        bq.reshape(T * n_pad, words),
        featsq.reshape(T * n_sb, k),
        swT.transpose(1, 0, 2).reshape(S, T * n_pad),
        n_bins=n_bins, r_sub=r_sub, variance=variance, interpret=interpret,
    )
    return out.reshape(T, n_sb, S, k * n_bins)


# ---------------------------------------------------------------------------
# the quantizer: a frame's values to their bins, read as the device keeps it
# ---------------------------------------------------------------------------

# a grid step of the quantizer: at most this many rows (lanes of the frame's
# transpose) by this many columns (a lane tile of row-major bins, four packed
# uint8 sublane tiles of rows-minor ones), or this many words of packed bins
BINIZE_ROWS = 2048
BINIZE_COLS = 128
_BINIZE_VMEM_LIMIT = 32 * 1024 * 1024


def binize_tile(n: int, words: int = 0) -> tuple[int, int, int]:
    """``(rows, columns, vmem bytes)`` of a grid step of
    :func:`rf_binize_pass` over ``n`` rows: the frame's block and the bins'
    block two buffers each (the pipeline's), the edges' block two. With
    ``words`` (the bins four to an int32 word, ``words`` a row) a step reads
    the columns of one byte plane of as many words as it writes."""
    rows = min(BINIZE_ROWS, -(-n // 128) * 128)
    cols = min(BINIZE_COLS, words) if words else BINIZE_COLS
    return rows, cols, 2 * cols * rows * (4 + (4 if words else 1)) + 2 * cols * 128 * 4


def rf_binize_declined(
    n_local: int, d: int, n_edges: int, dtype, devices: int = 1, device=None, words: int = 0
) -> str:
    """The failing terms of :func:`rf_binize_pass`'s gate, comma-joined
    (empty: admitted). A TPU; f32 values; at most 127 edges a column (one
    lane tile holds a column's edges); a frame on one device (the kernel is
    one device's program: a row-sharded frame keeps XLA's pass, which XLA
    partitions); a grid step inside VMEM; at least one column tile; and a
    frame the device keeps with its ROWS minor (``linalg.rows_minor``: a
    width that is no multiple of 128), which the kernel reads as its
    transpose, a bitcast — a row-major frame would get a relayout of the
    whole frame in front of it. With ``words`` (packed bins, ``words`` a
    row): a byte plane's block of the frame is whole sublane tiles, and a
    step's words are a lane tile or all of them. A pure function of its
    arguments and the backend: the forest says on its ``forest.binize`` and
    ``forest.binize_batch`` spans which pass ran, and why."""
    terms = [
        ("dtype", dtype == jnp.float32),
        ("edges<=127", n_edges <= 127),
        ("one_device", devices == 1),
        ("vmem", binize_tile(n_local, words)[2] <= _BINIZE_VMEM_LIMIT),
        ("cols>=128", d >= BINIZE_COLS),
    ]
    if words:
        terms.append(("words", words % BINIZE_COLS == 0 or (words < BINIZE_COLS and words % 8 == 0)))
    if not FORCE_INTERPRET:
        from .linalg import rows_minor

        on_tpu = jax.default_backend() == "tpu"
        terms.insert(0, ("backend", on_tpu))
        terms.append(("rows_minor", on_tpu and rows_minor(device or jax.devices()[0], n_local, d)))
    return ",".join(name for name, ok in terms if not ok)


def rf_binize_pass(
    X: jax.Array, edges: jax.Array, *, d_pad: int, rows_minor_out: bool,
    packed: bool = False, interpret: bool = False,
) -> jax.Array:
    """(n, d) f32 x (d, n_edges) -> (n, d_pad) uint8: ``#{edges <= x}`` per
    value, 0 in the pad columns — the count of ``tree_kernels.binize``, bit
    for bit, in one read of the frame. ``packed``: the same bins four to an
    int32 word, (n, d_pad / 4), in ``tree_kernels._pack_bins``' planar
    layout (byte ``p`` of word ``w`` holds column ``p * d_pad / 4 + w``).

    The frame is read as its (d, n) transpose, the bytes of a rows-minor
    frame: a grid step takes a (128 columns, rows) block, so a vreg holds 8
    columns of 128 rows. A column's edges are sorted into one lane tile of
    the (columns, 128) table (NaN past the last edge and in the pad
    columns); the count is then a search of that sorted row — ``pos += s``
    where ``table[pos + s - 1] <= x``, for s = 64 .. 1 — seven lane gathers
    (``take_along_axis`` within a vreg), compares and selects a vreg where
    the compare-count is 127 compares and adds. Sorting moves no count (a
    count does not see the edges' order); on a sorted row ``e <= x`` is true
    then false, and a NaN edge or value is never ``<=``, so NaN lands in
    bin 0 as the count puts it. Packed, the grid's innermost axis runs over
    the four byte planes of a block of words, the block staying in VMEM: a
    step counts one plane's columns, as a step of the byte bins does, and
    ORs them into the words shifted to their byte (a count is at most 127:
    no word is negative). The bins are written at their default layout:
    rows minor (``rows_minor_out``) as the (d_pad, n) or (d_pad / 4, n)
    transpose, a bitcast of the result; row-major through one transpose of
    a (128, 128) int32 block of counts a lane tile."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = X.shape
    n_edges = edges.shape[1]
    words = d_pad // 4 if packed else 0
    rows, cols, _ = binize_tile(n, words)
    planes = 4 if packed else 1
    # blocks from one byte plane's columns to the next's
    plane_blocks = words // cols if packed else 0
    steps = tuple(1 << i for i in reversed(range(n_edges.bit_length())))
    out_width = words if packed else d_pad
    last_x_block = pl.cdiv(d, cols) - 1
    table = jnp.full((pl.cdiv(d_pad, cols) * cols, 128), jnp.nan, jnp.float32)
    table = table.at[:d, :n_edges].set(jnp.sort(edges.astype(jnp.float32), axis=1))
    out_dtype = jnp.int32 if packed else jnp.uint8

    def kern(e_ref, x_ref, o_ref):
        plane = pl.program_id(2)
        groups = [e_ref[g * 8:(g + 1) * 8, :] for g in range(cols // 8)]

        def lane_tile(t, _):
            lanes = pl.ds(pl.multiple_of(t * 128, 128), 128)
            counts = []
            for g, row in enumerate(groups):
                x = x_ref[g * 8:(g + 1) * 8, lanes]
                pos = jnp.zeros((8, 128), jnp.int32)
                for s in steps:
                    e = jnp.take_along_axis(row, pos + (s - 1), axis=1, mode="promise_in_bounds")
                    pos = jnp.where(e <= x, pos + s, pos)
                counts.append(pos)
            c = jnp.concatenate(counts, axis=0)
            at = (slice(None), lanes) if rows_minor_out else (lanes, slice(None))
            c = c if rows_minor_out else c.T
            if packed:
                # the plane's byte, OR'd into the words the earlier planes wrote
                c = jnp.where(plane == 0, 0, o_ref[at]) | (c << (8 * plane))
            o_ref[at] = c.astype(out_dtype)

        lax.fori_loop(0, rows // 128, lane_tile, None)

    if rows_minor_out:
        out_shape, out_spec = (out_width, n), pl.BlockSpec((cols, rows), lambda i, j, p: (i, j))
    else:
        out_shape, out_spec = (n, out_width), pl.BlockSpec((rows, cols), lambda i, j, p: (j, i))
    out = pl.pallas_call(
        kern,
        grid=(pl.cdiv(out_width, cols), pl.cdiv(n, rows), planes),
        in_specs=[
            pl.BlockSpec((cols, 128), lambda i, j, p: (i + p * plane_blocks, 0)),
            # a pad column block past the frame reads its last block: the
            # table's NaN rows count it 0 whatever it holds
            pl.BlockSpec((cols, rows), lambda i, j, p: (jnp.minimum(i + p * plane_blocks, last_x_block), j)),
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_BINIZE_VMEM_LIMIT
        ),
        name="rf_binize_pass",
        interpret=interpret,
    )(table, X.T)
    return out.T if rows_minor_out else out


# ---------------------------------------------------------------------------
# packed-forest lockstep traversal (inference): hop-2 of the two-hop
# descent for ALL trees fused into one kernel per row block
# ---------------------------------------------------------------------------

# Rows per traversal grid block. VMEM at the cap: packed rows
# (B, 128) i32 + i1 (B, T_pad) + per-tree (B, 256) one-hot / (B, 64)
# table-row transients + (B, T_pad) output — ~6 MB at B=1024, T_pad=64,
# double-buffered well inside the 100 MB budget; the hop-2 tables
# (T_pad * 2^k1, 64) f32 ride along whole (<= 4 MB at T_pad=64, k1=8).
TRAVERSE_BLOCK = 1024

_TRAVERSE_LOWERING_OK: dict = {}


def packed_traverse_ok(t_pad: int, k1: int, k2: int, words: int) -> bool:
    """Trace-time gate for ``packed_traverse``: TPU (or interpret), a
    row's packed bins within one lane-shuffle width (probe: W=256 fails
    to lower, so d_pad <= 512), the two-hop split shape in range, and a
    probed lowering. Row-count alignment is NOT gated — the callers pad
    rows to TRAVERSE_BLOCK internally."""
    Wp = max(64, words)
    ok = (
        (jax.default_backend() == "tpu" or FORCE_INTERPRET)
        and 1 <= k2 <= 6
        and 1 <= k1 <= 8
        and Wp <= 128
        and t_pad % 8 == 0
    )
    if ok and not FORCE_INTERPRET:
        key = ("trav", t_pad, k1, k2, Wp)

        def compile_fn():
            K1 = 1 << k1
            p = jax.ShapeDtypeStruct((2 * TRAVERSE_BLOCK, Wp), jnp.int32)
            i = jax.ShapeDtypeStruct((2 * TRAVERSE_BLOCK, t_pad), jnp.int32)
            f = jax.ShapeDtypeStruct((t_pad * K1, 64), jnp.int32)
            t = jax.ShapeDtypeStruct((t_pad * K1, 64), jnp.int32)
            packed_traverse.lower(
                p, i, f, t, k1=k1, k2=k2, d_pad=4 * words
            ).compile()

        from .linalg import probe_pallas_lowering

        ok = probe_pallas_lowering(
            _TRAVERSE_LOWERING_OK, key, compile_fn,
            "RF packed-forest traversal",
        )
    return ok


@functools.partial(
    jax.jit, static_argnames=("k1", "k2", "d_pad", "interpret")
)
def packed_traverse(
    packed: jax.Array,   # (n, d_pad/4) int32 row bins four to a word
                         # (tree_kernels._pack_bins), n % B == 0
    i1: jax.Array,       # (n, T_pad) int32 hop-1 heap indices
    feat2: jax.Array,    # (T_pad * 2^k1, 64) int32 hop-2 feature tables
    thr2: jax.Array,     # (T_pad * 2^k1, 64) int32 hop-2 thresholds
    *,
    k1: int,
    k2: int,
    d_pad: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Global leaf index per (row, tree): (n, T_pad) int32.

    One pallas_call descends the row block through EVERY tree's hop-2
    subtree in lockstep — the FIL move, on TPU terms. Per tree (static
    loop, fully fused by Mosaic):

      row   = onehot(l7) @ tbl[t]        table row-select on the MXU
                                         (HIGHEST keeps f32 operands —
                                         feature ids may exceed bf16's
                                         exact-integer range)
      xv    = lane-shuffle byte gather   the row's feature bins, one
                                         in-register tpu.dynamic_gather
      bits  = (xv > thr) & is_split      fused bin-space compare (the
                                         exact training-side rule:
                                         bin(x) > t  <=>  x >= edge[t])
      leaf  = navigate + arithmetic id   masked advance, k2 steps

    All integer math — leaf ids are bit-identical to the per-tree bins
    descent. Rows already at a hop-1 leaf (i1 < 2^k1 - 1) keep their
    hop-1 index via the final select; their hop-2 work is masked out by
    the same select, not skipped (lockstep has no divergence)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    n, words = packed.shape
    Wp = max(64, words)  # lane-shuffle operand width (gate caps at 128)
    if words < Wp:
        packed = jnp.pad(packed, ((0, 0), (0, Wp - words)))
    T_pad = i1.shape[1]
    K1 = 1 << k1
    n1 = K1 - 1
    LANES = feat2.shape[1]
    B = TRAVERSE_BLOCK
    f2f = feat2.astype(jnp.float32)
    t2f = thr2.astype(jnp.float32)

    def kern(p_ref, i_ref, f_ref, t_ref, o_ref):
        iv1_all = i_ref[...]                               # (B, T_pad)
        lane_k1 = lax.broadcasted_iota(jnp.int32, (B, K1), 1)
        pbins = p_ref[...]                                 # (B, Wp)
        cols = []
        for t in range(T_pad):
            iv1 = lax.slice_in_dim(iv1_all, t, t + 1, axis=1)  # (B, 1)
            l7 = jnp.clip(iv1 - n1, 0, K1 - 1)
            oh = (lane_k1 == l7).astype(jnp.float32)       # (B, K1)
            ft = f_ref[t * K1 : (t + 1) * K1, :]           # (K1, 64)
            tt = t_ref[t * K1 : (t + 1) * K1, :]
            rfeat = jnp.dot(
                oh, ft, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )                                              # (B, 64)
            rthr = jnp.dot(
                oh, tt, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            ridx = jnp.clip(rfeat.astype(jnp.int32), 0, d_pad - 1)
            if LANES < Wp:
                ridx = jnp.concatenate(
                    [ridx, jnp.zeros((B, Wp - LANES), jnp.int32)], axis=1
                )
            w = jnp.take_along_axis(pbins, ridx % words, axis=1)
            xv = (w >> ((ridx // words) * 8)) & 0xFF       # (B, Wp)
            xv = lax.slice_in_dim(xv, 0, LANES, axis=1)    # (B, 64)
            is_split = rfeat >= 0.0
            bits = ((xv.astype(jnp.float32) > rthr) & is_split).astype(
                jnp.int32
            )
            enc = (1 + bits) * is_split.astype(jnp.int32)  # (B, 64)
            m = jnp.zeros_like(iv1)                        # (B, 1)
            for s in range(k2):
                lo = (1 << s) - 1
                wd = 1 << s
                sl = lax.slice_in_dim(enc, lo, lo + wd, axis=1)
                il = jnp.clip(m - lo, 0, wd - 1)
                lanes = lax.broadcasted_iota(jnp.int32, (B, wd), 1)
                e = jnp.where(lanes == il, sl, 0).sum(
                    axis=1, keepdims=True
                )
                e = jnp.where(m >= lo, e, 0)
                m = jnp.where(e > 0, 2 * m + e, m)
            delta = jnp.zeros_like(m)
            for j in range(1, k2 + 1):
                delta = delta + (m + 1 >= (1 << j)).astype(jnp.int32)
            pd = jnp.left_shift(jnp.int32(1), delta)       # 2^delta
            j_local = m - (pd - 1)
            gid = (K1 * pd - 1) + l7 * pd + j_local
            cols.append(jnp.where(iv1 < n1, iv1, gid))     # (B, 1)
        o_ref[...] = jnp.concatenate(cols, axis=1)

    return pl.pallas_call(
        kern,
        grid=(n // B,),
        in_specs=[
            pl.BlockSpec((B, Wp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (B, T_pad), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (T_pad * K1, LANES), lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (T_pad * K1, LANES), lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec((B, T_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, T_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(packed, i1, f2f, t2f)
