"""VMEM-resident Pallas SGD engine for the UMAP embedding optimization.

The XLA epoch loop (``umap_kernels.optimize_embedding_rows``) is bound by
random gathers against an HBM-resident embedding whose minor dim is 2:
per epoch it fetches K tail rows plus K*neg negative rows per CSR-padded
row — ~1.8M 8-byte random reads at the 65k bench shape — while the whole
(65536, 2) f32 table is only 512 KB. This engine is the counter-move:
the gather TABLE stays VMEM-resident across the entire epoch while the
CSR-padded row streams (heads, tails, probabilities, negative ids) flow
HBM→VMEM block by block, and every tail/negative fetch becomes an
on-chip ``dynamic_gather`` instead of an HBM transaction. The embedding
is written back once per epoch (512 KB — noise), not once per gather.

Division of labor per epoch (and why):

* in-kernel — the K + K*neg random row gathers per CSR row (144 of the
  145 gathered rows per row at the bench config) and the full gradient
  arithmetic (attractive + negative-sampling terms, clip discipline);
* XLA side — the sorted head gather (1/145 of the gather traffic,
  near-sequential), the sorted ``segment_sum`` (<1 ms measured) and the
  ``emb + alpha*upd`` apply, plus the per-epoch randomness (see below).

Randomness has two modes:

* ``rng="xla"`` — the Bernoulli slot uniforms are drawn with the *exact*
  ``jax.random`` stream of the XLA path (same ``fold_in``/``split``
  order, shared via ``umap_kernels.epoch_rng_keys``) and streamed into
  the kernel. Same-seed outputs match ``optimize_embedding_rows`` to
  float associativity — this is the parity-testable mode, and the only
  mode under interpret (``interpret=True`` has no CPU rule for
  ``prng_seed`` on the installed jax 0.9.0 — checked; the TPU
  ``InterpretParams`` mode accepts it but returns all-zero bits).
* ``rng="onchip"`` — the kernel draws the slot mask from the TPU
  hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``), removing the
  (R, K) uniform stream from HBM entirely. Statistically equivalent
  (uniform marginal per slot), not bit-equal to the XLA stream.

Negative-sample indices reproduce the XLA path's tiled-permutation
semantics exactly: tn[r, k, s] = src[perm[(((r - offs[s]) mod R)·K + k)
mod n_tab]], materialized per epoch as cheap contiguous tiles/rolls of
the (n_tab,) permutation — integer copies, never an embedding gather.

Hardware gating: the compiled kernel is ruled out for now (see
``umap_sgd_pallas_ok``: jax 0.9.0 refuses its row gather), so the engine
runs in interpret mode only and TPU fits take the XLA loop. Engine
selection is ``TPUML_UMAP_OPT`` = auto | pallas | xla, mirroring
``TPUML_RF_APPLY``.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..runtime import envspec
from .umap_kernels import epoch_alpha, epoch_rng_keys

# Test hook (mirrors ops.rf_pallas.FORCE_INTERPRET): run the kernel
# through the Pallas interpreter on CPU so tests cover the real body.
FORCE_INTERPRET = False

# CSR rows per grid block. 256 divides both row buckets the fit uses
# (4096 and 256); transform batches are padded up to it with inert rows.
BLOCK_ROWS = 256

def resolve_umap_opt() -> str:
    """Validated ``TPUML_UMAP_OPT`` (auto | pallas | xla)."""
    return str(envspec.get("TPUML_UMAP_OPT"))


def default_rng_mode() -> str:
    """On-chip PRNG on real TPU hardware; the XLA stream everywhere else
    (the Pallas interpreter has no rule for ``pltpu.prng_seed``)."""
    if FORCE_INTERPRET or jax.default_backend() != "tpu":
        return "xla"
    return "onchip"


def umap_sgd_pallas_ok(
    n_tab: int, K: int, C: int, neg: int, rng: str = "xla"
) -> bool:
    """Trace-time gate: slot widths in range and the lane-padded table
    inside the VMEM budget.

    Interpret mode only, by rule: the installed Pallas TPU lowering
    (jax 0.9.0, ``pallas/mosaic/lowering.py`` gather rule) takes
    ``take_along_axis`` only where indices and table have the same shape
    and refuses this kernel's (B·K, C)-from-(n_tab, C) row gather at any
    size (``tests/test_chip_compile.py`` keeps the refusal as a strict
    xfail). Until the gather is rewritten every TPU fit takes the XLA
    epoch loop by this rule, not by a caught compile error; whoever
    repairs it re-opens the gate to ``jax.default_backend() == "tpu"``
    behind ``ops.linalg.probe_pallas_lowering``, as ``rf_pallas`` does."""
    ok = (
        FORCE_INTERPRET
        and 1 <= C <= 8
        and 1 <= K <= 128
        and 1 <= neg <= 16
        and K * (1 + neg) <= 1024
        # Mosaic lane-pads the (n_tab, C<=8) f32 table to (8, 128) tiles:
        # n_tab * 512 B resident. Cap at 64 MB so streams + double
        # buffers fit the 100 MB vmem budget (65536 rows -> 33.5 MB).
        and n_tab * 512 <= 64 * 1024 * 1024
    )
    # the interpreter has no rule for the on-chip PRNG
    return ok and rng != "onchip"


def select_sgd_engine(
    n_tab: int, K: int, C: int, neg: int, *, rng: str | None = None
) -> str:
    """Resolve ``TPUML_UMAP_OPT`` against the gate/probe: returns
    ``"pallas"`` or ``"xla"``. An explicit ``pallas`` at a config the
    static gate rules out warns and runs the XLA loop."""
    mode = resolve_umap_opt()
    if mode == "xla":
        return "xla"
    if rng is None:
        rng = default_rng_mode()
    if umap_sgd_pallas_ok(n_tab, K, C, neg, rng):
        return "pallas"
    if mode == "pallas":
        logging.getLogger("spark_rapids_ml_tpu.umap").warning(
            "TPUML_UMAP_OPT=pallas but the VMEM-resident SGD kernel is "
            "unavailable for config (n_tab=%d, K=%d, C=%d, neg=%d, rng=%s);"
            " falling back to the XLA epoch loop",
            n_tab, K, C, neg, rng,
        )
    return "xla"


@functools.partial(
    jax.jit,
    static_argnames=("a", "b", "gamma", "attract_scale", "rng", "interpret"),
)
def sgd_epoch_rows(
    src: jax.Array,        # (n_tab, C) f32 gather table — VMEM-resident
    h: jax.Array,          # (R, C) f32 head rows (pre-gathered, sorted)
    tails_pad: jax.Array,  # (R, K) int32 tail ids
    p_pad: jax.Array,      # (R, K) f32 slot activation probabilities
    neg_ids: jax.Array,    # (R, neg*K) int32 negative ids, slot-major per s
    u,                     # (R, K) f32 slot uniforms (rng="xla") or None
    seed: jax.Array,       # (1, 1) int32 per-epoch seed (rng="onchip")
    *,
    a: float,
    b: float,
    gamma: float,
    attract_scale: float,
    rng: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """One SGD epoch over CSR-padded rows: per-row gradient sums (R, C).

    The caller applies the sorted ``segment_sum`` and the ``alpha`` step —
    exactly the XLA path's epoch tail — so the two engines share every
    instruction outside the gather/gradient hot loop. R must be a
    BLOCK_ROWS multiple (the wrapper pads with inert p=0 rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = FORCE_INTERPRET
    R, K = tails_pad.shape
    n_tab, C = src.shape
    neg = neg_ids.shape[1] // K
    B = BLOCK_ROWS
    n_blocks = R // B

    def kern(seed_ref, src_ref, h_ref, t_ref, p_ref, n_ref, *rest):
        if rng == "xla":
            u_ref, o_ref = rest
        else:
            (o_ref,) = rest
        srcv = src_ref[...]                       # (n_tab, C) resident
        hv = h_ref[...]                           # (B, C)
        p = p_ref[...]                            # (B, K)
        if rng == "xla":
            unif = u_ref[...]
        else:
            # decorrelate grid blocks off the per-epoch seed
            pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
            bits = pltpu.prng_random_bits((B, K))
            unif = (bits >> jnp.uint32(8)).astype(jnp.float32) * (
                1.0 / (1 << 24)
            )
        active = (unif < p).astype(jnp.float32)   # (B, K)

        def gather_rows(ids2d):
            # (B, K) ids -> (B, K, C) table rows via the sublane
            # dynamic_gather form (take_along_axis with matching rank)
            m = ids2d.shape[0] * ids2d.shape[1]
            flat = ids2d.reshape(m, 1)
            g = jnp.take_along_axis(
                srcv, jnp.broadcast_to(flat, (m, C)), axis=0
            )
            return g.reshape(ids2d.shape[0], ids2d.shape[1], C)

        def clip4(x):
            return jnp.clip(x, -4.0, 4.0)

        t = gather_rows(t_ref[...])               # (B, K, C)
        diff = hv[:, None, :] - t
        d2 = (diff * diff).sum(axis=2)            # (B, K)
        ac = (-2.0 * a * b * d2 ** (b - 1.0)) / (a * d2**b + 1.0)
        ac = jnp.where(d2 > 0.0, ac, 0.0) * active
        grad = clip4(ac[..., None] * diff) * attract_scale

        nids = n_ref[...]                         # (B, neg*K)
        for s in range(neg):
            tn = gather_rows(nids[:, s * K : (s + 1) * K])
            diff_n = hv[:, None, :] - tn
            d2n = (diff_n * diff_n).sum(axis=2)
            rc = (2.0 * gamma * b) / ((0.001 + d2n) * (a * d2n**b + 1.0))
            rc = jnp.where(d2n > 0.0, rc, 0.0) * active
            grad = grad + clip4(rc[..., None] * diff_n)

        o_ref[...] = grad.sum(axis=1)             # (B, C)

    in_specs = [
        pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec(
            (n_tab, C), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec((B, C), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((B, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((B, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(
            (B, neg * K), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
    ]
    args = [seed, src, h, tails_pad, p_pad, neg_ids]
    if rng == "xla":
        in_specs.append(
            pl.BlockSpec((B, K), lambda i: (i, 0), memory_space=pltpu.VMEM)
        )
        args.append(u)
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_epochs", "a", "b", "gamma", "initial_alpha",
        "negative_sample_rate", "self_table", "rng", "interpret",
        "epoch_span",
    ),
)
def umap_sgd_pallas(
    emb_head: jax.Array,    # (n_head, C) embedding being optimized
    table: jax.Array,       # (n_tab, C) frozen tail table (transform); the
                            # SAME array for fit (self_table=True)
    row_heads: jax.Array,   # (R,) int32, sorted ascending
    tails_pad: jax.Array,   # (R, K) int32
    p_pad: jax.Array,       # (R, K) f32 sampling probabilities
    key: jax.Array,
    *,
    n_epochs: int,
    a: float,
    b: float,
    gamma: float = 1.0,
    initial_alpha: float = 1.0,
    negative_sample_rate: int = 5,
    self_table: bool = True,
    rng: str = "xla",
    interpret: bool | None = None,
    epoch_offset=0,
    epoch_span: int | None = None,
) -> jax.Array:
    """Drop-in engine for ``umap_kernels.optimize_embedding_rows`` with the
    gather/gradient hot loop in the VMEM-resident Pallas kernel.

    Epoch structure mirrors the XLA path exactly: randomness is drawn via
    the shared ``epoch_rng_keys`` stream (uniforms only materialize for
    ``rng="xla"``), negatives reproduce the tiled-permutation + per-sample
    row-roll semantics as precomputed index tiles, and the epoch tail
    (sorted segment_sum, ``emb + alpha*upd``) is byte-for-byte the same
    code path — so ``rng="xla"`` outputs are same-seed equivalent.

    ``epoch_offset``/``epoch_span`` (the checkpoint/resume segmenting
    contract of ``optimize_embedding_rows``): run absolute epochs
    ``[offset, offset + span)``. All per-epoch state — epoch keys, alpha,
    the on-chip PRNG's ``seed_base + e`` — is a function of the absolute
    index, so segmented runs match single-shot ones."""
    from jax import lax

    R, K = tails_pad.shape
    n_head, C = emb_head.shape
    n_tab = table.shape[0]
    neg = int(negative_sample_rate)
    reps = -(-(R * K) // n_tab)
    pad_rows = (-R) % BLOCK_ROWS

    # Kernel block padding: randomness and roll moduli are computed at the
    # ORIGINAL R (parity with the XLA path); padded rows carry p = 0
    # (never activate), tail/negative id 0 (valid, gradient masked) and
    # head n_head-1, keeping row_heads ascending for the sorted
    # segment_sum — the build_row_adjacency padding discipline.
    tails_b = jnp.pad(tails_pad, ((0, pad_rows), (0, 0)))
    p_b = jnp.pad(p_pad, ((0, pad_rows), (0, 0)))
    heads_b = jnp.pad(
        row_heads, (0, pad_rows), constant_values=n_head - 1
    )
    # per-epoch seed base for the on-chip PRNG (ignored under rng="xla");
    # drawn off a side-channel fold so epoch keys stay untouched
    seed_base = jax.random.randint(
        jax.random.fold_in(key, 0x5EED), (), 0, jnp.iinfo(jnp.int32).max,
        dtype=jnp.int32,
    )

    span = n_epochs if epoch_span is None else int(epoch_span)
    e0 = jnp.asarray(epoch_offset, jnp.int32)

    def epoch(i, emb):
        e = e0 + i  # absolute epoch: RNG + alpha match single-shot runs
        src = emb if self_table else table
        k1, k2, k3 = epoch_rng_keys(key, e)
        alpha = epoch_alpha(initial_alpha, e, n_epochs)
        u = None
        if rng == "xla":
            u = jnp.pad(
                jax.random.uniform(k1, (R, K)), ((0, pad_rows), (0, 0))
            )
        # negatives: tn[r,k,s] = src[perm[(((r-offs[s]) mod R)*K + k) mod
        # n_tab]] — the XLA path's fused tile/roll views, materialized as
        # integer index tiles (contiguous copies, no embedding gather)
        perm = jax.random.permutation(k2, n_tab)
        pidx = (
            jnp.tile(perm, (reps,))[: R * K].reshape(R, K).astype(jnp.int32)
        )
        offs = jax.random.randint(k3, (neg,), 0, R)
        neg_ids = jnp.concatenate(
            [jnp.roll(pidx, offs[s], axis=0) for s in range(neg)], axis=1
        )
        neg_b = jnp.pad(neg_ids, ((0, pad_rows), (0, 0)))
        # sorted head gather stays in XLA: 1/(1+K+K*neg) of the gather
        # traffic, near-sequential by construction
        h_b = jnp.pad(emb[row_heads], ((0, pad_rows), (0, 0)))
        seed_e = (seed_base + e).astype(jnp.int32).reshape(1, 1)
        row_upd = sgd_epoch_rows(
            src, h_b, tails_b, p_b, neg_b, u, seed_e,
            a=a, b=b, gamma=gamma,
            attract_scale=2.0 if self_table else 1.0,
            rng=rng, interpret=interpret,
        )
        upd = jax.ops.segment_sum(
            row_upd, heads_b, num_segments=n_head, indices_are_sorted=True
        )
        return emb + alpha * upd

    return lax.fori_loop(0, span, epoch, emb_head)
