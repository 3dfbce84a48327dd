"""Gradient-boosted trees on the binned-histogram forest engine.

Boosting is sequential over rounds but parallel WITHIN a round: each
round fits ``n_out`` trees (1 for regression / binary logistic, K for
multiclass softmax) on per-row gradient statistics, and those trees ride
the tree-batched level-wise builder (``_grow_trees_batched``) as one
T-batched dispatch — the same fused segmented histograms, one-hot
matmuls, and Pallas sub-block kernels the RandomForest path uses.

Two deliberate departures from the RF growth contract:

- **Rows stay data-parallel, trees see ALL rows.** RF assigns trees to
  devices (each tree trains on its shard); boosting needs every tree to
  see the full gradient field, so ``gbt_round`` runs the batched builder
  under ``shard_map`` with ``axis_name=DP_AXIS`` — per-level histograms
  and parent stats are ``psum``'d across the mesh while the (N, d) binned
  matrix never replicates. Split decisions are computed from identical
  (all-reduced) histograms on every device, so the fitted tables come out
  replicated for free; only the margin state stays sharded.
- **Leaf values come from the gradient stats, Newton-style.** The tree
  is grown with variance impurity on the residual (slot layout
  ``(w, r, r^2[, h])``), and the leaf prediction is ``sum(r)/sum(h)``
  (logistic/softmax; second-order) or ``sum(r)/sum(w)`` (squared loss:
  the mean residual). The learning-rate-scaled values are computed ON
  DEVICE inside the round — the exact f32 numbers used to update the
  training margins are the numbers the model stores, so transform-time
  margins reproduce training margins bit-for-bit.

Loss conventions match sklearn's gradient boosting (their test oracle):
squared error fits mean residuals; binary logistic fits
``r = y - sigmoid(margin)`` with ``h = p(1-p)``; multiclass softmax fits
one tree per class per round on ``r_k = 1[y=k] - p_k`` with the
``(K-1)/K`` damping on leaf values (MultinomialDeviance).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh

from ..parallel.layout import LAYOUT
from ..parallel.mesh import DP_AXIS
from .tree_kernels import ForestConfig, _grow_trees_batched


class GBTConfig(NamedTuple):
    """Static (compile-time) boosting configuration.

    ``loss``: "squared" | "logistic" | "multinomial".
    ``n_out``: trees grown per round (1, or n_classes for multinomial).
    ``tree``: the per-round tree build config. ``n_stats`` must be 3 for
    squared loss (w, r, r^2) and 4 otherwise (w, r, r^2, h) — the hessian
    slot rides through every histogram reduction untouched because
    variance impurity reads slots 0-2 only.
    """

    loss: str
    n_out: int
    learning_rate: float
    tree: ForestConfig


def _row_stats(y: jax.Array, marg: jax.Array, mask: jax.Array, cfg: GBTConfig):
    """Per-row sufficient stats (n_out, n, S) for this round's trees."""
    w = mask
    if cfg.loss == "squared":
        r = (y - marg[:, 0]) * w
        return jnp.stack([w, r, r * r], axis=1)[None]
    if cfg.loss == "logistic":
        p = jax.nn.sigmoid(marg[:, 0])
        r = (y - p) * w
        h = jnp.maximum(p * (1.0 - p), 1e-12) * w
        return jnp.stack([w, r, r * r, h], axis=1)[None]
    if cfg.loss == "multinomial":
        p = jax.nn.softmax(marg, axis=1)                 # (n, K)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), cfg.n_out, dtype=marg.dtype)
        r = (onehot - p) * w[:, None]                    # (n, K)
        h = jnp.maximum(p * (1.0 - p), 1e-12) * w[:, None]
        return jnp.stack(
            [
                jnp.broadcast_to(w[:, None], r.shape),
                r,
                r * r,
                h,
            ],
            axis=2,
        ).transpose(1, 0, 2)                             # (K, n, 4)
    raise ValueError(f"unknown GBT loss {cfg.loss!r}")


def _leaf_values(leaf_stats: jax.Array, cfg: GBTConfig) -> jax.Array:
    """(T, M) learning-rate-scaled leaf predictions from raw leaf stats."""
    if cfg.loss == "squared":
        val = leaf_stats[:, :, 1] / jnp.maximum(leaf_stats[:, :, 0], 1e-12)
    else:
        val = leaf_stats[:, :, 1] / jnp.maximum(leaf_stats[:, :, 3], 1e-12)
        if cfg.loss == "multinomial":
            val = val * ((cfg.n_out - 1.0) / cfg.n_out)
    return cfg.learning_rate * val


@functools.partial(jax.jit, static_argnames=("mesh", "cfg"))
def gbt_round(
    bins: jax.Array,     # (N_pad, d_pad) uint8, dp-sharded
    mask: jax.Array,     # (N_pad,) float, dp-sharded
    y: jax.Array,        # (N_pad,) float labels, dp-sharded
    margins: jax.Array,  # (N_pad, V) float raw margins, dp-sharded
    key: jax.Array,      # (2,) uint32, replicated
    *,
    mesh: Mesh,
    cfg: GBTConfig,
) -> Dict[str, jax.Array]:
    """One boosting round: fit this round's tree batch on the current
    gradient field and advance the margins.

    Returns replicated tree tables (``feature``, ``threshold_bin``,
    ``leaf_stats``, ``gain``, ``values`` — the lr-scaled leaf payloads)
    plus the updated dp-sharded ``margins``.
    """

    def per_device(bins_l, mask_l, y_l, marg_l, key_r):
        sw = _row_stats(y_l, marg_l, mask_l, cfg)        # (T, n_l, S)
        # per-output feature-subset keys; bootstrap is off in boosting
        # (Spark's subsamplingRate=1 default), so only kf is consumed
        kf = jax.vmap(lambda j: jax.random.fold_in(key_r, j))(
            jnp.arange(cfg.n_out)
        )
        out = _grow_trees_batched(
            bins_l, sw, kf, cfg.tree,
            axis_name=DP_AXIS, return_rows=True,
        )
        vscaled = _leaf_values(out["leaf_stats"], cfg)   # (T, M)
        # leaf assignment per (tree, local row) came out of growth —
        # no second descent over the training set
        upd = jax.vmap(lambda v, nd: v[nd])(vscaled, out["node"])
        marg_new = marg_l + upd.transpose(1, 0) * mask_l[:, None]
        return (
            out["feature"],
            out["threshold_bin"],
            out["leaf_stats"],
            out["gain"],
            vscaled,
            marg_new,
        )

    feat, thr_bin, leaf_stats, gain, values, margins = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows(), LAYOUT.replicated()),
        # tree tables are computed from all-reduced histograms — identical
        # on every device, so they leave replicated (check_vma=False as in
        # build_forest: the builder's internals mix manual collectives)
        out_specs=(LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.replicated(), LAYOUT.rows()),
        check_vma=False,
    )(bins, mask, y, margins, key)
    return {
        "feature": feat,
        "threshold_bin": thr_bin,
        "leaf_stats": leaf_stats,
        "gain": gain,
        "values": values,
        "margins": margins,
    }


@functools.partial(jax.jit, static_argnames=("mesh", "loss"))
def gbt_loss(
    y: jax.Array,        # (N_pad,) dp-sharded
    margins: jax.Array,  # (N_pad, V) dp-sharded
    mask: jax.Array,     # (N_pad,) dp-sharded
    *,
    mesh: Mesh,
    loss: str,
) -> jax.Array:
    """Mean training loss at the current margins (round logging)."""

    def per_device(y_l, marg_l, mask_l):
        if loss == "squared":
            per_row = (y_l - marg_l[:, 0]) ** 2
        elif loss == "logistic":
            m = marg_l[:, 0]
            # -[y log p + (1-y) log(1-p)] in the stable logaddexp form
            per_row = jnp.logaddexp(0.0, m) - y_l * m
        else:
            logp = jax.nn.log_softmax(marg_l, axis=1)
            per_row = -jnp.take_along_axis(
                logp, y_l.astype(jnp.int32)[:, None], axis=1
            )[:, 0]
        s = lax.psum(jnp.sum(per_row * mask_l), DP_AXIS)
        n = lax.psum(jnp.sum(mask_l), DP_AXIS)
        return s / jnp.maximum(n, 1.0)

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(LAYOUT.rows(), LAYOUT.rows(), LAYOUT.rows()),
        out_specs=LAYOUT.replicated(),
        check_vma=False,
    )(y, margins, mask)
