"""The wide-subset variance histogram on the chip, route by route.

    chiprun -- python3 scripts/rf_wide_hist_microbench.py [--fit TREES]

One chunk of a level of ``rf_reg_dbx`` (16,384 node-sorted rows of 3072 uint8
bins, 32 one-node blocks in runs of ``--nodes`` nodes — 3, a chunk of level 5's
32 nodes of ~19 blocks each — 1000 sampled features a node
in 1024 slots — ascending, as the level hands them since PR 40 — 128 bins,
three float32 statistics), timed four ways (PERF.md section 6, PRs 39, 40):

* ``kernel``: ``rf_pallas.subblock_hist_sel_wide`` as the level runs it — the
  slot tile outermost (a block fetched once a tile), a tile's selection over
  its window of columns, the rows as int32 words four bins to a word (the
  three variants below read bf16 rows);
* ``parent``: PR 39's kernel — the same grid, every tile's selection one
  product over all 3072 columns;
* ``block_once_full_width``: the block read once (grid (blocks, tiles), the
  tile innermost, a node's whole sums resident in VMEM), the full width;
* ``block_once_window``: the block read once and the window (PR 40's first
  build, dropped: it saved 0.05–0.07 ms a chunk and its resident sums
  narrowed the gate to k * nb under ~150,000 lanes);

the last three private copies here (``_variant``), each with the HBM bytes a
call reckoned from its grid (rows, statistics, the nodes' sums in and out) and
the mean ``sel_cols`` (columns a tile's selection reads). And what the level
puts around the kernel a chunk (the whole-row gather with its bf16 cast, the
statistics' split, the slots put back in the draw's order at level 5's 32
nodes). ``--fit T`` then fits T trees of the
cell's estimator on the cell's frame twice (cold, warm) and prints the seconds
and the ``forest.grow_group`` span. A reader's aid, not a benchmark metric.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def timed(fn, *args, reps=10):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def _variant(bq, feats_b, parts, nodes_b, live_blocks, acc, *, n_bins, block_major, windowed):
    """``rf_pallas.subblock_hist_sel_wide`` with the grid order and the window
    as switches, for the one-factor timings: ``block_major`` = grid (blocks,
    slot tiles) with a node's whole sums as the out block; ``windowed=False``
    = one product of the block's whole rows with the tile's (d_pad, 128)
    one-hot (PR 39's). Both off is PR 39's kernel."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from spark_rapids_ml_tpu.ops import rf_pallas as rp

    n_pad, d_pad = bq.shape
    n_blocks, k = feats_b.shape
    nb, R, T, P = n_bins, rp.WIDE_BLOCK_ROWS, rp.WIDE_SLOTS, rp.WIDE_STAT_ROWS
    G = min(rp._WIDE_BIN_GROUP, nb)
    n_tiles = k // T
    lo, cnt = rp.wide_windows(feats_b, d_pad)
    win = jnp.stack([lo, cnt], axis=-1).reshape(-1).astype(jnp.int32)
    out_w = k * nb if block_major else nb * T

    def kern(live_ref, nodes_ref, win_ref, b_ref, f_ref, p_ref, acc_ref, out_ref):
        i, t = pl.program_id(0), pl.program_id(1)
        if not block_major:
            i, t = t, i

        @pl.when(i < live_ref[0])
        def _():
            first = (i == 0) | (nodes_ref[i] != nodes_ref[jnp.maximum(i - 1, 0)])
            f_row = f_ref[0]
            if windowed:
                def select(c, selected):
                    col = pl.multiple_of(c * 128, 128)
                    sel = (lax.broadcasted_iota(jnp.int32, (128, T), 0) + col == f_row).astype(jnp.bfloat16)
                    return selected + jnp.dot(b_ref[:, pl.ds(col, 128)], sel, preferred_element_type=jnp.float32)

                w = 2 * (i * n_tiles + t)
                selected = lax.fori_loop(win_ref[w], win_ref[w] + win_ref[w + 1], select, jnp.zeros((R, T), jnp.float32))
            else:
                sel = (lax.broadcasted_iota(jnp.int32, (d_pad, T), 0) == f_row).astype(jnp.bfloat16)
                selected = jnp.dot(b_ref[:], sel, preferred_element_type=jnp.float32)
            lane_bin = (lax.broadcasted_iota(jnp.int32, (1, G * T), 1) // T).astype(jnp.float32)
            for g in range(nb // G):
                oh = (pltpu.repeat(selected, G, axis=1) == lane_bin + float(g * G)).astype(jnp.bfloat16)
                sl = (pl.ds(pl.multiple_of((t * nb + g * G) * T, 128), G * T) if block_major
                      else slice(g * G * T, (g + 1) * G * T))
                base = jnp.where(first, acc_ref[0, :, sl], out_ref[0, :, sl])
                out_ref[0, :, sl] = base + jnp.dot(p_ref[:], oh, preferred_element_type=jnp.float32)

        @pl.when((live_ref[0] == 0) & (i == 0))
        def _():
            out_ref[:] = acc_ref[:]

    if block_major:
        grid, blk, tile = (n_blocks, n_tiles), (lambda a, b: a), (lambda a, b: b)
        sums = lambda a, b, live, nodes: (nodes[rp._live_index(a, live)], 0, 0)  # noqa: E731
    else:
        grid, blk, tile = (n_tiles, n_blocks), (lambda a, b: b), (lambda a, b: a)
        sums = lambda a, b, live, nodes: (nodes[rp._live_index(b, live)], 0, a)  # noqa: E731
    spec = lambda shape, f: pl.BlockSpec(shape, f, memory_space=pltpu.VMEM)  # noqa: E731
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid,
            in_specs=[
                spec((R, d_pad), lambda a, b, live, nodes, w: (rp._live_index(blk(a, b), live), 0)),
                spec((1, 1, T), lambda a, b, live, nodes, w: (rp._live_index(blk(a, b), live), 0, tile(a, b))),
                spec((P, R), lambda a, b, live, nodes, w: (0, rp._live_index(blk(a, b), live))),
                spec((1, P, out_w), lambda a, b, live, nodes, w: sums(a, b, live, nodes)),
            ],
            out_specs=spec((1, P, out_w), lambda a, b, live, nodes, w: sums(a, b, live, nodes)),
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=100 * 1024 * 1024),
        interpret=rp.FORCE_INTERPRET,
        name="rf_hist_sel_pass_wide",
    )(live_blocks.reshape(1), nodes_b.astype(jnp.int32), win, bq, feats_b.reshape(n_blocks, 1, k), parts, acc)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fit", type=int, default=0)
    ap.add_argument("--rows", type=int, default=500_000)
    ap.add_argument("--nodes", type=int, default=3, help="nodes in the chunk's 32 blocks")
    args = ap.parse_args()
    from spark_rapids_ml_tpu.ops import rf_pallas as rp
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    print("device", jax.devices()[0].device_kind, flush=True)
    chunk, d_pad, nb, S, n_nodes = 16384, 3072, 128, 3, args.nodes
    if jax.default_backend() != "tpu":  # a rehearsal of the script itself: interpret mode, a toy chunk
        rp.FORCE_INTERPRET, chunk, nb, n_nodes = True, 1024, 32, 2
    R = rp.WIDE_BLOCK_ROWS
    blocks = chunk // R
    rng = np.random.default_rng(0)
    n = args.rows
    bins = jnp.asarray(rng.integers(0, nb, (n, d_pad), dtype=np.uint8))
    ids = jnp.asarray(rng.permutation(n)[:chunk].astype(np.int32))
    swT = jnp.asarray((rng.normal(size=(S, chunk)) * 1e3).astype(np.float32))
    nodes = jnp.asarray((np.arange(blocks) * n_nodes // blocks).astype(np.int32))
    live = jnp.asarray([blocks], jnp.int32)
    out = {}

    rows_of = jax.jit(lambda b, i: b[i].astype(jnp.bfloat16))
    out["rows_gather_bf16_ms"] = 1e3 * timed(rows_of, bins, ids)
    out["rows_gather_u8_ms"] = 1e3 * timed(jax.jit(lambda b, i: b[i]), bins, ids)
    out["split_ms"] = 1e3 * timed(jax.jit(rp.split_f32_exact), swT)
    rows_bf, parts = rows_of(bins, ids), rp.split_f32_exact(swT)
    from spark_rapids_ml_tpu.ops.tree_kernels import _pack_bins

    rows_words = jax.jit(_pack_bins)(bins)[ids]   # the program's kernel reads the rows as words

    def hbm_bytes(k, block_major):
        """Bytes a call moves, from its grid: a block's bf16 rows and its 16
        statistics' rows (once, or once a slot tile), each node's whole sums
        read and written once (a tile's share of them, once a tile)."""
        rows = blocks * R * (d_pad + rp.WIDE_STAT_ROWS) * 2
        sums = len(set(np.asarray(nodes).tolist())) * 2 * rp.WIDE_STAT_ROWS * k * nb * 4
        return rows * (1 if block_major else k // rp.WIDE_SLOTS) + sums

    variants = (
        ("kernel", None), ("parent", (False, False)),
        ("block_once_full_width", (True, False)), ("block_once_window", (True, True)),
    )
    k = 1024
    for label, variant in variants:
        feats = np.stack([np.concatenate([np.sort(rng.permutation(3000)[:1000]), np.full(k - 1000, 3000)]) for _ in range(n_nodes)])
        feats = feats.astype(np.int32)
        feats_b = jnp.asarray(feats)[nodes]
        acc = jnp.zeros((n_nodes, rp.WIDE_STAT_ROWS, k * nb), jnp.float32)
        if variant is None:
            step = lambda a, fb=feats_b: rp.subblock_hist_sel_wide(rows_words, fb, parts, nodes, live, a, n_bins=nb)  # noqa: E731
        else:
            step = jax.jit(functools.partial(_variant, rows_bf, feats_b, parts, nodes, live, n_bins=nb,
                                             block_major=variant[0], windowed=variant[1]), donate_argnums=0)
        acc = jax.block_until_ready(step(acc))
        t = time.perf_counter()
        for _ in range(10):
            acc = step(acc)
        jax.block_until_ready(acc)
        out[label + "_kernel_ms"] = 1e3 * (time.perf_counter() - t) / 10
        block_major, windowed = variant or (False, True)
        out[label + "_hbm_mb"] = hbm_bytes(k, block_major) / 1e6
        win = np.asarray(rp.wide_windows(feats_b, d_pad)[1]) if windowed else np.full((1,), d_pad // 128)
        out[label + "_sel_cols"] = float(128 * win.mean())
        if label == "kernel":  # against float64 on the host, one node and slot tile
            got = np.asarray(rp.wide_hist_nodes(acc, S, k, nb)[int(nodes[0])]) / 11.0
            sel = np.asarray(nodes) == int(nodes[0])
            rows_sel = np.flatnonzero(np.repeat(sel, R))
            bsel = np.asarray(bins[ids])[rows_sel][:, feats[int(nodes[0])][:128]].astype(np.int64)
            want = np.zeros((S, 128, nb))
            for s in range(S):
                wgt = np.asarray(swT[s], np.float64)[rows_sel]
                for j in range(128):
                    want[s, j] = np.bincount(bsel[:, j], weights=wgt, minlength=nb)
            out["kernel_rel_err"] = float(np.abs(got[:, :128] - want).max() / np.abs(want).max())
        del acc

    draw = jnp.asarray(np.stack([rng.permutation(3000)[:k] for _ in range(32)]).astype(np.int32))
    sums = jnp.asarray(rng.normal(size=(32, rp.WIDE_STAT_ROWS, k * nb)).astype(np.float32))
    out["slot_order_ms"] = 1e3 * timed(jax.jit(lambda a, f: rp.wide_hist_nodes(a, S, k, nb, draw=f)), sums, draw)
    out["slot_order_none_ms"] = 1e3 * timed(jax.jit(lambda a: rp.wide_hist_nodes(a, S, k, nb)), sums)
    del sums

    from spark_rapids_ml_tpu.ops.rf_pallas import subblock_hist

    row_feats = jnp.asarray(rng.integers(0, 3000, (chunk, 1024)).astype(np.int32))
    if jax.default_backend() != "tpu":
        row_feats = row_feats[:, :64]
    gather = jax.jit(lambda b, i, f: b[i[:, None], f])
    out["gather_1024_cols_ms"] = 1e3 * timed(gather, bins, ids, row_feats, reps=3)
    binq = gather(bins, ids, row_feats).astype(jnp.int32)[:, :16]
    out["gather_route_one_of_64_calls_ms"] = 1e3 * timed(
        lambda: subblock_hist(binq, swT, None, n_bins=nb, r_sub=64, variance=True, transposed_sw=True)
    )
    print(json.dumps(out), flush=True)
    del bins, rows_bf, rows_words

    if args.fit:
        from chipbench.data import gen_data
        from spark_rapids_ml_tpu.data import DataFrame
        from spark_rapids_ml_tpu.regression import RandomForestRegressor
        from spark_rapids_ml_tpu.runtime import telemetry

        cols = gen_data.make(3900000011, n, 3000, {"kind": "regression"})
        df = DataFrame({"features": cols["features"]}).withColumn("label", cols["label"])
        spans = []
        telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
        est = RandomForestRegressor(numTrees=args.fit, maxDepth=6, maxBins=128, seed=1)
        for run in ("cold", "warm", "warm2"):
            spans.clear()
            t = time.perf_counter()
            model = est.fit(df)
            t_fit = time.perf_counter() - t
            t = time.perf_counter()
            pred = np.asarray(model.transform(df).column("prediction"))
            t_tr = time.perf_counter() - t
            grow = [s for s in spans if s["name"] == "forest.grow_group"]
            print(json.dumps({"run": run, "trees": args.fit, "fit_s": t_fit, "transform_s": t_tr,
                              "grow_s": [s["dur"] / 1e6 for s in grow], "grow_args": grow[0]["args"] if grow else None,
                              "rmse": float(np.sqrt(np.mean((pred - cols["label"]) ** 2))),
                              "y_std": float(np.std(cols["label"]))}, default=str), flush=True)
        attrs = model._get_model_attributes()
        leaf = np.asarray(attrs["leaf_stats"])
        print("root stats", leaf[:, 0].tolist(), "splits a tree", float((np.asarray(attrs["features"]) >= 0).sum()) / args.fit)
        print("peak", (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
