"""The wide-subset variance histogram on the chip, route by route.

    chiprun -- python3 scripts/rf_wide_hist_microbench.py [--fit TREES]

One chunk of a level of ``rf_reg_dbx`` (16,384 node-sorted rows of 3072 uint8
bins, 1000 sampled features a node in 1024 slots, 128 bins, three float32
statistics), timed three ways (PERF.md section 6, PR 39):

* ``subset``: ``rf_pallas.subblock_hist_sel_wide`` over the node's 1024 slots
  — the route taken;
* ``whole_rows``: the same kernel over all 3072 columns (identity features):
  the route that histograms whole rows and masks the subset in the gain
  search, its selection product included (an upper bound by that one product);
* ``gather``: the parent's path — the per-row gather of 1024 sampled columns
  and ONE of the 64 feature-chunk calls of ``subblock_hist`` at HIGHEST it fed.

And what the level puts around the kernel a chunk (the whole-row gather with
its bf16 cast, the statistics' split). ``--fit T`` then fits T trees of the
cell's estimator on the cell's frame twice (cold, warm) and prints the seconds
and the ``forest.grow_group`` span. A reader's aid, not a benchmark metric.
"""
import argparse
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fit", type=int, default=0)
    ap.add_argument("--rows", type=int, default=500_000)
    args = ap.parse_args()
    from spark_rapids_ml_tpu.ops import rf_pallas as rp
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    print("device", jax.devices()[0].device_kind, flush=True)
    chunk, d_pad, nb, S, n_nodes = 16384, 3072, 128, 3, 32
    if jax.default_backend() != "tpu":  # a rehearsal of the script itself: interpret mode, a toy chunk
        rp.FORCE_INTERPRET, chunk, nb, n_nodes = True, 1024, 32, 2
    R = rp.WIDE_BLOCK_ROWS
    blocks = chunk // R
    rng = np.random.default_rng(0)
    n = args.rows
    bins = jnp.asarray(rng.integers(0, nb, (n, d_pad), dtype=np.uint8))
    ids = jnp.asarray(rng.permutation(n)[:chunk].astype(np.int32))
    swT = jnp.asarray((rng.normal(size=(S, chunk)) * 1e3).astype(np.float32))
    nodes = jnp.asarray(np.sort(rng.integers(0, n_nodes, blocks)).astype(np.int32))
    live = jnp.asarray([blocks], jnp.int32)
    out = {}

    rows_of = jax.jit(lambda b, i: b[i].astype(jnp.bfloat16))
    out["rows_gather_bf16_ms"] = 1e3 * timed(rows_of, bins, ids)
    out["rows_gather_u8_ms"] = 1e3 * timed(jax.jit(lambda b, i: b[i]), bins, ids)
    out["split_ms"] = 1e3 * timed(jax.jit(rp.split_f32_exact), swT)
    rows_bf, parts = rows_of(bins, ids), rp.split_f32_exact(swT)

    for name, k in (("subset", 1024), ("whole_rows", 3072)):
        feats = np.stack([rng.permutation(d_pad)[:k] for _ in range(n_nodes)]).astype(np.int32)
        feats_b = jnp.asarray(feats)[nodes]
        acc = jnp.zeros((n_nodes, rp.WIDE_STAT_ROWS, k * nb), jnp.float32)
        step = lambda a, fb=feats_b: rp.subblock_hist_sel_wide(rows_bf, fb, parts, nodes, live, a, n_bins=nb)  # noqa: E731
        acc = jax.block_until_ready(step(acc))
        t = time.perf_counter()
        for _ in range(10):
            acc = step(acc)
        jax.block_until_ready(acc)
        out[name + "_kernel_ms"] = 1e3 * (time.perf_counter() - t) / 10
        if name == "subset":  # against float64 on the host, one node and slot tile
            got = np.asarray(rp.wide_hist_nodes(acc, S, k, nb)[int(nodes[0])]) / 11.0
            sel = np.asarray(nodes) == int(nodes[0])
            rows_sel = np.flatnonzero(np.repeat(sel, R))
            bsel = np.asarray(bins[ids])[rows_sel][:, feats[int(nodes[0])][:128]].astype(np.int64)
            want = np.zeros((S, 128, nb))
            for s in range(S):
                wgt = np.asarray(swT[s], np.float64)[rows_sel]
                for j in range(128):
                    want[s, j] = np.bincount(bsel[:, j], weights=wgt, minlength=nb)
            out["subset_rel_err"] = float(np.abs(got[:, :128] - want).max() / np.abs(want).max())
        del acc

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
    del bins, rows_bf

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
