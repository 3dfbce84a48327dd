"""The forest `rf_dbx.job` serves, as a file: one warm job of the cell's
estimator on the cell's frame for ``--seed`` (``chipbench/run.py``'s own data
and runner), its model tables to ``--out`` (.npz). Run from the root of a
checkout, so two checkouts' forests can be compared on one machine:

    (cd _parent && python3 ../scripts/rf_forest_tables.py --seed 7 --out ../chiprun_out/parent.npz)
    python3 scripts/rf_forest_tables.py --seed 7 --out chiprun_out/change.npz --against chiprun_out/parent.npz

``--against`` prints, a table, whether it equals the other file's to the bit
(``threshold_bins``: at the split nodes, and apart from them) — as they are,
and with every split of a PURE node taken out of both (``prune_pure``: before
PR 38 a TPU split pure nodes on the rounding noise of a float32 division) —
and the span's counts (``live_share``, ``closed_at_birth``). A reader's aid,
no benchmark metric. ``--rows`` for a rehearsal off the chip."""
import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

TABLES = ("features", "leaf_stats", "gains", "thresholds", "threshold_bins", "bin_edges")


def prune_pure(t: dict) -> dict:
    """The forest with no split of a node whose class counts are pure: such a
    node becomes a leaf (feature -1, gain, threshold and bin 0, its counts
    kept) and everything below it, or below any leaf, holds nothing."""
    t = {k: np.array(t[k]) for k in TABLES}
    feat, leaf = t["features"], t["leaf_stats"]
    present = np.zeros(feat.shape, bool)
    present[:, 0] = True
    for i in range(feat.shape[1]):
        cut = present[:, i] & (feat[:, i] >= 0) & ((leaf[:, i] > 0).sum(axis=-1) <= 1)
        feat[cut, i] = -1
        if 2 * i + 2 < feat.shape[1]:
            present[:, 2 * i + 1] = present[:, 2 * i + 2] = present[:, i] & (feat[:, i] >= 0)
    feat[~present] = -1
    leaf[~present] = 0
    for k in ("gains", "thresholds", "threshold_bins"):
        t[k][feat < 0] = 0
    return t


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--against", default=None)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    with open(os.path.join("chipbench", "configs", "rf_dbx.json")) as f:
        config = json.load(f)

    from chipbench.data import gen_data
    from chipbench.traffic import closed_loop
    from spark_rapids_ml_tpu.classification import RandomForestClassifier
    from spark_rapids_ml_tpu.runtime import telemetry
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    rows = args.rows or int(config["rows"])
    columns = gen_data.make(args.seed, rows, int(config["cols"]), config["data"]["params"])
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    runner = closed_loop.Runner(config, {"steps": ["fit"]}, columns, RandomForestClassifier, 1)
    model = runner.run_job()["model"]
    grow = [s["args"] for s in spans if s["name"] == "forest.grow_group"]
    counts = {k: [g[k] for g in grow if k in g] for k in ("live_share", "closed_at_birth", "live_rows_by_level")}
    print(json.dumps({"seed": args.seed, "rows": rows, **counts}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    np.savez(args.out, **{k: model[k] for k in TABLES})
    if args.against:
        other = np.load(args.against)
        split = model["features"] >= 0
        same = {k: bool(np.array_equal(model[k], other[k])) for k in TABLES}
        same["features_split_nodes"] = int(split.sum())
        same["threshold_bins_at_split_nodes"] = bool(np.array_equal(model["threshold_bins"][split], other["threshold_bins"][split]))
        same["threshold_bins_differ_at_leaves"] = int((model["threshold_bins"] != other["threshold_bins"])[~split].sum())
        print(json.dumps({"equal_to_the_bit": same}), flush=True)
        mine, theirs = prune_pure(model), prune_pure(other)
        print(json.dumps({"equal_with_no_split_of_a_pure_node": {k: bool(np.array_equal(mine[k], theirs[k])) for k in TABLES},
                          "split_nodes": {"this": int(split.sum()), "other": int((other["features"] >= 0).sum()),
                                          "this_pruned": int((mine["features"] >= 0).sum()), "other_pruned": int((theirs["features"] >= 0).sum())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
