"""``rf_dbx``: the work counts by hand, the floor at full size, and what its
comparison can tell at a size a test run holds. The control (40,000 x 3000,
three seeds), the broken harness, the rehearsal (``--rows 8192``: every metric
``null``) and the names are parametrised over every configuration and cell in
the files beside this one; the hand counts of this configuration's
``fit_work`` are here and not in ``test_work.py``, which a PR that adds a cell
may not edit."""
import copy
import importlib
import json
import os

import numpy as np

from chipbench.work import rf_dbx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model(leaf_stats, cols, bins):
    return {"leaf_stats": np.asarray(leaf_stats, np.float32), "bin_edges": np.zeros((cols, bins - 1), np.float32)}


def test_rf_work_by_hand():
    # n=1000 rows, d=16 columns (4 features a node), 8 bins, ONE tree of depth 2
    # (7 nodes): the root holds 1000 weighted rows, its children 600 and 400,
    # the last level is never searched. Row-levels = 1000 + 600 + 400 = 2000;
    # updates = 2000 x 4 = 8000; bytes = 8000 bin bytes + 9 x 2000.
    leaf = np.zeros((1, 7, 2))
    leaf[0, 0] = [500, 500]
    leaf[0, 1], leaf[0, 2] = [400, 200], [100, 300]
    leaf[0, 3:] = [[300, 100], [100, 100], [50, 150], [50, 150]]
    h = rf_dbx.hist_work(16, _model(leaf, 16, 8))
    assert h["row_levels"] == 2000 and h["flops"] == 8000 and h["bytes"] == 8000 + 18000
    # binize: 1000 x 16 x 7 compares, X read (4 bytes) and bins written (1 byte)
    assert rf_dbx.binize_work(1000, 16, 8) == {"flops": 112_000.0, "bytes": 80_000.0}
    # the sketch: all 1000 rows, ceil(log2 1000) = 10 compares a value
    assert rf_dbx.sketch_work(1000, 16) == {"flops": 160_000.0, "bytes": 64_000.0}
    w = rf_dbx.fit_work(1000, 16, _model(leaf, 16, 8))
    assert w["flops"] == 8000 + 112_000 + 160_000 and w["bytes"] == 26_000 + 80_000 + 64_000
    assert rf_dbx.features_per_node(3000) == 55


def test_full_size_floor_seconds():
    # the cell's shape on one v5e, 16 full trees of depth 13: 13 levels x 500,000
    # weighted rows x 55 features = 3.6e8 updates a tree; binize is the larger
    # term, 1.9e11 compares = 0.97 ms at the peak against 7.5e9 bytes = 9.2 ms:
    # the algorithm's floor is HBM-bound and four orders below a second a tree
    leaf = np.zeros((16, (1 << 14) - 1, 2), np.float32)
    for level in range(14):
        leaf[:, (1 << level) - 1:(1 << (level + 1)) - 1] = 250_000.0 / (1 << level)
    model = _model(leaf, 3000, 128)
    h = rf_dbx.hist_work(3000, model)
    assert abs(h["flops"] - 16 * 13 * 500_000 * 55) < 1e-3 * h["flops"]
    w = rf_dbx.fit_work(500_000, 3000, model)
    assert w["bytes"] / 819e9 > w["flops"] / 197e12
    assert 0.015 < w["bytes"] / 819e9 < 0.03


def test_faults_of_the_references_own_fit_are_not_correct_at_4000_rows():
    with open(os.path.join(ROOT, "chipbench", "configs", "rf_dbx.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["estimator"]["params"]["numTrees"] = 2
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    columns = data.make(3500000029, 4000, int(config["cols"]), config["data"]["params"])

    def ok(**fault):
        return {k: v <= config["limits"][k] for k, v in ref.check(config, columns, [ref.reference_job(config, columns, **fault)])}

    assert all(ok().values())
    assert not ok(fit_rows=2000)["count_err"] and not ok(bootstrap=False)["count_err"]
    assert not ok(runner_up=True)["split_excess"] and not ok(alter_row=5)["out_err"]
    # the bf16 control moves no row across a split here: on this set a split
    # lies in the gap between the classes, and 4000 rows put none within a
    # bf16 rounding of one; at 40,000 rows and at full size they do (the
    # parametrised control test beside this file, and PERF.md section 2)
