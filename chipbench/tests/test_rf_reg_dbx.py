"""``rf_reg_dbx``: the work counts by hand, the floor at full size, and what
its comparison can tell at a size a test run holds (by hand: tier-1 collects
``tests/`` only, and holds the same cases at 4,096 x 300). The rehearsal and
the names are parametrised over every configuration and cell in the files
beside this one; this configuration's hand counts and BOTH of its controls
are here, in a file of their own."""
import copy
import importlib
import json
import os

import numpy as np
import pytest

from chipbench.work import rf_reg_dbx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model(leaf_stats, cols, bins):
    return {"leaf_stats": np.asarray(leaf_stats, np.float32), "bin_edges": np.zeros((cols, bins - 1), np.float32)}


def _config(trees=2):
    with open(os.path.join(ROOT, "chipbench", "configs", "rf_reg_dbx.json")) as f:
        config = copy.deepcopy(json.load(f))
    config["estimator"]["params"]["numTrees"] = trees
    return config


def test_rf_reg_work_by_hand():
    # n=1000 weighted rows, d=16 columns (ceil(16/3) = 6 features a node), 8
    # bins, ONE tree of depth 2 (7 nodes): the root holds 1000, its children
    # 600 and 400, the last level is never searched. Row-levels = 2000;
    # updates = 2000 x 6 = 12000; bytes = 12000 bin bytes + 17 x 2000 (the
    # routing's bin, three float32 statistics and the weight, a row and level).
    leaf = np.zeros((1, 7, 3))
    leaf[0, :, 0] = [1000, 600, 400, 300, 300, 150, 250]
    leaf[0, :, 1] = 7.0                        # the sums of w*y and w*y^2 count for nothing
    leaf[0, :, 2] = 1e9
    h = rf_reg_dbx.hist_work(16, _model(leaf, 16, 8))
    assert h["row_levels"] == 2000 and h["flops"] == 12000 and h["bytes"] == 12000 + 34000
    w = rf_reg_dbx.fit_work(1000, 16, _model(leaf, 16, 8))
    # + binize (1000 x 16 x 7 compares; 5 bytes a value) + the sketch (1000 x 16 x 10; 4 bytes a value)
    assert w["flops"] == 12000 + 112_000 + 160_000 and w["bytes"] == 46_000 + 80_000 + 64_000
    assert rf_reg_dbx.features_per_node(3000) == 1000 and rf_reg_dbx.features_per_node(1100) == 367


def test_full_size_floor_seconds():
    # the cell's shape on one v5e, 15 full trees of depth 6 on a Poisson(1)
    # bootstrap (500,000 weighted rows a level): 6 x 500,000 x 1000 = 3e9
    # updates a tree, 4.5e10 a forest = 0.23 ms at the MXU's peak and 55 ms at
    # the HBM's: the histogram's floor is HBM-bound, and with binize (9.2 ms)
    # the fit's is under a tenth of a second — no share of it can pass 100%
    leaf = np.zeros((15, 127, 3), np.float32)
    for level in range(7):
        leaf[:, (1 << level) - 1:(1 << (level + 1)) - 1, 0] = 500_000.0 / (1 << level)
    model = _model(leaf, 3000, 128)
    h = rf_reg_dbx.hist_work(3000, model)
    assert abs(h["flops"] - 15 * 6 * 500_000 * 1000) < 1e-6 * h["flops"]
    assert h["bytes"] / 819e9 > h["flops"] / 197e12 and 0.05 < h["bytes"] / 819e9 < 0.06
    w = rf_reg_dbx.fit_work(500_000, 3000, model)
    assert 0.06 < w["bytes"] / 819e9 < 0.1


@pytest.mark.parametrize("seed", [3900000029, 3900000031])
def test_both_controls_are_not_correct_at_40000_rows(seed):
    config = _config()
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    columns = data.make(seed, 40_000, int(config["cols"]), config["data"]["params"])

    def ok(**fault):
        return {k: v <= config["limits"][k] for k, v in ref.check(config, columns, [ref.reference_job(config, columns, **fault)])}

    assert all(ok().values())
    assert not ok(control=True)["count_err"]
    stat = ok(stat_control=True)
    assert not stat["mean_err"] and not stat["var_err"]


def test_faults_of_the_references_own_fit_are_not_correct_at_4000_rows():
    config = _config()
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    columns = data.make(3900000037, 4000, int(config["cols"]), config["data"]["params"])

    def ok(**fault):
        return {k: v <= config["limits"][k] for k, v in ref.check(config, columns, [ref.reference_job(config, columns, **fault)])}

    assert all(ok().values())
    assert not ok(fit_rows=2000)["count_err"] and not ok(bootstrap=False)["count_err"]
    assert not ok(cut_depth=4)["split_excess"] and not ok(runner_up=True)["split_excess"]
    assert not ok(alter_row=5)["out_err"]
