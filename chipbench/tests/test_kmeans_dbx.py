"""``kmeans_dbx``: the work counts by hand, and what its comparison can and
cannot tell. The control, the broken harness, the rehearsal and the names are
parametrised over every configuration and cell in the files beside this one."""
import copy
import importlib
import json
import os

from chipbench.work import kmeans_dbx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_kmeans_work_by_hand():
    import numpy as np

    # n=1000, d=10, k=5, 4 iterations: per iteration distances 2ndk + sums 2ndk
    # = 4·1000·10·5 = 200,000 operations and one read of X (40,000 bytes); the
    # cost pass 2ndk = 100,000 operations and one more read
    model = {"n_iter": np.asarray(4), "cluster_centers": np.zeros((5, 10), np.float32)}
    w = kmeans_dbx.fit_work(1000, 10, model)
    assert w["flops"] == 4 * 200_000 + 100_000 == 900_000
    assert w["bytes"] == 5 * 40_000 == 200_000
    assert kmeans_dbx.iter_work(1000, 10, 5) == {"flops": 200_000.0, "bytes": 40_000.0}


def test_full_size_floor_seconds():
    # the cell's own shape on one v5e: an iteration is compute-bound, 6e12
    # operations at 197 TFLOP/s = 30.5 ms against 7.3 ms for one read of X
    w = kmeans_dbx.iter_work(500_000, 3000, 1000)
    assert abs(w["flops"] / 197e12 - 0.030457) < 1e-5
    assert abs(w["bytes"] / 819e9 - 0.007326) < 1e-5


def test_a_fit_stopped_early_is_caught_by_obj_excess_only_while_centres_still_move():
    """The configuration's file says it plainly: on these blobs Lloyd is at a
    fixed point after 10 to 15 iterations, so a fit stopped at 3 is caught (by
    ``obj_excess``), one stopped at 20 of 30 is the same model."""
    with open(os.path.join(ROOT, "chipbench", "configs", "kmeans_dbx.json")) as f:
        config = json.load(f)
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    columns = data.make(3000000029, 40_000, int(config["cols"]), config["data"]["params"])

    def stopped_at(iterations):
        early = copy.deepcopy(config)
        early["estimator"]["params"]["maxIter"] = iterations
        return dict(ref.check(config, columns, [ref.reference_job(early, columns)]))

    assert stopped_at(1)["obj_excess"] > config["limits"]["obj_excess"]
    late = stopped_at(20)
    assert all(late[k] <= config["limits"][k] for k in config["limits"]), late
