"""trace_reduce on a small trace recorded on the chip (one logreg job at
32,768 × 3000 on a TPU v5 lite, PR 26) and on hand-made intervals."""
import json
import os

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE = os.path.join(HERE, "data", "logreg_32768rows.xplane.pb")


def test_union_clip_and_self_times_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert tr.total(tr.union([(5, 7), (0, 2), (1, 3)])) == 5
    assert tr.clip([[0, 3], [5, 7]], [(2, 6)]) == [[2, 3], [5, 6]]
    # a while of 10 spanning two body ops of 3 and 4: self 3, and one op outside
    ops = [("while", 0, 10), ("a", 1, 4), ("b", 5, 9), ("a", 12, 13)]
    assert tr.self_times(ops) == {"while": 3, "a": 4, "b": 4}
    assert tr.short("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3"


def test_recorded_chip_trace():
    with open(os.path.join(ROOT, "chipbench", "configs", "logreg_dbx.json")) as f:
        annotations = json.load(f)["annotations"]
    s = tr.reduce(TRACE, annotations)
    assert s["devices"] == 1
    assert s["phase_count"] == {"preprocess": 1, "dispatch": 1, "transform": 1}
    # the job's own range is the window; the device was busy 42.6% of it
    assert abs(s["window_s"] - 0.176212902) < 1e-9
    assert abs(s["busy_s"] - 0.074995972) < 1e-9
    assert 0 < s["busy_s"] < s["window_s"]
    # all but a millisecond of the device's work lies inside the solver dispatch
    assert abs(s["busy_in_s"]["dispatch"] - 0.074471852) < 1e-9
    assert abs(s["busy_in_s"]["transform"] - 0.00052412) < 1e-9
    assert s["busy_in_s"]["preprocess"] == 0.0
    # the runtime's transfer thread: 21 ms of the solver dispatch wait for the frame
    assert abs(s["transfer_in_s"]["dispatch"] - 0.021420227) < 1e-9
    assert abs(s["transfer_in_s"]["transform"] - 0.014502799) < 1e-9
    assert s["transfer_in_s"]["dispatch"] < s["phase_s"]["dispatch"]
    ops = dict(s["breakdown"]["device_ops"])
    assert len(s["breakdown"]["device_ops"]) == 10
    # the two passes over X of each evaluation lead; the loop itself is all but empty
    top = [k for k, _ in s["breakdown"]["device_ops"][:2]]
    assert all(k.startswith("multiply_reduce_fusion") for k in top)
    assert sum(ops.values()) <= s["busy_s"] * 1.0001
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert abs(sum(gaps.values()) + s["busy_s"] - s["window_s"]) < 1e-6
    assert set(gaps) <= {"preprocess", "dispatch", "transform", "outside_phases"}


def test_trace_without_device_plane_gives_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    tr.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.JOB):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert tr.reduce(tr.find_xplane(str(tmp_path)), {}) is None
