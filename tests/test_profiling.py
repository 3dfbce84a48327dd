"""Tracing/profiling subsystem (SURVEY §5: the reference wraps phases in
NVTX ranges, ``RapidsRowMatrix.scala:62,70``; here phases are
``jax.profiler`` trace annotations + TensorBoard captures)."""

import glob

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.runtime import telemetry
from spark_rapids_ml_tpu.utils.profiling import annotate, trace


def test_fit_under_profile_capture(tmp_path, rng):
    """A fit inside a profiler capture produces a TensorBoard trace and
    identical results (annotations must never perturb numerics)."""
    X = rng.normal(size=(120, 6)).astype(np.float32)
    df = DataFrame({"features": X})
    plain = PCA(k=2, num_workers=2).fit(df)
    with trace(str(tmp_path)):
        traced = PCA(k=2, num_workers=2).fit(df)
    np.testing.assert_allclose(traced.components_, plain.components_)
    assert glob.glob(str(tmp_path / "plugins" / "profile" / "*")), (
        "no TensorBoard profile written"
    )


def test_trace_noop_without_dir():
    with trace(None):
        pass  # transparent


def test_annotate_and_timed(tmp_path):
    """A phase is written twice: the hand-written annotation, and the live
    span's own ``tpuml:`` annotation with its id — both land in a capture
    (``timed``, a third timing of the same phases into a debug log, is
    gone)."""
    from jax.profiler import ProfileData

    telemetry.reset_telemetry()
    spans = []
    telemetry.add_span_sink(lambda ev, thread: spans.append(ev))
    try:
        with trace(str(tmp_path)):
            with annotate("phase"), telemetry.span("phase", rows=3):
                np.zeros(3).sum()
    finally:
        telemetry.reset_telemetry()
    (span,) = spans
    (xplane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {
        e.name: dict(e.stats)
        for plane in ProfileData.from_file(xplane).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
        if e.name in ("phase", "tpuml:phase")
    }
    assert set(events) == {"phase", "tpuml:phase"}
    assert events["tpuml:phase"] == {"span_id": span["args"]["span_id"]}
    assert span["args"]["rows"] == 3 and span["dur"] > 0
