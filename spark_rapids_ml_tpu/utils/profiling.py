"""Tracing / profiling — the NVTX-range analog.

The reference wraps its phases in NVTX ranges so nsys can attribute time
(``/root/reference/jvm/src/main/scala/org/apache/spark/ml/linalg/distributed/RapidsRowMatrix.scala:62,70``)
and the Python benchmarks do phase wall-clock timing
(``python/benchmark/benchmark/utils.py:42``). The TPU-native equivalents:

* :func:`annotate` — a ``jax.profiler.TraceAnnotation`` scope; shows up as
  a named range on the TensorBoard trace timeline (and is a no-op when no
  trace is being captured). ``core.py`` writes three by hand, whose names
  readers of a trace match: ``<Estimator>.preprocess``, ``<Estimator>.fit``
  around the solver dispatch, ``<Model>.transform``. Every live
  ``runtime.telemetry`` span writes its own as ``tpuml:<span name>``.
* :func:`trace` — capture a TensorBoard profile of a code region into a
  directory (``tensorboard --logdir <dir>`` → Profile tab).
* :class:`StageTimer` — accumulating per-stage breakdown; each stage is
  also a ``runtime.telemetry`` span, so the report dicts built from
  ``totals`` and the exported trace see the same measurement.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator, Optional

import jax

from ..runtime import telemetry


def annotate(name: str):
    """Named range on the profiler timeline (no-op outside a capture)."""
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a TensorBoard profile of the region when ``log_dir`` is
    set; transparent otherwise."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StageTimer:
    """Accumulating per-stage wall-clock breakdown for repeated pipelines
    (the packed-forest transform engine wraps its quantize/traverse
    dispatch and host materialization per micro-batch; one summary line
    per transform call).

    Dispatch stages measure ASYNC enqueue
    time — device wait lands in whichever stage first materializes
    results (``np.asarray``). The split still attributes host-side costs
    (staging, packing, output copies) faithfully.
    """

    def __init__(self, name: str) -> None:
        self.name = name
        self.totals: dict = {}
        self.counts: dict = {}
        # fold threads overlap host-side transform/eval work since the
        # PR-8 _FOLD_DEVICE_LOCK narrowing, so the accumulators need a
        # real lock
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def stage(self, label: str) -> Iterator[None]:
        ts = telemetry.timed_span(f"{self.name}.{label}")
        ts.__enter__()
        try:
            yield
        finally:
            ts.__exit__(None, None, None)
            with self._lock:
                self.totals[label] = self.totals.get(label, 0.0) + ts.seconds
                self.counts[label] = self.counts.get(label, 0) + 1

    def log_summary(self, logger) -> None:
        """Debug-log accumulated stages and reset for the next call."""
        with self._lock:
            if not self.totals:
                return
            parts = ", ".join(
                f"{k}={v:.4f}s/{self.counts[k]}x"
                for k, v in sorted(self.totals.items())
            )
            self.totals.clear()
            self.counts.clear()
        logger.debug("%s stages: %s", self.name, parts)
