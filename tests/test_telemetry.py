"""Telemetry runtime: span nesting (including across threads), trace
export validity, Prometheus format, histogram ring bounds, the retrace
watchdog, the counters shim's kind-aware deltas, the defaults-inert
contract (env unset => no files, no spans, bit-identical results), span
events from retries and injected faults, the crash-path flush, and the
multi-host merge (parity between scripts/merge_traces.py and
telemetry.merge_metric_snapshots)."""

import importlib.util
import json
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import counters, faults, telemetry
from spark_rapids_ml_tpu.runtime.retry import with_retries

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset_telemetry()
    yield
    telemetry.reset_telemetry()


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """Enable tracing into a per-test directory."""
    monkeypatch.setenv("TPUML_TRACE", str(tmp_path))
    return tmp_path


def _load_by_path(name):
    spec = importlib.util.spec_from_file_location(
        f"_test_{name}", os.path.join(REPO_ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_trace(tdir):
    files = [f for f in os.listdir(tdir) if f.startswith("trace-")]
    assert len(files) == 1, files
    with open(os.path.join(tdir, files[0])) as f:
        return json.load(f)


# --- spans -----------------------------------------------------------------


def test_span_nesting_and_attrs(traced):
    with telemetry.span("outer", phase="a"):
        with telemetry.span("inner") as sp:
            sp.set_attr(rows=42)
    telemetry.flush()

    doc = _load_trace(traced)
    xs = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert set(xs) == {"outer", "inner"}
    outer, inner = xs["outer"], xs["inner"]
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert "parent_id" not in outer["args"]  # root spans have no parent
    assert outer["args"]["phase"] == "a"
    assert inner["args"]["rows"] == 42
    # complete events nest in time: ts/dur are microseconds
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1

    stats = telemetry.span_stats()
    assert stats["outer"]["count"] == 1
    assert stats["outer"]["wall_seconds"] >= stats["inner"]["wall_seconds"]


def test_span_parenting_across_threads(traced):
    """bind_context carries the active span into worker threads — the
    same mechanism the CV fold pool and the streaming decode/stage
    threads use."""
    def work():
        with telemetry.span("child"):
            pass

    with telemetry.span("root"):
        t = threading.Thread(target=telemetry.bind_context(work))
        t.start()
        t.join()
    telemetry.flush()

    doc = _load_trace(traced)
    xs = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert xs["child"]["args"]["parent_id"] == xs["root"]["args"]["span_id"]
    # distinct threads get distinct tids (and thread_name metadata)
    assert xs["child"]["tid"] != xs["root"]["tid"]
    meta_tids = {
        e["tid"] for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert {xs["child"]["tid"], xs["root"]["tid"]} <= meta_tids


def test_trace_file_and_event_log_valid(traced):
    with telemetry.span("a"):
        pass
    with telemetry.span("b"):
        pass
    telemetry.flush()

    doc = _load_trace(traced)
    assert isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "M", "i")
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float))
            assert e["dur"] >= 0

    logs = [f for f in os.listdir(traced) if f.startswith("events-")]
    assert len(logs) == 1
    with open(os.path.join(traced, logs[0])) as f:
        lines = [json.loads(line) for line in f]
    assert {rec["name"] for rec in lines} == {"a", "b"}
    assert all("wall_seconds" in rec for rec in lines)


def test_timed_span_measures_even_untraced():
    ts = telemetry.timed_span("anything")
    with ts:
        pass
    assert ts.seconds >= 0.0
    # nothing recorded: tracing is off
    assert telemetry.span_stats() == {}


def test_kmeans_fit_trace_covers_fit(traced):
    """End-to-end: a traced fit produces a loadable trace whose root
    span covers the whole fit and whose children account for the bulk
    of it."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    df = DataFrame({"features": X})
    KMeans(k=3, maxIter=2, seed=0).setFeaturesCol("features").fit(df)
    telemetry.flush()

    stats = telemetry.span_stats()
    assert "KMeans.fit" in stats
    assert "preprocess" in stats and "fit.dispatch" in stats
    root = stats["KMeans.fit"]["wall_seconds"]
    covered = (
        stats["preprocess"]["wall_seconds"]
        + stats["fit.dispatch"]["wall_seconds"]
    )
    assert covered <= root
    assert covered >= 0.95 * root

    doc = _load_trace(traced)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"KMeans.fit", "preprocess", "fit.dispatch"} <= names


# --- metrics ---------------------------------------------------------------


def test_histogram_ring_is_bounded(monkeypatch):
    monkeypatch.setenv("TPUML_TELEMETRY_RESERVOIR", "4")
    h = telemetry.histogram("span_seconds")
    for i in range(100):
        h.observe(float(i))
    series = h.value()
    assert series.count == 100
    assert series.sum == sum(range(100))
    assert series.min == 0.0 and series.max == 99.0
    # deterministic last-N ring, not an unbounded (or sampled) buffer
    assert list(series.ring) == [96.0, 97.0, 98.0, 99.0]


def test_metric_kind_mismatch_raises():
    with pytest.raises(ValueError, match="registered as a gauge"):
        # deliberate kind mismatch: the runtime check under test
        # tpuml: ignore[TPU007]
        telemetry.counter("resumed_from")


def test_prometheus_dump_format():
    telemetry.counter("retries").inc(3)
    telemetry.gauge("hbm_budget_bytes").set(1024.0, site="gang_fit")
    telemetry.histogram("span_seconds").observe(0.5, name="x")
    text = telemetry.prometheus_dump()
    lines = text.splitlines()
    assert "# TYPE tpuml_retries counter" in lines
    assert "tpuml_retries 3" in lines
    assert "# TYPE tpuml_hbm_budget_bytes gauge" in lines
    assert 'tpuml_hbm_budget_bytes{site="gang_fit"} 1024' in lines
    assert "# TYPE tpuml_span_seconds summary" in lines
    assert 'tpuml_span_seconds{name="x",quantile="0.5"} 0.5' in lines
    assert 'tpuml_span_seconds_count{name="x"} 1' in lines
    assert 'tpuml_span_seconds_sum{name="x"} 0.5' in lines
    # every sample line belongs to a HELP/TYPE-declared family
    for line in lines:
        if line and not line.startswith("#"):
            assert line.startswith("tpuml_")

    snap = telemetry.metrics_snapshot()
    assert snap["retries"]["kind"] == "counter"
    json.dumps(snap)  # snapshot must be JSON-clean


def test_write_metrics_files(traced):
    telemetry.counter("retries").inc()
    paths = telemetry.write_metrics()
    assert paths is not None
    prom, js = paths
    assert os.path.exists(prom) and os.path.exists(js)
    with open(js) as f:
        snap = json.load(f)
    assert snap["retries"]["series"][0]["value"] == 1


# --- counters shim ---------------------------------------------------------


def test_counters_shim_roundtrip():
    counters.bump("retries")
    counters.bump("retries", 2)
    counters.note("resumed_from", 7)
    snap = counters.snapshot()
    assert snap["retries"] == 3
    assert snap["resumed_from"] == 7
    assert counters.get("retries") == 3


def test_delta_since_gauge_is_kind_driven():
    """Regression: gauge semantics in delta_since must follow the
    declared metric kind, not a hard-coded name match."""
    counters.note("my_shim_gauge", 5)  # tpuml: ignore[TPU007]
    counters.bump("my_shim_counter", 2)  # tpuml: ignore[TPU007]
    base = counters.snapshot()
    counters.note("my_shim_gauge", 9)  # tpuml: ignore[TPU007]
    counters.bump("my_shim_counter", 3)  # tpuml: ignore[TPU007]
    delta = counters.delta_since(base)
    # gauge: last value, NOT 9 - 5; counter: the increment
    assert delta["my_shim_gauge"] == 9
    assert delta["my_shim_counter"] == 3
    # unchanged metrics are omitted
    assert counters.delta_since(counters.snapshot()) == {}
    # shim-created metrics carry the right registry kinds
    assert telemetry.metric_kind("my_shim_gauge") == "gauge"
    assert telemetry.metric_kind("my_shim_counter") == "counter"


# --- retrace watchdog ------------------------------------------------------


def test_retrace_watchdog_detects_storm(traced, monkeypatch):
    monkeypatch.setenv("TPUML_TELEMETRY_RETRACE_LIMIT", "2")
    assert telemetry.install_retrace_watchdog()

    # the package logger doesn't propagate to root (caplog can't see
    # it) — attach a capturing handler directly
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("spark_rapids_ml_tpu")
    handler = _Capture(level=logging.WARNING)
    logger.addHandler(handler)
    try:
        with telemetry.span("retrace.victim"):
            # a fresh jit per call: every invocation recompiles — the
            # storm TPU003 exists to catch, forced deliberately
            for n in range(1, 6):
                # deliberate recompile storm: the watchdog under test
                # tpuml: ignore[TPU003]
                fn = jax.jit(lambda x: x * 2.0)
                fn(jnp.ones((n, 3), jnp.float32)).block_until_ready()
    finally:
        logger.removeHandler(handler)

    compiles = telemetry.counter("xla_compiles").value(
        site="retrace.victim"
    )
    assert compiles is not None and compiles > 2
    assert telemetry.counter("retrace_storms").value() == 1
    warnings = [r for r in records if "retrace storm" in r.getMessage()]
    assert len(warnings) == 1  # warn-once per site
    assert "retrace.victim" in warnings[0].getMessage()


# --- defaults-inert --------------------------------------------------------


def test_defaults_inert_no_spans_no_files(tmp_path, monkeypatch):
    monkeypatch.delenv("TPUML_TRACE", raising=False)
    assert not telemetry.enabled()
    # the disabled span is a shared singleton: zero per-call allocation
    assert telemetry.span("a") is telemetry.span("b", k=1)
    with telemetry.span("a") as sp:
        sp.set_attr(x=1)
    assert telemetry.span_stats() == {}
    assert telemetry.flush() is None
    assert telemetry.write_metrics() is None
    assert os.listdir(tmp_path) == []


def test_traced_fit_bit_identical_to_untraced(tmp_path, monkeypatch):
    rng = np.random.default_rng(1)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    df = DataFrame({"features": X})

    def centers():
        m = KMeans(k=3, maxIter=4, seed=0).setFeaturesCol("features").fit(df)
        return m.cluster_centers_

    monkeypatch.delenv("TPUML_TRACE", raising=False)
    plain = centers()
    monkeypatch.setenv("TPUML_TRACE", str(tmp_path))
    traced = centers()
    assert plain.tobytes() == traced.tobytes()


# --- histogram quantile edge cases -----------------------------------------


def test_quantile_empty_and_single_sample():
    h = telemetry._Hist(8)
    assert h.quantile(0.5) is None  # empty: None, not IndexError
    h.observe(3.0)
    for q in (-1.0, 0.0, 0.5, 1.0, 2.0):  # single sample: any q, clamped
        assert h.quantile(q) == 3.0
    h.observe(5.0)
    assert h.quantile(0.0) == 3.0
    assert h.quantile(1.0) == 5.0


# --- span events: retries + fault injection --------------------------------


def test_retry_records_span_event(traced):
    calls = []

    def boom():
        calls.append(1)
        if len(calls) < 2:
            raise ValueError("transient")
        return 42

    with telemetry.span("retry.root"):
        out = with_retries(
            boom, what="test-op", retries=2, backoff_ms=0.01,
            sleep=lambda _s: None,
        )
    assert out == 42
    telemetry.flush()

    doc = _load_trace(traced)
    points = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert len(points) == 1
    ev = points[0]
    assert ev["name"] == "retry"
    assert ev["args"]["what"] == "test-op"
    assert ev["args"]["attempt"] == 1
    assert "transient" in ev["args"]["error"]
    root = next(
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "retry.root"
    )
    assert ev["args"]["span_id"] == root["args"]["span_id"]

    logs = [f for f in os.listdir(traced) if f.startswith("events-")]
    with open(os.path.join(traced, logs[0])) as f:
        lines = [json.loads(line) for line in f]
    assert any(
        rec["event"] == "point" and rec["name"] == "retry" for rec in lines
    )


def test_fault_injection_records_event_and_counter(traced, monkeypatch):
    monkeypatch.setenv("TPUML_FAULT_SPEC", "ingest:chunk:0:raise")
    faults.reset_faults()
    try:
        with telemetry.span("faulty.fit"):
            with pytest.raises(faults.InjectedFault):
                faults.fault_site("ingest:chunk")
    finally:
        faults.reset_faults()
    telemetry.flush()

    assert telemetry.counter("fault_injections").value(kind="raise") == 1
    doc = _load_trace(traced)
    ev = next(
        e for e in doc["traceEvents"]
        if e.get("ph") == "i" and e["name"] == "fault_injected"
    )
    assert ev["args"]["site"] == "ingest:chunk"
    assert ev["args"]["action"] == "raise"


def test_add_span_event_noop_untraced(tmp_path, monkeypatch):
    monkeypatch.delenv("TPUML_TRACE", raising=False)
    telemetry.add_span_event("retry", what="x")
    assert telemetry.flush() is None
    assert os.listdir(tmp_path) == []


# --- crash-path flush ------------------------------------------------------


def test_atexit_flush_survives_crash(tmp_path):
    """An unhandled exception mid-run must still leave the trace shard
    AND a metric snapshot on disk (the atexit flush), even though
    write_metrics was never called."""
    prog = (
        "from spark_rapids_ml_tpu.runtime import telemetry\n"
        "with telemetry.span('crash.victim'):\n"
        "    pass\n"
        "telemetry.counter('retries').inc(5)\n"
        "raise RuntimeError('boom')\n"
    )
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", TPUML_TRACE=str(tmp_path))
    r = subprocess.run(
        [sys.executable, "-c", prog], env=env, cwd=REPO_ROOT,
        capture_output=True, text=True,
    )
    assert r.returncode != 0 and "boom" in r.stderr
    names = os.listdir(tmp_path)
    traces = [f for f in names if f.startswith("trace-")]
    metrics = [f for f in names if f.startswith("metrics-") and f.endswith(".json")]
    assert len(traces) == 1 and len(metrics) == 1, names
    with open(os.path.join(tmp_path, metrics[0])) as f:
        snap = json.load(f)
    assert snap["retries"]["series"][0]["value"] == 5


# --- multi-host aggregation ------------------------------------------------


def _sample_snapshots():
    return [
        {
            "retries": {"kind": "counter",
                        "series": [{"labels": {}, "value": 2}]},
            "hbm_budget_bytes": {
                "kind": "gauge",
                "series": [{"labels": {"site": "gang_fit"}, "value": 10.0}],
            },
            "span_seconds": {
                "kind": "histogram",
                "series": [{"labels": {"name": "fit"}, "count": 3,
                            "sum": 1.5, "min": 0.1, "max": 1.0, "p50": 0.4}],
            },
        },
        {
            "retries": {"kind": "counter",
                        "series": [{"labels": {}, "value": 5}]},
            "hbm_budget_bytes": {
                "kind": "gauge",
                "series": [{"labels": {"site": "gang_fit"}, "value": 30.0}],
            },
            "span_seconds": {
                "kind": "histogram",
                "series": [{"labels": {"name": "fit"}, "count": 1,
                            "sum": 2.0, "min": 2.0, "max": 2.0, "p50": 2.0}],
            },
        },
    ]


def test_merge_metric_snapshots_rules():
    merged = telemetry.merge_metric_snapshots(_sample_snapshots())
    assert merged["retries"]["series"][0]["value"] == 7  # counters SUM
    assert merged["hbm_budget_bytes"]["series"][0]["value"] == 30.0  # gauge MAX
    h = merged["span_seconds"]["series"][0]
    assert h["count"] == 4 and h["sum"] == 3.5
    assert h["min"] == 0.1 and h["max"] == 2.0
    assert "p50" not in h  # per-rank ring quantiles cannot merge — dropped


def test_merge_traces_script_parity_and_tracks():
    mt = _load_by_path("merge_traces")
    snaps = _sample_snapshots()
    assert mt.merge_metric_snapshots(snaps) == telemetry.merge_metric_snapshots(
        snaps
    )

    def shard(rank, pid):
        return {
            "traceEvents": [
                {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                 "args": {"name": "spark_rapids_ml_tpu"}},
                {"name": "fit", "ph": "X", "ts": 0.0, "dur": 5.0,
                 "pid": pid, "tid": 1, "args": {"span_id": 1}},
            ],
            "metadata": {"process_index": rank},
        }

    merged = mt.merge_trace_docs([shard(0, 111), shard(1, 222)])
    assert merged["metadata"]["hosts"] == [0, 1]
    tracks = {
        e["pid"]: e["args"]["name"]
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert set(tracks) == {0, 1}
    assert "111" in tracks[0] and "222" in tracks[1]
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in xs} == {0, 1}  # events remapped to rank pids


def test_aggregate_metrics_single_process_degrades_to_local(traced):
    telemetry.counter("retries").inc(3)
    agg = telemetry.aggregate_metrics()
    assert agg == telemetry.merge_metric_snapshots(
        [telemetry.metrics_snapshot()]
    )
    assert agg["retries"]["series"][0]["value"] == 3


# --- a live span installs nothing in jax -----------------------------------


@pytest.fixture(params=["sink", "trace_env"])
def live_events(request, tmp_path, monkeypatch):
    """Make spans live one of the two ways; returns a callable giving
    the completed span events (from the sink, or from the trace file)."""
    monkeypatch.delenv("TPUML_TRACE", raising=False)
    if request.param == "sink":
        events = []
        telemetry.add_span_sink(lambda ev, _thread: events.append(ev))
        return lambda: events
    monkeypatch.setenv("TPUML_TRACE", str(tmp_path))

    def from_trace_file():
        telemetry.flush()
        return _load_trace(tmp_path)["traceEvents"]

    return from_trace_file


def test_live_spans_patch_nothing_in_jax(live_events):
    from jax._src import compiler

    compile_entry = compiler.backend_compile_and_load

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    with telemetry.span("live.victim", rows=16) as sp:
        sp.set_attr(cols=4)
        f(jnp.ones((16, 4), jnp.float32)).block_until_ready()

    assert compiler.backend_compile_and_load is compile_entry
    assert compile_entry.__module__ == "jax._src.compiler"  # jax's own
    (ev,) = [e for e in live_events() if e["name"] == "live.victim"]
    # what the call site set, plus the span's own id: no cost model, no fence
    assert ev["args"] == {
        "rows": 16, "cols": 4, "span_id": ev["args"]["span_id"],
    }
