"""Roofline attribution (runtime/roofline.py), multi-host aggregation,
the bench-regression gate, and the crash-path flush: span attrs sourced
from XLA cost_analysis (never hand formulas), clean absence on
cost-model fallback, peak-spec env overrides, merge parity between
scripts/merge_traces.py and telemetry.merge_metric_snapshots, and the
gate rules over real-shaped BENCH trajectories."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.runtime import faults, roofline, telemetry
from spark_rapids_ml_tpu.runtime.retry import with_retries

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset_telemetry()
    yield
    telemetry.reset_telemetry()


@pytest.fixture
def traced(tmp_path, monkeypatch):
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


# --- cost-analysis attribution ---------------------------------------------


def test_span_attrs_from_cost_analysis(traced):
    """A fresh jit inside a span must annotate the span with the XLA
    cost model's FLOPs/bytes — checked against cost_analysis() of an
    identical program, not a hand formula."""
    x = jnp.ones((64, 128), jnp.float32)

    with telemetry.span("roof.fit"):
        # deliberate in-span compile: the attribution moment under test
        # tpuml: ignore[TPU003]
        r = jax.jit(lambda a: (a @ a.T).sum())(x)
        r.block_until_ready()
    telemetry.flush()

    expected = jax.jit(lambda a: (a @ a.T).sum()).lower(x).compile()
    ca = expected.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    if not ca or not ca.get("flops", 0) > 0:
        pytest.skip("backend reports no cost analysis")

    doc = _load_trace(traced)
    ev = next(
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "roof.fit"
    )
    assert ev["args"]["flops_total"] == pytest.approx(ca["flops"])
    assert ev["args"]["bytes_total"] >= 0
    assert ev["args"]["cost_programs"] >= 1
    assert 0 < ev["args"]["mfu"]
    assert ev["args"]["bound"] in ("compute", "memory")

    stats = telemetry.span_stats()["roof.fit"]
    assert stats["flops_total"] == pytest.approx(ca["flops"])
    assert stats["mfu"] > 0

    snap = telemetry.metrics_snapshot()
    flops_series = snap["span_flops_total"]["series"]
    assert any(
        s["labels"].get("name") == "roof.fit" and s["value"] > 0
        for s in flops_series
    )


def test_fallback_attrs_cleanly_absent(traced, monkeypatch):
    """When the backend reports no usable cost analysis, roofline attrs
    must be absent — never 0.0 or NaN MFU."""
    monkeypatch.setattr(roofline, "_extract_cost", lambda _ex: None)
    with telemetry.span("roof.nocost"):
        # deliberate in-span compile: the fallback path under test
        # tpuml: ignore[TPU003]
        jax.jit(lambda a: a + 1.0)(jnp.ones((3,))).block_until_ready()
    telemetry.flush()

    doc = _load_trace(traced)
    ev = next(
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "roof.nocost"
    )
    assert "flops_total" not in ev["args"]
    assert "mfu" not in ev["args"]
    stats = telemetry.span_stats()["roof.nocost"]
    assert "mfu" not in stats and "flops_total" not in stats
    assert "span_mfu" not in telemetry.metrics_snapshot()


def test_extract_cost_rejects_unknown():
    class _Exec:
        def __init__(self, ca):
            self._ca = ca

        def cost_analysis(self):
            return self._ca

    assert roofline._extract_cost(_Exec({"flops": -1.0})) is None  # XLA unknown
    assert roofline._extract_cost(_Exec({"bytes accessed": 5.0})) is None
    assert roofline._extract_cost(_Exec(None)) is None
    assert roofline._extract_cost(_Exec({"flops": 8.0})) == (8.0, 0.0)
    assert roofline._extract_cost(
        _Exec([{"flops": 4.0, "bytes accessed": 2.0}])
    ) == (4.0, 2.0)


def test_annotate_without_cost_is_empty():
    assert roofline.annotate("never.attributed", 0.0, 0.5) == {}


def test_peak_flops_override_scales_mfu(monkeypatch):
    n_dev = len(jax.devices())

    def _attributed_mfu(peak):
        telemetry.reset_telemetry()  # clears site costs + peak cache
        monkeypatch.setenv("TPUML_PEAK_FLOPS", str(peak))
        monkeypatch.setenv("TPUML_PEAK_HBM_GBPS", "100")
        roofline._TLS.pending = [(2e9, 1e9)]
        roofline._consume_pending("ovr.site")
        return roofline.annotate("ovr.site", 1.0, 1.0)

    attrs = _attributed_mfu(1e12)
    assert attrs["mfu"] == pytest.approx(2e9 / (1e12 * n_dev), rel=1e-3)
    attrs2 = _attributed_mfu(2e12)
    assert attrs2["mfu"] == pytest.approx(attrs["mfu"] / 2, rel=1e-3)
    # bytes: 1e9 B in 1 s = 1 GB/s against a 100 GB/s peak -> memory frac
    # 0.01 vs mfu 0.001: the verdict flips with the flops peak
    assert attrs2["achieved_gbps"] == pytest.approx(1.0, rel=1e-3)
    assert attrs2["bound"] == "memory"


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


# --- bench-regression gate -------------------------------------------------


def _entry(seconds, vs, mfu, **kw):
    d = {
        "samples_per_sec_per_chip": 1e6, "fit_seconds": seconds,
        "vs_baseline": vs, "mfu": mfu,
    }
    d.update(kw)
    return d


def test_bench_regress_rules():
    br = _load_by_path("bench_regress")
    base = {
        "pca": _entry(1.0, 2.0, 0.2),
        "hostonly": _entry(10.0, 1.0, 0.1, host_only=True),
        "nomfu": _entry(1.0, 1.0, 0.0),
        "dropped": _entry(1.0, 1.0, 0.1),
    }
    # within noise everywhere: pass
    cur_ok = {
        "pca": _entry(1.1, 1.9, 0.19),
        "hostonly": _entry(20.0, 1.0, 0.1, host_only=True),
        "nomfu": _entry(1.05, 1.05, 0.0),
        "new": _entry(9.0, 0.5, 0.0),
    }
    rows, failed = br.compare(base, cur_ok, 0.15)
    assert not failed
    status = {(n, f): s for n, f, _b, _c, _d, s in rows}
    assert status[("hostonly", "fit_seconds")] == "skip:host-only"
    assert status[("nomfu", "mfu")] == "skip:zero-baseline"
    assert status[("new", "-")] == "skip:new-entry"
    assert status[("dropped", "-")] == "skip:entry-dropped"

    # each gated field regressing alone must fail
    for bad in (
        {"pca": _entry(1.2, 2.0, 0.2)},      # seconds +20%
        {"pca": _entry(1.0, 1.6, 0.2)},      # vs_baseline -20%
        {"pca": _entry(1.0, 2.0, 0.15)},     # mfu -25%
    ):
        rows, failed = br.compare({"pca": base["pca"]}, bad, 0.15)
        assert failed, rows
    # improvements never fail
    rows, failed = br.compare(
        {"pca": base["pca"]}, {"pca": _entry(0.5, 4.0, 0.4)}, 0.15
    )
    assert not failed


def test_bench_regress_serving_p99_gate(tmp_path):
    """Serving tail latency gates: p99_ms growth past threshold fails,
    and the serving entry's nested sweep dicts survive the tail parse
    (the flat-brace fallback scan cannot see entries with sub-objects)."""
    br = _load_by_path("bench_regress")
    serving = _entry(
        1.0, 3.0, 0.0, p99_ms=10.0,
        qps_sweep={"64": {"p50_ms": 4.0, "p99_ms": 12.0}},
    )
    rows, failed = br.compare(
        {"serving": serving},
        {"serving": _entry(1.0, 3.0, 0.0, p99_ms=11.0)},
        0.15,
    )
    assert not failed, rows
    rows, failed = br.compare(
        {"serving": serving},
        {"serving": _entry(1.0, 3.0, 0.0, p99_ms=20.0)},
        0.15,
    )
    assert failed, rows
    raw = {"metric": "serving_fit_throughput", "serving": serving}
    w = tmp_path / "BENCH_r09.json"
    w.write_text(json.dumps(
        {"n": 9, "rc": 0, "tail": "noise before\n" + json.dumps(raw)}
    ))
    assert br.parse_bench_file(str(w)) == {"serving": serving}


def test_bench_regress_tuned_vs_default_gate():
    """The autotuner ratio gates two ways: trajectory (shrink past
    -threshold vs the prior run) and an absolute floor at 1.0-threshold
    that bites even on new and host_only entries — the ratio is
    measured back-to-back in one run, so the machine cancels out and
    'no prior run' is no excuse for losing to the default."""
    br = _load_by_path("bench_regress")
    good = _entry(1.0, 1.1, 0.0, tuned_vs_default=1.2)
    # steady ratio: pass
    rows, failed = br.compare({"autotune": good}, {"autotune": good}, 0.15)
    assert not failed, rows
    # default-wins run (exactly 1.0) clears the floor with room
    rows, failed = br.compare(
        {}, {"autotune": _entry(1.0, 1.0, 0.0, tuned_vs_default=1.0)}, 0.15
    )
    assert not failed, rows
    # trajectory collapse: 1.2 -> 0.95 is -21%, past the 15% threshold
    rows, failed = br.compare(
        {"autotune": good},
        {"autotune": _entry(1.0, 1.1, 0.0, tuned_vs_default=0.95)},
        0.15,
    )
    assert failed, rows
    # absolute floor fires with NO prior entry at all...
    rows, failed = br.compare(
        {}, {"autotune": _entry(1.0, 1.0, 0.0, tuned_vs_default=0.7)}, 0.15
    )
    assert failed, rows
    # ...and host_only does not shelter it (same-run ratio)
    rows, failed = br.compare(
        {"autotune": good},
        {"autotune": _entry(
            1.0, 1.1, 0.0, tuned_vs_default=0.7, host_only=True
        )},
        0.15,
    )
    assert failed, rows
    assert any("tuned_vs_default>=floor" in r[1] for r in rows)
    # just above the floor, trajectory skipped by host_only: pass
    rows, failed = br.compare(
        {"autotune": good},
        {"autotune": _entry(
            1.0, 1.1, 0.0, tuned_vs_default=0.9, host_only=True
        )},
        0.15,
    )
    assert not failed, rows


def test_bench_regress_parses_wrapper_and_raw(tmp_path):
    br = _load_by_path("bench_regress")
    raw = {
        "metric": "pca_fit_throughput", "value": 1.0,
        "pca": _entry(1.0, 2.0, 0.2),
    }
    wrapper = {
        "n": 7, "cmd": "python bench.py", "rc": 0,
        "tail": "noise before\n" + json.dumps(raw)[5:],  # truncated head
        "parsed": None,
    }
    wpath = tmp_path / "BENCH_r07.json"
    wpath.write_text(json.dumps(wrapper))
    assert br.parse_bench_file(str(wpath)) == {"pca": raw["pca"]}
    rpath = tmp_path / "current.json"
    rpath.write_text(json.dumps(raw))
    assert br.parse_bench_file(str(rpath)) == {"pca": raw["pca"]}
    # whole-CLI smoke: r07 vs a 2x-slower r08 must exit 1 naming pca
    slow = dict(wrapper, tail=json.dumps(
        {"pca": _entry(2.0, 2.0, 0.2)}
    ))
    (tmp_path / "BENCH_r08.json").write_text(json.dumps(slow))
    rc = br.main(["--trajectory", str(tmp_path / "BENCH_r*.json")])
    assert rc == 1


# --- defaults-inert --------------------------------------------------------


def test_roofline_inert_when_untraced(tmp_path, monkeypatch):
    for var in ("TPUML_TRACE", "TPUML_PEAK_FLOPS", "TPUML_PEAK_HBM_GBPS"):
        monkeypatch.delenv(var, raising=False)
    with telemetry.span("quiet"):
        # deliberate fresh compile: inertness must hold even around one
        # tpuml: ignore[TPU003]
        jax.jit(lambda a: a * 3.0)(jnp.ones((4,))).block_until_ready()
    assert telemetry.span_stats() == {}
    snap = telemetry.metrics_snapshot()
    assert "span_flops_total" not in snap and "span_mfu" not in snap
    assert os.listdir(tmp_path) == []
