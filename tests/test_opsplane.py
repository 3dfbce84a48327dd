"""Live operations plane: the defaults-inert contract (no env => no
socket, no thread, no sink, bit-identical fits), live /metrics and
/statusz scrapes mid-streamed-fit, the /readyz warmup flip, flight
recorder ring bounds and the SIGTERM crash dump (``TPUML_TRACE``
unset), the one-shot SLO burn alert on a synthetic p99 spike, and
rank-tagged flight shard merging via ``scripts/merge_traces.py``.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.runtime import opsplane, telemetry
from spark_rapids_ml_tpu.serving import ModelRegistry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_OPS_ENVS = (
    "TPUML_OPS_PORT",
    "TPUML_OPS_HOST",
    "TPUML_FLIGHT_DIR",
    "TPUML_FLIGHT_EVENTS",
    "TPUML_SLO_EVAL_MS",
    "TPUML_SLO_BURN_THRESHOLD",
    "TPUML_TRACE",
)


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    for var in _OPS_ENVS:
        monkeypatch.delenv(var, raising=False)
    opsplane.stop()
    telemetry.reset_telemetry()
    yield
    opsplane.stop()
    telemetry.reset_telemetry()


@pytest.fixture(scope="module")
def linreg_model():
    """A resident that coalesces on the CPU backend too: the registry's
    pad-invariance probe passes for it there (PCA's k=3 projection does
    not, and would serve exact shapes with nothing to warm)."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(96, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6)).astype(np.float32)
    return LinearRegression().fit(DataFrame({"features": X, "label": y}))


def _get(path):
    """(status, content-type, body) from the running ops server —
    HTTPError carries the 4xx/5xx bodies the endpoints serve."""
    host, port = opsplane.address()
    url = f"http://{host}:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.headers.get("Content-Type", ""), r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), e.read()


def _ops_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith(("tpuml-ops", "tpuml-slo"))
    ]


def _flight_shards(d):
    return sorted(f for f in os.listdir(d) if f.startswith("flight-"))


def _load_by_path(name):
    spec = importlib.util.spec_from_file_location(
        f"_test_ops_{name}", os.path.join(REPO_ROOT, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- defaults inert --------------------------------------------------------


def test_defaults_inert_no_socket_no_thread_no_sink():
    """With neither TPUML_OPS_PORT nor TPUML_FLIGHT_DIR set the plane
    refuses to start: no listening socket, no background thread, no
    span sink (spans stay the shared disabled singleton)."""
    assert opsplane.ensure_started() is False
    assert not opsplane.started()
    assert opsplane.address() is None
    assert opsplane.flight_recorder() is None
    assert _ops_threads() == []
    # no sink attached: the disabled span singleton still short-circuits
    assert telemetry.span("a") is telemetry.span("b", k=1)
    assert telemetry.active_spans() == []


def test_ops_enabled_fit_bit_identical(monkeypatch):
    """A fit under a live ops plane (server + flight sink running) is
    bit-identical to the plain fit — observation must not perturb."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    df = DataFrame({"features": X})

    def centers():
        m = KMeans(k=3, maxIter=4, seed=0).setFeaturesCol("features").fit(df)
        return m.cluster_centers_

    plain = centers()
    monkeypatch.setenv("TPUML_OPS_PORT", "0")
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()
    observed = centers()
    assert plain.tobytes() == observed.tobytes()
    # the sink really saw the fit: the flight ring is non-empty
    assert len(opsplane.flight_recorder()) > 0


# --- endpoints -------------------------------------------------------------


def test_endpoint_shapes_and_routes(monkeypatch):
    monkeypatch.setenv("TPUML_OPS_PORT", "0")  # ephemeral port
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()
    assert opsplane.ensure_started()  # idempotent
    host, port = opsplane.address()
    assert host == "127.0.0.1" and port > 0

    with telemetry.span("probe"):
        pass

    code, ctype, body = _get("/healthz")
    assert code == 200 and json.loads(body) == {"status": "ok"}

    code, ctype, body = _get("/metrics")
    assert code == 200
    assert ctype.startswith("text/plain")
    lines = body.decode().splitlines()
    assert any(line.startswith("# TYPE tpuml_") for line in lines)
    for line in lines:
        if line and not line.startswith("#"):
            assert line.startswith("tpuml_"), line

    code, _, body = _get("/flight")
    assert code == 200
    doc = json.loads(body)
    assert doc["metadata"]["flight"] is True
    assert "probe" in {
        e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"
    }

    code, _, body = _get("/nope")
    assert code == 404
    assert "/statusz" in json.loads(body)["routes"]

    # the scrapes themselves were metered
    reqs = telemetry.counter("ops_requests_total")
    assert reqs.value(endpoint="metrics") == 1
    assert reqs.value(endpoint="other") == 1


def test_statusz_reports_active_span_tree(monkeypatch):
    monkeypatch.setenv("TPUML_OPS_PORT", "0")
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()
    with telemetry.span("outer", phase="x"):
        with telemetry.span("inner"):
            code, _, body = _get("/statusz")
    assert code == 200
    st = json.loads(body)
    assert st["pid"] == os.getpid()
    spans = {s["name"]: s for s in st["active_spans"]}
    assert {"outer", "inner"} <= set(spans)
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["outer"]["age_seconds"] >= spans["inner"]["age_seconds"]
    assert st["flight"]["capacity"] > 0


# --- live scrape during a streamed fit -------------------------------------


def test_live_scrape_during_streamed_kmeans_fit(monkeypatch):
    """The satellite contract: a streamed fit auto-starts the plane and
    answers /metrics + /statusz scrapes while chunks are still folding.
    The scrape fires from a span sink on the first completed
    `stream.fold`, so it provably lands mid-fit."""
    monkeypatch.setenv("TPUML_OPS_PORT", "0")
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    rng = np.random.default_rng(0)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    df = DataFrame({"features": X})

    scrapes = []

    def scrape_on_fold(ev, thread_name):
        if ev.get("name") == "stream.fold" and not scrapes:
            scrapes.append((_get("/metrics"), _get("/statusz")))

    telemetry.add_span_sink(scrape_on_fold)
    try:
        KMeans(
            k=3, maxIter=2, seed=0, num_workers=2,
            streaming=True, stream_chunk_rows=64,
        ).setFeaturesCol("features").fit(df)
    finally:
        telemetry.remove_span_sink(scrape_on_fold)

    assert opsplane.started()  # iter_device_chunks brought the plane up
    assert scrapes, "no stream.fold span completed during the fit"
    (mcode, mctype, mbody), (scode, _sctype, sbody) = scrapes[0]
    assert mcode == 200 and mctype.startswith("text/plain")
    assert any(
        line.startswith("# TYPE tpuml_")
        for line in mbody.decode().splitlines()
    )
    assert scode == 200
    st = json.loads(sbody)
    # the ingest loop had already filed its heartbeat when we scraped
    assert "stream_ingest" in st["heartbeat_ages_s"]
    assert st["heartbeat_ages_s"]["stream_ingest"] >= 0.0
    # the fit was mid-flight: its ingest span was live in the tree
    assert "stream.ingest" in {s["name"] for s in st["active_spans"]}
    # observation did not destabilize the fit
    storms = telemetry.counter("retrace_storms").value()
    assert not storms


# --- readiness -------------------------------------------------------------


def test_readyz_flips_on_registry_warmup(monkeypatch, linreg_model):
    monkeypatch.setenv("TPUML_OPS_PORT", "0")
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()

    # nothing tracked: liveness + storm check only
    code, _, body = _get("/readyz")
    assert code == 200 and json.loads(body)["ready"]

    reg = ModelRegistry(warmup=False)
    entry = reg.register("linreg", linreg_model)
    assert entry.coalesce  # premise: linreg coalesces on this backend

    code, _, body = _get("/readyz")
    assert code == 503
    payload = json.loads(body)
    assert not payload["ready"]
    assert any("warmup_pending" in r for r in payload["reasons"])
    code, _, body = _get("/statusz")
    st = json.loads(body)
    assert st["ready"] is False
    assert st["registries"][0]["models"]["linreg"]["pending_buckets"]

    reg.warm(entry)
    code, _, body = _get("/readyz")
    assert code == 200 and json.loads(body)["ready"]
    code, _, body = _get("/statusz")
    assert json.loads(body)["ready"] is True


# --- flight recorder -------------------------------------------------------


def test_flight_ring_bounded_and_deterministic():
    rec = opsplane.FlightRecorder(4)
    for i in range(100):
        rec.sink(
            {"name": f"e{i}", "ph": "X", "pid": 1, "tid": 7,
             "ts": i, "dur": 1, "args": {}},
            "worker",
        )
    assert len(rec) == 4 and rec.capacity == 4
    doc = rec.document("test")
    xs = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert xs == ["e96", "e97", "e98", "e99"]  # deterministic last-N
    threads = {
        e["tid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert threads == {7: "worker"}
    assert doc["metadata"]["reason"] == "test"
    # no directory configured: dump declines rather than guessing
    assert rec.dump("test") is None


def test_flight_ring_capacity_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUML_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("TPUML_FLIGHT_EVENTS", "8")
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()
    assert opsplane.address() is None  # flight-only: no HTTP server
    for i in range(50):
        with telemetry.span(f"s{i}"):
            pass
    rec = opsplane.flight_recorder()
    assert rec.capacity == 8 and len(rec) == 8
    path = rec.dump("manual")
    assert os.path.basename(path) == f"flight-r00-{os.getpid()}.json"
    with open(path) as f:
        doc = json.load(f)
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert names == [f"s{i}" for i in range(42, 50)]
    assert telemetry.counter("flight_dumps_total").value(reason="manual") == 1


def test_sigterm_crash_dump_without_tracing(tmp_path):
    """A killed run with TPUML_TRACE unset still yields a loadable
    flight shard: the SIGTERM handler dumps the ring, then chains to
    the default disposition so the exit status stays conventional."""
    child = (
        "import os, time\n"
        "from spark_rapids_ml_tpu.runtime import opsplane, telemetry\n"
        "assert os.environ.get('TPUML_TRACE') is None\n"
        "assert opsplane.ensure_started()\n"
        "with telemetry.span('prelude'):\n"
        "    with telemetry.span('work'):\n"
        "        pass\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    env = dict(os.environ)
    for var in _OPS_ENVS:
        env.pop(var, None)
    env.update(
        TPUML_FLIGHT_DIR=str(tmp_path),
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO_ROOT,
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", child],
        cwd=REPO_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    try:
        line = proc.stdout.readline()
        assert "READY" in line, line
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        proc.kill()
        proc.stdout.close()
    assert rc == -signal.SIGTERM  # chained default disposition

    shards = _flight_shards(tmp_path)
    assert len(shards) == 1, shards
    with open(os.path.join(tmp_path, shards[0])) as f:
        doc = json.load(f)
    assert doc["metadata"]["flight"] is True
    assert doc["metadata"]["reason"] == "signal"
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"prelude", "work"} <= names


# --- SLO burn --------------------------------------------------------------


def test_slo_burn_alert_on_p99_spike(tmp_path, monkeypatch):
    """A synthetic serving p99 spike: both burn windows cross the
    threshold after two violating ticks, the alert counter increments
    once per episode, and the flight dump is one-shot per process."""
    monkeypatch.setenv("TPUML_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")  # keep cadence quiet
    assert opsplane.ensure_started()
    ev = opsplane._EVALUATOR

    for _ in range(8):
        telemetry.histogram("serve_p99_ms").observe(900.0, model="m")

    st = ev.tick(now=1000.0)
    assert not st["serving_p99_ms"]["alerting"]  # one tick never alerts
    st = ev.tick(now=1001.0)
    assert st["serving_p99_ms"]["alerting"]
    assert st["serving_p99_ms"]["burn_short"] >= 1.0
    alerts = telemetry.counter("slo_burn_alerts")
    assert alerts.value(slo="serving_p99_ms") == 1
    assert _flight_shards(tmp_path) == [
        f"flight-r00-{os.getpid()}.json"
    ]
    rec = opsplane.flight_recorder()
    assert rec.dumps == {"slo_burn": 1}

    # still burning: no re-alert, no second dump
    ev.tick(now=1002.0)
    assert alerts.value(slo="serving_p99_ms") == 1
    assert rec.dumps == {"slo_burn": 1}
    assert opsplane.slo_status()["serving_p99_ms"]["alerting"]

    # recovery: flood the ring with in-objective samples, age the
    # violating ticks out of both windows
    for _ in range(4096):
        telemetry.histogram("serve_p99_ms").observe(1.0, model="m")
    st = ev.tick(now=10_000.0)
    assert not st["serving_p99_ms"]["alerting"]

    # a second burn episode re-alerts — but the dump stays one-shot
    for _ in range(4096):
        telemetry.histogram("serve_p99_ms").observe(900.0, model="m")
    ev.tick(now=10_001.0)
    st = ev.tick(now=10_002.0)
    assert st["serving_p99_ms"]["alerting"]
    assert alerts.value(slo="serving_p99_ms") == 2
    assert rec.dumps == {"slo_burn": 1}
    assert _flight_shards(tmp_path) == [
        f"flight-r00-{os.getpid()}.json"
    ]


def test_slo_window_measures_need_two_snapshots(tmp_path, monkeypatch):
    """window_delta SLOs measure increments between ticks: a
    retrace-storm counter bump alerts on the next two ticks, and an
    idle counter never measures at all."""
    monkeypatch.setenv("TPUML_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("TPUML_SLO_EVAL_MS", "60000")
    assert opsplane.ensure_started()
    ev = opsplane._EVALUATOR

    st = ev.tick(now=1.0)  # baseline snapshot: nothing measured yet
    assert st["fit_retrace_storms"]["last_value"] is None
    telemetry.counter("retrace_storms").inc()
    st = ev.tick(now=2.0)
    assert st["fit_retrace_storms"]["last_value"] == 1.0
    assert not st["fit_retrace_storms"]["alerting"]  # single tick
    telemetry.counter("retrace_storms").inc()
    st = ev.tick(now=3.0)
    assert st["fit_retrace_storms"]["alerting"]
    # fault_injections never moved: no ticks, no alert
    assert st["fit_fault_injections"]["last_value"] is None
    assert not st["fit_fault_injections"]["alerting"]


# --- shard merging ---------------------------------------------------------


def test_flight_shards_merge_rank_tagged(tmp_path, monkeypatch):
    """Two ranks' flight dumps merge like trace shards: per-host track
    groups keyed by process_index, flight metadata preserved."""
    monkeypatch.setenv("TPUML_FLIGHT_DIR", str(tmp_path))
    pid = os.getpid()
    for rank in (0, 1):
        monkeypatch.setenv("TPUML_PROC_ID", str(rank))
        rec = opsplane.FlightRecorder(16)
        rec.sink(
            {"name": f"work.r{rank}", "ph": "X", "pid": pid, "tid": 1,
             "ts": 0, "dur": 5, "args": {}},
            "MainThread",
        )
        path = rec.dump("test")
        assert os.path.basename(path) == f"flight-r{rank:02d}-{pid}.json"
    monkeypatch.delenv("TPUML_PROC_ID")

    mt = _load_by_path("merge_traces")
    assert mt.main([str(tmp_path)]) == 0
    with open(os.path.join(tmp_path, "merged-flight.json")) as f:
        merged = json.load(f)
    assert merged["metadata"]["flight"] is True
    assert merged["metadata"]["hosts"] == [0, 1]
    pnames = {
        e["args"]["name"]
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert pnames == {f"host0 (pid {pid})", f"host1 (pid {pid})"}
    xs = {
        e["name"]: e["pid"]
        for e in merged["traceEvents"]
        if e.get("ph") == "X"
    }
    assert xs == {"work.r0": 0, "work.r1": 1}
