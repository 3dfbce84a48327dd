"""Live operations plane: in-process scrape/health endpoints, an
always-on flight recorder, and SLO burn-rate evaluation.

PRs 9-10 made runs self-describing *after the fact* — files written at
exit under ``TPUML_TRACE``. This module answers the questions an
operator has while the process is still running:

- **HTTP endpoints** (``TPUML_OPS_PORT``; stdlib ``http.server`` on a
  daemon thread, bound to ``TPUML_OPS_HOST``):

  - ``/metrics``  — live Prometheus text from the typed registry
    (:func:`telemetry.prometheus_dump`, the same formatter
    ``write_metrics`` uses for the exit-time ``.prom`` shard).
  - ``/healthz``  — plain liveness (the process can answer).
  - ``/readyz``   — 200 only when every tracked
    :class:`serving.ModelRegistry` has its coalescable residents fully
    ladder-warmed AND ``retrace_storms == 0``; 503 with JSON reasons
    otherwise — the admission signal ROADMAP's elastic-scheduler item
    needs.
  - ``/statusz``  — JSON: active span tree with wall-clock ages,
    registry residency vs the ``hbm_*`` gauges, serve queue depth and
    batch fill, gang/ingest-ring occupancy, heartbeat ages for the
    long-running loops, and the SLO burn table.
  - ``/flight``   — the flight recorder's current ring as a
    Perfetto-loadable JSON document, served from memory.

- **Flight recorder** — a deterministic last-``TPUML_FLIGHT_EVENTS``
  ring of completed spans and instant events, fed by a
  :func:`telemetry.add_span_sink` hook, kept in memory even when
  ``TPUML_TRACE`` is unset. Dumped as a rank-tagged shard
  (``flight-r00-<pid>.json``, merged by ``scripts/merge_traces.py``)
  into ``TPUML_FLIGHT_DIR`` (falling back to the ``TPUML_TRACE`` dir)
  on SIGTERM, at interpreter exit, and once — ever — on the first SLO
  burn alert, so postmortems no longer require pre-enabled tracing.

- **SLO evaluation** — the declared catalog in :mod:`runtime.slo`,
  measured from periodic :func:`telemetry.metrics_snapshot` ticks every
  ``TPUML_SLO_EVAL_MS``; an alert fires when both burn windows cross
  ``TPUML_SLO_BURN_THRESHOLD``, incrementing ``slo_burn_alerts`` and
  triggering the one-shot flight dump.

Defaults are inert: with neither ``TPUML_OPS_PORT`` nor
``TPUML_FLIGHT_DIR`` set, :func:`ensure_started` returns False without
binding a socket, spawning a thread, attaching a sink, or touching
signal handlers (``tests/test_opsplane.py`` asserts all four).
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import signal
import threading
import time
import weakref
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from . import envspec, lockwitness, slo, telemetry

_LOGGER = logging.getLogger("spark_rapids_ml_tpu")

__all__ = [
    "ensure_started",
    "started",
    "stop",
    "address",
    "track_registry",
    "track_runtime",
    "track_router",
    "track_lifecycle",
    "flight_recorder",
    "slo_status",
    "FlightRecorder",
]


_LOCK = lockwitness.make_rlock("opsplane.plane")
_STARTED = False
_RECORDER: Optional["FlightRecorder"] = None
_SERVER: Optional[ThreadingHTTPServer] = None
_SERVER_THREAD: Optional[threading.Thread] = None
_EVALUATOR: Optional["_SloEvaluator"] = None
_ADDR: Optional[Tuple[str, int]] = None
_PREV_SIGTERM: Any = None
_SIGTERM_INSTALLED = False
# weakrefs so tracking never extends a registry/runtime lifetime
_REGISTRIES: List["weakref.ref[Any]"] = []
_RUNTIMES: List["weakref.ref[Any]"] = []
_SCHEDULERS: List["weakref.ref[Any]"] = []
_ROUTERS: List["weakref.ref[Any]"] = []
_LIFECYCLES: List["weakref.ref[Any]"] = []


def _active() -> bool:
    """The opt-in gate: any ops/flight env present."""
    return (
        envspec.get("TPUML_OPS_PORT") is not None
        or envspec.get("TPUML_FLIGHT_DIR") is not None
    )


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------


class FlightRecorder:
    """Bounded last-N ring of completed span/instant events.

    Attached as a telemetry span sink, so it sees every event a trace
    file would — but holds only the newest ``max_events`` in memory
    (deterministic FIFO, no sampling) and writes nothing until asked.
    """

    def __init__(self, max_events: int) -> None:
        self._lock = lockwitness.make_lock("opsplane.flight")
        self._events: Deque[Dict[str, Any]] = deque(maxlen=int(max_events))
        self._threads: Dict[int, str] = {}
        self.dumps: Dict[str, int] = {}

    def sink(self, ev: Dict[str, Any], thread_name: str) -> None:
        with self._lock:
            self._events.append(ev)
            tid = ev.get("tid")
            if tid is not None:
                self._threads.setdefault(tid, thread_name)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def capacity(self) -> int:
        return int(self._events.maxlen or 0)

    def document(self, reason: str) -> Dict[str, Any]:
        """The ring as a Perfetto/Chrome-trace JSON document, tagged
        like a trace shard (``process_index`` metadata plus
        ``flight: true`` and the dump trigger)."""
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
        pid = os.getpid()
        meta: List[Dict[str, Any]] = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": pid,
                "tid": 0,
                "args": {"name": "spark_rapids_ml_tpu"},
            }
        ]
        for tid, tname in sorted(threads.items()):
            meta.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "metadata": {
                "process_index": telemetry._process_index(),
                "flight": True,
                "reason": reason,
            },
        }

    def dump(self, reason: str) -> Optional[str]:
        """Write the ring as ``flight-r<rank>-<pid>.json`` into
        ``TPUML_FLIGHT_DIR`` (or the ``TPUML_TRACE`` dir). Atomic
        (tmp + replace) because the crash paths call this mid-flight.
        Returns the path, or None when no directory is configured."""
        out_dir = envspec.get("TPUML_FLIGHT_DIR") or envspec.get(
            "TPUML_TRACE"
        )
        if not out_dir:
            return None
        doc = self.document(reason)
        os.makedirs(out_dir, exist_ok=True)
        tag = f"r{telemetry._process_index():02d}-{os.getpid()}"
        path = os.path.join(out_dir, f"flight-{tag}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        with self._lock:
            self.dumps[reason] = self.dumps.get(reason, 0) + 1
        telemetry.counter("flight_dumps_total").inc(reason=reason)
        return path


# --------------------------------------------------------------------------
# SLO evaluator
# --------------------------------------------------------------------------


class _SloEvaluator(threading.Thread):
    """Ticks :func:`telemetry.metrics_snapshot` every
    ``TPUML_SLO_EVAL_MS``, scores each cataloged SLO's burn rate, and
    fires the one-shot flight dump on the first alert."""

    # bound the per-SLO tick history: at the 10 ms floor this still
    # covers the default 300 s long window
    MAX_TICKS = 65536

    def __init__(
        self,
        recorder: FlightRecorder,
        period_s: float,
        threshold: float,
    ) -> None:
        super().__init__(name="tpuml-slo-eval", daemon=True)
        self._recorder = recorder
        self._period = float(period_s)
        self._threshold = float(threshold)
        self._halt = threading.Event()
        self._state_lock = lockwitness.make_lock("opsplane.slo")
        self._prev: Optional[Dict[str, Any]] = None
        self._ticks: Dict[str, Deque[Tuple[float, bool]]] = {
            s.name: deque(maxlen=self.MAX_TICKS) for s in slo.CATALOG
        }
        self._alerted: set = set()
        self._burn_dumped = False
        self._state: Dict[str, Any] = {}

    def run(self) -> None:
        while not self._halt.wait(self._period):
            try:
                self.tick()
            except Exception:  # evaluation must never kill the thread
                _LOGGER.exception("ops: SLO evaluation tick failed")

    def halt(self) -> None:
        self._halt.set()

    def tick(self, now: Optional[float] = None) -> Dict[str, Any]:
        """One evaluation pass (public so tests can drive it without
        the thread's cadence)."""
        if now is None:
            now = time.monotonic()
        # fleet-merged when a tracked router has out-of-process
        # replicas; exactly the local snapshot otherwise — so the SLO
        # table answers for the fleet, not the process
        snap = _fleet_snapshot()
        state: Dict[str, Any] = {}
        for spec in slo.CATALOG:
            value = slo.measured_value(spec, snap, self._prev)
            ticks = self._ticks[spec.name]
            if value is not None:
                ticks.append((now, slo.violates(spec, value)))
            st = slo.evaluate(spec, list(ticks), now, self._threshold)
            st["last_value"] = value
            if st["alerting"]:
                if spec.name not in self._alerted:
                    self._alerted.add(spec.name)
                    telemetry.counter("slo_burn_alerts").inc(slo=spec.name)
                    _LOGGER.warning(
                        "ops: SLO %s burning (short=%.2f long=%.2f, "
                        "objective %s %s)",
                        spec.name, st["burn_short"], st["burn_long"],
                        spec.sense, spec.objective,
                    )
                    if not self._burn_dumped:
                        # the one-shot contract: exactly one slo_burn
                        # flight dump per process, whichever SLO burns
                        # first
                        self._burn_dumped = True
                        try:
                            self._recorder.dump("slo_burn")
                        except Exception:
                            _LOGGER.exception("ops: burn dump failed")
            else:
                self._alerted.discard(spec.name)
            state[spec.name] = st
        self._prev = snap
        with self._state_lock:
            self._state = state
        return state

    def status(self) -> Dict[str, Any]:
        with self._state_lock:
            return dict(self._state)


def slo_status() -> Dict[str, Any]:
    """The latest per-SLO burn table (empty before the first tick or
    while the plane is down)."""
    ev = _EVALUATOR
    return ev.status() if ev is not None else {}


# --------------------------------------------------------------------------
# tracked subsystems
# --------------------------------------------------------------------------


def track_registry(registry: Any) -> None:
    """Weakly track a ModelRegistry for readiness/status introspection.
    Pure bookkeeping: never starts the plane, never keeps the registry
    alive."""
    with _LOCK:
        _prune(_REGISTRIES)
        _REGISTRIES.append(weakref.ref(registry))


def track_runtime(runtime: Any) -> None:
    """Weakly track a ServingRuntime for live queue-depth reporting."""
    with _LOCK:
        _prune(_RUNTIMES)
        _RUNTIMES.append(weakref.ref(runtime))


def track_scheduler(scheduler: Any) -> None:
    """Weakly track a FitScheduler (same contract as track_runtime):
    /statusz reads its stats(), /readyz gates on its loop heartbeat,
    and the SIGTERM handler drains it before the flight dump."""
    with _LOCK:
        _prune(_SCHEDULERS)
        _SCHEDULERS.append(weakref.ref(scheduler))


def track_router(router: Any) -> None:
    """Weakly track a serving Router: /statusz gains the fleet roll-up
    section, /readyz gates on the fleet having a routable replica, the
    SLO evaluator scores fleet-merged snapshots, and the SIGTERM
    handler drains the whole fleet before the flight dump."""
    with _LOCK:
        _prune(_ROUTERS)
        _ROUTERS.append(weakref.ref(router))


def track_lifecycle(lifecycle: Any) -> None:
    """Weakly track a ModelLifecycle: /statusz gains the lifecycle
    section (canaries, drift, version breakers, refreshers), /readyz
    reports 503 with a ``swap_in_progress`` reason while a hot-swap's
    warmup is incomplete, and the SIGTERM handler drains lifecycles
    FIRST — refresh drivers halt and canaries roll back before the
    router/runtime/scheduler drains, so no half-evaluated candidate
    can promote into a dying process."""
    with _LOCK:
        _prune(_LIFECYCLES)
        _LIFECYCLES.append(weakref.ref(lifecycle))


def _fleet_snapshot() -> Dict[str, Any]:
    """The snapshot SLO evaluation and /statusz quantile tables read:
    the local process's metrics, merged (reservoirs pooled) with every
    out-of-process replica snapshot a tracked router can fetch. With no
    router — or an all-loopback fleet — this is exactly the local
    snapshot, byte-identical to pre-fleet behavior."""
    local = telemetry.metrics_snapshot()
    extra: List[Dict[str, Any]] = []
    for router in _live(_ROUTERS):
        try:
            if not router.is_closed():
                extra.extend(router.replica_snapshots())
        except Exception:
            continue
    if not extra:
        return local
    return telemetry.merge_metric_snapshots([local] + extra)


def _prune(refs: List["weakref.ref[Any]"]) -> None:
    refs[:] = [r for r in refs if r() is not None]


def _live(refs: List["weakref.ref[Any]"]) -> List[Any]:
    with _LOCK:
        out = [r() for r in refs]
    return [o for o in out if o is not None]


# --------------------------------------------------------------------------
# readiness + status
# --------------------------------------------------------------------------


# a dispatcher with queued work that has not beaten for this long is
# reported stalled (the idle beat is ~1 Hz, so this is ~30 missed
# beats — far past any sane batch window, short of a long cold compile)
DISPATCHER_STALL_S = 30.0

# how long the SIGTERM handler lets each serving runtime drain before
# dumping the flight recorder and chaining to the previous disposition
SIGTERM_DRAIN_TIMEOUT_S = 5.0


def _readiness() -> Tuple[bool, List[str]]:
    reasons: List[str] = []
    storms = telemetry.counter("retrace_storms").value()
    if storms:
        reasons.append(f"retrace_storms={int(storms)}")
    for reg in _live(_REGISTRIES):
        try:
            swapping = reg.swaps_in_progress()
        except Exception:
            swapping = {}
        if swapping:
            # a flip whose warmup is incomplete: the prior version is
            # still serving, but rolling-update orchestration must not
            # advance to the next pod until the flip lands
            reasons.append(f"swap_in_progress={json.dumps(swapping)}")
        try:
            ws = reg.warmup_state()
        except Exception:
            continue
        if not ws.get("ready", True):
            pending = {
                name: m["pending_buckets"]
                for name, m in ws.get("models", {}).items()
                if m.get("pending_buckets")
            }
            reasons.append(f"warmup_pending={json.dumps(pending)}")
    for rt in _live(_RUNTIMES):
        try:
            if rt.is_closed():
                continue  # a cleanly closed runtime is not a fault
            if rt.is_draining():
                reasons.append("serving_draining")
            elif rt.dispatcher_started() and not rt.dispatcher_alive():
                reasons.append("serve_dispatcher_dead")
            else:
                age = rt.heartbeat_age_s()
                if (
                    age is not None
                    and age > DISPATCHER_STALL_S
                    and rt.queue_depth() > 0
                ):
                    reasons.append(
                        f"serve_dispatcher_stalled_age_s={age:.1f}"
                    )
            open_breakers = sorted(
                m for m, state in rt.breaker_states().items()
                if state == "open"
            )
            if open_breakers:
                reasons.append(
                    f"breaker_open={json.dumps(open_breakers)}"
                )
        except Exception:
            continue
    for router in _live(_ROUTERS):
        try:
            if router.is_closed():
                continue  # a cleanly closed router is not a fault
            if router.healthy_count() == 0:
                reasons.append("router_no_healthy_replicas")
            open_replicas = sorted(
                str(st["replica"]) for st in router.replica_states()
                if st.get("breaker") == "open"
            )
            if open_replicas:
                reasons.append(
                    f"router_breaker_open={json.dumps(open_replicas)}"
                )
        except Exception:
            continue
    for sched in _live(_SCHEDULERS):
        try:
            if sched.is_closed():
                continue  # a cleanly closed scheduler is not a fault
            if sched.is_draining():
                reasons.append("sched_draining")
            elif sched.dispatcher_started() and not sched.dispatcher_alive():
                reasons.append("sched_loop_dead")
            else:
                age = sched.heartbeat_age_s()
                if (
                    age is not None
                    and age > DISPATCHER_STALL_S
                    and sched.queue_depth() > 0
                ):
                    reasons.append(f"sched_loop_stalled_age_s={age:.1f}")
            open_breakers = sorted(
                t for t, state in sched.breaker_states().items()
                if state == "open"
            )
            if open_breakers:
                reasons.append(
                    f"sched_breaker_open={json.dumps(open_breakers)}"
                )
        except Exception:
            continue
    return (not reasons, reasons)


def _statusz() -> Dict[str, Any]:
    now = time.monotonic()
    snap = telemetry.metrics_snapshot()

    def _series(name: str) -> List[Dict[str, Any]]:
        return list((snap.get(name) or {}).get("series") or [])

    def _scalar(name: str) -> Optional[float]:
        for s in _series(name):
            if not s["labels"]:
                return s.get("value")
        return None

    heartbeats = {
        s["labels"].get("loop", "?"): round(now - float(s["value"]), 3)
        for s in _series("loop_heartbeat_ts")
    }
    hbm = {
        "budget_bytes": {
            s["labels"].get("site", "?"): s["value"]
            for s in _series("hbm_budget_bytes")
        },
        "live_bytes": {
            s["labels"].get("site", "?"): s["value"]
            for s in _series("hbm_live_bytes")
        },
    }
    serving: Dict[str, Any] = {
        "queue_depth_live": [
            rt.queue_depth() for rt in _live(_RUNTIMES)
        ],
        "queue_depth_gauge": _scalar("serve_queue_depth"),
        "batch_fill": [
            {
                "model": s["labels"].get("model", "?"),
                "count": s.get("count"),
                "p50": s.get("p50"),
                "p99": s.get("p99"),
            }
            for s in _series("serve_batch_fill")
        ],
        "p99_ms": [
            {
                "model": s["labels"].get("model", "?"),
                "count": s.get("count"),
                "p50": s.get("p50"),
                "p99": s.get("p99"),
            }
            for s in _series("serve_p99_ms")
        ],
        "draining": [rt.is_draining() for rt in _live(_RUNTIMES)],
        "dispatcher_alive": [
            rt.dispatcher_alive() for rt in _live(_RUNTIMES)
        ],
        "breakers": {
            model: state
            for rt in _live(_RUNTIMES)
            for model, state in rt.breaker_states().items()
        },
        "shed_total": {
            "{}/{}".format(
                s["labels"].get("model", "?"),
                s["labels"].get("reason", "?"),
            ): s.get("value")
            for s in _series("serve_shed_total")
        },
        "deadline_miss_total": {
            s["labels"].get("model", "?"): s.get("value")
            for s in _series("serve_deadline_miss_total")
        },
        "dispatch_errors": (
            telemetry.counter("serve_dispatch_errors_total").value() or 0
        ),
    }
    gang = {
        "dispatches": telemetry.counter("gang_dispatches").value() or 0,
        "lanes_total": telemetry.counter("gang_lanes_total").value() or 0,
    }
    scheduler: Dict[str, Any] = {
        "instances": [s.stats() for s in _live(_SCHEDULERS)],
        "draining": [s.is_draining() for s in _live(_SCHEDULERS)],
        "loop_alive": [s.dispatcher_alive() for s in _live(_SCHEDULERS)],
        "breakers": {
            tenant: state
            for s in _live(_SCHEDULERS)
            for tenant, state in s.breaker_states().items()
        },
        "fit_ms": [
            {
                "tenant": s["labels"].get("tenant", "?"),
                "count": s.get("count"),
                "p50": s.get("p50"),
                "p99": s.get("p99"),
            }
            for s in _series("sched_fit_ms")
        ],
        "shed_total": {
            "{}/{}".format(
                s["labels"].get("tenant", "?"),
                s["labels"].get("reason", "?"),
            ): s.get("value")
            for s in _series("sched_shed_total")
        },
        "preemptions": (
            telemetry.counter("sched_preemptions_total").value() or 0
        ),
        "resumes": telemetry.counter("sched_resumes_total").value() or 0,
        "dispatch_errors": (
            telemetry.counter("sched_dispatch_errors_total").value() or 0
        ),
    }
    fleet: List[Dict[str, Any]] = []
    for router in _live(_ROUTERS):
        entry: Dict[str, Any] = {
            "policy": getattr(router, "policy", "?"),
            "closed": router.is_closed(),
        }
        try:
            entry["replicas"] = router.replica_states()
            entry["healthy"] = router.healthy_count()
            entry["warmup"] = router.fleet_warmup_state()
            # measured fleet p99 from merged (pooled-reservoir)
            # snapshots — the pod-scale answer to "how slow are we"
            entry["p99_ms"] = router.fleet_p99_ms()
        except Exception as exc:
            entry["error"] = str(exc)
        fleet.append(entry)
    router_sheds = {
        "{}/{}".format(
            s["labels"].get("model", "?"), s["labels"].get("reason", "?")
        ): s.get("value")
        for s in _series("router_shed_total")
    }
    lifecycle: List[Dict[str, Any]] = []
    for lc in _live(_LIFECYCLES):
        try:
            lifecycle.append(lc.status())
        except Exception as exc:
            lifecycle.append({"error": str(exc)})
    ready, reasons = _readiness()
    rec = _RECORDER
    return {
        "pid": os.getpid(),
        "process_index": telemetry._process_index(),
        "ready": ready,
        "ready_reasons": reasons,
        "active_spans": telemetry.active_spans(),
        "registries": [
            reg.warmup_state() for reg in _live(_REGISTRIES)
        ],
        "serving": serving,
        "fleet": {"routers": fleet, "router_shed_total": router_sheds},
        "scheduler": scheduler,
        "lifecycle": lifecycle,
        "heartbeat_ages_s": heartbeats,
        "ingest_ring_occupancy": _scalar("ingest_ring_occupancy"),
        "gang": gang,
        "slo": slo_status(),
        "flight": {
            "events": len(rec) if rec is not None else 0,
            "capacity": rec.capacity if rec is not None else 0,
            "dumps": dict(rec.dumps) if rec is not None else {},
        },
    }


# --------------------------------------------------------------------------
# HTTP server
# --------------------------------------------------------------------------


class _Handler(BaseHTTPRequestHandler):
    server_version = "tpuml-ops"
    protocol_version = "HTTP/1.1"

    # the ops server must never spam stderr with access logs
    def log_message(self, fmt: str, *args: Any) -> None:
        return None

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        t0 = time.perf_counter()
        route = self.path.split("?", 1)[0]
        endpoint = "other"
        code = 200
        ctype = "application/json"
        try:
            if route == "/metrics":
                endpoint = "metrics"
                body = telemetry.prometheus_dump().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif route == "/healthz":
                endpoint = "healthz"
                body = json.dumps({"status": "ok"}).encode()
            elif route == "/readyz":
                endpoint = "readyz"
                ready, reasons = _readiness()
                code = 200 if ready else 503
                body = json.dumps(
                    {"ready": ready, "reasons": reasons}
                ).encode()
            elif route == "/statusz":
                endpoint = "statusz"
                body = json.dumps(
                    _statusz(), sort_keys=True, default=str
                ).encode()
            elif route == "/flight":
                endpoint = "flight"
                rec = _RECORDER
                if rec is None:
                    code = 503
                    body = json.dumps(
                        {"error": "flight recorder not running"}
                    ).encode()
                else:
                    body = json.dumps(rec.document("http")).encode()
            else:
                code = 404
                body = json.dumps(
                    {
                        "error": f"no route {route}",
                        "routes": [
                            "/metrics", "/healthz", "/readyz",
                            "/statusz", "/flight",
                        ],
                    }
                ).encode()
        except Exception as exc:  # a handler bug must not kill the fit
            code = 500
            body = json.dumps({"error": str(exc)}).encode()
        # counted before the reply goes out: a client that reads the counter
        # right after its response must find its own request in it
        telemetry.counter("ops_requests_total").inc(endpoint=endpoint)
        try:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except Exception:  # client went away mid-write
            pass
        telemetry.histogram("ops_request_seconds").observe(
            time.perf_counter() - t0, endpoint=endpoint
        )


# --------------------------------------------------------------------------
# crash-path dumps
# --------------------------------------------------------------------------


def _atexit_dump() -> None:
    rec = _RECORDER
    if rec is not None and len(rec):
        try:
            rec.dump("atexit")
        except Exception:
            pass


def _on_sigterm(signum: int, frame: Any) -> None:
    # lifecycle drivers drain FIRST: refresh threads halt (no new fits
    # land in a scheduler about to drain) and in-flight canaries roll
    # back typed (reason="shutdown") before serving admission stops —
    # a half-evaluated candidate must never promote into a dying
    # process; then the graceful serving drain (admission stops,
    # /readyz flips 503, in-flight work flushes, every future resolves
    # typed) so the flight dump below captures the post-drain state;
    # bounded — a wedged dispatcher cannot stall death past the timeout
    for lc in _live(_LIFECYCLES):
        try:
            lc.drain(timeout=SIGTERM_DRAIN_TIMEOUT_S)
        except Exception:
            pass
    for router in _live(_ROUTERS):
        try:
            router.drain(timeout=SIGTERM_DRAIN_TIMEOUT_S)
        except Exception:
            pass
    for rt in _live(_RUNTIMES):
        try:
            rt.drain(timeout=SIGTERM_DRAIN_TIMEOUT_S)
        except Exception:
            pass
    for sched in _live(_SCHEDULERS):
        try:
            sched.drain(timeout=SIGTERM_DRAIN_TIMEOUT_S)
        except Exception:
            pass
    rec = _RECORDER
    if rec is not None:
        try:
            rec.dump("signal")
        except Exception:
            pass
    prev = _PREV_SIGTERM
    if callable(prev):
        prev(signum, frame)
    else:
        # chain to the default disposition: restore and re-raise so
        # the process still dies with the conventional SIGTERM status
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)


def _install_crash_paths() -> None:
    global _PREV_SIGTERM, _SIGTERM_INSTALLED
    atexit.register(_atexit_dump)
    try:
        _PREV_SIGTERM = signal.signal(signal.SIGTERM, _on_sigterm)
        _SIGTERM_INSTALLED = True
    except ValueError:  # not the main thread; atexit still covers exit
        _SIGTERM_INSTALLED = False


# --------------------------------------------------------------------------
# lifecycle
# --------------------------------------------------------------------------


def started() -> bool:
    return _STARTED


def address() -> Optional[Tuple[str, int]]:
    """(host, port) the ops server is listening on — with
    ``TPUML_OPS_PORT=0`` this is where the ephemeral port shows up —
    or None while no server runs."""
    return _ADDR


def flight_recorder() -> Optional[FlightRecorder]:
    return _RECORDER


def ensure_started() -> bool:
    """Start the ops plane once, iff opted in.

    With neither ``TPUML_OPS_PORT`` nor ``TPUML_FLIGHT_DIR`` set this
    is a cheap False: no socket, no thread, no sink, no signal handler
    — the defaults-inert contract. Otherwise: attach the flight
    recorder sink and crash-path dumps, start the SLO evaluator, and —
    when a port is configured — bind the HTTP server. Idempotent;
    called from the serving runtime and the streaming ingest loop, and
    safe to call directly."""
    global _STARTED, _RECORDER, _SERVER, _SERVER_THREAD, _EVALUATOR, _ADDR
    if not _active():
        return False
    with _LOCK:
        if _STARTED:
            return True
        _RECORDER = FlightRecorder(int(envspec.get("TPUML_FLIGHT_EVENTS")))
        telemetry.add_span_sink(_RECORDER.sink)
        _install_crash_paths()
        _EVALUATOR = _SloEvaluator(
            _RECORDER,
            period_s=float(envspec.get("TPUML_SLO_EVAL_MS")) / 1000.0,
            threshold=float(envspec.get("TPUML_SLO_BURN_THRESHOLD")),
        )
        _EVALUATOR.start()
        port = envspec.get("TPUML_OPS_PORT")
        if port is not None:
            host = str(envspec.get("TPUML_OPS_HOST"))
            server = ThreadingHTTPServer((host, int(port)), _Handler)
            server.daemon_threads = True
            _SERVER = server
            _ADDR = (server.server_address[0], server.server_address[1])
            _SERVER_THREAD = threading.Thread(
                target=server.serve_forever,
                name="tpuml-ops-http",
                daemon=True,
                kwargs={"poll_interval": 0.1},
            )
            _SERVER_THREAD.start()
            _LOGGER.info(
                "ops: serving /metrics /healthz /readyz /statusz "
                "/flight on http://%s:%d", _ADDR[0], _ADDR[1],
            )
        _STARTED = True
        return True


def stop() -> None:
    """Tear the plane down (test isolation): close the socket, halt the
    threads, detach the sink, restore the SIGTERM disposition, and
    unregister the atexit dump. Safe when never started."""
    global _STARTED, _RECORDER, _SERVER, _SERVER_THREAD, _EVALUATOR
    global _ADDR, _PREV_SIGTERM, _SIGTERM_INSTALLED
    with _LOCK:
        server, thread = _SERVER, _SERVER_THREAD
        evaluator, recorder = _EVALUATOR, _RECORDER
        _SERVER = _SERVER_THREAD = None
        _EVALUATOR = None
        _RECORDER = None
        _ADDR = None
        _STARTED = False
        _REGISTRIES.clear()
        _RUNTIMES.clear()
        _SCHEDULERS.clear()
        _ROUTERS.clear()
        _LIFECYCLES.clear()
    if server is not None:
        try:
            server.shutdown()
            server.server_close()
        except Exception:
            pass
    if thread is not None:
        thread.join(timeout=5.0)
    if evaluator is not None:
        evaluator.halt()
        evaluator.join(timeout=5.0)
    if recorder is not None:
        telemetry.remove_span_sink(recorder.sink)
    atexit.unregister(_atexit_dump)
    if _SIGTERM_INSTALLED:
        try:
            signal.signal(
                signal.SIGTERM,
                _PREV_SIGTERM if _PREV_SIGTERM is not None
                else signal.SIG_DFL,
            )
        except ValueError:
            pass
        _SIGTERM_INSTALLED = False
        _PREV_SIGTERM = None
