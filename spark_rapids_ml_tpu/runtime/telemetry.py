"""Unified telemetry runtime: structured spans, typed metrics, watchdogs.

One layer answers "where did this fit's wall time go" across host
threads, streaming stages, and device dispatches:

- **Spans** — hierarchical wall-clock intervals with ``contextvars``
  parent propagation that survives worker threads (the fold pool in
  ``tuning.py``, the decode/stage threads in ``ops/streaming.py``) via
  :func:`bind_context`. A span measures wall time; each live span also
  writes a ``jax.profiler.TraceAnnotation``, so inside a profiler
  capture it lies on the profiler's clock beside the device's own
  events. Spans export as a Chrome-trace/Perfetto JSON plus a JSONL
  event log under ``TPUML_TRACE=<dir>``.
- **Typed metrics** — counter / gauge / histogram-with-bounded-ring,
  optionally labeled, cataloged in :mod:`metricspec` (lint rule TPU007
  keeps call sites and catalog in sync). The legacy
  :mod:`runtime.counters` API is a shim over this registry. Exports:
  Prometheus text format and a JSON snapshot.
- **Retrace watchdog** — counts XLA backend compilations per innermost
  active span (``jax.monitoring`` events) and warns once per site past
  ``TPUML_TELEMETRY_RETRACE_LIMIT`` — the runtime enforcement of lint
  rule TPU003.
- **HBM accounting** — :func:`record_hbm_estimate` files each budget
  resolver's peak estimate (gang fit, tree batch, stream staging) as a
  labeled gauge next to the backend's live memory stats.
- **Multi-host** — every output file is tagged with the process index
  (``trace-r00-<pid>.json``), :func:`aggregate_metrics` merges metric
  snapshots across hosts through the ``parallel/mesh.py`` collectives,
  and ``scripts/merge_traces.py`` folds per-host shards into one
  Perfetto trace with per-host tracks.

- **Span sinks** — :func:`add_span_sink` attaches a callable fed every
  completed span/instant event; the live operations plane
  (:mod:`runtime.opsplane`) uses this to keep a bounded in-memory
  flight recorder without enabling file export. While a sink is
  attached, spans are live even with ``TPUML_TRACE`` unset, but the
  trace buffers, ``span_stats``, and ``spans_recorded`` stay empty.

Defaults are inert: with ``TPUML_TRACE`` unset and no sink attached,
:func:`span` returns a shared no-op, nothing is recorded or written,
and outputs are bit-identical to an uninstrumented run
(``tests/test_telemetry.py`` asserts this bitwise).
"""

from __future__ import annotations

import atexit
import contextvars
import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

from . import envspec, lockwitness, metricspec

_LOGGER = logging.getLogger("spark_rapids_ml_tpu")

__all__ = [
    "enabled",
    "span",
    "timed_span",
    "bind_context",
    "add_span_sink",
    "remove_span_sink",
    "active_spans",
    "counter",
    "gauge",
    "histogram",
    "metric_kind",
    "add_span_event",
    "span_stats",
    "flush",
    "prometheus_dump",
    "metrics_snapshot",
    "merge_metric_snapshots",
    "aggregate_metrics",
    "write_metrics",
    "record_hbm_estimate",
    "install_retrace_watchdog",
    "reset_telemetry",
]


# --------------------------------------------------------------------------
# enable gates
# --------------------------------------------------------------------------


def enabled() -> bool:
    """True when ``TPUML_TRACE`` is set (spans record and export)."""
    return envspec.is_set("TPUML_TRACE")


def _recording() -> bool:
    """True when spans must be live objects: tracing is enabled OR a
    span sink (the ops-plane flight recorder) is attached. Sinks see
    every completed span/event but nothing is buffered for file export
    unless ``TPUML_TRACE`` is also set — the recorder keeps its own
    bounded ring."""
    return bool(_SINKS) or enabled()


def _trace_dir() -> Optional[str]:
    return envspec.get("TPUML_TRACE")


def _process_index() -> int:
    """This process's rank for the multi-host trace-shard layout.

    Read from the launcher-provided ``TPUML_PROC_ID`` (the same source
    ``parallel/context.py`` initializes the jax world from) rather than
    ``jax.process_index()`` — resolving a filename must never initialize
    a backend (flush runs from atexit and crash paths).
    """
    try:
        return int(envspec.get("TPUML_PROC_ID"))
    except Exception:
        return 0


# --------------------------------------------------------------------------
# typed metrics registry
# --------------------------------------------------------------------------

# RLock: _Hist.quantile locks its ring copy, and the exporters call it
# while already holding the registry lock
_MLOCK = lockwitness.make_rlock("telemetry.metrics")
_METRICS: Dict[str, "_Metric"] = {}


class _Hist:
    """Exact running count/sum/min/max plus a deterministic last-N ring
    (no sampling randomness — TPU004 applies to telemetry too)."""

    __slots__ = ("count", "sum", "min", "max", "ring")

    def __init__(self, reservoir: int) -> None:
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.ring: Deque[float] = deque(maxlen=reservoir)

    def observe(self, value: float) -> None:
        self.count += 1
        self.sum += value
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value
        self.ring.append(value)

    def quantile(self, q: float) -> Optional[float]:
        """Deterministic ring quantile: None on an empty reservoir, the
        lone observation for a single sample (any ``q``), exact min/max
        at q=0/1, and out-of-range ``q`` clamped — never an IndexError
        or interpolated garbage. The ring copy happens under the metric
        lock: a sort racing a concurrent ``observe`` would otherwise
        raise "deque mutated during iteration"."""
        with _MLOCK:
            ordered = sorted(self.ring)
        if not ordered:
            return None
        q = min(1.0, max(0.0, q))
        return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1)))]


class _Metric:
    """One named metric: kind + labeled series map.

    ``legacy`` series stay visible through ``counters.snapshot()`` /
    ``delta_since`` (the ``_resilience_report`` contract); typed-only
    metrics export through Prometheus/JSON instead.
    """

    __slots__ = ("name", "kind", "legacy", "series")

    def __init__(self, name: str, kind: str, legacy: bool) -> None:
        self.name = name
        self.kind = kind
        self.legacy = legacy
        self.series: Dict[Tuple[Tuple[str, str], ...], Any] = {}

    @staticmethod
    def _key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
        return tuple(sorted((k, str(v)) for k, v in labels.items()))

    def inc(self, by: int = 1, **labels: Any) -> None:
        if self.kind != "counter":
            raise ValueError(f"{self.name} is a {self.kind}, not a counter")
        key = self._key(labels)
        with _MLOCK:
            self.series[key] = self.series.get(key, 0) + int(by)

    def set(self, value: float, **labels: Any) -> None:
        if self.kind != "gauge":
            raise ValueError(f"{self.name} is a {self.kind}, not a gauge")
        key = self._key(labels)
        with _MLOCK:
            self.series[key] = value

    def observe(self, value: float, **labels: Any) -> None:
        if self.kind != "histogram":
            raise ValueError(
                f"{self.name} is a {self.kind}, not a histogram"
            )
        key = self._key(labels)
        with _MLOCK:
            h = self.series.get(key)
            if h is None:
                h = self.series[key] = _Hist(
                    int(envspec.get("TPUML_TELEMETRY_RESERVOIR"))
                )
            h.observe(value)

    def value(self, **labels: Any) -> Any:
        with _MLOCK:
            return self.series.get(self._key(labels))


def _metric(name: str, kind: str, *, legacy: bool = False) -> _Metric:
    """The metric instance for ``name``, created on first use.

    Cataloged names take their kind (and legacy visibility) from
    :mod:`metricspec` — asking for a cataloged gauge as a counter is a
    ``ValueError``, which is what makes gauge-vs-counter a property of
    the metric rather than a name check. Uncataloged names are allowed
    at runtime (lint rule TPU007 rejects them statically in repo code).
    """
    with _MLOCK:
        m = _METRICS.get(name)
        if m is None:
            spec = metricspec.SPEC.get(name)
            if spec is not None:
                m = _Metric(name, spec.kind, spec.legacy)
            else:
                m = _Metric(name, kind, legacy)
            _METRICS[name] = m
    if m.kind != kind:
        raise ValueError(
            f"metric {name!r} is registered as a {m.kind}, not a {kind}"
        )
    return m


def counter(name: str) -> _Metric:
    return _metric(name, "counter")


def gauge(name: str) -> _Metric:
    return _metric(name, "gauge")


def histogram(name: str) -> _Metric:
    return _metric(name, "histogram")


def metric_kind(name: str) -> str:
    """The kind of ``name`` — live instance first, then the catalog,
    defaulting to ``counter`` for uncataloged dynamic names."""
    with _MLOCK:
        m = _METRICS.get(name)
    if m is not None:
        return m.kind
    spec = metricspec.SPEC.get(name)
    return spec.kind if spec is not None else "counter"


# legacy counters.py bridge -------------------------------------------------


def _legacy_metric(name: str, kind: str) -> _Metric:
    """Shim entry point: uncataloged names created here stay visible in
    ``counters.snapshot()`` like the pre-registry dict did."""
    return _metric(name, kind, legacy=True)


def _legacy_snapshot() -> Dict[str, int]:
    with _MLOCK:
        out: Dict[str, int] = {}
        for name, m in _METRICS.items():
            if not m.legacy or m.kind == "histogram":
                continue
            v = m.series.get(())
            if v is not None:
                out[name] = int(v)
        return out


def _reset_metrics() -> None:
    with _MLOCK:
        _METRICS.clear()


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------

_CURRENT: "contextvars.ContextVar[Optional[_Span]]" = contextvars.ContextVar(
    "tpuml_current_span", default=None
)
_IDS = itertools.count(1)

_RLOCK = lockwitness.make_lock("telemetry.trace")
_EPOCH: Optional[float] = None  # perf_counter origin of trace timestamps
_EVENTS: List[Dict[str, Any]] = []  # chrome-trace "X" events
_PENDING_LINES: List[str] = []  # jsonl lines not yet appended to disk
_THREADS: Dict[int, str] = {}  # tid -> thread name (trace metadata)
_STATS: Dict[str, List[float]] = {}  # name -> [count, wall_s]
_ATEXIT_REGISTERED = False
# span sinks: callables fed every completed span/instant event dict
# (chrome-trace shape) plus the originating thread name — the ops-plane
# flight recorder attaches here. While any sink is attached, spans are
# live even with TPUML_TRACE unset (see _recording()).
_SINKS: List[Any] = []
# open spans, span_id -> {span_id, parent_id, name, thread, t0} — the
# /statusz active-span-tree source; empty whenever nothing records
_ACTIVE: Dict[int, Dict[str, Any]] = {}


def add_span_sink(fn: Any) -> None:
    """Attach ``fn(event_dict, thread_name)`` to every completed span
    and instant event. Attaching makes spans live (allocated, parented,
    timed) even when ``TPUML_TRACE`` is unset; file export stays gated
    on the env. Sink exceptions are swallowed — observability must
    never fail the fit."""
    with _RLOCK:
        if fn not in _SINKS:
            _SINKS.append(fn)


def remove_span_sink(fn: Any) -> None:
    with _RLOCK:
        try:
            _SINKS.remove(fn)
        except ValueError:
            pass


def active_spans() -> List[Dict[str, Any]]:
    """Open spans right now: ``[{span_id, parent_id, name, thread,
    age_seconds}, ...]`` sorted by span_id (creation order), so a
    client can rebuild the live span tree with wall-clock ages. Empty
    while nothing records."""
    now = time.perf_counter()
    with _RLOCK:
        snap = [dict(rec) for rec in _ACTIVE.values()]
    out = []
    for rec in sorted(snap, key=lambda r: r["span_id"]):
        rec["age_seconds"] = round(now - rec.pop("t0"), 6)
        out.append(rec)
    return out


class _NullSpan:
    """Shared no-op returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set_attr(self, **attrs: Any) -> None:
        return None


_NULL = _NullSpan()

#: Prefix of a live span's ``jax.profiler.TraceAnnotation``. It keeps the
#: spans apart from the hand-written phase annotations
#: (``utils/profiling.annotate``), which readers of a trace match by name.
ANNOTATION_PREFIX = "tpuml:"
# jax.profiler.TraceAnnotation, resolved by the first live span
# (_ensure_hooks): importing this module must not import jax
_ANNOTATION: Any = None


class _Span:
    """One live span: wall interval + attrs."""

    __slots__ = (
        "name",
        "attrs",
        "span_id",
        "parent_id",
        "_token",
        "_t0",
        "tid",
        "thread_name",
        "_annotation",
    )

    def __init__(self, name: str, attrs: Dict[str, Any]) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        parent = _CURRENT.get()
        self.parent_id = parent.span_id if parent is not None else None
        if parent is not None and parent.attrs.get("warmup"):
            # warmup is a property of the whole subtree: a declared-
            # compilation site (serving warmup/probe) calls into closures
            # that open their own dispatch spans, and the retrace
            # watchdog reads the INNERMOST span — without inheritance
            # those inner sites would score the absorbed compiles as
            # storms
            self.attrs.setdefault("warmup", True)
        self.span_id = next(_IDS)
        self._token = _CURRENT.set(self)
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        # the span's twin on the profiler's clock: inside a jax.profiler
        # capture it lands on the host plane as ``tpuml:<name>`` with the
        # ids as event stats, so a device gap can be laid against the span
        # that covered it; outside a capture it is one atomic load
        ids = {"span_id": self.span_id}
        if self.parent_id is not None:
            ids["parent_id"] = self.parent_id
        self._annotation = _ANNOTATION(ANNOTATION_PREFIX + self.name, **ids)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        with _RLOCK:
            _ACTIVE[self.span_id] = {
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "name": self.name,
                "thread": self.thread_name,
                "t0": self._t0,
            }
        return self

    def set_attr(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def __exit__(self, *exc: Any) -> None:
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(None, None, None)
        _CURRENT.reset(self._token)
        _record(self, dur)
        return None


def span(name: str, **attrs: Any) -> Any:
    """A context manager for one named span.

    No-op (a shared singleton, no allocation or recording) while
    ``TPUML_TRACE`` is unset and no span sink is attached. The returned
    object supports ``set_attr(**kw)`` in both modes.
    """
    if not _recording():
        return _NULL
    _ensure_hooks()
    return _Span(name, attrs)


class timed_span:
    """A span that always measures wall time (``.seconds`` after exit),
    recording to the trace only when tracing is enabled. The report
    dicts (``_fit_report`` / ``_transform_report`` / ...) read their
    stage seconds from this layer, so enabling the trace never changes
    what they contain."""

    __slots__ = ("_span", "_t0", "seconds")

    def __init__(self, name: str, **attrs: Any) -> None:
        self._span = span(name, **attrs)
        self.seconds = 0.0

    def __enter__(self) -> "timed_span":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> Any:
        self.seconds = time.perf_counter() - self._t0
        return self._span.__exit__(*exc)


def bind_context(fn: Any) -> Any:
    """Wrap ``fn`` so invocations on another thread inherit the caller's
    span stack. Captures the current ``contextvars`` context once; each
    call runs in a private copy (one Context object cannot be entered
    concurrently). Identity while nothing records."""
    if not _recording():
        return fn
    snap = contextvars.copy_context()

    def _bound(*args: Any, **kwargs: Any) -> Any:
        return snap.copy().run(fn, *args, **kwargs)

    return _bound


def _record(s: _Span, dur: float) -> None:
    global _EPOCH, _ATEXIT_REGISTERED
    root_closed = s.parent_id is None
    exporting = enabled()
    with _RLOCK:
        _ACTIVE.pop(s.span_id, None)
        if _EPOCH is None:
            _EPOCH = s._t0
        ts_us = (s._t0 - _EPOCH) * 1e6
        args: Dict[str, Any] = dict(s.attrs)
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        ev = {
            "name": s.name,
            "ph": "X",
            "ts": round(ts_us, 3),
            "dur": round(dur * 1e6, 3),
            "pid": os.getpid(),
            "tid": s.tid,
            "args": args,
        }
        # the file-export buffers (trace JSON, JSONL log, span_stats)
        # and their metrics stay gated on TPUML_TRACE — the sink-only
        # path (ops-plane flight recorder) accumulates nothing here,
        # preserving the inertness sentinel semantics of spans_recorded
        if exporting:
            _EVENTS.append(ev)
            _THREADS.setdefault(s.tid, s.thread_name)
            _PENDING_LINES.append(
                json.dumps(
                    {
                        "event": "span",
                        "name": s.name,
                        "span_id": s.span_id,
                        "parent_id": s.parent_id,
                        "thread": s.thread_name,
                        "ts_us": round(ts_us, 3),
                        "wall_seconds": round(dur, 6),
                        "attrs": s.attrs,
                    },
                    sort_keys=True,
                    default=str,
                )
            )
            st = _STATS.get(s.name)
            if st is None:
                st = _STATS[s.name] = [0, 0.0]
            st[0] += 1
            st[1] += dur
            if not _ATEXIT_REGISTERED:
                _ATEXIT_REGISTERED = True
                atexit.register(_atexit_flush)
        sinks = list(_SINKS)
    for sink in sinks:
        try:
            sink(ev, s.thread_name)
        except Exception:  # a broken sink must never fail a span close
            pass
    if exporting:
        counter("spans_recorded").inc()
        histogram("span_seconds").observe(dur, name=s.name)
        if root_closed:
            flush()


def _atexit_flush() -> None:
    """Crash-path persistence: at interpreter exit (including an
    unhandled exception unwinding mid-fit) write whatever the buffers
    hold — the trace shard, pending JSONL lines, AND a metric snapshot,
    so a postmortem has both the timeline and the counters."""
    try:
        flush()
    except Exception:
        pass
    try:
        write_metrics()
    except Exception:
        pass


def add_span_event(name: str, **attrs: Any) -> None:
    """Record an instant event (a point in time, not an interval) under
    the innermost active span — retries, injected faults, and similar
    occurrences show up inline on the trace timeline for postmortems.
    No-op while nothing records (tracing disabled, no sink attached)."""
    if not _recording():
        return
    global _EPOCH, _ATEXIT_REGISTERED
    exporting = enabled()
    cur = _CURRENT.get()
    t = threading.current_thread()
    tid = t.ident or 0
    now = time.perf_counter()
    with _RLOCK:
        if _EPOCH is None:
            _EPOCH = now
        ts_us = (now - _EPOCH) * 1e6
        args: Dict[str, Any] = dict(attrs)
        if cur is not None:
            args["span_id"] = cur.span_id
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",  # thread-scoped instant marker
            "ts": round(ts_us, 3),
            "pid": os.getpid(),
            "tid": tid,
            "args": args,
        }
        if exporting:
            _EVENTS.append(ev)
            _THREADS.setdefault(tid, t.name)
            _PENDING_LINES.append(
                json.dumps(
                    {
                        "event": "point",
                        "name": name,
                        "span": cur.name if cur is not None else None,
                        "thread": t.name,
                        "ts_us": round(ts_us, 3),
                        "attrs": attrs,
                    },
                    sort_keys=True,
                    default=str,
                )
            )
            if not _ATEXIT_REGISTERED:
                _ATEXIT_REGISTERED = True
                atexit.register(_atexit_flush)
        sinks = list(_SINKS)
    for sink in sinks:
        try:
            sink(ev, t.name)
        except Exception:
            pass


def span_stats() -> Dict[str, Dict[str, float]]:
    """Per-span-name running aggregates: ``{name: {count,
    wall_seconds}}`` (empty while tracing never enabled — the inertness
    sentinel)."""
    with _RLOCK:
        return {
            name: {"count": int(st[0]), "wall_seconds": st[1]}
            for name, st in _STATS.items()
        }


def flush() -> Optional[str]:
    """Write the Chrome-trace JSON (rewritten whole) and append pending
    JSONL span events under ``TPUML_TRACE``. Called automatically at
    every root-span close and at interpreter exit; safe to call any
    time. Returns the trace file path, or None when there is nothing to
    write or the env was unset meanwhile.

    Rank-aware layout: every filename carries the process index
    (``trace-r00-<pid>.json``), so N hosts pointed at one shared
    ``TPUML_TRACE`` directory write N disjoint shards that
    ``scripts/merge_traces.py`` folds into a single cluster-wide
    Perfetto trace. The shard's own ``process_index`` rides along as
    trace-document metadata for the merger.
    """
    out_dir = _trace_dir()
    with _RLOCK:
        if out_dir is None or not _EVENTS:
            return None
        rank = _process_index()
        meta = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": os.getpid(),
                "tid": 0,
                "args": {"name": "spark_rapids_ml_tpu"},
            }
        ]
        for tid, tname in sorted(_THREADS.items()):
            meta.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": os.getpid(),
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
        doc = {
            "traceEvents": meta + _EVENTS,
            "displayTimeUnit": "ms",
            "metadata": {"process_index": rank},
        }
        pending, _PENDING_LINES[:] = _PENDING_LINES[:], []
        os.makedirs(out_dir, exist_ok=True)
        tag = f"r{rank:02d}-{os.getpid()}"
        trace_path = os.path.join(out_dir, f"trace-{tag}.json")
        tmp = trace_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, trace_path)
        if pending:
            events_path = os.path.join(out_dir, f"events-{tag}.jsonl")
            with open(events_path, "a") as f:
                f.write("\n".join(pending) + "\n")
        return trace_path


def reset_telemetry() -> None:
    """Clear spans, metrics and watchdog state (test isolation)."""
    global _EPOCH
    with _RLOCK:
        _EPOCH = None
        _EVENTS.clear()
        _PENDING_LINES.clear()
        _THREADS.clear()
        _STATS.clear()
        _ACTIVE.clear()
        _SINKS.clear()
    _reset_metrics()
    with _WD_LOCK:
        _WD_COUNTS.clear()
        _WD_WARNED.clear()


# --------------------------------------------------------------------------
# metric exports
# --------------------------------------------------------------------------

_QUANTILES = (0.5, 0.95, 0.99)


def _label_str(key: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [
        f'{k}="{v}"'.replace("\n", " ")
        for k, v in key
    ]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def prometheus_dump() -> str:
    """Every live metric in Prometheus text exposition format
    (``tpuml_`` prefix; histograms exported summary-style from the
    bounded ring plus exact ``_count`` / ``_sum``)."""
    with _MLOCK:
        metrics = sorted(_METRICS.items())
        lines: List[str] = []
        for name, m in metrics:
            spec = metricspec.SPEC.get(name)
            doc = spec.doc if spec is not None else "(uncataloged metric)"
            pname = f"tpuml_{name}"
            ptype = "summary" if m.kind == "histogram" else m.kind
            lines.append(f"# HELP {pname} {doc}".replace("\n", " "))
            lines.append(f"# TYPE {pname} {ptype}")
            for key, v in sorted(m.series.items()):
                if m.kind == "histogram":
                    for q in _QUANTILES:
                        qv = v.quantile(q)
                        if qv is None:
                            continue
                        qlabel = 'quantile="%g"' % q
                        lines.append(
                            f"{pname}{_label_str(key, qlabel)} {qv:g}"
                        )
                    lines.append(
                        f"{pname}_count{_label_str(key)} {v.count}"
                    )
                    lines.append(f"{pname}_sum{_label_str(key)} {v.sum:g}")
                else:
                    lines.append(f"{pname}{_label_str(key)} {v:g}")
        return "\n".join(lines) + ("\n" if lines else "")


def metrics_snapshot() -> Dict[str, Any]:
    """A JSON-able snapshot of every live metric: kind plus each labeled
    series (histograms as count/sum/min/max + ring quantiles + the
    sorted bounded reservoir itself, so a cross-rank merge can quantile
    the fleet exactly instead of approximating from count/sum)."""
    with _MLOCK:
        out: Dict[str, Any] = {}
        for name, m in sorted(_METRICS.items()):
            series = []
            for key, v in sorted(m.series.items()):
                labels = dict(key)
                if m.kind == "histogram":
                    series.append(
                        {
                            "labels": labels,
                            "count": v.count,
                            "sum": v.sum,
                            "min": v.min,
                            "max": v.max,
                            "reservoir": sorted(v.ring),
                            **{
                                f"p{int(q * 100)}": v.quantile(q)
                                for q in _QUANTILES
                            },
                        }
                    )
                else:
                    series.append({"labels": labels, "value": v})
            out[name] = {"kind": m.kind, "series": series}
        return out


def write_metrics(out_dir: Optional[str] = None) -> Optional[Tuple[str, str]]:
    """Write ``metrics-r00-<pid>.prom`` (text format) and
    ``metrics-r00-<pid>.json`` (snapshot) into ``out_dir`` (default:
    the ``TPUML_TRACE`` directory), process-index-tagged like the trace
    shards. Returns the two paths, or None when no directory is
    configured."""
    out_dir = out_dir or _trace_dir()
    if out_dir is None:
        return None
    os.makedirs(out_dir, exist_ok=True)
    tag = f"r{_process_index():02d}-{os.getpid()}"
    prom = os.path.join(out_dir, f"metrics-{tag}.prom")
    js = os.path.join(out_dir, f"metrics-{tag}.json")
    with open(prom, "w") as f:
        f.write(prometheus_dump())
    with open(js, "w") as f:
        json.dump(metrics_snapshot(), f, indent=2, sort_keys=True)
    return prom, js


# --------------------------------------------------------------------------
# cross-host aggregation
# --------------------------------------------------------------------------


#: Bound on a merged reservoir: concatenated per-rank rings are sorted
#: and evenly downsampled to at most this many samples, so an N-rank
#: fold stays O(cap) no matter the fleet size. Mirrored verbatim in
#: ``scripts/merge_traces.py`` (stdlib-only, cannot import this module).
RESERVOIR_MERGE_CAP = 4096


def _merged_quantile(ordered: List[float], q: float) -> float:
    """The exact ``_Hist.quantile`` rule over an already-sorted list."""
    q = min(1.0, max(0.0, q))
    return ordered[min(len(ordered) - 1, int(q * (len(ordered) - 1)))]


def _fold_reservoir(samples: List[float]) -> List[float]:
    """Sort concatenated per-rank reservoirs and evenly downsample to
    ``RESERVOIR_MERGE_CAP`` keeping both endpoints — deterministic
    (TPU004: no sampling randomness) and input-order-independent."""
    ordered = sorted(samples)
    n = len(ordered)
    cap = RESERVOIR_MERGE_CAP
    if n <= cap:
        return ordered
    return [ordered[i * (n - 1) // (cap - 1)] for i in range(cap)]


def merge_metric_snapshots(snaps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-process :func:`metrics_snapshot` dicts into one
    cluster-wide view, kind-aware per labeled series: counters SUM,
    gauges MAX (each rank's last-write is a local reading; the peak is
    the conservative cluster answer), histogram count/sum SUM with
    min/max merged and per-rank reservoirs concatenated, sorted,
    bounded to ``RESERVOIR_MERGE_CAP``, and re-quantiled — merged p99
    is measured over the pooled samples, not approximated. Snapshots
    predating the reservoir export (no ``reservoir`` key) still merge;
    their per-rank quantiles are dropped rather than faked.

    ``scripts/merge_traces.py`` implements these same rules over the
    on-disk ``metrics-r*-*.json`` shards; ``dryrun_multichip`` parity-
    checks the two implementations against each other.
    """
    merged: Dict[str, Any] = {}
    for snap in snaps:
        for name, entry in snap.items():
            kind = entry.get("kind", "counter")
            slot = merged.setdefault(name, {"kind": kind, "series": {}})
            for series in entry.get("series", []):
                labels = series.get("labels", {})
                key = tuple(sorted(labels.items()))
                have = slot["series"].get(key)
                if kind == "histogram":
                    if have is None:
                        slot["series"][key] = {
                            "labels": labels,
                            "count": series.get("count", 0),
                            "sum": series.get("sum", 0.0),
                            "min": series.get("min"),
                            "max": series.get("max"),
                            "reservoir": list(
                                series.get("reservoir") or []
                            ),
                        }
                    else:
                        have["count"] += series.get("count", 0)
                        have["sum"] += series.get("sum", 0.0)
                        for fld, pick in (("min", min), ("max", max)):
                            v = series.get(fld)
                            if v is not None:
                                have[fld] = (
                                    v if have[fld] is None
                                    else pick(have[fld], v)
                                )
                        have["reservoir"].extend(
                            series.get("reservoir") or []
                        )
                else:
                    value = series.get("value", 0)
                    if have is None:
                        slot["series"][key] = {
                            "labels": labels, "value": value,
                        }
                    elif kind == "gauge":
                        have["value"] = max(have["value"], value)
                    else:
                        have["value"] += value
    out: Dict[str, Any] = {}
    for name, entry in sorted(merged.items()):
        series_out = []
        for k in sorted(entry["series"]):
            s = entry["series"][k]
            if entry["kind"] == "histogram":
                res = _fold_reservoir(s.pop("reservoir"))
                if res:
                    s["reservoir"] = res
                    for q in (0.5, 0.95, 0.99):
                        s[f"p{int(q * 100)}"] = _merged_quantile(res, q)
            series_out.append(s)
        out[name] = {"kind": entry["kind"], "series": series_out}
    return out


def aggregate_metrics() -> Dict[str, Any]:
    """The cluster-wide merged metric snapshot: allgather every
    process's :func:`metrics_snapshot` through the ``parallel/mesh.py``
    host collectives and fold with :func:`merge_metric_snapshots`.
    Single-process (and any collective failure) degrades to the merge
    of the local snapshot alone — same shape, local values."""
    local = metrics_snapshot()
    snaps = [local]
    try:
        from ..parallel.mesh import allgather_host_blobs

        blobs = allgather_host_blobs(
            json.dumps(local, sort_keys=True, default=str).encode()
        )
        if len(blobs) > 1:
            snaps = [json.loads(b.decode()) for b in blobs]
    except Exception:
        _LOGGER.debug("aggregate_metrics: host allgather unavailable")
    return merge_metric_snapshots(snaps)


# --------------------------------------------------------------------------
# retrace watchdog (runtime TPU003)
# --------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WD_LOCK = lockwitness.make_lock("telemetry.watchdog")
_WD_INSTALLED = False
_WD_CHECKED = False
_WD_COUNTS: Dict[str, int] = {}
_WD_WARNED: set = set()


def _retrace_limit() -> int:
    return int(envspec.get("TPUML_TELEMETRY_RETRACE_LIMIT"))


def _watchdog_active() -> bool:
    """The listener cannot be unregistered once installed, but its
    EFFECT must follow the live opt-in: telemetry recording, or an
    explicit retrace limit in the environment. Otherwise a process (or
    test) that traced once charges every later untraced compile to the
    ``<untraced>`` site — where no span can carry the ``warmup`` attr —
    and legitimate warmup ladders score as storms long after the trace
    env is gone."""
    if _recording():
        return True
    try:
        return envspec.is_set("TPUML_TELEMETRY_RETRACE_LIMIT")
    except Exception:
        return False


def _on_event_duration(event: str, duration: float, **kw: Any) -> None:
    if event != _COMPILE_EVENT:
        return
    try:  # a listener exception would poison every jax compile
        if not _watchdog_active():
            return
        cur = _CURRENT.get()
        site = cur.name if cur is not None else "<untraced>"
        counter("xla_compiles").inc(1, site=site)
        histogram("xla_compile_seconds").observe(duration, site=site)
        if cur is not None and cur.attrs.get("warmup"):
            # declared-compilation sites (`span(..., warmup=True)`): the
            # serving registry's per-bucket warmup exists precisely to
            # absorb first-shape compiles, so they are counted in
            # xla_compiles but never scored as a retrace storm
            return
        storm = False
        with _WD_LOCK:
            count = _WD_COUNTS[site] = _WD_COUNTS.get(site, 0) + 1
            if site not in _WD_WARNED:
                limit = _retrace_limit()
                storm = limit > 0 and count > limit
                if storm:
                    _WD_WARNED.add(site)
        if storm:
            counter("retrace_storms").inc()
            _LOGGER.warning(
                "retrace storm: %d XLA compilations attributed to span "
                "site %r (limit %d) — a traced argument is likely "
                "changing every call (static shape/env read inside jit; "
                "see docs/static_analysis.md TPU003 and "
                "docs/observability.md)",
                count,
                site,
                limit,
            )
    except Exception:
        pass


def install_retrace_watchdog() -> bool:
    """Register the compile-event listener (idempotent). Returns True
    when installed (now or earlier), False when jax.monitoring is
    unavailable. Listeners cannot be unregistered, so this only happens
    on explicit opt-in: ``TPUML_TRACE`` set, an explicit
    ``TPUML_TELEMETRY_RETRACE_LIMIT``, or a direct call."""
    global _WD_INSTALLED
    with _WD_LOCK:
        if _WD_INSTALLED:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _WD_INSTALLED = True
        return True


def _ensure_hooks() -> None:
    """Bind ``TraceAnnotation``, install the retrace watchdog's
    compile-event listener and register the crash-path atexit flush on
    the first live span; cheap after the first call."""
    global _WD_CHECKED, _ATEXIT_REGISTERED, _ANNOTATION
    if _WD_CHECKED:
        return
    from jax.profiler import TraceAnnotation

    _ANNOTATION = TraceAnnotation
    _WD_CHECKED = True
    if _retrace_limit() > 0:
        install_retrace_watchdog()
    with _RLOCK:
        if not _ATEXIT_REGISTERED:
            _ATEXIT_REGISTERED = True
            atexit.register(_atexit_flush)


# --------------------------------------------------------------------------
# HBM accounting
# --------------------------------------------------------------------------


def record_hbm_estimate(site: str, nbytes: float) -> None:
    """File a budget resolver's peak HBM estimate (``site`` is
    ``gang_fit`` / ``tree_batch`` / ``stream_stage`` /
    ``serve_registry``) next to the backend's live bytes-in-use where
    reported. No-op while nothing records (tracing disabled, no ops
    plane), so budget resolution stays allocation-free by default."""
    if not _recording():
        return
    gauge("hbm_budget_bytes").set(float(nbytes), site=site)
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        if stats and "bytes_in_use" in stats:
            gauge("hbm_live_bytes").set(
                float(stats["bytes_in_use"]), site=site
            )
    except Exception:  # backends without memory_stats
        pass
