"""Micro-batched request queue over the serving registry.

Concurrent ``predict()`` calls coalesce inside a bounded batch window
(``TPUML_SERVE_BATCH_WINDOW_US``) and dispatch as a small fixed set of
padded power-of-two bucket shapes (``TPUML_SERVE_MAX_BUCKET_ROWS``
caps the ladder), so the compile cache stays bounded no matter what
request shapes arrive — the retrace watchdog's ``retrace_storms == 0``
is the enforced steady-state contract.

Bit-identity contract (tested per family in ``tests/test_serving.py``):

- Padding duplicates a real request row and the pad tail is sliced off
  before results route back, so a coalesced request's outputs are
  bit-identical to a direct ``model.transform`` of the same rows —
  XLA's row-wise kernels are padding- and offset-invariant for >= 2
  rows.
- Single-row requests dispatch at their exact shape: XLA lowers an
  (1, d) matmul to a gemv specialization whose accumulation order
  differs from the gemm used at any padded width (~1e-5 divergence),
  so padding a 1-row request would break bitwise parity.
- UMAP requests never coalesce: the transform refine draws
  negative-sample offsets from ``[0, n_rows)`` and normalizes edge
  weights by a batch-global max, so ANY row-count change perturbs
  every output row. UMAP's fast path is residency (frozen training
  table + memoized IVF index built once, see ``umap.ivf_build``).

Overload & failure behavior (tested in
``tests/test_serving_resilience.py``, see ``docs/serving.md``):

- Every request may carry a deadline (``deadline_ms=`` or
  ``TPUML_SERVE_DEFAULT_DEADLINE_MS``); a request whose deadline
  expires while queued fails with :class:`DeadlineExceeded` *before*
  padding/dispatch, and the packer orders earliest-deadline-first
  (stable within arrival order) so a tight deadline is never parked
  behind a loose one.
- Admission (``serving/admission.py``) sheds with :class:`Overloaded`
  at enqueue when the queue is full, the wait estimate already blows
  the deadline, or the model's circuit breaker is open.
- Group dispatch runs through ``retry.with_retries``;
  ``RESOURCE_EXHAUSTED`` splits the group and retries halves at exact
  shapes (the PR-3 halving contract), never re-padding a failed shape.
- The dispatcher is crash-proof: an unexpected dispatch exception
  fails that batch's futures, bumps ``serve_dispatch_errors_total``,
  and the loop keeps serving. ``drain()``/``close()`` resolve every
  outstanding future (typed :class:`ShuttingDown`) — no future ever
  hangs, including requests racing ``close()``.
"""

from __future__ import annotations

import logging
import math
import queue
import threading
import time
from dataclasses import dataclass, field
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

import numpy as np

from ..runtime import (
    autotune,
    envspec,
    faults,
    lockwitness,
    opsplane,
    retry,
    telemetry,
)
from .admission import (
    AdmissionController,
    DeadlineExceeded,
    Overloaded,
    ServingError,
    ShuttingDown,
)
from .registry import MIN_BUCKET_ROWS, ModelRegistry, ResidentModel

__all__ = [
    "ServingRuntime",
    "ServingError",
    "DeadlineExceeded",
    "Overloaded",
    "ShuttingDown",
]

logger = logging.getLogger("spark_rapids_ml_tpu.serving.runtime")

# dispatcher wakes at least this often while idle so the
# loop_heartbeat_ts age stays a liveness signal (a dead thread's age
# grows; a merely idle one beats ~1 Hz)
_IDLE_TICK_S = 1.0


@dataclass
class _Request:
    name: str
    X: np.ndarray
    future: "Future[Dict[str, np.ndarray]]"
    t_enqueue: float = field(default_factory=time.perf_counter)
    deadline: Optional[float] = None  # absolute perf_counter seconds
    settled: bool = False

    @property
    def rows(self) -> int:
        return int(self.X.shape[0])


_SHUTDOWN = object()


@dataclass
class _ShadowRoute:
    """Mirror a deterministic fraction of one model's admitted traffic
    to a shadow entry (the canary candidate). Callers always receive
    the LIVE entry's output — the shadow future is observed only by
    ``on_pair`` — so canarying never perturbs served bits."""

    alias: str
    fraction: float
    # called with (live_out, shadow_out) when both sides of a mirrored
    # request resolve; a failed side passes None
    on_pair: Optional[Any] = None
    count: int = 0
    lock: Any = field(
        default_factory=lambda: lockwitness.make_lock("serving.shadow")
    )

    def take(self) -> bool:
        """Deterministic request picker: mirror request n exactly when
        ``floor(n * fraction)`` advances — no RNG (the TPU004 house
        rule), and any window of requests mirrors within one request of
        the configured fraction."""
        with self.lock:
            self.count += 1
            n = self.count
        return int(n * self.fraction) > int((n - 1) * self.fraction)


def _bucket_rows(n: int, max_bucket: int) -> int:
    """Padded row count for an ``n``-row dispatch: next power of two,
    floored at MIN_BUCKET_ROWS, capped at the ladder top (grouping
    never exceeds the cap; an oversized single request runs exact)."""
    if n >= max_bucket:
        return n
    b = MIN_BUCKET_ROWS
    while b < n:
        b <<= 1
    return b


class ServingRuntime:
    """The online serving facade: a registry of device-resident models
    plus one dispatcher thread micro-batching concurrent requests.

    Explicit-construction only — building this object is the opt-in.
    ``with ServingRuntime() as rt: rt.register(...); rt.predict(...)``.
    """

    def __init__(
        self,
        registry: Optional[ModelRegistry] = None,
        batch_window_us: Optional[int] = None,
        max_bucket_rows: Optional[int] = None,
        warmup: Optional[bool] = None,
        queue_limit: Optional[int] = None,
        default_deadline_ms: Optional[float] = None,
        breaker_fails: Optional[int] = None,
        breaker_cooldown_ms: Optional[float] = None,
        rank: Optional[int] = None,
    ) -> None:
        # replica identity under a pod-scale router (serving/router.py):
        # stamps this runtime's warmup spans and residency reports with
        # its rank. None (the default) is byte-identical single-replica
        # serving.
        self.rank = None if rank is None else int(rank)
        self._rank_tag = "" if rank is None else f".r{int(rank)}"
        self.registry = registry or ModelRegistry(
            warmup=warmup, max_bucket_rows=max_bucket_rows, rank=rank
        )
        window_us = (
            int(envspec.get("TPUML_SERVE_BATCH_WINDOW_US"))
            if batch_window_us is None else int(batch_window_us)
        )
        if (
            batch_window_us is None
            and not envspec.is_set("TPUML_SERVE_BATCH_WINDOW_US")
            and autotune.active()
        ):
            # consult-only: the window trades p99 against batch fill, so
            # a winner needs both ends of the trade measured — never from
            # inside a live runtime's constructor; nothing in the tree
            # writes this entry (ROADMAP.md D14)
            tune_key = autotune.shape_key(k=MIN_BUCKET_ROWS)
            tuned = autotune.consult("serve_batch_window_us", tune_key)
            if isinstance(tuned, int) and 0 <= tuned <= 100_000:
                window_us = tuned
            else:
                autotune.record_heuristic(
                    "serve_batch_window_us", tune_key, window_us
                )
        self._window_s = window_us / 1e6
        default_deadline_ms = (
            envspec.get("TPUML_SERVE_DEFAULT_DEADLINE_MS")
            if default_deadline_ms is None else float(default_deadline_ms)
        )
        self._default_deadline_s = (
            None if default_deadline_ms is None else default_deadline_ms / 1e3
        )
        self.admission = AdmissionController(
            queue_limit=queue_limit,
            breaker_fails=breaker_fails,
            breaker_cooldown_ms=breaker_cooldown_ms,
        )
        self._queue: "queue.Queue[Any]" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._draining = False
        self._lock = lockwitness.make_lock("serving.state")
        # outstanding (admitted, unresolved) requests; the condition
        # lets drain() wait for the dispatcher to finish in-flight work
        self._pending = 0
        self._idle = lockwitness.make_condition("serving.idle")
        self._inflight: List[_Request] = []
        self._last_beat: Optional[float] = None
        # lifecycle hooks, both empty (and cost-free) by default:
        # result observers see every successful dispatch's host outputs
        # (drift gauges); shadow routes mirror a traffic fraction to a
        # canary entry without touching what callers receive
        self._observers: List[Any] = []
        self._shadows: Dict[str, _ShadowRoute] = {}

    # -- lifecycle ---------------------------------------------------------
    def __enter__(self) -> "ServingRuntime":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    def start(self) -> None:
        # a long-lived serving process is exactly what the ops plane
        # exists for: make it scrape-able (no-op unless opted in) and
        # let /statusz read the live queue depth
        opsplane.ensure_started()
        opsplane.track_runtime(self)
        with self._lock:
            if self._thread is not None or self._closed:
                return
            # spans opened on the dispatcher inherit the constructor's
            # context so traces nest under the caller's span, if any
            self._thread = threading.Thread(
                target=telemetry.bind_context(self._serve_loop),
                name="tpuml-serve-dispatch",
                daemon=True,
            )
            self._thread.start()

    def close(self) -> None:
        """Stop immediately: no new admissions, dispatcher exits after
        the batch it is on, anything still queued resolves with
        :class:`ShuttingDown`. Use :meth:`drain` to finish queued work
        first."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            t = self._thread
        if t is not None:
            self._queue.put(_SHUTDOWN)
            t.join()
        self._abort_outstanding()

    def drain(self, timeout: float = 30.0) -> Dict[str, Any]:
        """Graceful shutdown: stop admission (``/readyz`` goes 503 and
        new ``predict`` calls raise :class:`ShuttingDown`), let the
        dispatcher flush everything already admitted, then close. Any
        request still unresolved at ``timeout`` — including a batch
        wedged inside a device call — is failed with
        :class:`ShuttingDown`; this never hangs past the timeout and
        never strands a future."""
        with self._lock:
            if self._closed:
                return {"drained": True, "aborted": 0}
            self._draining = True
            t = self._thread
        deadline = time.monotonic() + max(0.0, float(timeout))
        with self._idle:
            while self._pending > 0:
                remain = deadline - time.monotonic()
                if remain <= 0:
                    break
                self._idle.wait(min(remain, 0.1))
        with self._lock:
            if self._closed:  # lost a race against close()/second drain
                return {"drained": True, "aborted": 0}
            self._closed = True
        if t is not None:
            self._queue.put(_SHUTDOWN)
            # bounded join: a dispatcher wedged in entry.fn must not
            # turn drain into the hang it exists to prevent
            t.join(timeout=max(0.5, deadline - time.monotonic() + 0.5))
        aborted = self._abort_outstanding()
        if t is not None and t.is_alive():
            # the wedged dispatcher's sentinel was swept up with the
            # aborted queue; re-arm it so the thread exits if its
            # device call ever returns
            self._queue.put(_SHUTDOWN)
        return {"drained": aborted == 0, "aborted": aborted}

    def _abort_outstanding(self) -> int:
        """Resolve every still-unsettled request (queued or in-flight)
        with :class:`ShuttingDown`. Safe against the dispatcher racing
        a late resolution — ``_settle`` is first-writer-wins."""
        n = 0
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _SHUTDOWN:
                continue
            self._settle(
                item,
                exc=ShuttingDown(
                    "ServingRuntime is closed; request aborted before dispatch"
                ),
            )
            n += 1
        for r in list(self._inflight):
            if self._settle(
                r,
                exc=ShuttingDown(
                    "ServingRuntime is closed; request aborted mid-dispatch"
                ),
            ):
                n += 1
        return n

    # -- registry passthrough ---------------------------------------------
    def register(self, name: str, model: Any) -> ResidentModel:
        return self.registry.register(name, model)

    def load(self, name: str, path: str) -> ResidentModel:
        return self.registry.load(name, path)

    def swap(
        self, name: str, model: Any = None, path: Optional[str] = None,
    ) -> ResidentModel:
        """Zero-downtime hot-swap of ``name`` to a new version (see
        :meth:`ModelRegistry.swap`): the dispatcher keeps serving vN
        while vN+1 stages and warms; in-flight and queued requests are
        never shed — each dispatched batch resolves its entry once, so
        requests ride whichever version is routed at dispatch time."""
        return self.registry.swap(name, model=model, path=path)

    # -- lifecycle hooks ----------------------------------------------------
    def add_result_observer(self, fn: Any) -> None:
        """Register ``fn(entry, host)`` to be called after every
        successful group dispatch with the valid-row host outputs (pad
        tail already sliced). Observer failures are logged, never
        propagated — observation must not fail serving."""
        self._observers.append(fn)

    def remove_result_observer(self, fn: Any) -> None:
        try:
            self._observers.remove(fn)
        except ValueError:
            pass

    def set_shadow(
        self,
        name: str,
        alias: str,
        fraction: float,
        on_pair: Optional[Any] = None,
    ) -> None:
        """Mirror ``fraction`` of ``name``'s admitted requests to the
        registered entry ``alias``. Mirrored requests are fire-and-
        forget copies: callers still get (only) the live entry's
        output, and a shed/failed mirror never surfaces to them."""
        if not 0.0 < fraction <= 1.0:
            raise ValueError(
                f"shadow fraction must be in (0, 1], got {fraction}"
            )
        if alias == name:
            raise ValueError("shadow alias must differ from the live name")
        self._shadows[name] = _ShadowRoute(
            alias=alias, fraction=float(fraction), on_pair=on_pair
        )

    def clear_shadow(self, name: str) -> None:
        self._shadows.pop(name, None)

    def shadow_routes(self) -> Dict[str, str]:
        return {n: s.alias for n, s in self._shadows.items()}

    # -- request surface ---------------------------------------------------
    def predict_async(
        self,
        name: str,
        X: np.ndarray,
        deadline_ms: Optional[float] = None,
    ) -> "Future[Dict[str, np.ndarray]]":
        """Enqueue one request; the future resolves to the model's
        output-column dict with exactly ``X.shape[0]`` rows per column.

        ``deadline_ms`` (default ``TPUML_SERVE_DEFAULT_DEADLINE_MS``;
        unset = wait forever) bounds queue time: admission sheds with
        :class:`Overloaded` when the deadline is already unmeetable,
        and an admitted request whose deadline passes before dispatch
        fails with :class:`DeadlineExceeded`."""
        if self._closed:
            raise ShuttingDown()
        self.start()
        X = np.asarray(X)
        if X.ndim != 2 or X.shape[0] == 0:
            raise ValueError(
                f"predict expects a non-empty (n, d) batch, got {X.shape}"
            )
        entry = self.registry.get(name)  # KeyError before enqueue
        if entry.model._float32_inputs:
            X = np.ascontiguousarray(X, dtype=np.float32)
        else:
            X = np.ascontiguousarray(X)
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        deadline_s = (
            self._default_deadline_s if deadline_ms is None
            else deadline_ms / 1e3
        )
        now = time.perf_counter()
        fut: "Future[Dict[str, np.ndarray]]" = Future()
        req = _Request(
            name=name, X=X, future=fut, t_enqueue=now,
            deadline=None if deadline_s is None else now + deadline_s,
        )
        # admission and enqueue are one atomic step against close():
        # once _closed is set under this lock, nothing lands behind the
        # shutdown sentinel (the old hung-future race)
        with self._lock:
            if self._closed:
                raise ShuttingDown()
            if self._draining:
                telemetry.counter("serve_shed_total").inc(
                    1, model=name, reason="draining"
                )
                raise ShuttingDown(
                    "ServingRuntime is closed to new requests (draining)"
                )
            self.admission.admit(name, self._queue.qsize(), deadline_s)
            faults.fault_site("serve:admit")
            with self._idle:
                self._pending += 1
            self._queue.put(req)
        telemetry.counter("serve_requests_total").inc(1, model=name)
        shadow = self._shadows.get(name)
        if shadow is not None and shadow.take():
            # outside self._lock (non-reentrant): the mirrored enqueue
            # re-enters predict_async for the alias
            self._mirror(shadow, name, X, fut, deadline_ms)
        return fut

    def _mirror(
        self,
        shadow: _ShadowRoute,
        name: str,
        X: np.ndarray,
        live_fut: "Future[Dict[str, np.ndarray]]",
        deadline_ms: Optional[float],
    ) -> None:
        """Fire the shadow copy of an admitted request and pair the two
        futures for ``on_pair`` scoring. Best-effort by design: a
        mirror the alias cannot admit (breaker, queue, drain) is
        dropped silently — shadow load must never shed live traffic or
        surface canary errors to callers."""
        try:
            shadow_fut = self.predict_async(
                shadow.alias, X, deadline_ms=deadline_ms
            )
        except Exception:
            return
        telemetry.counter("canary_requests_total").inc(1, model=name)
        cb = shadow.on_pair
        if cb is None:
            return
        state: Dict[str, Any] = {}
        state_lock = threading.Lock()

        def _settle_pair(side: str, fut: "Future[Dict[str, np.ndarray]]") -> None:
            try:
                out: Optional[Dict[str, np.ndarray]] = fut.result()
            except BaseException:
                out = None  # a failed side scores as missing, not fatal
            with state_lock:
                state[side] = out
                if len(state) < 2:
                    return
            try:
                cb(state["live"], state["shadow"])
            except Exception:
                logger.exception("serving: shadow pair callback failed")

        live_fut.add_done_callback(lambda f: _settle_pair("live", f))
        shadow_fut.add_done_callback(lambda f: _settle_pair("shadow", f))

    def predict(
        self,
        name: str,
        X: np.ndarray,
        timeout: Optional[float] = None,
        deadline_ms: Optional[float] = None,
    ) -> Dict[str, np.ndarray]:
        return self.predict_async(name, X, deadline_ms=deadline_ms).result(
            timeout
        )

    def queue_depth(self) -> int:
        """Requests waiting right now (the live reading behind
        `/statusz`, vs the per-drain `serve_queue_depth` gauge)."""
        return self._queue.qsize()

    # -- introspection (ops plane) ----------------------------------------
    def is_closed(self) -> bool:
        return self._closed

    def is_draining(self) -> bool:
        return self._draining and not self._closed

    def dispatcher_alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def dispatcher_started(self) -> bool:
        return self._thread is not None

    def heartbeat_age_s(self) -> Optional[float]:
        beat = self._last_beat
        return None if beat is None else max(0.0, time.monotonic() - beat)

    def breaker_states(self) -> Dict[str, str]:
        return self.admission.breaker_states()

    # -- request settlement ------------------------------------------------
    def _settle(
        self,
        req: _Request,
        *,
        result: Optional[Dict[str, np.ndarray]] = None,
        exc: Optional[BaseException] = None,
    ) -> bool:
        """Resolve a request exactly once (first writer wins) and
        release its slot in the pending count."""
        with self._idle:
            if req.settled:
                return False
            req.settled = True
            self._pending -= 1
            if self._pending <= 0:
                self._idle.notify_all()
        try:
            if exc is not None:
                req.future.set_exception(exc)
            else:
                req.future.set_result(result)
        except Exception:  # future cancelled by the caller: settled anyway
            pass
        return True

    # -- dispatcher --------------------------------------------------------
    def _beat(self) -> None:
        self._last_beat = time.monotonic()
        telemetry.gauge("loop_heartbeat_ts").set(
            self._last_beat, loop="serve_dispatch"
        )

    def _serve_loop(self) -> None:
        # crash-proof: an exception escaping a tick fails at most that
        # tick's batch (handled in _dispatch_safe); anything escaping
        # even that is counted and the loop restarts — the dispatcher
        # never dies silently while predict_async keeps enqueueing
        while True:
            try:
                if self._serve_tick():
                    return
            except Exception:
                telemetry.counter("serve_dispatch_errors_total").inc()
                logger.exception(
                    "serving: dispatcher tick failed — restarting loop"
                )

    def _serve_tick(self) -> bool:
        """One drain-coalesce-dispatch cycle; True = shutdown."""
        self._beat()
        try:
            item = self._queue.get(timeout=_IDLE_TICK_S)
        except queue.Empty:
            return False
        if item is _SHUTDOWN:
            return True
        batch: List[_Request] = [item]
        deadline = time.perf_counter() + self._window_s
        stop = False
        while True:
            remain = deadline - time.perf_counter()
            if remain <= 0:
                # window closed — still sweep anything already queued
                # (coalesces the backlog under sustained load)
                try:
                    while True:
                        nxt = self._queue.get_nowait()
                        if nxt is _SHUTDOWN:
                            stop = True
                            break
                        batch.append(nxt)
                except queue.Empty:
                    pass
                break
            try:
                nxt = self._queue.get(timeout=remain)
            except queue.Empty:
                break
            if nxt is _SHUTDOWN:
                stop = True
                break
            batch.append(nxt)
        telemetry.gauge("serve_queue_depth").set(self._queue.qsize())
        self._inflight = batch
        try:
            self._dispatch_safe(batch)
        finally:
            self._inflight = []
        return stop

    def _dispatch_safe(self, batch: List[_Request]) -> None:
        try:
            self._dispatch(batch)
        except Exception as e:
            # unexpected dispatch failure (bug or injected chaos): fail
            # this batch's futures, count it, keep the loop alive
            telemetry.counter("serve_dispatch_errors_total").inc()
            logger.exception(
                "serving: dispatch failed; failing %d request(s)", len(batch)
            )
            for r in batch:
                self._settle(r, exc=e)

    def _dispatch(self, batch: List[_Request]) -> None:
        by_model: "Dict[str, List[_Request]]" = {}
        for r in batch:
            by_model.setdefault(r.name, []).append(r)
        for name, reqs in by_model.items():
            try:
                entry = self.registry.get(name)
            except Exception as e:
                for r in reqs:
                    self._settle(r, exc=e)
                continue
            reqs = self._filter_deadlines(entry, reqs)
            for group in self._group(entry, reqs):
                self._run_group(entry, group)

    def _filter_deadlines(
        self, entry: ResidentModel, reqs: List[_Request]
    ) -> List[_Request]:
        """Fail deadline-missed requests BEFORE padding/dispatch: an
        expired request never costs device time, and a request whose
        remaining budget is under the model's EWMA batch service time
        is failed now rather than packed into a group it cannot make."""
        now = time.perf_counter()
        est = self.admission.service_estimate_s(entry.name)
        live: List[_Request] = []
        for r in reqs:
            if r.deadline is None:
                live.append(r)
                continue
            remain = r.deadline - now
            if remain <= 0:
                msg = (
                    f"deadline expired {-remain * 1e3:.1f} ms before "
                    f"dispatch (model {entry.name!r})"
                )
            elif est is not None and remain < est:
                msg = (
                    f"remaining deadline {remain * 1e3:.1f} ms is under "
                    f"the estimated batch service time {est * 1e3:.1f} ms "
                    f"(model {entry.name!r})"
                )
            else:
                live.append(r)
                continue
            telemetry.counter("serve_deadline_miss_total").inc(
                1, model=entry.name
            )
            self._settle(r, exc=DeadlineExceeded(msg))
        return live

    def _group(
        self, entry: ResidentModel, reqs: List[_Request]
    ) -> List[List[_Request]]:
        """Deadline-aware greedy packing into bucket-capped groups:
        earliest-deadline-first, stable within arrival order (the sort
        is a no-op when no request carries a deadline). Non-coalescable
        families and single-row requests dispatch alone (the
        bit-identity contract, see the module docstring)."""
        reqs = sorted(
            reqs,
            key=lambda r: math.inf if r.deadline is None else r.deadline,
        )
        max_bucket = self.registry.max_bucket_rows
        groups: List[List[_Request]] = []
        cur: List[_Request] = []
        cur_rows = 0
        for r in reqs:
            if not entry.coalesce or r.rows < 2 or r.rows > max_bucket:
                groups.append([r])
                continue
            if cur and cur_rows + r.rows > max_bucket:
                groups.append(cur)
                cur, cur_rows = [], 0
            cur.append(r)
            cur_rows += r.rows
        if cur:
            groups.append(cur)
        return groups

    def _run_group(
        self,
        entry: ResidentModel,
        group: List[_Request],
        pad_ok: bool = True,
    ) -> None:
        n = sum(r.rows for r in group)
        # pad only shapes the contract allows: coalescable family and
        # >= 2 valid rows (a lone 1-row or oversized request runs exact);
        # halved retry groups run exact too (pad_ok=False) — re-padding
        # a shape that just OOMed would retry the same allocation
        pad = (
            pad_ok and entry.coalesce
            and 2 <= n <= self.registry.max_bucket_rows
        )
        bucket = _bucket_rows(n, self.registry.max_bucket_rows) if pad else n
        t0 = time.perf_counter()
        try:
            X = (
                group[0].X if len(group) == 1
                else np.concatenate([r.X for r in group], axis=0)
            )
            if bucket > n:
                # pad by duplicating a real row: finite values, no
                # NaN/Inf poisoning, and row-wise kernels ignore rows
                # they don't emit
                X = np.concatenate(
                    [X, np.repeat(X[:1], bucket - n, axis=0)], axis=0
                )
            # a cold (model, bucket) pays its XLA compiles under a
            # dedicated warmup site; the steady-state `serve.batch` site
            # must attribute ZERO compiles (retrace_storms == 0 gate)
            attrs = dict(
                model=entry.name, rows=n, bucket=bucket,
                fill=round(n / bucket, 4),
            )
            if bucket in entry.warmed:
                span_name = "serve.batch"
            else:
                span_name = (
                    f"serve.warmup.{entry.name}.b{bucket}{self._rank_tag}"
                )
                attrs["warmup"] = True
                entry.warmed.add(bucket)

            def _dispatch_once() -> Dict[str, np.ndarray]:
                faults.fault_site("serve:dispatch")
                with telemetry.span(span_name, **attrs):
                    out = entry.fn(X)
                faults.fault_site("serve:transfer")
                return {k: np.asarray(v)[:n] for k, v in out.items()}

            # transient errors back off per TPUML_RETRIES (default 0 =
            # single attempt); RESOURCE_EXHAUSTED gives up immediately
            # so the halving path below degrades instead of re-failing
            host = retry.with_retries(
                _dispatch_once,
                what=f"serve:{entry.name}",
                giveup=retry.is_resource_exhausted,
            )
        except Exception as e:
            if retry.is_resource_exhausted(e) and len(group) > 1:
                # the PR-3 halving contract, at group granularity:
                # split and retry halves at exact shapes — each half is
                # a strictly smaller allocation, so this terminates
                mid = (len(group) + 1) // 2
                logger.warning(
                    "serving: RESOURCE_EXHAUSTED on %d-row group for %r — "
                    "splitting into %d + %d request(s) at exact shapes",
                    n, entry.name, mid, len(group) - mid,
                )
                telemetry.add_span_event(
                    "serve_group_halved",
                    model=entry.name, rows=n, requests=len(group),
                )
                self._run_group(entry, group[:mid], pad_ok=False)
                self._run_group(entry, group[mid:], pad_ok=False)
                return
            self.admission.breaker(entry.name).record_failure()
            for r in group:
                self._settle(r, exc=e)
            return
        self.admission.breaker(entry.name).record_success()
        self.admission.note_batch(
            entry.name, time.perf_counter() - t0, len(group)
        )
        if self._observers:
            for obs in list(self._observers):
                try:
                    obs(entry, host)
                except Exception:
                    logger.exception(
                        "serving: result observer failed for %r", entry.name
                    )
        telemetry.histogram("serve_batch_fill").observe(
            n / bucket, model=entry.name
        )
        lo = 0
        done = time.perf_counter()
        for r in group:
            hi = lo + r.rows
            self._settle(
                r, result={k: v[lo:hi] for k, v in host.items()}
            )
            telemetry.histogram("serve_p99_ms").observe(
                (done - r.t_enqueue) * 1e3, model=entry.name
            )
            lo = hi
