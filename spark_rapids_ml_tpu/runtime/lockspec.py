"""Typed catalog of every named lock in ``runtime/`` and ``serving/``.

Single source of truth for the repo's lock hierarchy — the concurrency
analog of :mod:`metricspec` for metric names. Each entry gives a lock a
stable dotted name, a **rank**, and its declared home (module / class /
attribute). The rank is the lock-order discipline: a thread may only
acquire a lock whose rank is *strictly greater* than every lock it
already holds. Two enforcement layers read this catalog:

- ``tpuml_lint`` rule TPU010 (static): nested ``with`` acquisitions in
  one function body must ascend in rank, every lock constructed in
  ``runtime/``/``serving/`` must go through :mod:`runtime.lockwitness`
  with a name declared here, and a cataloged name must be constructed
  in its declared module.
- :mod:`runtime.lockwitness` (runtime, opt-in via
  ``TPUML_LOCK_WITNESS``): checks the same rank discipline on the real
  per-thread acquisition order, across call boundaries the AST pass
  cannot see.

Deliberately stdlib-only (no jax/numpy, no relative imports): the
linter loads this file directly via ``importlib`` without importing the
package, so the hierarchy check runs even where jax does not.

Rank bands (outermost first — the order a request naturally descends):

====  ====================================================derived
10    ops-plane coordinator (owns subsystem refs + thread startup)
20s   lifecycle (swap/canary/drift orchestration)
30s   fit scheduler (queue state, breaker map)
36-47 serving data plane (router fleet, runtime, replicas)
50s   model registry + admission primitives
70s   SLO evaluator state
80s   fault injection, autotuner
88+   flight recorder + telemetry registries (innermost leaves —
      every layer above records metrics/spans while holding its own
      lock, so these must never wrap a call back out)
====  ====================================================
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

KINDS = ("lock", "rlock", "condition")


@dataclass(frozen=True)
class LockSpec:
    """One cataloged lock. ``kind`` is lock|rlock|condition."""

    name: str
    rank: int
    kind: str
    # declared home: repo-relative module path, owning class ("" for
    # module level), and attribute name. TPU010 rejects a cataloged
    # name constructed outside its declared module.
    module: str
    cls: str
    attr: str
    doc: str


def _registry(*specs: LockSpec) -> Dict[str, LockSpec]:
    out: Dict[str, LockSpec] = {}
    ranks: Dict[int, str] = {}
    for s in specs:
        assert s.kind in KINDS, f"{s.name}: bad kind {s.kind}"
        assert s.name not in out, f"duplicate registration {s.name}"
        assert s.rank not in ranks, (
            f"{s.name}: rank {s.rank} already held by {ranks[s.rank]} — "
            "ranks are unique so every ordering question has one answer"
        )
        out[s.name] = s
        ranks[s.rank] = s.name
    return out


_RT = "spark_rapids_ml_tpu/runtime"
_SV = "spark_rapids_ml_tpu/serving"

SPEC: Dict[str, LockSpec] = _registry(
    # --- ops-plane coordinator (outermost) --------------------------------
    LockSpec(
        "opsplane.plane", 10, "rlock", f"{_RT}/opsplane.py", "", "_LOCK",
        "Ops-plane module state: server/evaluator startup, tracked "
        "subsystem refs. Outermost — holders start threads and walk "
        "every subsystem's status hooks.",
    ),
    # --- continuous-training lifecycle ------------------------------------
    LockSpec(
        "lifecycle.manager", 20, "rlock",
        f"{_SV}/lifecycle.py", "ModelLifecycle", "_lock",
        "Lifecycle orchestration state (versions, canaries, breakers); "
        "holders call into the scheduler and registry below.",
    ),
    LockSpec(
        "lifecycle.canary", 22, "lock",
        f"{_SV}/lifecycle.py", "_Canary", "lock",
        "One canary's mirrored-pair tally.",
    ),
    LockSpec(
        "lifecycle.drift", 24, "lock",
        f"{_SV}/lifecycle.py", "_DriftState", "lock",
        "One model's drift baseline/window accumulators.",
    ),
    # --- fit scheduler -----------------------------------------------------
    LockSpec(
        "scheduler.state", 30, "lock",
        f"{_RT}/scheduler.py", "FitScheduler", "_lock",
        "Scheduler queue/dispatcher state; also the lock under the "
        "scheduler's Condition (`_cv` shares it).",
    ),
    LockSpec(
        "scheduler.breakers", 32, "lock",
        f"{_RT}/scheduler.py", "FitScheduler", "_block",
        "Per-tenant breaker map; `submit` takes it while holding "
        "`scheduler.state` (the one sanctioned scheduler nesting).",
    ),
    # --- serving data plane ------------------------------------------------
    LockSpec(
        "router.fleet", 36, "lock",
        f"{_SV}/router.py", "Router", "_lock",
        "Router replica table + health/ordering state; replica calls "
        "(which take the locks below) happen outside it.",
    ),
    LockSpec(
        "serving.state", 40, "lock",
        f"{_SV}/runtime.py", "ServingRuntime", "_lock",
        "ServingRuntime buckets/admission/shutdown state.",
    ),
    LockSpec(
        "serving.shadow", 42, "lock",
        f"{_SV}/runtime.py", "_ShadowRoute", "lock",
        "One shadow route's mirrored-tally state.",
    ),
    LockSpec(
        "serving.idle", 44, "condition",
        f"{_SV}/runtime.py", "ServingRuntime", "_idle",
        "Idle/backpressure waiters; briefly taken with `serving.state` "
        "held on the enqueue path.",
    ),
    LockSpec(
        "router.replica_proc", 46, "lock",
        f"{_SV}/router.py", "SubprocessReplica", "_plock",
        "One subprocess replica's lifecycle (spawn/kill/restart).",
    ),
    LockSpec(
        "router.replica_wire", 47, "lock",
        f"{_SV}/router.py", "SubprocessReplica", "_wlock",
        "One subprocess replica's wire protocol (framed writes).",
    ),
    # --- registry + admission ----------------------------------------------
    LockSpec(
        "registry.models", 50, "rlock",
        f"{_SV}/registry.py", "ModelRegistry", "_lock",
        "Model registry entries/budget; warmup and swap stage work run "
        "outside it, metric filing happens under it.",
    ),
    LockSpec(
        "admission.controller", 54, "lock",
        f"{_SV}/admission.py", "AdmissionController", "_lock",
        "Admission controller's per-model breaker map.",
    ),
    LockSpec(
        "admission.ewma", 56, "lock",
        f"{_RT}/admission.py", "ServiceEwma", "_lock",
        "One service-time EWMA accumulator.",
    ),
    LockSpec(
        "admission.breaker", 58, "lock",
        f"{_RT}/admission.py", "CircuitBreaker", "_lock",
        "One circuit breaker's state machine; the state-change callback "
        "(telemetry gauge) fires under it.",
    ),
    # --- SLO evaluator ------------------------------------------------------
    LockSpec(
        "opsplane.slo", 72, "lock",
        f"{_RT}/opsplane.py", "_SloEvaluator", "_state_lock",
        "SLO burn-rate evaluator tick state; holders snapshot the "
        "telemetry registry and may trigger a flight dump.",
    ),
    # --- fault injection + autotuner ---------------------------------------
    LockSpec(
        "faults.plan", 80, "lock",
        f"{_RT}/faults.py", "FaultInjector", "_lock",
        "One fault injector's hit counters and pending actions.",
    ),
    LockSpec(
        "faults.cache", 81, "lock",
        f"{_RT}/faults.py", "", "_cache_lock",
        "The process-wide parsed-plan cache.",
    ),
    LockSpec(
        "autotune.file", 82, "lock",
        f"{_RT}/autotune.py", "", "_FILE_LOCK",
        "Serializes the tuning-cache file's read-merge-replace cycle so "
        "concurrent in-process stores cannot drop each other's entries; "
        "held only around local file I/O, never around probes. Below "
        "autotune.cache: the merge's corrupt-file path takes the "
        "warn-once lock while holding this one.",
    ),
    LockSpec(
        "autotune.cache", 83, "lock",
        f"{_RT}/autotune.py", "", "_LOCK",
        "Autotuner in-memory cache + probe bookkeeping; holders may "
        "file autotune metrics (telemetry band below) but never call "
        "back out into dispatch layers.",
    ),
    # --- flight recorder + telemetry (innermost leaves) --------------------
    LockSpec(
        "opsplane.flight", 88, "lock",
        f"{_RT}/opsplane.py", "FlightRecorder", "_lock",
        "Flight-recorder ring. Near-innermost: the recorder is a span "
        "sink, so any thread may reach it while holding its own "
        "subsystem lock mid-span-close.",
    ),
    LockSpec(
        "telemetry.metrics", 90, "rlock",
        f"{_RT}/telemetry.py", "", "_MLOCK",
        "The typed metric registry. Innermost band: every layer above "
        "records metrics while holding its own lock.",
    ),
    LockSpec(
        "telemetry.trace", 91, "lock",
        f"{_RT}/telemetry.py", "", "_RLOCK",
        "Span/trace buffers and sink list.",
    ),
    LockSpec(
        "telemetry.watchdog", 92, "lock",
        f"{_RT}/telemetry.py", "", "_WD_LOCK",
        "Retrace-watchdog per-site compile counts.",
    ),
)


def registered_names() -> Tuple[str, ...]:
    return tuple(SPEC)
