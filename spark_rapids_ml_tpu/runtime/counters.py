"""Process-wide resilience counters (back-compat shim).

The original int-dict registry this module held now lives in the typed
metrics layer (:mod:`runtime.telemetry`), with the metric catalog in
:mod:`runtime.metricspec` — gauge-vs-counter semantics are a property
of the registered metric, not a name check here. This shim keeps the
API every call site and test already uses (``bump`` / ``note`` /
``get`` / ``snapshot`` / ``delta_since`` / ``reset``), so
``core``'s ``_resilience_report`` keeps its ``retries`` /
``resumed_from`` deltas and tests can still assert the clean path is
fully inert (all deltas zero).

Names bumped through this shim must be declared in
``runtime/metricspec.py`` — lint rule TPU007 rejects uncataloged metric
names in repo code (the counter analog of TPU002's env/doc drift rule).
"""

from __future__ import annotations

from typing import Dict

from . import telemetry


def bump(name: str, by: int = 1) -> None:
    """Increment counter ``name`` by ``by`` (creates it at 0)."""
    telemetry._legacy_metric(name, "counter").inc(int(by))


def note(name: str, value: int) -> None:
    """Set gauge ``name`` to ``value`` (last-write-wins semantics)."""
    telemetry._legacy_metric(name, "gauge").set(int(value))


def get(name: str) -> int:
    return int(telemetry._legacy_snapshot().get(name, 0))


def snapshot() -> Dict[str, int]:
    """A point-in-time copy of every legacy-visible counter/gauge."""
    return telemetry._legacy_snapshot()


def delta_since(base: Dict[str, int]) -> Dict[str, int]:
    """Counter changes since ``base`` (a prior :func:`snapshot`).

    Gauges are reported as their current value when it changed; plain
    counters as the difference — decided by each metric's registered
    kind (``metricspec`` / the live registry), not its name. Keys with
    zero delta are omitted so the clean path reports ``{}``.
    """
    cur = snapshot()
    out: Dict[str, int] = {}
    for name, value in cur.items():
        if telemetry.metric_kind(name) == "gauge":
            if value != base.get(name, 0):
                out[name] = value
        else:
            d = value - base.get(name, 0)
            if d:
                out[name] = d
    return out


def reset() -> None:
    """Zero every counter (test isolation)."""
    telemetry._reset_metrics()
