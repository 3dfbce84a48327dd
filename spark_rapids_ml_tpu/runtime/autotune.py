"""Measured knob autotuner: shape-keyed search with a persistent cache.

ROADMAP item 5. The framework's ``auto`` resolvers (wire dtype, gang
width, tree batch, ANN nlist/nprobe, serve batch window, stream stage
depth) pick values from hand-derived cost models. This module closes
the loop with the hardware's actual answer — the classic empirical-
autotuning move (ATLAS / AutoTVM): measure a small candidate grid with
short dispatches of the real jitted work, keep the winner, and persist
it keyed by the workload shape so the search runs once per
(knob, shape, backend), not once per fit.

Three layers:

- **shape-keyed tuning cache** — one JSON file
  (``autotune-cache.json`` under ``TPUML_AUTOTUNE_CACHE``), written
  atomically (tmp + ``os.replace``) by rank 0 only, keyed by
  ``knob|signature`` where the signature buckets n/d/k to powers of
  two and pins dtype, backend + device kind, and the mesh's dp×mp.
  Corrupt / truncated / concurrently-rewritten files are tolerated:
  the tuner warns **once** and falls back to heuristics — a broken
  cache can slow a fit down, never break it.
- **probe engine** — :func:`probe` runs a successive-halving search
  over a per-knob candidate list. Every measurement executes under an
  ``autotune.probe.<knob>`` span carrying the inheritable
  ``warmup=True`` attr, so probe compiles count in ``xla_compiles``
  but are never scored as retrace storms (the serving-warmup
  contract). The search is wall-clock bounded by
  ``TPUML_AUTOTUNE_BUDGET_MS``; the heuristic default is always
  measured first, so a truncated search can never do worse than no
  tuner. Fitness is measured seconds (lower wins).
- **resolver hook** — :func:`consult` (cache read) and :func:`tune`
  (consult-else-probe) are checked by every ``auto`` resolver before
  its static heuristic, gated by ``TPUML_AUTOTUNE=off|on|force``.
  ``off`` (the default) short-circuits before any cache or file I/O:
  no reads, no probes, bit-identical outputs. ``force`` re-probes
  even over an existing entry. Decisions (value + provenance
  ``cache_hit|probed|heuristic``) are collected per fit into
  ``_fit_report["autotuned"]`` and counted on the
  ``autotune_cache_hits/misses/probes_total`` + ``autotune_probe_ms``
  metrics.

See ``docs/autotune.md`` for the search strategy, shape-signature
semantics, and the measured tuned-vs-default table.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from . import envspec, lockwitness, telemetry

_LOGGER = logging.getLogger("spark_rapids_ml_tpu.autotune")

CACHE_FILENAME = "autotune-cache.json"
CACHE_VERSION = 1

# A candidate must beat the heuristic default by more than this margin
# to displace it: ties (and measurement noise) resolve toward the
# default, so "the default already wins" shows the tuner RETURNING the
# default instead of churning on noise.
DEFAULT_MARGIN = 0.02

_LOCK = lockwitness.make_lock("autotune.cache")
_FILE_LOCK = lockwitness.make_lock("autotune.file")

# in-memory cache state, all guarded by _LOCK:
#   path    — cache file the entries were loaded from (None = memory-only)
#   entries — {"knob|signature": entry dict}
#   loaded  — whether a load was attempted for `path`
_STATE: Dict[str, Any] = {"path": None, "entries": {}, "loaded": False}
_WARNED: set = set()

# per-fit decision collector (contextvar so concurrent scheduler fits
# on different threads collect independently)
_DECISIONS: contextvars.ContextVar[Optional[List[Dict[str, Any]]]] = (
    contextvars.ContextVar("tpuml_autotune_decisions", default=None)
)


# --------------------------------------------------------------------------
# mode gates
# --------------------------------------------------------------------------


def mode() -> str:
    """Validated ``TPUML_AUTOTUNE`` (off | on | force)."""
    return str(envspec.get("TPUML_AUTOTUNE"))


def active() -> bool:
    """True when the tuner may consult the cache or probe. The ``off``
    default returns False before any file or cache access — the
    defaults-inert gate every resolver checks first."""
    return mode() != "off"


def _budget_s() -> float:
    return float(envspec.get("TPUML_AUTOTUNE_BUDGET_MS")) / 1e3


# --------------------------------------------------------------------------
# shape signatures
# --------------------------------------------------------------------------


def _bucket(x: int) -> int:
    """Round up to the next power of two (0 stays 0): workloads whose
    sizes share a pow2 bucket share a tuning entry."""
    x = int(x)
    if x <= 0:
        return 0
    return 1 << (x - 1).bit_length()


def _backend_signature() -> str:
    """``platform:device_kind`` of the live backend; tuned winners never
    travel across device generations."""
    try:
        import jax

        dev = jax.devices()[0]
        kind = str(getattr(dev, "device_kind", dev.platform))
        return f"{dev.platform}:{kind}".replace(" ", "_")
    except Exception:
        return "unknown:unknown"


def _mesh_signature(mesh: Any) -> str:
    if mesh is None:
        return "1x1"
    try:
        dp = int(mesh.shape.get("dp", 1))
        mp = int(mesh.shape.get("mp", 1))
        return f"{dp}x{mp}"
    except Exception:
        return "1x1"


def shape_key(
    *,
    n: int = 0,
    d: int = 0,
    k: int = 0,
    dtype: Any = None,
    mesh: Any = None,
    **extra: Any,
) -> str:
    """Canonical workload-shape signature for one tuning decision.

    ``n``/``d``/``k`` (rows / features / output arity) are bucketed to
    powers of two; ``dtype``, backend + device kind, and the mesh's
    dp×mp are pinned exactly. ``extra`` key=value pairs (sorted) extend
    the signature for knob-specific shape inputs (e.g. tree depth)."""
    parts = [
        f"n={_bucket(n)}",
        f"d={_bucket(d)}",
        f"k={_bucket(k)}",
        f"dtype={str(dtype) if dtype is not None else 'na'}",
        f"backend={_backend_signature()}",
        f"mesh={_mesh_signature(mesh)}",
    ]
    for key in sorted(extra):
        parts.append(f"{key}={extra[key]}")
    return "|".join(parts)


# --------------------------------------------------------------------------
# persistent cache
# --------------------------------------------------------------------------


def _cache_path() -> Optional[str]:
    root = envspec.get("TPUML_AUTOTUNE_CACHE")
    if not root:
        return None
    return os.path.join(str(root), CACHE_FILENAME)


def _warn_once(tag: str, msg: str, *args: Any) -> None:
    with _LOCK:
        if tag in _WARNED:
            return
        _WARNED.add(tag)
    _LOGGER.warning(msg, *args)


def _read_entries(path: str) -> Dict[str, Any]:
    """Parse one cache file; corrupt/partial content degrades to {} with
    a loud-once warning (heuristics are always a safe answer)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        entries = doc.get("entries")
        if doc.get("version") != CACHE_VERSION or not isinstance(entries, dict):
            raise ValueError(
                f"version={doc.get('version')!r} entries={type(entries).__name__}"
            )
        return {
            key: e
            for key, e in entries.items()
            if isinstance(e, dict) and "value" in e
        }
    except FileNotFoundError:
        return {}
    except Exception as e:  # torn write, concurrent writer, hand edits…
        _warn_once(
            f"corrupt:{path}",
            "autotune cache %s is unreadable (%s); ignoring it and "
            "falling back to heuristics — delete or re-probe "
            "(TPUML_AUTOTUNE=force) to rebuild",
            path,
            e,
        )
        return {}


def _entries() -> Dict[str, Any]:
    """The live entry map, (re)loaded when the configured path changed."""
    path = _cache_path()
    with _LOCK:
        if _STATE["loaded"] and _STATE["path"] == path:
            return _STATE["entries"]
    loaded = _read_entries(path) if path else {}
    with _LOCK:
        # keep winners probed in-process before/without a cache file
        loaded.update(
            {
                key: e
                for key, e in _STATE["entries"].items()
                if key not in loaded
            }
        )
        _STATE.update(path=path, entries=loaded, loaded=True)
        return _STATE["entries"]


def _persist(entries: Dict[str, Any]) -> None:
    """Atomic rank-0 write (tmp + rename), merging the on-disk map so
    concurrent processes tuning different knobs both land."""
    path = _cache_path()
    if path is None:
        return
    if int(envspec.get("TPUML_PROC_ID")) != 0:
        return  # rank-0-written, like the trace/metric shard convention
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # the file lock makes read-merge-replace atomic against sibling
        # THREADS; sibling PROCESSES race benignly — os.replace keeps
        # the file valid and a lost entry re-probes next run
        with _FILE_LOCK:
            merged = _read_entries(path)
            merged.update(entries)
            doc = {"version": CACHE_VERSION, "entries": merged}
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except Exception as e:
        _warn_once(
            f"write:{path}",
            "autotune cache %s is unwritable (%s); tuned winners stay "
            "in-process for this run",
            path,
            e,
        )


def cache_key(knob: str, key: str) -> str:
    return f"{knob}|{key}"


def lookup(knob: str, key: str) -> Optional[Dict[str, Any]]:
    """The stored entry for (knob, key), or None. No metrics, no
    provenance — :func:`consult` is the resolver-facing read."""
    return _entries().get(cache_key(knob, key))


# --------------------------------------------------------------------------
# decisions + per-fit collection
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Decision:
    """One resolved knob: what the tuner answered and why."""

    knob: str
    key: str
    value: Any
    provenance: str  # cache_hit | probed | heuristic
    fitness_s: Optional[float] = None
    probe_ms: Optional[float] = None

    def as_report(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "knob": self.knob,
            "key": self.key,
            "value": self.value,
            "provenance": self.provenance,
        }
        if self.fitness_s is not None:
            out["fitness_s"] = round(self.fitness_s, 6)
        if self.probe_ms is not None:
            out["probe_ms"] = round(self.probe_ms, 3)
        return out


@contextlib.contextmanager
def collect() -> Iterator[List[Dict[str, Any]]]:
    """Collect every decision made on this context (fit) into a list —
    the ``_fit_report["autotuned"]`` provenance. Nested collectors see
    only their own scope."""
    sink: List[Dict[str, Any]] = []
    token = _DECISIONS.set(sink)
    try:
        yield sink
    finally:
        _DECISIONS.reset(token)


def _note(decision: Decision) -> None:
    sink = _DECISIONS.get()
    if sink is not None:
        sink.append(decision.as_report())


def record_heuristic(knob: str, key: str, value: Any) -> None:
    """A resolver fell through to its static heuristic while the tuner
    is active: file the provenance so ``autotuned`` reports are
    complete. No-op (and no allocation) when the tuner is off."""
    if not active():
        return
    _note(Decision(knob=knob, key=key, value=value, provenance="heuristic"))


# --------------------------------------------------------------------------
# resolver hooks
# --------------------------------------------------------------------------


def consult(knob: str, key: str) -> Optional[Any]:
    """Cache-read hook every ``auto`` resolver checks before its static
    heuristic. Returns the stored winner or None (miss / tuner off).
    ``force`` mode still answers from the cache here — re-probing is
    the job of the sites that CAN measure (:func:`tune`)."""
    if not active():
        return None
    entry = lookup(knob, key)
    if entry is None:
        telemetry.counter("autotune_cache_misses").inc(1, knob=knob)
        return None
    telemetry.counter("autotune_cache_hits").inc(1, knob=knob)
    _note(
        Decision(
            knob=knob,
            key=key,
            value=entry["value"],
            provenance="cache_hit",
            fitness_s=entry.get("fitness_s"),
        )
    )
    return entry["value"]


def store(
    knob: str,
    key: str,
    value: Any,
    *,
    fitness_s: Optional[float] = None,
    probe_ms: Optional[float] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> None:
    """Record (and persist, rank 0) a winner for (knob, key)."""
    entry: Dict[str, Any] = {
        "value": value,
        "provenance": "probed",
        "ts": time.time(),
    }
    if fitness_s is not None:
        entry["fitness_s"] = round(float(fitness_s), 6)
    if probe_ms is not None:
        entry["probe_ms"] = round(float(probe_ms), 3)
    if extra:
        entry.update(extra)
    entries = _entries()
    with _LOCK:
        entries[cache_key(knob, key)] = entry
    _persist({cache_key(knob, key): entry})


def probe(
    knob: str,
    key: str,
    candidates: Sequence[Any],
    measure: Callable[[Any], Optional[float]],
    *,
    reps: int = 2,
    budget_ms: Optional[float] = None,
    store_result: bool = True,
) -> Decision:
    """Successive-halving search over ``candidates`` scored by
    ``measure`` (seconds per probe dispatch, lower wins; None =
    infeasible, candidate dropped).

    ``candidates[0]`` is the heuristic default and is ALWAYS measured
    (before the budget gate), so the search can never return something
    worse-measured than the default. Each round measures the surviving
    candidates once and keeps the best half; ``reps`` bounds the round
    count, the wall-clock budget (``TPUML_AUTOTUNE_BUDGET_MS`` unless
    ``budget_ms`` overrides) stops new measurements mid-search. Every
    measurement runs under an ``autotune.probe.<knob>`` span with the
    inheritable ``warmup=True`` attr: probe compiles never score as
    retrace storms."""
    if not candidates:
        raise ValueError(f"autotune probe for {knob!r}: empty candidate list")
    budget = (_budget_s() if budget_ms is None else float(budget_ms) / 1e3)
    site = f"autotune.probe.{knob}"
    t_start = time.perf_counter()
    scores: Dict[int, float] = {}  # candidate index -> best seconds

    def _measure(idx: int) -> None:
        with telemetry.span(site, warmup=True, knob=knob, candidate=idx):
            try:
                s = measure(candidates[idx])
            except Exception as e:  # an infeasible candidate, not a crash
                _LOGGER.info(
                    "autotune %s: candidate %r failed the probe (%s); dropped",
                    knob, candidates[idx], e,
                )
                s = None
        if s is not None:
            prev = scores.get(idx)
            scores[idx] = float(s) if prev is None else min(prev, float(s))
        elif idx in scores:
            del scores[idx]

    _measure(0)  # the default: measured unconditionally
    alive = list(range(len(candidates)))
    for rnd in range(max(1, int(reps))):
        for idx in alive:
            if idx == 0 and rnd == 0:
                continue  # already measured above
            if time.perf_counter() - t_start > budget:
                break
            _measure(idx)
        measured = [i for i in alive if i in scores]
        if not measured:
            break
        measured.sort(key=lambda i: scores[i])
        alive = measured[: max(1, len(measured) // 2)]
        if len(alive) == 1 or time.perf_counter() - t_start > budget:
            break

    elapsed_ms = (time.perf_counter() - t_start) * 1e3
    best_idx = min(scores, key=lambda i: scores[i]) if scores else 0
    if (
        best_idx != 0
        and 0 in scores
        and scores[0] <= scores[best_idx] * (1.0 + DEFAULT_MARGIN)
    ):
        best_idx = 0  # within noise of the default: keep the default
    best_s = scores.get(best_idx)

    extra: Dict[str, Any] = {
        "candidates": len(candidates),
        "measured": len(scores),
        "default_s": round(scores[0], 6) if 0 in scores else None,
    }
    telemetry.counter("autotune_probes_total").inc(1, knob=knob)
    telemetry.histogram("autotune_probe_ms").observe(elapsed_ms, knob=knob)
    decision = Decision(
        knob=knob,
        key=key,
        value=candidates[best_idx],
        provenance="probed",
        fitness_s=best_s,
        probe_ms=elapsed_ms,
    )
    if store_result:
        store(
            knob,
            key,
            decision.value,
            fitness_s=best_s,
            probe_ms=elapsed_ms,
            extra=extra,
        )
    _note(decision)
    _LOGGER.info(
        "autotune %s [%s]: %r in %.0f ms (%d/%d candidates measured%s)",
        knob, key, decision.value, elapsed_ms, len(scores), len(candidates),
        "" if best_s is None else f", best {best_s * 1e3:.2f} ms",
    )
    return decision


def tune(
    knob: str,
    key: str,
    candidates: Sequence[Any],
    measure: Callable[[Any], Optional[float]],
    *,
    reps: int = 2,
    budget_ms: Optional[float] = None,
) -> Optional[Any]:
    """The full resolver hook for sites that can measure in place:
    cache hit wins (``on``), otherwise probe + store; ``force``
    re-probes over any entry. Returns None when the tuner is off or
    the probe machinery fails — the caller's heuristic is always the
    fallback, a broken tuner never breaks a fit."""
    if not active():
        return None
    if mode() != "force":
        hit = consult(knob, key)
        if hit is not None:
            return hit
    else:
        # force still files the miss/hit count so warm-vs-cold is
        # observable, then re-probes regardless
        consult(knob, key)
    try:
        return probe(
            knob, key, candidates, measure, reps=reps, budget_ms=budget_ms
        ).value
    except Exception as e:
        _warn_once(
            f"probe:{knob}",
            "autotune probe for %s failed (%s); using the static "
            "heuristic for this and future shapes this run",
            knob,
            e,
        )
        return None


def reset_autotune() -> None:
    """Drop in-memory cache state and warn-once markers (test isolation).
    The on-disk cache file is untouched."""
    with _LOCK:
        _STATE.update(path=None, entries={}, loaded=False)
        _WARNED.clear()


def last_entries() -> Dict[str, Any]:
    """Snapshot of the in-memory entry map (diagnostics / tests)."""
    return dict(_entries())
