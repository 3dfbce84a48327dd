"""Roofline attribution: XLA cost-model numbers per span site.

PR 9's telemetry records *where* wall time goes; this layer records *how
far from the hardware ceiling* each stage runs. At compile time the
installed hook captures ``cost_analysis()`` (FLOPs, bytes accessed —
the XLA cost model, not hand formulas) of every executable the backend
produces and hands it to the same ``jax.monitoring`` compile-event
listener the retrace watchdog uses, which attributes it to the
innermost active span site via the span ``contextvars``. When a span at
an attributed site closes, :func:`annotate` combines the site's
per-call cost with the span's fenced device time (wall time when no
fence ran) and the per-platform peak-spec table to produce
``flops_total`` / ``bytes_total`` / ``mfu`` / ``achieved_gbps`` /
``bound`` span attributes and the ``span_mfu`` / ``span_achieved_gbps``
/ ``span_flops_total`` / ``span_bytes_total`` metrics.

Peak specs come from ``TPUML_PEAK_FLOPS`` / ``TPUML_PEAK_HBM_GBPS``
when set, else from a per-device-kind table (bf16 peak FLOP/s and HBM
GB/s per chip, scaled by the device count — the same denominator
``bench.py`` uses).

Semantics worth knowing before reading numbers:

- A site's per-call cost is the SUM over the distinct programs compiled
  while that site was innermost (a fit that compiles a preamble and a
  while-loop body executes both per call). Shape-driven recompiles add
  their variants' cost too — a site in a retrace storm (TPU003) reads
  high, which is a feature.
- Programs compiled at one site but re-executed under another (compile
  under ``fit.dispatch``, reuse in ``transform``) stay attributed to
  the compiling site. Cost capture happens at compile time only; there
  is no per-execution hook.
- Everything here is best-effort and opt-in: installation happens only
  while ``TPUML_TRACE`` is set, every capture path swallows failures
  (``cost_analysis`` unavailable, negative/missing FLOPs, jax internals
  moved), and with nothing captured spans carry NO roofline attributes
  — absent, never zero or NaN (``tests/test_roofline.py``).
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from . import envspec, lockwitness


__all__ = [
    "install",
    "installed",
    "annotate",
    "aggregate",
    "site_costs",
    "peak_specs",
    "chip_peaks",
    "reset_roofline",
]

# --------------------------------------------------------------------------
# per-platform peak specs
# --------------------------------------------------------------------------

# The one peak table, keyed by ``device_kind`` substring: (bf16 peak FLOP/s,
# HBM GB/s) per chip, datasheet figures (v5e: Google Cloud "TPU v5e" —
# 197 TFLOP/s bf16, 819 GB/s). ``bench.py`` reads it through
# :func:`chip_peaks` so measured and derived MFU share a denominator.
_PEAKS_BY_KIND: Tuple[Tuple[str, float, float], ...] = (
    ("v6", 918e12, 1640.0),
    ("v5p", 459e12, 2765.0),
    ("v5 lite", 197e12, 819.0),
    ("v5e", 197e12, 819.0),
    ("v5", 459e12, 2765.0),
    ("v4", 275e12, 1228.0),
    ("v3", 123e12, 900.0),
    ("v2", 45e12, 700.0),
)
# nominal figures for the CPU backend only: they keep ratios finite in
# host-only runs, whose results are flagged ``host_only`` and are never a
# device metric
_CPU_PEAK_FLOPS = 1e12
_CPU_PEAK_HBM_GBPS = 100.0

_PEAK_LOCK = lockwitness.make_lock("roofline.peaks")
_PEAK_CACHE: Optional[Tuple[float, float, int]] = None


def chip_peaks(device_kind: str, platform: str) -> Tuple[float, float]:
    """``(peak_flops, peak_hbm_gbps)`` per chip for a device.

    The CPU backend gets the nominal host figures. An accelerator whose
    ``device_kind`` is not in the table is an error, not a default: a
    utilization against a made-up peak is worse than none.
    """
    if platform == "cpu":
        return _CPU_PEAK_FLOPS, _CPU_PEAK_HBM_GBPS
    kind = device_kind.lower()
    for key, flops, gbps in _PEAKS_BY_KIND:
        if key in kind:
            return flops, gbps
    raise ValueError(
        f"no peak figures for device_kind {device_kind!r} (platform "
        f"{platform!r}); add it to runtime/roofline._PEAKS_BY_KIND with "
        "its source"
    )


def peak_specs() -> Tuple[float, float, int]:
    """``(peak_flops_per_chip, peak_hbm_gbps_per_chip, device_count)``.

    Env overrides win; otherwise :func:`chip_peaks` of the first device.
    Cached after first resolution — by the time a compile has been
    attributed the backend is necessarily up, so the device probe cannot
    initialize anything the program was not already using.
    """
    global _PEAK_CACHE
    with _PEAK_LOCK:
        if _PEAK_CACHE is not None:
            return _PEAK_CACHE
        import jax

        devices = jax.devices()
        flops = envspec.get("TPUML_PEAK_FLOPS")
        gbps = envspec.get("TPUML_PEAK_HBM_GBPS")
        if flops is None or gbps is None:
            t_flops, t_gbps = chip_peaks(
                devices[0].device_kind, devices[0].platform
            )
            flops = t_flops if flops is None else flops
            gbps = t_gbps if gbps is None else gbps
        _PEAK_CACHE = (float(flops), float(gbps), len(devices))
        return _PEAK_CACHE


# --------------------------------------------------------------------------
# compile-time capture
# --------------------------------------------------------------------------

_LOCK = lockwitness.make_lock("roofline.state")
_INSTALLED = False
_ORIG_BACKEND_COMPILE: Any = None
# site -> [flops_per_call, bytes_per_call, n_programs]
_SITE_COST: Dict[str, List[float]] = {}
_TLS = threading.local()  # .pending: cost dicts awaiting the compile event


def _extract_cost(executable: Any) -> Optional[Tuple[float, float]]:
    """``(flops, bytes_accessed)`` from an executable's cost analysis,
    or None when the backend reports nothing usable (missing key,
    zero/negative FLOPs — XLA's "unknown" convention)."""
    try:
        ca = executable.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):  # jax.stages.Compiled convention
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if not isinstance(flops, (int, float)) or flops <= 0:
        return None
    if not isinstance(nbytes, (int, float)) or nbytes < 0:
        nbytes = 0.0
    return float(flops), float(nbytes)


def _wrapped_backend_compile(*args: Any, **kwargs: Any) -> Any:
    executable = _ORIG_BACKEND_COMPILE(*args, **kwargs)
    try:
        cost = _extract_cost(executable)
        if cost is not None:
            pending = getattr(_TLS, "pending", None)
            if pending is None:
                pending = _TLS.pending = []
            pending.append(cost)
    except Exception:  # capture must never fail a compile
        pass
    return executable


def _consume_pending(site: str) -> None:
    """Called by telemetry's ``jax.monitoring`` compile-event listener
    (synchronously on the compiling thread, right after the wrapped
    compile returned): attribute every pending cost capture to the
    innermost active span site."""
    pending = getattr(_TLS, "pending", None)
    if not pending:
        return
    _TLS.pending = []
    with _LOCK:
        rec = _SITE_COST.get(site)
        if rec is None:
            rec = _SITE_COST[site] = [0.0, 0.0, 0]
        for flops, nbytes in pending:
            rec[0] += flops
            rec[1] += nbytes
            rec[2] += 1


def install() -> bool:
    """Wrap the backend compile entry point so executables surface their
    cost analysis, and make sure the shared ``jax.monitoring`` listener
    is registered (idempotent). Returns True once the hook is active.

    The wrap targets ``jax._src.compiler.backend_compile_and_load``, the
    function every ``jit`` compile of the installed jax (0.9.0) goes
    through. It is a jax-internal symbol: when it is absent this
    **raises** — a hook that installs and then attributes nothing is the
    defect this replaces.
    """
    global _INSTALLED, _ORIG_BACKEND_COMPILE
    with _LOCK:
        if _INSTALLED:
            return True
        from jax._src import compiler as _jax_compiler

        if not hasattr(_jax_compiler, "backend_compile_and_load"):
            raise RuntimeError(
                "roofline: jax._src.compiler.backend_compile_and_load is "
                "absent in this jax; the cost-analysis hook has to be moved "
                "to the function jit compiles through"
            )
        _ORIG_BACKEND_COMPILE = _jax_compiler.backend_compile_and_load
        _jax_compiler.backend_compile_and_load = _wrapped_backend_compile
        _INSTALLED = True
    # the compile-event listener is the attribution path (telemetry owns
    # it; it calls back into _consume_pending) — register outside _LOCK,
    # telemetry takes its own locks
    from . import telemetry

    telemetry.install_retrace_watchdog()
    return True


def installed() -> bool:
    with _LOCK:
        return _INSTALLED


# --------------------------------------------------------------------------
# span-close annotation
# --------------------------------------------------------------------------


def annotate(site: str, device_s: float, wall_s: float) -> Dict[str, Any]:
    """Roofline attributes for one closing span at ``site``: empty when
    no cost was ever attributed there (metrics cleanly absent), else
    ``flops_total`` / ``bytes_total`` plus — when the span has positive
    time — ``mfu``, ``achieved_gbps``, and the ``bound`` verdict.

    ``device_s`` (the fenced time) is the preferred denominator; wall
    time stands in when no fence ran. Also files the ``span_mfu`` /
    ``span_achieved_gbps`` histograms and the ``span_flops_total`` /
    ``span_bytes_total`` counters, labeled by site.
    """
    with _LOCK:
        rec = _SITE_COST.get(site)
        if rec is None:
            return {}
        flops, nbytes, n_programs = rec
    attrs: Dict[str, Any] = {
        "flops_total": flops,
        "bytes_total": nbytes,
        "cost_programs": n_programs,
    }
    from . import telemetry

    telemetry.counter("span_flops_total").inc(int(flops), name=site)
    telemetry.counter("span_bytes_total").inc(int(nbytes), name=site)
    seconds = device_s if device_s > 0 else wall_s
    if seconds > 0:
        peak_flops, peak_gbps, n_dev = peak_specs()
        mfu = flops / (seconds * peak_flops * n_dev)
        gbps = nbytes / seconds / 1e9
        frac_hbm = gbps / (peak_gbps * n_dev)
        attrs["mfu"] = round(mfu, 6)
        attrs["achieved_gbps"] = round(gbps, 3)
        attrs["bound"] = "compute" if mfu >= frac_hbm else "memory"
        telemetry.histogram("span_mfu").observe(mfu, name=site)
        telemetry.histogram("span_achieved_gbps").observe(gbps, name=site)
    return attrs


def site_costs() -> Dict[str, Dict[str, float]]:
    """Per-site compile-time cost attribution:
    ``{site: {flops_per_call, bytes_per_call, programs}}``."""
    with _LOCK:
        return {
            site: {
                "flops_per_call": rec[0],
                "bytes_per_call": rec[1],
                "programs": int(rec[2]),
            }
            for site, rec in _SITE_COST.items()
        }


def aggregate(stats: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, Any]]:
    """Fold roofline aggregates into a :func:`telemetry.span_stats`-shaped
    dict: for every site with attributed cost, add ``flops_total`` (per
    call x span count), ``bytes_total``, and — on positive time — the
    aggregate ``mfu`` / ``achieved_gbps`` / ``bound``. Sites without
    cost pass through untouched, so the CPU/interpret fallback keeps the
    PR-9 shape exactly."""
    costs = site_costs()
    if not costs:
        return stats
    peak_flops, peak_gbps, n_dev = peak_specs()
    out: Dict[str, Dict[str, Any]] = {}
    for site, st in stats.items():
        st = dict(st)
        rec = costs.get(site)
        if rec is not None:
            flops = rec["flops_per_call"] * st["count"]
            nbytes = rec["bytes_per_call"] * st["count"]
            st["flops_total"] = flops
            st["bytes_total"] = nbytes
            seconds = st["device_seconds"] or st["wall_seconds"]
            if seconds > 0:
                mfu = flops / (seconds * peak_flops * n_dev)
                gbps = nbytes / seconds / 1e9
                st["mfu"] = round(mfu, 6)
                st["achieved_gbps"] = round(gbps, 3)
                st["bound"] = (
                    "compute" if mfu >= gbps / (peak_gbps * n_dev)
                    else "memory"
                )
        out[site] = st
    return out


def reset_roofline() -> None:
    """Clear attribution state and the peak cache (test isolation); the
    compile hook itself stays installed — like monitoring listeners it
    cannot be meaningfully unregistered mid-process."""
    global _PEAK_CACHE
    with _LOCK:
        _SITE_COST.clear()
    _TLS.pending = []
    with _PEAK_LOCK:
        _PEAK_CACHE = None
