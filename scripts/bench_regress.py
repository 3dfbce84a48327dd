#!/usr/bin/env python
"""CI gate: fail when the newest bench run regresses against the prior one.

Reads the ``BENCH_r*.json`` trajectory (driver wrapper files holding the
bench stdout/stderr tail) plus optionally a current raw ``bench.py``
output line, extracts the per-entry metric dicts, and compares the
newest run against the most recent prior run that produced entries:

- ``fit_seconds``   — regression when it grows past ``+threshold``
- ``vs_baseline``   — regression when it shrinks past ``-threshold``
- ``mfu``           — regression when it shrinks past ``-threshold``
- ``p99_ms``        — regression when it grows past ``+threshold``
  (serving tail latency; only entries that report it gate on it)
- ``serve_batch_fill`` — regression when it shrinks past ``-threshold``
  (micro-batch fill collapse wastes the padded dispatch)
- ``qps_sweep[<q>].p99_ms`` — every swept QPS level's tail gates like
  ``p99_ms``, so a regression visible only at high offered load cannot
  hide behind the top-level number
- ``aggregate_goodput_qps`` / ``replica_scaling_efficiency`` —
  regressions when they shrink past ``-threshold`` (the router bench's
  fleet goodput and its fraction of perfect N-replica scaling)
- ``fleet_p99_ms`` — regression when it grows past ``+threshold``
  (fleet tail measured from the MERGED per-rank reservoirs)
- ``tuned_vs_default`` — regression when it shrinks past ``-threshold``
  AND, unconditionally, when it falls below the absolute floor
  ``1.0 - threshold``: the autotuner measures the default config first
  and falls back to it on a loss, so a tuned run that loses to the
  default means the search or the cache is broken, not that the
  hardware got slower. The floor gates even ``host_only`` and
  first-appearance entries — tuned and default are measured
  back-to-back in the SAME run on the same machine, so whatever machine
  that is cancels out of the ratio.

Rules that keep the gate honest on real trajectories:

- ``host_only`` entries (an explicit ``--platform cpu`` run) measure the
  host, not the chip — their run-to-run swings are host noise, so they
  are reported but never gate.
- Zero/missing baselines (mfu 0.0 where no cost model applies,
  vs_baseline 0.0 from an unreachable-baseline run) cannot express a
  ratio — skipped, not failed.
- Entries present only in the current run are new coverage, not a
  regression.

Exit status: 0 when nothing regressed, 1 with a readable table naming
every offending entry/field otherwise. Deliberately stdlib-only (runs
in CI before any jax import).

Usage:
    python scripts/bench_regress.py                       # newest vs prior
    python scripts/bench_regress.py --current out.json    # gate a fresh run
    python scripts/bench_regress.py --threshold 0.20
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# per-entry dicts inside a (possibly truncated) bench stdout tail:
# '"pca": {...}' — entries never nest, so a flat brace group is enough
_ENTRY_RE = re.compile(r'"(\w+)":\s*(\{[^{}]*\})')

Entries = Dict[str, Dict[str, Any]]


def _entries_from_text(text: str) -> Entries:
    """Per-entry metric dicts from raw bench output (or a tail of it).

    Complete metric lines parse as whole-line JSON first — entries with
    nested sub-dicts (the serving entry's qps/window sweeps) are invisible
    to the flat-brace scan. The full metric line may also be truncated at
    the front by the driver's tail capture, so the fallback scans for
    every ``"name": {...}`` group and keeps the ones that look like bench
    entries (fit_seconds + samples_per_sec_per_chip). Later occurrences
    win, matching "last line is the real emit" semantics.
    """
    out: Entries = {}
    for line in text.splitlines():
        line = line.strip()
        if not (line.startswith("{") and line.endswith("}")):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            out.update(
                {
                    k: v
                    for k, v in doc.items()
                    if isinstance(v, dict)
                    and "fit_seconds" in v
                    and "samples_per_sec_per_chip" in v
                }
            )
    if out:
        return out
    for m in _ENTRY_RE.finditer(text):
        try:
            v = json.loads(m.group(2))
        except ValueError:
            continue
        if (
            isinstance(v, dict)
            and "fit_seconds" in v
            and "samples_per_sec_per_chip" in v
        ):
            out[m.group(1)] = v
    return out


def parse_bench_file(path: str) -> Entries:
    """Entries from either a driver wrapper (``{"n", "cmd", "rc",
    "tail", ...}``) or a raw ``bench.py`` output file; empty dict when
    the run produced none (crashed before the emit)."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        return _entries_from_text(text)
    if isinstance(doc, dict) and "tail" in doc:
        return _entries_from_text(doc.get("tail") or "")
    if isinstance(doc, dict):
        return {
            k: v
            for k, v in doc.items()
            if isinstance(v, dict) and "fit_seconds" in v
        }
    return {}


def _run_key(path: str) -> Tuple[int, str]:
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    return (int(m.group(1)) if m else -1, path)


def trajectory_files(pattern: str) -> List[str]:
    return sorted(glob.glob(pattern), key=_run_key)


_STATIC_FIELDS = (
    ("fit_seconds", +1),      # +1: larger is worse
    ("vs_baseline", -1),      # -1: smaller is worse
    ("mfu", -1),
    ("p99_ms", +1),           # serving tail latency: growth is a failure
    ("serve_batch_fill", -1),  # fill collapse = micro-batching regression
    ("goodput_qps", -1),      # overload goodput collapse = shedding broke
    ("shed_frac", +1),        # shedding more at the same offered load
    ("fits_per_sec", -1),     # fit-scheduler capacity regression
    ("fit_p99_ms", +1),       # scheduled-fit tail latency growth
    ("aggregate_goodput_qps", -1),        # fleet goodput collapse
    ("replica_scaling_efficiency", -1),   # router stopped spreading load
    ("fleet_p99_ms", +1),     # merged-reservoir fleet tail growth
    ("swap_p99_delta_ms", +1),  # hot-swap tail disturbance growth
    ("rollback_ms", +1),      # canary re-flip latency growth
    ("tuned_vs_default", -1),  # autotuner stopped beating/matching default
)

# tuned_vs_default also has an ABSOLUTE floor (see compare): the probe
# engine measures the default first, so a ratio below 1.0 - threshold is
# a broken search/cache regardless of what any prior run posted.
_ABS_FLOOR_FIELD = "tuned_vs_default"

_QPS_FIELD_RE = re.compile(r"^qps_sweep\[(.+)\]\.p99_ms$")


def _gate_fields(
    b: Dict[str, Any], c: Dict[str, Any]
) -> List[Tuple[str, int]]:
    """The (field, worse_sign) list for one entry pair: the static
    fields plus a flattened ``qps_sweep[<q>].p99_ms`` (+1) for every
    swept QPS level either run reports — a regression that only shows
    at high offered load must not slip a gate that reads the top-level
    p99 alone."""
    fields = list(_STATIC_FIELDS)
    levels: set = set()
    for src in (b, c):
        sweep = src.get("qps_sweep")
        if isinstance(sweep, dict):
            for q, sub in sweep.items():
                if isinstance(sub, dict) and "p99_ms" in sub:
                    levels.add(str(q))
    def _qkey(q: str) -> Tuple[int, Any]:
        try:
            return (0, int(q))
        except ValueError:
            return (1, q)
    for q in sorted(levels, key=_qkey):
        fields.append((f"qps_sweep[{q}].p99_ms", +1))
    return fields


def _field_value(entry: Dict[str, Any], field: str) -> Any:
    m = _QPS_FIELD_RE.match(field)
    if m is None:
        return entry.get(field)
    sweep = entry.get("qps_sweep")
    if isinstance(sweep, dict):
        sub = sweep.get(m.group(1))
        if isinstance(sub, dict):
            return sub.get("p99_ms")
    return None


def compare(
    base: Entries,
    cur: Entries,
    threshold: float,
) -> Tuple[List[Tuple[str, str, float, float, float, str]], bool]:
    """Per-entry/per-field comparison rows and the overall verdict.

    Rows are ``(entry, field, base, cur, delta_fraction, status)`` with
    status one of ``ok`` / ``REGRESS`` / ``skip:<reason>``; the bool is
    True when any row regressed.
    """
    rows: List[Tuple[str, str, float, float, float, str]] = []
    failed = False
    for name in sorted(set(base) | set(cur)):
        b, c = base.get(name), cur.get(name)
        if c is None:
            rows.append((name, "-", 0.0, 0.0, 0.0, "skip:entry-dropped"))
            continue
        # absolute floor: gates every current entry reporting the field,
        # including new and host_only ones (same-run back-to-back
        # ratio — the machine cancels out; "no prior run" is no excuse)
        fv = c.get(_ABS_FLOOR_FIELD)
        if fv is not None:
            fv = float(fv)
            floor = 1.0 - threshold
            bad = fv < floor
            rows.append(
                (
                    name, f"{_ABS_FLOOR_FIELD}>=floor", floor, fv,
                    fv - 1.0, "REGRESS" if bad else "ok",
                )
            )
            failed = failed or bad
        if b is None:
            rows.append((name, "-", 0.0, 0.0, 0.0, "skip:new-entry"))
            continue
        host_only = b.get("host_only") or c.get("host_only")
        for field, worse_sign in _gate_fields(b, c):
            bv, cv = _field_value(b, field), _field_value(c, field)
            if bv is None or cv is None:
                continue
            bv, cv = float(bv), float(cv)
            if bv <= 0:
                rows.append((name, field, bv, cv, 0.0, "skip:zero-baseline"))
                continue
            delta = (cv - bv) / bv
            if host_only:
                rows.append((name, field, bv, cv, delta, "skip:host-only"))
                continue
            regress = worse_sign * delta > threshold
            rows.append(
                (name, field, bv, cv, delta, "REGRESS" if regress else "ok")
            )
            failed = failed or regress
    return rows, failed


def format_table(
    rows: List[Tuple[str, str, float, float, float, str]],
) -> str:
    header = ("entry", "field", "base", "current", "delta", "status")
    table = [header] + [
        (name, field, f"{bv:.4g}", f"{cv:.4g}", f"{delta:+.1%}", status)
        for name, field, bv, cv, delta, status in rows
    ]
    widths = [max(len(r[i]) for r in table) for i in range(len(header))]
    lines = []
    for i, r in enumerate(table):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--trajectory",
        default=os.path.join(REPO_ROOT, "BENCH_r*.json"),
        help="glob of prior-run files, ordered by _r<N> (default: repo"
             " BENCH_r*.json)",
    )
    ap.add_argument(
        "--current",
        default=None,
        help="current-run file (wrapper or raw bench output); default:"
             " the newest trajectory file gates against the one before it",
    )
    ap.add_argument(
        "--threshold", type=float, default=0.15,
        help="noise threshold as a fraction (default 0.15 = ±15%%)",
    )
    args = ap.parse_args(argv)

    runs: List[Tuple[str, Entries]] = []
    for path in trajectory_files(args.trajectory):
        entries = parse_bench_file(path)
        if entries:
            runs.append((path, entries))
        else:
            print(f"bench_regress: {path}: no entries (skipped)")
    if args.current is not None:
        cur_path, cur = args.current, parse_bench_file(args.current)
        if not cur:
            print(f"bench_regress: {cur_path}: no entries in current run")
            return 1
    else:
        if len(runs) < 2:
            print(
                "bench_regress: need >= 2 parseable runs in the trajectory "
                f"(have {len(runs)}) — nothing to gate"
            )
            return 0
        cur_path, cur = runs.pop()
    if runs:
        base_path, base = runs[-1]
    else:
        # no trajectory yet: nothing to compare, but the absolute-floor
        # fields still gate the current run on its own
        print("bench_regress: no prior run — absolute floors only")
        base_path, base = "(none)", {}

    rows, failed = compare(base, cur, args.threshold)
    print(
        f"bench_regress: {os.path.basename(cur_path)} vs "
        f"{os.path.basename(base_path)} (threshold ±{args.threshold:.0%})"
    )
    print(format_table(rows))
    if failed:
        bad = sorted(
            {f"{name}.{field}" for name, field, *_rest, st in rows
             if st == "REGRESS"}
        )
        print(f"bench_regress: REGRESSION in {', '.join(bad)}")
        return 1
    print("bench_regress: no regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())
