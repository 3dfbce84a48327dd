"""The program's spans and the device's programs on one clock.

A live ``runtime/telemetry`` span writes itself into the profiler's trace as
a ``TraceAnnotation`` named ``tpuml:<span name>`` with ``span_id`` and
``parent_id`` as event stats (host plane). This module reads the traced
job's ``.xplane.pb`` — with ``jax.profiler.ProfileData`` and nothing else,
once for all readers — and gives:

* ``spans``: the ``tpuml:`` events inside the ``chipbench.job`` range, with
  ids, parents, start and end on the profiler's clock; their attributes
  (``program``, ``n_evals``, ``bytes`` ...) come from the sink's events of
  the same ``span_id`` (``ctx["spans"]``);
* ``modules``: the device's ``XLA Modules`` events — one per run of a
  compiled program, ``jit_<function>(<fingerprint>)`` with its start and end
  on the chip;
* ``ops``: the device's ``XLA Ops`` events with their whole HLO line, which
  names an operation's operands by shape whatever implements it;
* the idle gaps of the traced job put down to the INNERMOST span that
  covered them (``trace_reduce``'s ``breakdown.idle_gaps`` knows three
  phases).

The three terms of a resident fit tile the interval from the opening of its
first ``h2d.enqueue`` to the close of its ``solver.fetch``:

    input_wait     opening of h2d.enqueue -> start on the device of the
                   first module event of solver.launch's ``program``
    solver_device  that start -> end of the program's last module event
    fetch_tail     that end -> close of solver.fetch

Where the trace has no such event (the CPU rehearsal has no device plane, a
program without these spans has no ``tpuml:`` event) every function here
returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import functools
import os
import re
import sys

from chipbench import trace_reduce as tr

PREFIX = "tpuml:"
MODULES_LINE = "XLA Modules"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".chipbench_trace")
HLO_DTYPES = {"float32": "f32", "bfloat16": "bf16", "float64": "f64"}
CONTAINERS = ("while", "conditional", "call")   # their events span the events of their bodies
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
NS = 1e-9


@functools.lru_cache(maxsize=2)
def read(path: str) -> dict:
    """One parse of the trace: ``job`` (lo, hi) or None, ``spans``,
    ``modules`` and ``ops`` per device plane. Times in nanoseconds."""
    from jax.profiler import ProfileData

    job, spans, modules, ops = None, [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == tr.OPS_LINE:
                    ops[plane.name] = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.JOB:
                        job = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(PREFIX):
                        stats = dict(e.stats)
                        spans.append({
                            "name": e.name[len(PREFIX):], "span_id": stats.get("span_id"),
                            "parent_id": stats.get("parent_id"), "lo": e.start_ns, "hi": e.start_ns + e.duration_ns,
                        })
    if job:
        spans = [s for s in spans if job[0] <= s["lo"] and s["hi"] <= job[1]]
    spans.sort(key=lambda s: (s["lo"], -s["hi"]))
    return {"job": job, "spans": spans, "modules": modules, "ops": {k: v for k, v in ops.items() if v}}


def traced(ctx) -> dict | None:
    """The traced job's parse, or None where no trace was taken. ``ctx`` may
    hand the file over as ``ctx["xplane"]``; today it is looked up where
    ``run.py`` keeps it until every reader has run."""
    path = ctx.get("xplane") or tr.find_xplane(TRACE_DIR)
    if not path or not os.path.exists(path):
        return None
    trace = read(path)
    if path not in _PRINTED:
        _PRINTED.add(path)
        idle = idle_by_span(trace)
        if idle:
            print("chipbench: idle by span: " + ", ".join(f"{k} {v:.4f} s" for k, v in idle[:10]), file=sys.stderr, flush=True)
    return trace


_PRINTED: set = set()


def attrs(ctx, span) -> dict:
    """The attributes of a traced span: the sink's event of the same id."""
    for ev in ctx["spans"]:
        if ev["args"].get("span_id") == span["span_id"]:
            return ev["args"]
    return {}


def descendants(trace, root):
    ids, out = {root["span_id"]}, []
    for s in trace["spans"]:                      # sorted by start: a parent comes before its children
        if s["parent_id"] in ids:
            ids.add(s["span_id"])
            out.append(s)
    return out


def idle_by_span(trace) -> list:
    """[(span name, idle seconds)], most first: each idle gap of the device
    inside the traced job goes to the innermost span that covered it (a
    span's idle time less its children's), the rest to ``outside_spans``.
    Averaged over the device planes."""
    if not trace["ops"] or not trace["job"]:
        return []
    w_lo, w_hi = trace["job"]
    by, n_dev = {}, len(trace["ops"])
    for ops in trace["ops"].values():
        busy = tr.clip(tr.union([(lo, hi) for _, lo, hi in ops]), [(w_lo, w_hi)])
        edges = [w_lo] + [x for iv in busy for x in iv] + [w_hi]
        gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        inside = {s["span_id"]: tr.total(tr.clip(gaps, [(s["lo"], s["hi"])])) for s in trace["spans"]}
        own = dict(inside)
        roots = 0.0
        for s in trace["spans"]:
            if s["parent_id"] in own:
                own[s["parent_id"]] -= inside[s["span_id"]]
            else:
                roots += inside[s["span_id"]]
        for s in trace["spans"]:
            by[s["name"]] = by.get(s["name"], 0.0) + own[s["span_id"]]
        by["outside_spans"] = by.get("outside_spans", 0.0) + tr.total(gaps) - roots
    return sorted(((k, v / n_dev * NS) for k, v in by.items() if v > 0), key=lambda kv: -kv[1])


def traced_fit(ctx) -> dict | None:
    """The traced job's fit: its root span, its first ``h2d.enqueue``, its
    ``solver.launch`` and ``solver.fetch`` with their attributes, and the
    device's module events of the launch's ``program`` (comma-separated
    function names, any of them) from the enqueue to the fetch's close."""
    trace = traced(ctx)
    if not trace or not trace["modules"]:
        return None
    roots = [s for s in trace["spans"] if s["name"] == ctx["config"]["annotations"]["fit"]]
    if not roots:
        return None
    inner = descendants(trace, roots[0])
    first = {}
    for s in inner:
        first.setdefault(s["name"], s)
    if not all(k in first for k in ("h2d.enqueue", "solver.launch", "solver.fetch")):
        return None
    enqueue, launch, fetch = first["h2d.enqueue"], first["solver.launch"], first["solver.fetch"]
    programs = [p for p in str(attrs(ctx, launch).get("program", "")).split(",") if p]
    runs = [
        (name, lo, hi) for events in trace["modules"].values() for name, lo, hi in events
        if enqueue["lo"] <= lo <= fetch["hi"] and any(p in name for p in programs)
    ]
    if not runs:
        return None
    return {
        "trace": trace, "root": roots[0], "spans": inner, "enqueue": enqueue, "launch": launch, "fetch": fetch,
        "fetch_attrs": attrs(ctx, fetch), "device_lo": min(r[1] for r in runs), "device_hi": max(r[2] for r in runs),
    }


def fit_split(ctx) -> dict | None:
    """Seconds of the traced fit's three terms (module docstring)."""
    fit = traced_fit(ctx)
    if not fit:
        return None
    return {
        "input_wait": (fit["device_lo"] - fit["enqueue"]["lo"]) * NS,
        "solver_device": (fit["device_hi"] - fit["device_lo"]) * NS,
        "fetch_tail": (fit["fetch"]["hi"] - fit["device_hi"]) * NS,
    }


def opcode_and_operands(hlo_line: str):
    """``%x = f32[8]{0} fusion(f32[8,4]{1,0} %p), kind=kLoop`` ->
    (``fusion``, everything after its opening parenthesis)."""
    rhs = hlo_line.split(" = ", 1)[-1]
    m = _OPCODE.search(rhs)                      # the result type comes first, so a blank precedes the opcode
    return (m.group(1), rhs[m.end():]) if m else ("", "")


def frame_reads(ctx) -> dict | None:
    """Device operations of the traced fit's solver program that lie inside
    one of its ``while`` events and take an operand of the frame's own shape,
    ``<dtype>[<rows on a device>,<cols>]``: every read of X inside the
    solver's loop, whatever implements the pass (an XLA fusion, a Pallas
    custom call). Returns their count, the shape and the names counted."""
    fit = traced_fit(ctx)
    if not fit:
        return None
    config, trace = ctx["config"], fit["trace"]
    dt, cols = HLO_DTYPES.get(config["dtype"]), int(config["cols"])
    shape = re.compile(rf"\b{dt}\[(\d+),{cols}\]")
    n_dev, count, names, rows_seen = len(trace["ops"]), 0, {}, None
    for ops in trace["ops"].values():
        mine = [(name, lo, hi) + opcode_and_operands(name) for name, lo, hi in ops if fit["device_lo"] <= lo and hi <= fit["device_hi"]]
        loops = tr.union([(lo, hi) for _, lo, hi, opcode, _ in mine if opcode == "while"])
        candidates = {int(r) for *_, operands in mine for r in shape.findall(operands)}
        fitting = [r for r in candidates if r * n_dev >= ctx["rows"]]
        if not fitting:
            continue
        rows_seen = min(fitting)
        operand = f"{dt}[{rows_seen},{cols}]"
        for name, lo, hi, opcode, operands in mine:
            if opcode in CONTAINERS or opcode.endswith("-done") or operand not in operands:
                continue
            if any(w_lo <= lo and hi <= w_hi for w_lo, w_hi in loops):
                count += 1
                names[tr.short(name)] = names.get(tr.short(name), 0) + 1
    if rows_seen is None:
        return None
    return {"reads": count / n_dev, "shape": f"{dt}[{rows_seen},{cols}]", "ops": names}


# ---- the sink's spans of the whole window (no trace needed) ----

def named(ctx, name):
    return [s for s in ctx["spans"] if s["name"] == name]


def seconds_per(ctx, names, per) -> float | None:
    """Total seconds of the window's spans called ``names`` over the number
    of spans called ``per``; None where either is missing."""
    calls = named(ctx, per)
    spans = [s for n in names for s in named(ctx, n)]
    if not calls or not spans:
        return None
    return sum(s["dur"] for s in spans) * 1e-6 / len(calls)


def transform_call(ctx) -> str:
    """The root span around the whole of ``Model.transform``."""
    return ctx["config"]["annotations"]["transform"] + ".call"
