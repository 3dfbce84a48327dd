"""The link's spans laid against the device's events: where a job's idle
seconds go, wait by wait.

The program opens, inside ``h2d.enqueue``, an ``h2d.put`` around every row
block's ``device_put`` and write dispatch, an ``h2d.wait`` around the loop's
one wait and an ``h2d.fold`` around a caller's fold dispatch
(``parallel/mesh._put_row_blocks``); in a transform a ``transform.h2d``
around a batch's put and a ``transform.d2h`` around every fetch of an output
(``core.batch_to_device`` / ``output_to_host``). This module reads them with
``span_reduce`` (the ``tpuml:`` events of the traced job on the profiler's
clock, the sink's events of the whole window for their attributes) and names
every wait by what the DEVICE was waiting for, read on the profiler's clock
— not by the host call that happened to block:

    crossing       opening of the fit's first h2d.enqueue -> end on the device
                   of the last run of its ``write_program``: the frame has landed
    a batch        opening of its transform.apply -> close of its last
                   transform.d2h; inside it
      input wait   opening -> start on the device of the first operation that
                   names the batch by shape (the first program of the window
                   where none does): the batch's way up
      fetch tail   end of the batch's last device operation -> close of its
                   last transform.d2h: the answer's way back

The traced job's idle seconds (counted as ``device_idle_pct`` counts them:
the job's range less the union of ``XLA Ops``, averaged over the device
planes) are tiled by: the fit's input wait, the solver's interval and the
fit's fetch tail (``span_reduce.fit_split``), every batch's input wait and
fetch tail, and what lies outside all of those — the closure term,
``idle_unexplained_s.job``. ``idle_tiling`` gives the idle seconds INSIDE
each of those intervals, which add up to the job's by construction; a
metric that is an interval's length (``input_wait_s.fit``) is as much larger
than its idle part as the device was busy inside it (block writes, a
seeding's gather, the forest's sketch and ``binize``).

Where the trace has no such span or event (the CPU rehearsal has no device
plane, a program without these spans has no such ``tpuml:`` event, a frame
of one put has no ``write_program``) every function returns ``None``, never
0, and the metric is left out of the line.
"""

from __future__ import annotations

import functools
import re
import sys

from chipbench import span_reduce as sr
from chipbench import trace_reduce as tr

NS = sr.NS
_LAST: dict = {}   # of the run asked about last: its ``ctx`` and what was worked out from its trace


def once_a_run(fn):
    """``fn(ctx)`` worked out once a run: several readers ask for the same
    parse (a forest's trace holds hundreds of thousands of operations), and
    the harness hands every reader of a run the same ``ctx``."""
    @functools.wraps(fn)
    def asked(ctx):
        if _LAST.get("ctx") is not ctx:
            _LAST.clear()
            _LAST["ctx"] = ctx
        if fn.__name__ not in _LAST:
            _LAST[fn.__name__] = fn(ctx)
        return _LAST[fn.__name__]
    return asked


traced_fit = once_a_run(sr.traced_fit)


# ---- the device's busy and idle time inside an interval ----

@once_a_run
def busy_by_plane(ctx) -> dict:
    """Per device plane of the traced job the merged busy intervals: the
    union of ``XLA Ops``. Empty where there is no trace."""
    trace = sr.traced(ctx)
    return {plane: tr.union([(lo, hi) for _, lo, hi in ops]) for plane, ops in trace["ops"].items()} if trace else {}


def idle_in(busy: dict, lo: float, hi: float) -> float:
    """Idle nanoseconds inside [lo, hi], averaged over the device planes."""
    if hi <= lo or not busy:
        return 0.0
    return sum((hi - lo) - tr.total(tr.clip(merged, [(lo, hi)])) for merged in busy.values()) / len(busy)


# ---- the fit's crossing ----

@once_a_run
def crossing(ctx) -> dict | None:
    """The traced fit's crossing: ``lo`` the opening of its first
    ``h2d.enqueue``, ``hi`` the end on the device of the last ``XLA
    Modules`` event of that span's ``write_program`` before ``solver.fetch``
    closes, ``seconds`` between the two, ``bytes`` the sum over the fit's
    ``h2d.put`` spans and ``puts`` their number."""
    fit = traced_fit(ctx)
    if not fit:
        return None
    program = sr.attrs(ctx, fit["enqueue"]).get("write_program")
    if not program:
        return None
    lo, close = fit["enqueue"]["lo"], fit["fetch"]["hi"]
    ends = [
        e_hi for events in fit["trace"]["modules"].values() for name, e_lo, e_hi in events
        if program in name and lo <= e_lo and e_hi <= close
    ]
    puts = [sr.attrs(ctx, s).get("bytes") for s in fit["spans"] if s["name"] == "h2d.put"]
    if not ends or not puts or None in puts:
        return None
    return {"lo": lo, "hi": max(ends), "seconds": (max(ends) - lo) * NS, "bytes": sum(puts), "puts": len(puts)}


# ---- the transform, batch by batch ----

def _batch_shape(ctx, rows: int):
    """Matches an operand of the batch's own shape, in either orientation."""
    dt, cols = sr.HLO_DTYPES.get(ctx["config"]["dtype"]), int(ctx["config"]["cols"])
    if dt is None:
        return None
    return re.compile(rf"\b{dt}\[(?:{rows},{cols}|{cols},{rows})\]")


@once_a_run
def batches(ctx) -> list | None:
    """The batches of the traced job's first transform. For each: ``lo``
    (opening of its ``transform.apply``), ``hi`` (close of its last
    ``transform.d2h``) and per device plane ``first`` (start of the first
    operation that names the batch, else of the first program in the
    window) and ``last`` (end of the window's last operation) — ``None`` on a
    plane on which nothing ran in the window."""
    trace = sr.traced(ctx)
    if not trace or not trace["ops"]:
        return None
    roots = [s for s in trace["spans"] if s["name"] == sr.transform_call(ctx)]
    if not roots:
        return None
    inner = sr.descendants(trace, roots[0])
    applies = [s for s in inner if s["name"] == "transform.apply"]
    backs = [s for s in inner if s["name"] == "transform.d2h"]
    if not applies or not backs:
        return None
    out = []
    for i, apply in enumerate(applies):
        until = applies[i + 1]["lo"] if i + 1 < len(applies) else roots[0]["hi"]
        mine = [b for b in backs if apply["lo"] <= b["lo"] < until]
        if not mine:
            return None
        lo, hi = apply["lo"], max(b["hi"] for b in mine)
        rows = sr.attrs(ctx, apply).get("rows")
        shape = _batch_shape(ctx, int(rows)) if rows is not None else None
        first, last = {}, {}
        for plane, ops in trace["ops"].items():
            inside = [(name, o_lo, o_hi) for name, o_lo, o_hi in ops if lo <= o_lo and o_lo <= hi]
            named = [o_lo for name, o_lo, _ in inside if shape and shape.search(sr.opcode_and_operands(name)[1])]
            programs = [m_lo for _, m_lo, _ in trace["modules"].get(plane, []) if lo <= m_lo <= hi]
            first[plane] = min(named) if named else (min(programs) if programs else None)
            last[plane] = min(max(o_hi for _, _, o_hi in inside), hi) if inside else None
        out.append({"lo": lo, "hi": hi, "first": first, "last": last})
    return out


def transform_waits(ctx) -> dict | None:
    """Seconds of the traced transform's two waits, summed over its batches
    and averaged over the device planes: ``input_wait`` and ``fetch_tail``
    (module docstring), and the ``batches`` they were read from."""
    found = batches(ctx)
    if not found or any(None in b["first"].values() or None in b["last"].values() for b in found):
        return None
    n = len(found[0]["first"])
    up = sum(start - b["lo"] for b in found for start in b["first"].values())
    back = sum(b["hi"] - end for b in found for end in b["last"].values())
    return {"input_wait": up / n * NS, "fetch_tail": back / n * NS, "batches": found}


# ---- the job's idle seconds, tiled ----

def idle_in_solver(ctx) -> float | None:
    """Device-idle seconds inside ``fit_split``'s ``solver_device`` interval."""
    fit = traced_fit(ctx)
    if not fit or not fit["trace"]["ops"]:
        return None
    return idle_in(busy_by_plane(ctx), fit["device_lo"], fit["device_hi"]) * NS


@once_a_run
def idle_tiling(ctx) -> dict | None:
    """Idle seconds of the traced job inside each named interval, and what is
    left: ``input_wait_fit`` + ``in_solver`` + ``fetch_tail_fit`` +
    ``input_wait_transform`` + ``fetch_tail_transform`` + ``unexplained`` =
    ``total``, the job's idle seconds as ``device_idle_pct`` counts them.
    Printed on stderr, with the link's rate over the crossing."""
    fit, waits = traced_fit(ctx), transform_waits(ctx)
    if not fit or not waits or not fit["trace"]["job"]:
        return None
    trace = fit["trace"]
    busy = busy_by_plane(ctx)
    job_lo, job_hi = trace["job"]
    out = {
        "input_wait_fit": idle_in(busy, fit["enqueue"]["lo"], fit["device_lo"]),
        "in_solver": idle_in(busy, fit["device_lo"], fit["device_hi"]),
        "fetch_tail_fit": idle_in(busy, fit["device_hi"], fit["fetch"]["hi"]),
    }
    up = back = 0.0
    for b in waits["batches"]:
        for plane, merged in busy.items():
            up += idle_in({plane: merged}, b["lo"], b["first"][plane])
            back += idle_in({plane: merged}, b["last"][plane], b["hi"])
    out["input_wait_transform"] = up / len(busy)
    out["fetch_tail_transform"] = back / len(busy)
    total = idle_in(busy, job_lo, job_hi)
    out["unexplained"] = total - sum(out.values())
    out["total"] = total
    out = {k: v * NS for k, v in out.items()}
    terms = [k for k in out if k != "total"]
    print("chipbench: idle tiling: " + " + ".join(f"{k} {out[k]:.4f}" for k in terms) + f" = {out['total']:.4f} s of a "
          f"{(job_hi - job_lo) * NS:.4f} s job", file=sys.stderr, flush=True)
    up = crossing(ctx)
    if up and up["seconds"] > 0:
        print(f"chipbench: the frame crossed in {up['puts']} puts, {up['bytes'] / 1e9:.3f} GB in {up['seconds']:.4f} s: "
              f"{up['bytes'] / up['seconds'] / 1e9:.2f} GB/s", file=sys.stderr, flush=True)
    return out


# ---- the window's counters (the sink: every job) ----

def link_bytes_per_job(ctx) -> float | None:
    """Bytes the host handed to the link a job: ``host_bytes`` of every
    ``h2d.enqueue`` and ``bytes`` of every ``transform.h2d`` of the window,
    over its fits. None where no ``h2d.enqueue`` says ``host_bytes``."""
    fits = sr.named(ctx, ctx["config"]["annotations"]["fit"])
    up = [s["args"]["host_bytes"] for s in sr.named(ctx, "h2d.enqueue") if "host_bytes" in s["args"]]
    if not fits or not up:
        return None
    again = [s["args"].get("bytes", 0) for s in sr.named(ctx, "transform.h2d")]
    return (sum(up) + sum(again)) / len(fits)
