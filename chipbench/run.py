#!/usr/bin/env python3
"""chipbench/run.py — the one command of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which touches JAX once and starts no child. Everything that
belongs to one cell is looked up by the names in ``BENCHMARK.json``: the
workload's configuration (``chipbench/configs/<config>.json``: estimator
import path and parameters, sizes, data generator, reference, work function,
limits), its traffic mix (``chipbench/traffic/<mix>.json``, a data file that
names its generator, ``chipbench/traffic/<generator>.py``) and, in a traced
run, one reader per per-layer metric (``chipbench/layer_metrics/<metric>.py``).
This file names no estimator, no metric, no cell and no generator.

Set-up (import, device, data from ``--seed``, one warm job that compiles or
hits the persistent cache) is ``setup_s``; then the generator drives the
program for ``--seconds``; then, with the program's arrays freed and the
memory peak read, the plain reference judges what the timed jobs themselves
returned. The program runs as its users run it: the harness sets no option
of JAX or of the program but the compile cache's directory.
The LAST line of stdout is the result object; everything else goes before
it or to stderr.

Without a TPU (or with fewer chips than the cell asks for) the command exits
3 and prints no result. ``--rows N`` is the rehearsal: the same code end to
end at N rows on whatever backend JAX has, every metric value ``null``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"{what} {name!r} is not in BENCHMARK.json")


def import_object(path: str):
    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def load_reader(metric: str):
    """The per-layer metric's own file, whatever characters its name has."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None, help="rehearsal: run at this many rows, report no number")
    ap.add_argument("--keep-trace", default=None, help="copy the profiler's .xplane.pb here (traced run)")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "config")
    config = load_json(config_entry["file"])
    mix = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    rehearsal = args.rows is not None
    rows = args.rows if rehearsal else int(config["rows"])

    import jax

    t_import = time.perf_counter() - T0
    devices = jax.devices()
    t_device = time.perf_counter() - T0 - t_import
    platform = devices[0].platform
    if not rehearsal and (platform != "tpu" or len(devices) < int(cell["chips"])):
        log(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX has {len(devices)} x {platform}")
        return 3
    devices = devices[: int(cell["chips"])]

    from chipbench import trace_reduce
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache(0.0)
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append((time.perf_counter(), duration)) if event == COMPILE_EVENT else None
    )
    spans: list = []
    if args.trace:
        from spark_rapids_ml_tpu.runtime import telemetry

        telemetry.add_span_sink(lambda ev, thread: spans.append(ev))

    data_mod = importlib.import_module("chipbench.data." + config["data"]["module"])
    t = time.perf_counter()
    columns = data_mod.make(args.seed, rows, int(config["cols"]), config["data"]["params"])
    t_data = time.perf_counter() - t
    generator = importlib.import_module("chipbench.traffic." + mix["generator"])
    runner = generator.Runner(config, mix, columns, import_object(config["estimator"]["import"]), int(cell["chips"]))

    t = time.perf_counter()
    runner.warm()
    t_warm = time.perf_counter() - t
    n_setup_compiles = len(compiles)

    trace_dir = os.path.join(ROOT, ".chipbench_trace") if args.trace else None
    spans.clear()
    window_start = time.perf_counter()
    setup_s = window_start - T0
    log(f"chipbench: set-up {setup_s:.1f} s (imports {t_import:.1f} s, device {t_device:.1f} s, data {t_data:.1f} s, warm job {t_warm:.1f} s, "
        f"{n_setup_compiles} programs built or fetched, cache {cache_dir})")
    traced = []

    @contextlib.contextmanager
    def traced_job():
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_reduce.start(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.JOB):
                yield
        finally:
            jax.profiler.stop_trace()
            traced.append(True)

    window = runner.window(args.seconds, traced_job if args.trace else None)
    window_end = time.perf_counter()
    done, attempted, failed, window_s = window["jobs"], window["attempted"], window["failed"], window["window_s"]
    in_window = [c for c in compiles if window_start <= c[0] <= window_end]

    stats = [d.memory_stats() or {} for d in devices]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    runner.free()
    del runner
    gc.collect()

    # ---- correct: the plain reference judges what the timed jobs returned ----
    ref = importlib.import_module("chipbench.references." + config["reference"])
    t = time.perf_counter()
    numbers = ref.check(config, columns, done) if done else []
    t_ref = time.perf_counter() - t
    checks = {}
    for name, value in numbers:
        limit = config["limits"][name]
        checks[name] = {"value": float(value), "limit": limit, "ok": bool(math.isfinite(value) and value <= limit)}
    correct = bool(done) and failed == 0 and all(c["ok"] for c in checks.values())

    # ---- metrics ----
    metrics = {}
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices), "memory_peak_bytes": int(peak)}
    breakdown = None
    if not args.trace:
        values = dict(window["values"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if reports(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        summary = None
        if traced and done:
            xplane = trace_reduce.find_xplane(trace_dir)
            if args.keep_trace and xplane:
                os.makedirs(os.path.dirname(os.path.abspath(args.keep_trace)), exist_ok=True)
                shutil.copy(xplane, args.keep_trace)
            summary = trace_reduce.reduce(xplane, config["annotations"]) if xplane else None
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = summary["breakdown"]
        peaks = load_json("chipbench", "peaks.json")
        work_mod = importlib.import_module("chipbench.work." + config["work"])
        ctx = {
            "config": config, "cell": cell, "rows": rows, "spans": spans, "trace": summary,
            "jobs": done, "traced_job": done[0] if done else None, "values": window["values"],
            "window_compiles": len(in_window), "peak_bytes": int(peak),
            "peaks": peaks["chips"].get(devices[0].device_kind), "work": work_mod,
        }
        for m in bench["per_layer"]:
            if not reports(m, cell["name"]):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    if rehearsal:
        log("chipbench: REHEARSAL (--rows): host numbers below are not device metrics and are not reported")
        log("chipbench: rehearsal values " + json.dumps({k: v["value"] for k, v in metrics.items()}))
        metrics = {k: {"value": None, "unit": v["unit"]} for k, v in metrics.items()}
        device = {k: v for k, v in device.items() if k not in ("busy_s", "window_s")}
        breakdown = None

    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["jobs"] = len(done)
    result["measured_s"] = window_s
    result["reference_s"] = t_ref
    result["checks"] = checks
    log(f"chipbench: window {window_s:.1f} s, {len(done)} jobs, {len(in_window)} programs built in the window, "
        f"peak {peak / 1e9:.2f} GB, reference {t_ref:.1f} s")
    for name, c in checks.items():
        log(f"chipbench: check {name} = {c['value']:.6g}  limit {c['limit']:.6g}  {'ok' if c['ok'] else 'FAIL'}")
    log(f"chipbench: correct = {correct}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
