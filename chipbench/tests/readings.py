#!/usr/bin/env python3
"""Readings the limits are set from, in ONE process on the chip (set-up is
long, so the program's dozen seeds, the control's and the fault's are read
together): for each seed the frame is made, the program runs one job through
the harness's own runner at the cell's own size, its arrays are freed, and
the reference judges it; for the first ``--controls`` seeds the bf16 control
and for the first ``--faults`` seeds the reference fitted on half of the rows
are judged by the same comparison.

    python3 chipbench/tests/readings.py --workload logreg_dbx.job --seeds 12 --controls 3 --faults 3

``--config FILE`` reads a configuration that no cell of ``BENCHMARK.json``
uses (``--mix`` names its traffic); ``--data`` replaces the configuration's
data entry, as in ``--data '{"module": "gen_data", "params": {"kind":
"classification"}}'``: the program's faults on the source's own data sets
(PERF.md section 7.0) were read so. ``--newton N`` gives the logistic
reference N full Newton steps, which a separable set needs.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=None)
    ap.add_argument("--config", default=None, help="a configuration file that no cell uses, instead of --workload")
    ap.add_argument("--mix", default="fit_then_transform")
    ap.add_argument("--newton", type=int, default=None)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--data", default=None, help="JSON that replaces the configuration's data entry")
    args = ap.parse_args()
    from chipbench import run as harness

    if args.config:
        config = harness.load_json(args.config)
        cell = {"name": config["name"] + ".unlisted", "chips": 1, "traffic": args.mix}
    else:
        bench = harness.load_json("BENCHMARK.json")
        cell = harness.by_name(bench["workloads"], args.workload, "workload")
        config = harness.load_json(harness.by_name(bench["configs"], cell["config"], "config")["file"])
    mix = harness.load_json("chipbench", "traffic", cell["traffic"] + ".json")
    rows = args.rows or int(config["rows"])
    if args.data:
        config["data"] = json.loads(args.data)

    import jax

    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    jobs = importlib.import_module("chipbench.traffic." + mix["generator"])
    if args.newton:
        ref.NEWTON_STEPS = args.newton
    estimator = harness.import_object(config["estimator"]["import"])
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        columns = data.make(seed, rows, int(config["cols"]), config["data"]["params"])
        runner = jobs.Runner(config, mix, columns, estimator, int(cell["chips"]))
        t = time.perf_counter()
        job = runner.run_job()
        t_job = time.perf_counter() - t
        runner.free()
        del runner
        gc.collect()
        rec = {"cell": cell["name"], "seed": seed, "platform": jax.devices()[0].platform, "rows": rows, "data": config["data"],
               "job_s": t_job, "seconds": job["seconds"],
               "report": {k: float(job["model"][k]) for k in config.get("report", []) if k in job["model"]},
               "program": dict(ref.check(config, columns, [job]))}
        del job
        if i < args.controls:
            rec["control_bf16"] = dict(ref.check(config, columns, [ref.reference_job(config, columns, control=True)]))
        if i < args.faults:
            rec["fault_half_rows"] = dict(ref.check(config, columns, [ref.reference_job(config, columns, fit_rows=rows // 2)]))
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del columns
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
