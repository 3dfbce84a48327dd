#!/usr/bin/env python3
"""``rf_reg_dbx``'s readings, in ONE process on the chip: what ``readings.py``
reads (one job a seed through the harness's own runner, judged by the
reference) and, on the first ``--faults`` seeds, BOTH controls and every fault
this configuration's reference has, each the reference's own fit of 2 trees
put in the program's place — and the reference in its own place.

    python3 chipbench/tests/readings_rf_reg_dbx.py --seeds 6 --faults 1 --out chiprun_out/rf_reg_readings.jsonl

``--trees T`` fits T trees instead of the configuration's (the J(T) readings);
``--rows`` is the rehearsal off the chip.
"""
import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = {
    "reference_in_its_own_place": {},
    "control_bf16_frame": {"control": True},
    "control_bf16_statistics": {"stat_control": True},
    "fault_half_rows": None,                    # fit_rows = rows // 2
    "fault_bootstrap_off": {"bootstrap": False},
    "fault_cut_at_depth_3": {"cut_depth": 3},
    "fault_cut_at_depth_5": {"cut_depth": 5},
    "fault_runner_up_root": {"runner_up": True},
    "fault_one_row_altered": {"alter_row": 1234},
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=3_900_000_000)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from chipbench import run as harness

    config = harness.load_json("chipbench", "configs", "rf_reg_dbx.json")
    mix = harness.load_json("chipbench", "traffic", "fit_then_transform.json")
    if args.trees:
        config["estimator"]["params"]["numTrees"] = args.trees
    rows = args.rows or int(config["rows"])

    import jax

    from chipbench.data import gen_data
    from chipbench.references import rf_reg_dbx as ref
    from chipbench.traffic import closed_loop
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    estimator = harness.import_object(config["estimator"]["import"])
    small = copy.deepcopy(config)
    small["estimator"]["params"]["numTrees"] = 2
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        columns = gen_data.make(seed, rows, int(config["cols"]), config["data"]["params"])
        runner = closed_loop.Runner(config, mix, columns, estimator, 1)
        jobs = []
        for _ in range(2 if i == 0 else 1):     # the first seed's first job compiles
            t = time.perf_counter()
            jobs.append(runner.run_job())
            t_job = time.perf_counter() - t
        runner.free()
        del runner
        gc.collect()
        t = time.perf_counter()
        rec = {"seed": seed, "platform": jax.devices()[0].platform, "rows": rows, "trees": config["estimator"]["params"]["numTrees"],
               "job_s": t_job, "seconds": jobs[-1]["seconds"], "program": dict(ref.check(config, columns, jobs[-1:]))}
        rec["reference_s"] = time.perf_counter() - t
        del jobs
        if i < args.faults:
            for name, fault in FAULTS.items():
                fault = {"fit_rows": rows // 2} if fault is None else fault
                t = time.perf_counter()
                rec[name] = dict(ref.check(small, columns, [ref.reference_job(small, columns, **fault)]))
                rec[name]["seconds"] = time.perf_counter() - t
                print(json.dumps({name: rec[name]}), flush=True)
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
