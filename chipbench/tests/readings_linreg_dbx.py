#!/usr/bin/env python3
"""``linreg_dbx``'s readings, in ONE process on the chip: what ``readings.py``
reads (one job a seed through the harness's own runner, judged by the
reference) and, on the first ``--faults`` seeds, the control and every fault,
each the reference put in the program's place — and the reference in its own
place.

    python3 chipbench/tests/readings_linreg_dbx.py --seeds 12 --faults 1 --out chiprun_out/linreg_readings.jsonl

The faults: the bf16 control (the reference's own); a ridge of ``eps·trace``
on the standardized system (``eps`` of float32); the Gram one bf16 pass with
the solve exact; the transform one bf16 pass; the fit on half of the rows;
one row of the served predictions altered. ``--rows`` is the rehearsal off the
chip.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _job(beta, icpt, pred) -> dict:
    return {"model": {"coefficients": np.asarray(beta, np.float32), "intercept": np.float32(icpt)},
            "outputs": {"prediction": np.asarray(pred)}}


def _ridge(prob):
    """The standardized normal equations with ``eps·trace(A)`` added to A."""
    d = prob.d
    std = np.sqrt(np.diag(prob.Gc)[:d])
    A = prob.Gc[:d, :d] / np.outer(std, std)
    A = A + np.finfo(np.float32).eps * np.trace(A) * np.eye(d)
    beta = np.linalg.solve(A, prob.Gc[:d, d] / std) / std
    return beta, float(prob.m[d] - prob.m[:d] @ beta)


def fault_jobs(ref, config, columns, rows):
    """(name, job) of the reference in its own place, the control and each fault."""
    prob = ref.Problem(columns, config)
    beta, icpt = prob.solve()
    pred = prob.predict(beta, icpt)
    yield "reference_in_its_own_place", _job(beta, icpt, pred)
    altered = np.array(pred)
    altered[rows // 3] = 1.0 - altered[rows // 3]
    yield "fault_one_row_altered", _job(beta, icpt, altered)
    r_beta, r_icpt = _ridge(prob)
    yield "fault_ridge_eps_trace", _job(r_beta, r_icpt, prob.predict(r_beta, r_icpt))
    del prob
    gc.collect()
    low = ref.Problem(columns, config, control=True)
    g_beta, g_icpt = low.solve()
    yield "fault_bf16_transform", _job(beta, icpt, low.predict(beta, icpt))
    del low
    gc.collect()
    prob = ref.Problem(columns, config)
    yield "fault_bf16_gram_exact_solve", _job(g_beta, g_icpt, prob.predict(g_beta, g_icpt))
    del prob
    gc.collect()
    yield "control_bf16", ref.reference_job(config, columns, control=True)
    yield "fault_half_rows", ref.reference_job(config, columns, fit_rows=rows // 2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from chipbench import run as harness

    config = harness.load_json("chipbench", "configs", "linreg_dbx.json")
    mix = harness.load_json("chipbench", "traffic", "fit_then_transform.json")
    rows = args.rows or int(config["rows"])

    import jax

    from chipbench.data import gen_data
    from chipbench.references import linreg_dbx as ref
    from chipbench.traffic import closed_loop
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    estimator = harness.import_object(config["estimator"]["import"])
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
        rec = {"seed": seed, "platform": jax.devices()[0].platform, "rows": rows, "job_s": t_job,
               "seconds": jobs[-1]["seconds"], "program": dict(ref.check(config, columns, jobs[-1:]))}
        rec["reference_s"] = time.perf_counter() - t
        del jobs
        if i < args.faults:
            for name, job in fault_jobs(ref, config, columns, rows):
                t = time.perf_counter()
                rec[name] = dict(ref.check(config, columns, [job]))
                rec[name]["seconds"] = time.perf_counter() - t
                print(json.dumps({name: rec[name]}), flush=True)
                del job
                gc.collect()
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
