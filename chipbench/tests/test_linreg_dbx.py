"""``linreg_dbx`` at 40,000 x 3000 on the CPU: the program's fit is correct,
and the control and every fault — each the reference put in the program's
place (``readings_linreg_dbx.fault_jobs``) — are not, each by the number
PERF.md section 2 names; the reference in its own place is correct."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
ROWS, SEED = 40_000, 3000000005
CAUGHT_BY = {
    "reference_in_its_own_place": None,
    "fault_one_row_altered": "out_err",
    "fault_ridge_eps_trace": "rss_excess",
    "fault_bf16_transform": "out_err",
    "fault_bf16_gram_exact_solve": "rss_excess",
    "control_bf16": "rss_excess",
    "fault_half_rows": "rss_excess",
}


@pytest.fixture(scope="module")
def frame():
    with open(os.path.join(ROOT, "chipbench", "configs", "linreg_dbx.json")) as f:
        config = json.load(f)
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    return config, ref, data.make(SEED, ROWS, int(config["cols"]), config["data"]["params"])


def test_the_program_is_correct(frame):
    from chipbench import run

    config, ref, columns = frame
    generator = importlib.import_module("chipbench.traffic.closed_loop")
    runner = generator.Runner(config, {"steps": ["fit", "transform"]}, columns, run.import_object(config["estimator"]["import"]), 1)
    numbers = dict(ref.check(config, columns, [runner.run_job()]))
    print(numbers)
    assert all(np.isfinite(v) and v <= config["limits"][k] for k, v in numbers.items()), numbers


def test_each_fault_fails_its_limit(frame):
    from readings_linreg_dbx import fault_jobs

    config, ref, columns = frame
    seen = {}
    for name, job in fault_jobs(ref, config, columns, ROWS):
        numbers = dict(ref.check(config, columns, [job]))
        seen[name] = numbers
        print(name, numbers)
        by = CAUGHT_BY[name]
        if by is None:
            assert all(v <= config["limits"][k] for k, v in numbers.items()), numbers
        else:
            assert numbers[by] > config["limits"][by], (name, numbers)
    assert set(seen) == set(CAUGHT_BY)
