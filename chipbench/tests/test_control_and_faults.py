"""`correct` is a comparison that has been shown to fail.

* The control — the plain reference put in the program's place and computed
  in bfloat16 (X rounded on its way up, one-pass bf16 products) — has to come
  out as NOT correct under the limits the configuration carries. On the chip
  it was read at the cell's own size (PERF.md §2); here at a size a test run
  holds.
* The harness, driven past its look for a chip (``--rows``), with the timed
  path broken underneath, has to print ``"correct": false``: once with an
  answer altered where it is produced (one row of one transform batch), once
  with half of the rows left out of the fit. The unbroken run at the same
  size prints ``"correct": true``.
* ``linreg_dbx`` is in no cell: on the source's regression set the program's
  ``LinearRegression(regParam=0)`` is a ridge (PERF.md section 7.0). The test
  of it here states that fault and fails the day a program PR mends it — that
  PR then brings the cell.
"""
import importlib
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROWS = 40_000
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "chipbench", "configs")) if f.endswith(".json"))


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def _verdict(config, numbers):
    return all(np.isfinite(v) and v <= config["limits"][k] for k, v in numbers)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [5, 3000000005, 77])
def test_bf16_control_is_not_correct(name, seed):
    config = _config(name)
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    columns = data.make(seed, ROWS, int(config["cols"]), config["data"]["params"])
    numbers = ref.check(config, columns, [ref.reference_job(config, columns, control=True)])
    print(name, seed, numbers)
    assert not _verdict(config, numbers)


def test_program_fault_linreg_is_a_ridge_on_the_source_set():
    """PERF.md section 7.0: rss_excess 0.14-0.16 here (CPU, 40,000 rows), 250 times the limit."""
    from chipbench import run

    config = _config("linreg_dbx")
    data = importlib.import_module("chipbench.data." + config["data"]["module"])
    ref = importlib.import_module("chipbench.references." + config["reference"])
    generator = importlib.import_module("chipbench.traffic.closed_loop")
    columns = data.make(3000000005, ROWS, int(config["cols"]), config["data"]["params"])
    runner = generator.Runner(config, {"steps": ["fit", "transform"]}, columns, run.import_object(config["estimator"]["import"]), 1)
    numbers = dict(ref.check(config, columns, [runner.run_job()]))
    print(numbers)
    assert numbers["out_err"] <= config["limits"]["out_err"]
    assert numbers["rss_excess"] > 100 * config["limits"]["rss_excess"]


def _run(cell, capsys):
    from chipbench import run

    rc = run.main(["--workload", cell, "--seed", "3000000013", "--seconds", "0.1", "--trace", "0", "--rows", str(ROWS)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(cell, capsys):
    assert _run(cell, capsys)["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced(cell, capsys, monkeypatch):
    from spark_rapids_ml_tpu import core

    real = core._TpuModel._apply_batched

    def altered(self, fn, X):
        out = {k: np.array(v) for k, v in real(self, fn, X).items()}
        for col in out.values():
            if col.ndim == 1:
                col[len(col) // 3] = 1.0 - col[len(col) // 3]   # one row's prediction
        return out

    monkeypatch.setattr(core._TpuModel, "_apply_batched", altered)
    last = _run(cell, capsys)
    assert last["correct"] is False and not last["checks"]["out_err"]["ok"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_of_the_fit(cell, capsys, monkeypatch):
    from spark_rapids_ml_tpu import core
    from spark_rapids_ml_tpu.data import DataFrame

    real = core._TpuEstimator._pre_process_data

    def half(self, dataset):
        n = len(np.asarray(dataset.column(dataset.columns[0]))) // 2
        return real(self, DataFrame({c: np.asarray(dataset.column(c))[:n] for c in dataset.columns}))

    monkeypatch.setattr(core._TpuEstimator, "_pre_process_data", half)
    last = _run(cell, capsys)
    assert last["correct"] is False and not next(iter(last["checks"].values()))["ok"]
