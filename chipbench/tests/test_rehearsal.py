"""The CPU rehearsal's last line parses and has the contract's keys."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    cmd = [sys.executable, "chipbench/run.py", "--seed", "3000000011", "--seconds", "1", *extra]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_last_line(cell, trace):
    p = run("--workload", cell, "--trace", trace, "--rows", "8192")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert list(last)[-1] == "checks"
    assert last["device"]["platform"] == "cpu" and last["failed"] == 0 and last["attempted"] >= 2
    # a rehearsal reports no number under a metric's name
    assert last["metrics"] and all(m["value"] is None for m in last["metrics"].values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = bench["per_layer"] if trace == "1" else bench["end_to_end"]
    assert set(last["metrics"]) <= {m["name"] for m in group}
    if trace == "0":
        assert set(last["metrics"]) == {m["name"] for m in group}
    assert "chipbench: check" in p.stderr.strip().splitlines()[-2]


def test_no_accelerator_no_result():
    p = run("--workload", CELLS[0], "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
