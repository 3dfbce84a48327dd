"""BENCHMARK.json's own command still runs against the program.

Every cell is rehearsed on the CPU (``--rows``: the same code end to end
at a small size, every metric value ``null``) with the profiler and the
span sink off and on. ``--trace 1`` attaches the sink for the whole run,
warm job and compiles included. This is ``chipbench/tests/test_rehearsal.py``
(run by hand) held in tier-1, at fewer rows and with ``correct`` asserted;
it reads ``chipbench/`` and ``BENCHMARK.json`` and edits neither.
"""

import json
import os
import subprocess

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_rehearses_on_cpu(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TPUML_TRACE", None)
    p = subprocess.run(
        [
            *BENCH["command"], "--workload", cell, "--seed", "3000000011",
            "--seconds", "1", "--trace", trace, "--rows", "2048",
        ],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 2, last
    assert last["device"]["platform"] == "cpu"
    # a rehearsal reports no number under a metric's name
    assert last["metrics"]
    assert all(m["value"] is None for m in last["metrics"].values())
    if trace == "0":
        assert set(last["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    else:
        assert set(last["metrics"]) <= {m["name"] for m in BENCH["per_layer"]}
