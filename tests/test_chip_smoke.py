"""``chip_smoke.py`` off the chip: the phases and comparisons run as a
rehearsal, and the answer is still ``"ok": false`` with a non-zero exit —
there is no mode in which the script reports success without a TPU."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: the script wants 1
    proc = subprocess.run(
        [sys.executable, SCRIPT, *args], capture_output=True, text=True,
        env=env, timeout=600, cwd=REPO,
    )
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


def test_cpu_rehearsal_runs_every_phase_and_still_fails():
    proc, lines = _run("--rows", "8192", "--knn-items", "4096", "--knn-queries", "256")
    assert proc.returncode != 0, proc.stderr[-2000:]
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    phases = {l["phase"]: l for l in lines if "phase" in l and "checks" in l}
    assert list(phases) == ["pca", "kmeans", "linreg", "logreg", "knn"]
    for name, ph in phases.items():
        assert ph["ok"] is True, (name, ph["checks"])
        assert all(c["ok"] for c in ph["checks"]), (name, ph["checks"])
        # off the chip every gate reports the XLA path, as found
        assert ph["kernel"]["pallas"] is False, name


def test_default_shapes_are_refused_at_once_off_the_chip():
    proc, lines = _run()
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False and lines[-1]["device"]["platform"] == "cpu"
    assert not any("checks" in l for l in lines)  # no phase ran
    assert any("refused" in l for l in lines)
