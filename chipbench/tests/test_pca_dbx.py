"""``pca_dbx``: the work counts by hand, the floor at full size, and the
harness with the transform's answer altered where it is produced (the test
beside this one alters 1-D columns, and PCA's column is rows × k). The
control, the half-rows fault, the rehearsal and the names are parametrised
over every configuration and cell in the files beside this one."""
import json

import numpy as np

from chipbench.work import pca_dbx


def test_pca_work_by_hand():
    # n=1000, d=10: the symmetric half of XᵀX is 10·11/2 = 55 entries of 1000
    # multiply-adds, 110,000 operations, and one read of X (40,000 bytes)
    assert pca_dbx.gram_work(1000, 10) == {"flops": 110_000.0, "bytes": 40_000.0}
    assert pca_dbx.fit_work(1000, 10, {"components": np.zeros((3, 10))}) == pca_dbx.gram_work(1000, 10)


def test_full_size_floor_seconds():
    # the cell's own shape on one v5e: compute-bound, 4.5e12 operations at
    # 197 TFLOP/s = 22.9 ms against 7.3 ms for one read of X
    w = pca_dbx.gram_work(500_000, 3000)
    assert abs(w["flops"] / 197e12 - 0.022850) < 1e-5
    assert abs(w["bytes"] / 819e9 - 0.007326) < 1e-5


def test_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from chipbench import run
    from spark_rapids_ml_tpu import core

    real = core._TpuModel._apply_batched

    def altered(self, fn, X):
        out = {k: np.array(v) for k, v in real(self, fn, X).items()}
        for col in out.values():
            col[len(col) // 3, 0] *= 1.001      # one coordinate of one row
        return out

    monkeypatch.setattr(core._TpuModel, "_apply_batched", altered)
    assert run.main(["--workload", "pca_dbx.job", "--seed", "3000000013", "--seconds", "0.1", "--trace", "0", "--rows", "40000"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and not last["checks"]["out_err"]["ok"]
