"""Generator ``closed_loop``: one client, one job at a time.

A mix that names this generator (``"generator": "closed_loop"`` in its
``traffic/<mix>.json``) is data: ``steps``, the estimator-surface calls of one
job in order — any sequence of ``fit`` and ``transform`` of the same
host-resident frame, as the upstream harness's ``benchmark/bench_*.py`` runs
them. The frame is made once, before the window, from ``--seed``; the program
receives only the frame. The next job starts when the previous one has
returned, while the elapsed window is under ``--seconds``; the job in flight
is finished. Each step is timed on the host clock to its materialised result:
``fit`` to the returned model with its attributes on the host, ``transform``
to the output columns as numpy arrays.

A mix with another kind of step, several clients or an open loop is another
file beside this one, with the same surface: ``Runner(config, mix, columns,
estimator_cls, chips)`` with ``warm()``, ``window(seconds, first)`` and
``free()``. ``window`` returns what ``run.py`` prints: the finished jobs (what
the configuration's reference judges), operations attempted and failed, the
window's length and the end-to-end values under their metric names.
"""

from __future__ import annotations

import contextlib
import sys
import time

import numpy as np


class Runner:
    def __init__(self, config: dict, mix: dict, columns: dict, estimator_cls, chips: int):
        from spark_rapids_ml_tpu.data import DataFrame

        unknown = set(mix["steps"]) - {"fit", "transform"}
        if unknown:
            raise ValueError(f"closed_loop runs fit and transform steps, not {sorted(unknown)}")
        frame = config["frame"]
        self.df = DataFrame({frame["features"]: columns["features"]}).withColumn(frame["label"], columns["label"])
        self.estimator = estimator_cls(num_workers=chips, **config["estimator"]["params"])
        self.steps = list(mix["steps"])
        self.outputs = list(config["outputs"].values())
        self.report = list(config.get("report", []))

    def run_job(self) -> dict:
        """One job; returns its step seconds, the model's attributes and the
        output columns, all on the host."""
        seconds, model, attrs, outputs = {}, None, {}, {}
        for step in self.steps:
            t = time.perf_counter()
            if step == "fit":
                model = self.estimator.fit(self.df)
                attrs = {k: np.asarray(v) for k, v in model._get_model_attributes().items()}
            else:
                out = model.transform(self.df)
                outputs = {name: np.asarray(out.column(name)) for name in self.outputs}
            seconds[step] = seconds.get(step, 0.0) + time.perf_counter() - t
        return {"seconds": seconds, "model": attrs, "outputs": outputs}

    def warm(self) -> None:
        """Every program and shape the window uses: one whole job."""
        self.run_job()

    def window(self, seconds: float, first=None) -> dict:
        """Jobs for ``seconds``; ``first`` is a context manager put around the
        first job alone (the traced run's profiler)."""
        done, attempted, failed = [], 0, 0
        start = time.perf_counter()
        while not done or time.perf_counter() - start < seconds:
            attempted += len(self.steps)
            try:
                with first() if first and not done else contextlib.nullcontext():
                    job = self.run_job()
            except Exception as e:  # a job that raises is a failed operation, and the run is not correct
                print(f"chipbench: job raised {type(e).__name__}: {e}", file=sys.stderr, flush=True)
                failed += len(self.steps)
                break
            done.append(job)
            print(f"chipbench: job {len(done)}: " + ", ".join(f"{k} {v:.3f} s" for k, v in job["seconds"].items())
                  + "".join(f", {k}={job['model'][k]}" for k in self.report if k in job["model"]), file=sys.stderr, flush=True)
        window_s = time.perf_counter() - start
        values = {"job_s": window_s / max(len(done), 1)}
        for step in dict.fromkeys(self.steps):
            calls = self.steps.count(step) * max(len(done), 1)
            values[step + "_s"] = sum(j["seconds"][step] for j in done) / calls
        return {"jobs": done, "attempted": attempted, "failed": failed, "window_s": window_s, "values": values}

    def free(self) -> None:
        self.df = self.estimator = None
