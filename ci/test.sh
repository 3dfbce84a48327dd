#!/usr/bin/env bash
# CI entry point (the reference's ci/test.sh:20-57 runs lint+typecheck, the
# pytest suite, then a benchmark smoke). tpuml-lint (stdlib-only, see
# docs/static_analysis.md) always runs; the third-party format/typecheck
# tools run when installed and are skipped (with a notice) otherwise — the
# framework environments are hermetic images where pip installs are not
# always possible.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== static checks =="
python -m compileall -q spark_rapids_ml_tpu benchmark tests tpuml_lint benchmark_runner.py
# tpuml-lint is stdlib-only so (unlike the tools below) it always runs:
# TPU/JAX invariants + env-var registry/doc drift. Rule catalog and
# suppression syntax: docs/static_analysis.md.
python -m tpuml_lint spark_rapids_ml_tpu benchmark tests scripts ci benchmark_runner.py
# concurrency-correctness rules, explicitly against an empty baseline:
# the lock-hierarchy (TPU010), blocking-under-lock (TPU011) and
# thread-lifecycle (TPU012) findings must be zero — fixed, never
# grandfathered (runtime/lockspec.py is the declared hierarchy)
python -m tpuml_lint spark_rapids_ml_tpu benchmark tests scripts ci benchmark_runner.py \
    --no-baseline --rule TPU010 --rule TPU011 --rule TPU012
python scripts/gen_config_docs.py --check
if python -c "import black" 2>/dev/null; then
    python -m black --check spark_rapids_ml_tpu tests benchmark
else
    echo "black not installed; skipping format check"
fi
if python -c "import isort" 2>/dev/null; then
    python -m isort --check-only spark_rapids_ml_tpu tests benchmark
else
    echo "isort not installed; skipping import-order check"
fi
if python -c "import mypy" 2>/dev/null; then
    python -m mypy spark_rapids_ml_tpu tpuml_lint
else
    echo "mypy not installed; skipping typecheck"
fi

echo "== unit tests =="
# Slow-marked tests (the 2-process distributed suite and runner smokes) run
# by default — they are the multi-chip correctness evidence and add <2 min.
# Set SKIPSLOW=1 for a quick iteration loop.
SKIPSLOW="${SKIPSLOW:-}"
if [ -n "$SKIPSLOW" ]; then
    python -m pytest tests/ -q
else
    python -m pytest tests/ -q --runslow
fi

echo "== notebooks (headless, CPU) =="
if python -c "import nbclient, nbformat, ipykernel" 2>/dev/null; then
    python ci/run_notebooks.py
else
    echo "nbclient/ipykernel not installed; skipping notebook execution"
fi

echo "== benchmark smoke =="
./run_benchmark.sh cpu 5000 64

echo "== tree-batched growth dispatch + gbt fit/transform smoke =="
# TPUML_RF_TREE_BATCH contract: off and auto produce bit-identical
# forests at the same seed (batched growth is an execution-shape choice,
# never a semantics choice), bad values fail loudly, and the GBT
# estimators fit + transform end to end on the same engine stack.
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from spark_rapids_ml_tpu.classification import (
    GBTClassifier, RandomForestClassifier,
)
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.ops.tree_kernels import (
    ForestConfig, resolve_tree_batch,
)
from spark_rapids_ml_tpu.runtime import envspec

rng = np.random.default_rng(0)
X = rng.normal(size=(600, 16)).astype(np.float32)
y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
df = DataFrame({"features": X, "label": y})

kw = dict(numTrees=8, maxDepth=5, seed=3)
os.environ["TPUML_RF_TREE_BATCH"] = "off"
m_off = RandomForestClassifier(**kw).fit(df)
os.environ["TPUML_RF_TREE_BATCH"] = "auto"
m_auto = RandomForestClassifier(**kw).fit(df)
os.environ.pop("TPUML_RF_TREE_BATCH")
np.testing.assert_array_equal(m_off._features_arr, m_auto._features_arr)
np.testing.assert_array_equal(m_off._thresholds_arr, m_auto._thresholds_arr)
np.testing.assert_array_equal(m_off._leaf_stats_arr, m_auto._leaf_stats_arr)

cfg = ForestConfig(
    max_depth=4, n_bins=32, n_features=16, n_stats=2, impurity="gini",
    k_features=16, min_samples_leaf=1, min_info_gain=0.0,
    min_samples_split=2, bootstrap=True,
)
os.environ["TPUML_RF_TREE_BATCH"] = "nonsense"
try:
    resolve_tree_batch(8, cfg, 600)
except envspec.EnvSpecError:
    pass
else:
    raise SystemExit("TPUML_RF_TREE_BATCH=nonsense did not raise")
finally:
    os.environ.pop("TPUML_RF_TREE_BATCH")

model = GBTClassifier(maxIter=4, maxDepth=3, seed=1).fit(df)
out = model.transform(df)
acc = float((np.asarray(out["prediction"]) == y).mean())
assert acc > 0.9, acc
prob = np.asarray(out["probability"])
assert prob.shape == (600, 2)
np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-5)
print(f"tree-batch dispatch + gbt smoke OK (gbt acc {acc:.3f})")
EOF

echo "== umap sgd engine dispatch smoke =="
# TPUML_UMAP_OPT contract: bad modes fail loudly, and on a CPU host both
# auto and an explicit pallas request resolve to the XLA engine (probe
# fallback) instead of crashing the fit.
JAX_PLATFORMS=cpu python - <<'EOF'
import os
from spark_rapids_ml_tpu.ops import umap_pallas as up

os.environ["TPUML_UMAP_OPT"] = "bogus"
try:
    up.resolve_umap_opt()
except ValueError:
    pass
else:
    raise SystemExit("TPUML_UMAP_OPT=bogus did not raise")
for mode in ("auto", "xla", "pallas"):
    os.environ["TPUML_UMAP_OPT"] = mode
    eng = up.select_sgd_engine(1024, 24, 2, 5)
    assert eng == "xla", (mode, eng)
os.environ.pop("TPUML_UMAP_OPT")
print("umap engine dispatch smoke OK")
EOF

echo "== ivf graph + ann kneighbors smoke =="
# TPUML_UMAP_GRAPH=ivf must drive a full UMAP fit through the IVF-Flat
# graph engine, and the ApproximateNearestNeighbors estimator must answer
# kneighbors through the probe search at recall >= 0.95 on blobs.
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np
from sklearn.datasets import make_blobs

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors
from spark_rapids_ml_tpu.umap import UMAP

X, _ = make_blobs(n_samples=2000, n_features=16, centers=12, random_state=7)
X = X.astype(np.float32)

os.environ["TPUML_UMAP_GRAPH"] = "ivf"
model = UMAP(
    n_neighbors=10, n_epochs=10, random_state=0, init="random",
    num_workers=1,
).fit(DataFrame({"features": X}))
assert model._fit_report["graph_engine"] == "ivf", model._fit_report
os.environ.pop("TPUML_UMAP_GRAPH")

os.environ["TPUML_ANN_GATE_ROWS"] = "1"
ann = ApproximateNearestNeighbors(k=15, num_workers=1).fit(
    DataFrame({"features": X})
)
_, _, knn_df = ann.kneighbors(DataFrame({"features": X[:128]}))
assert ann._ann_report["engine"] == "ivf", ann._ann_report
os.environ.pop("TPUML_ANN_GATE_ROWS")

from sklearn.neighbors import NearestNeighbors as SkNN

_, exact = SkNN(n_neighbors=15, algorithm="brute").fit(X).kneighbors(X[:128])
got = np.asarray(knn_df["indices"])
recall = np.mean([len(set(g) & set(e)) / 15 for g, e in zip(got, exact)])
assert recall >= 0.95, f"ann recall {recall:.4f} < 0.95"
print(f"ivf graph + ann smoke OK (recall {recall:.4f})")
EOF

echo "== fault-injection + checkpoint/resume smoke =="
# Resilience contract (docs/fault_tolerance.md): a fit killed mid-iteration
# by an injected preemption, refit with TPUML_CKPT_DIR set, resumes from
# the snapshot and matches the uninterrupted fit exactly.
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import shutil
import tempfile

import numpy as np

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import counters, reset_faults
from spark_rapids_ml_tpu.runtime.faults import SimulatedPreemption

rng = np.random.default_rng(0)
X = rng.normal(size=(256, 5))
X[:64] += 4.0
df = DataFrame({"features": X})

def fit():
    return KMeans(
        k=4, maxIter=8, tol=1e-12, seed=5, num_workers=4,
        streaming=True, stream_chunk_rows=64,
    ).setFeaturesCol("features").fit(df)

clean = fit()

ckpt_dir = tempfile.mkdtemp(prefix="tpuml-ckpt-smoke-")
try:
    os.environ["TPUML_CKPT_DIR"] = ckpt_dir
    os.environ["TPUML_CKPT_EVERY"] = "1"
    os.environ["TPUML_FAULT_SPEC"] = "sgd:epoch:2:preempt"
    reset_faults()
    try:
        fit()
    except SimulatedPreemption:
        pass
    else:
        raise SystemExit("injected preemption did not fire")
    assert os.listdir(ckpt_dir), "no checkpoint committed before the fault"

    del os.environ["TPUML_FAULT_SPEC"]
    reset_faults()
    base = counters.snapshot()
    resumed = fit()
    delta = counters.delta_since(base)
    assert delta.get("resumed_fits") == 1, delta
    assert delta.get("resumed_from") == 2, delta
    np.testing.assert_allclose(
        resumed.cluster_centers_, clean.cluster_centers_, rtol=0, atol=1e-12
    )
    assert os.listdir(ckpt_dir) == [], "checkpoint not cleared on success"
finally:
    for var in ("TPUML_CKPT_DIR", "TPUML_CKPT_EVERY", "TPUML_FAULT_SPEC"):
        os.environ.pop(var, None)
    reset_faults()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
print("fault-injection + resume smoke OK")
EOF

# Quantized-wire dispatch smoke: an int8 streamed PCA fit completes end
# to end, the model's ingest report carries the resolved encoding, and
# the components track the f32 fit within the documented int8 tolerance.
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.runtime import counters

rng = np.random.default_rng(3)
X = rng.normal(size=(512, 8)).astype(np.float32)
df = DataFrame({"features": X})

def fit():
    return PCA(
        k=3, num_workers=4, streaming=True, stream_chunk_rows=64
    ).fit(df)

base = counters.snapshot()
m32 = fit()
assert m32._ingest_report["wire_dtype"] == "f32", m32._ingest_report
try:
    os.environ["TPUML_WIRE_DTYPE"] = "int8"
    m8 = fit()
finally:
    os.environ.pop("TPUML_WIRE_DTYPE", None)
assert m8._ingest_report["wire_dtype"] == "int8", m8._ingest_report
dots = np.abs((np.asarray(m32.components_) * np.asarray(m8.components_)).sum(axis=1))
np.testing.assert_allclose(dots, 1.0, atol=5e-2)
delta = counters.delta_since(base)
assert "wire_release_errors" not in delta, delta
print("quantized-wire dispatch smoke OK:", m8._ingest_report)
EOF

# Prefetch-ring overlap smoke: on a source whose decode is synthetically
# slow (sleeps release the GIL, so decode/stage/fold genuinely overlap
# even on the CPU backend), the pipelined pass must hide most of the
# slower leg: overlap_efficiency > 0.5 against independently timed legs.
JAX_PLATFORMS=cpu python - <<'EOF'
import contextlib
import time

import numpy as np

import jax.numpy as jnp

from spark_rapids_ml_tpu.data.chunks import Chunk, GeneratorChunkSource
from spark_rapids_ml_tpu.ops import streaming as st
from spark_rapids_ml_tpu.parallel.mesh import local_mesh

mesh = local_mesh()
chunk_rows, d, n_chunks = 8192, 256, 10
rows = chunk_rows * n_chunks
block = np.random.default_rng(0).standard_normal(
    (chunk_rows, d)).astype(np.float32)
mean0 = jnp.zeros((d,), jnp.float32)

def gen(start, count, seed):
    time.sleep(0.08)  # slow decode (object storage / parquet scan stand-in)
    return block[:count], None

def decode_leg():
    src = GeneratorChunkSource(gen, rows, d)
    for _ in src.iter_chunks(chunk_rows, np.float32):
        pass

def fold_leg(dev):
    acc = st.gram2_init(d, np.float32, False)
    for _ in range(n_chunks):
        acc = st.gram2_step(acc, dev["X"], dev["mask"], mean0)
    np.asarray(jnp.ravel(acc["G"])[:1])

def full_pass():
    src = GeneratorChunkSource(gen, rows, d)
    acc = st.gram2_init(d, np.float32, False)
    guard = st.StreamGuard()
    with contextlib.closing(
        st.iter_device_chunks(src, mesh, chunk_rows, np.float32,
                              need_y=False, need_w=False)
    ) as chunks:
        for _, dev in chunks:
            acc = st.gram2_step(acc, dev["X"], dev["mask"], mean0)
            guard.tick(dev, acc)
    guard.flush(acc)

dev0 = st.put_chunk(Chunk(X=block, n_valid=chunk_rows), mesh, np.float32)
fold_leg(dev0)  # compile outside the timers
t0 = time.perf_counter(); decode_leg(); t_decode = time.perf_counter() - t0
t0 = time.perf_counter(); fold_leg(dev0); t_fold = time.perf_counter() - t0
full_pass()  # warm the pipeline threads' first-iteration costs
# min over repeats: the smoke asserts the machinery CAN overlap, so
# scheduler noise should only forgive, never fail, the assertion
t_total = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    full_pass()
    t_total = min(t_total, time.perf_counter() - t0)
overlap = max(0.0, min(1.0, (t_decode + t_fold - t_total)
                       / max(min(t_decode, t_fold), 1e-9)))
print(f"ring overlap smoke: decode={t_decode:.3f}s fold={t_fold:.3f}s "
      f"total={t_total:.3f}s overlap_efficiency={overlap:.3f}")
assert overlap > 0.5, (t_decode, t_fold, t_total, overlap)
EOF

echo "== gang-fit dispatch smoke =="
# TPUML_GANG_FIT=4 CV run must come back with gang provenance in every
# sub-model's _fit_report, and with the env UNSET the sequential path must
# be bit-identical across runs with zero gang counters (defaults inert).
JAX_PLATFORMS=cpu python - <<'EOF'
import os

import numpy as np

from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
from spark_rapids_ml_tpu.runtime import counters
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

rng = np.random.default_rng(0)
X = rng.normal(size=(1500, 12))
y = (X @ rng.normal(size=12) + 0.5 * rng.normal(size=1500) > 0).astype(float)
df = DataFrame({"features": X, "label": y})
lr = LogisticRegression(maxIter=15, tol=1e-6)
grid = (
    ParamGridBuilder()
    .addGrid(lr.getParam("regParam"), [0.01, 0.1])
    .addGrid(lr.getParam("elasticNetParam"), [0.0, 0.5])
    .build()
)
eva = MulticlassClassificationEvaluator(metricName="accuracy")

# defaults-inert: env unset, two runs bitwise identical, no gang counters
os.environ.pop("TPUML_GANG_FIT", None)
counters.reset()
a = [m for _, m in lr.fitMultiple(df, grid)]
b = [m for _, m in lr.fitMultiple(df, grid)]
for x, z in zip(a, b):
    assert np.array_equal(np.asarray(x.coef_), np.asarray(z.coef_))
    assert x._fit_report == {}
assert counters.get("gang_dispatches") == 0, counters.snapshot()

os.environ["TPUML_GANG_FIT"] = "4"
counters.reset()
cv = CrossValidator(
    estimator=lr, estimatorParamMaps=grid, evaluator=eva, numFolds=3,
    seed=1, collectSubModels=True,
)
model = cv.fit(df)
lanes = {
    m._fit_report.get("gang_lanes")
    for fold in model.subModels for m in fold
}
assert lanes and None not in lanes, lanes
assert max(lanes) <= 4, lanes  # pinned width respected
assert counters.get("gang_dispatches") >= 1, counters.snapshot()
assert counters.get("gang_lanes_total") == 12, counters.snapshot()
print(
    "gang-fit smoke OK: dispatches", counters.get("gang_dispatches"),
    "lane widths", sorted(lanes),
)
EOF

echo "== 2-D mesh (model-axis) smoke =="
# TPUML_MESH_MP contract: mp=2 fits of PCA/KMeans/ANN on 8 virtual CPU
# devices match the mp=1 fits within the documented f32 tolerance
# (docs/mesh.md), every sharded fit reports its mp_degree + per-shard
# bytes, defaults stay inert (env unset => empty _fit_report), and the
# sharded kernels compile once per program shape — zero retrace storms.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python - <<'EOF'
import os

import numpy as np
from sklearn.datasets import make_blobs

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors
from spark_rapids_ml_tpu.runtime import telemetry

X, _ = make_blobs(n_samples=2048, n_features=16, centers=8, random_state=11)
X = X.astype(np.float32)
df = DataFrame({"features": X})
qdf = DataFrame({"features": X[:128]})

def fit_all():
    pca = PCA(k=4).setInputCol("features").fit(df)
    km = KMeans(k=6, maxIter=20, seed=2).setFeaturesCol("features").fit(df)
    ann = ApproximateNearestNeighbors(k=10, num_workers=1).fit(df)
    _, _, knn = ann.kneighbors(qdf)
    return pca, km, ann, np.asarray(knn["indices"])

os.environ.pop("TPUML_MESH_MP", None)
os.environ["TPUML_ANN_GATE_ROWS"] = "1"
telemetry.reset_telemetry()
pca1, km1, ann1, ids1 = fit_all()
assert pca1._fit_report == {} and km1._fit_report == {}, "defaults not inert"
assert "mp_degree" not in ann1._ann_report, ann1._ann_report

os.environ["TPUML_MESH_MP"] = "2"
pca2, km2, ann2, ids2 = fit_all()
os.environ.pop("TPUML_MESH_MP")
os.environ.pop("TPUML_ANN_GATE_ROWS")

for report, bytes_key in (
    (pca2._fit_report, "gram_shard_bytes"),
    (km2._fit_report, "centroid_shard_bytes"),
    (ann2._ann_report, "index_shard_bytes"),
):
    assert report["mp_degree"] == 2 and report[bytes_key] > 0, report

np.testing.assert_allclose(
    np.abs(np.asarray(pca1.components_)),
    np.abs(np.asarray(pca2.components_)), rtol=2e-4, atol=2e-4,
)
np.testing.assert_allclose(
    np.sort(np.asarray(km1.cluster_centers_), axis=0),
    np.sort(np.asarray(km2.cluster_centers_), axis=0),
    rtol=1e-3, atol=1e-3,
)
overlap = np.mean([len(set(a) & set(b)) / 10 for a, b in zip(ids1, ids2)])
assert overlap >= 0.99, overlap

storms = telemetry.metrics_snapshot().get("retrace_storms")
assert not storms or all(s["value"] == 0 for s in storms["series"]), storms
print(f"2-D mesh smoke OK: mp_degree 2 for pca/kmeans/ann, "
      f"ann overlap {overlap:.3f}, 0 retrace storms")
EOF

echo "== telemetry trace smoke =="
# A traced streamed KMeans fit must produce a Perfetto-loadable trace
# whose spans cover the fit end to end: the root span brackets the whole
# wall time and its direct children account for >=95% of it, with the
# streaming pipeline sites all present.
rm -rf /tmp/tpuml_trace_smoke
TPUML_TRACE=/tmp/tpuml_trace_smoke JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os

import numpy as np

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.runtime import telemetry

rng = np.random.default_rng(0)
X = rng.normal(size=(8192, 16)).astype(np.float32)
df = DataFrame({"features": X})
PCA(k=3).setFeaturesCol("features").fit(df)
KMeans(
    k=4, maxIter=3, seed=0, num_workers=4, streaming=True,
    stream_chunk_rows=1024,
).setFeaturesCol("features").fit(df)
telemetry.flush()

stats = telemetry.span_stats()
for site in ("PCA.fit", "KMeans.fit", "preprocess", "fit.dispatch",
             "stream.ingest", "stream.decode", "stream.fold",
             "kmeans.lloyd_pass"):
    assert site in stats, (site, sorted(stats))

tdir = "/tmp/tpuml_trace_smoke"
traces = [f for f in os.listdir(tdir) if f.startswith("trace-")]
assert len(traces) == 1, os.listdir(tdir)
with open(os.path.join(tdir, traces[0])) as f:
    doc = json.load(f)  # Perfetto accepts exactly this JSON object form
events = doc["traceEvents"]
assert all(e["ph"] in ("X", "M", "i") for e in events), events[:3]
names = {e["name"] for e in events if e["ph"] == "X"}
assert {"KMeans.fit", "stream.ingest", "stream.decode",
        "stream.fold", "kmeans.lloyd_pass"} <= names, sorted(names)
# cross-thread parenting survived: every non-root span's parent exists
ids = {e["args"]["span_id"] for e in events if e["ph"] == "X"}
for e in events:
    if e["ph"] == "X" and "parent_id" in e["args"]:
        assert e["args"]["parent_id"] in ids, e
# the KMeans root's direct children account for >=95% of its wall time
xs = [e for e in events if e["ph"] == "X"]
root_ev = next(e for e in xs if e["name"] == "KMeans.fit")
covered = sum(
    e["dur"] for e in xs
    if e["args"].get("parent_id") == root_ev["args"]["span_id"]
)
assert covered >= 0.95 * root_ev["dur"], (covered, root_ev["dur"])
logs = [f for f in os.listdir(tdir) if f.startswith("events-")]
assert len(logs) == 1, os.listdir(tdir)
with open(os.path.join(tdir, logs[0])) as f:
    for line in f:
        json.loads(line)
print(f"telemetry trace smoke OK: {len(names)} span sites, "
      f"coverage {covered / root_ev['dur']:.3f}")
EOF

# defaults inert: with TPUML_TRACE unset nothing is recorded, nothing is
# written, and a traced fit's math is bit-identical to an untraced one
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import tempfile

import numpy as np

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import telemetry

os.environ.pop("TPUML_TRACE", None)
rng = np.random.default_rng(5)
X = rng.normal(size=(2048, 8)).astype(np.float32)
df = DataFrame({"features": X})

def fit():
    return KMeans(k=3, maxIter=5, seed=0).setFeaturesCol("features").fit(df)

plain = fit()
assert telemetry.span_stats() == {}, telemetry.span_stats()
assert telemetry.flush() is None and telemetry.write_metrics() is None
assert telemetry.span("x") is telemetry.span("y")  # shared no-op singleton

tdir = tempfile.mkdtemp(prefix="tpuml-tele-inert-")
try:
    os.environ["TPUML_TRACE"] = tdir
    traced = fit()
finally:
    os.environ.pop("TPUML_TRACE", None)
assert np.asarray(plain.cluster_centers_).tobytes() == \
    np.asarray(traced.cluster_centers_).tobytes()
print("telemetry defaults-inert smoke OK")
EOF

echo "== multi-host trace merge smoke =="
# Two simulated ranks (the launcher's TPUML_PROC_ID layout) trace into
# one shared directory; merge_traces must fold the shards into a single
# Perfetto file with both host tracks and summed counters.
rm -rf /tmp/tpuml_merge_smoke
for RANK in 0 1; do
    TPUML_TRACE=/tmp/tpuml_merge_smoke TPUML_PROC_ID=$RANK \
    TPUML_NUM_PROCS=2 JAX_PLATFORMS=cpu python - <<'EOF'
import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.runtime import telemetry

@jax.jit
def f(x):
    return (x @ x.T).sum()

with telemetry.span("merge.fit"):
    f(jnp.ones((32, 32), jnp.float32)).block_until_ready()
telemetry.flush()
telemetry.write_metrics()
EOF
done
python scripts/merge_traces.py /tmp/tpuml_merge_smoke
python - <<'EOF'
import json
import os

tdir = "/tmp/tpuml_merge_smoke"
shards = [f for f in os.listdir(tdir)
          if f.startswith("trace-r") and f.endswith(".json")]
assert len(shards) == 2, shards
with open(os.path.join(tdir, "merged.json")) as f:
    doc = json.load(f)
assert doc["metadata"]["hosts"] == [0, 1], doc["metadata"]
tracks = {
    e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
    if e.get("ph") == "M" and e.get("name") == "process_name"
}
assert set(tracks) == {0, 1}, tracks
assert all(name.startswith("host") for name in tracks.values()), tracks
spans = [e for e in doc["traceEvents"]
         if e.get("ph") == "X" and e["name"] == "merge.fit"]
assert {e["pid"] for e in spans} == {0, 1}, spans
# aggregated counters stay consistent: merged spans_recorded == the sum
# over the per-rank snapshots == the span events in the merged trace
snaps = []
for fn in os.listdir(tdir):
    if fn.startswith("metrics-r") and fn.endswith(".json"):
        with open(os.path.join(tdir, fn)) as f:
            snaps.append(json.load(f))
per_rank = sum(s["spans_recorded"]["series"][0]["value"] for s in snaps)
with open(os.path.join(tdir, "merged-metrics.json")) as f:
    merged = json.load(f)
total = merged["spans_recorded"]["series"][0]["value"]
assert total == per_rank == len(spans) == 2, (total, per_rank, len(spans))
print(f"merge_traces smoke OK: hosts {sorted(tracks)}, "
      f"{total} spans across ranks")
EOF

echo "== serving runtime smoke =="
# In-process serving tier under trace: three co-resident families, a
# mixed-shape request sweep, and the hard gates — zero retrace storms,
# zero compiles attributed to the steady-state dispatch site, served
# outputs bit-identical to direct transforms, and a sane p99.
rm -rf /tmp/tpuml_trace_serve
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import time

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.models.tree import RandomForestClassifier
from spark_rapids_ml_tpu.models.umap import UMAP
from spark_rapids_ml_tpu.runtime import telemetry
from spark_rapids_ml_tpu.serving import ServingRuntime

rng = np.random.default_rng(19)
X = rng.normal(size=(512, 12)).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
df = DataFrame({"features": X, "label": y})
models = {
    "pca": PCA(k=3).fit(df),
    "rf": RandomForestClassifier(
        numTrees=4, maxDepth=4, seed=3, num_workers=1
    ).fit(df),
    "umap": UMAP(
        n_neighbors=5, n_epochs=15, random_state=3, num_workers=1
    ).fit(DataFrame({"features": X})),
}
queries = [rng.normal(size=(s, 12)).astype(np.float32)
           for s in (1, 2, 5, 13, 17, 33)]
# trace ONLY the serving tier: the storm gate is a serving contract,
# and a traced fit legitimately compiles many programs per site
os.environ["TPUML_TRACE"] = "/tmp/tpuml_trace_serve"
telemetry.reset_telemetry()
t0 = time.perf_counter()
with ServingRuntime(batch_window_us=1000, max_bucket_rows=64) as rt:
    for name, m in models.items():
        rt.register(name, m)
    for _rep in range(3):
        futs = [(name, q, rt.predict_async(name, q))
                for name in models for q in queries]
        for name, q, f in futs:
            out = f.result(300)
            direct = models[name].transform(DataFrame({"features": q}))
            for col, served in out.items():
                assert np.array_equal(served, np.asarray(direct[col])), (
                    name, col, q.shape)
elapsed = time.perf_counter() - t0

snap = telemetry.metrics_snapshot()
storms = snap.get("retrace_storms")
assert not storms or all(s["value"] == 0 for s in storms["series"]), storms
batch_compiles = [
    s for s in snap.get("xla_compiles", {}).get("series", [])
    if s["labels"].get("site") == "serve.batch"
]
assert batch_compiles == [], batch_compiles
stats = telemetry.span_stats()
assert stats["serve.batch"]["count"] > 0, sorted(stats)
p99 = snap["serve_p99_ms"]["series"]
assert {s["labels"]["model"] for s in p99} == set(models), p99
assert elapsed < 120, elapsed
print(f"serving smoke OK: {3 * len(models) * len(queries)} requests, "
      f"0 retrace storms, dispatch site compile-free")
EOF

echo "== live ops plane smoke =="
# Ops-plane contract (docs/observability.md): defaults inert (no env =>
# no socket, no thread), /metrics + /statusz + /healthz answered
# mid-streamed-fit with well-formed Prometheus/JSON, and a forced SLO
# burn producing exactly one flight dump tagged slo_burn.
rm -rf /tmp/tpuml_ops_smoke
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import threading
import time
import urllib.request

import numpy as np

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import opsplane, telemetry

flight_dir = "/tmp/tpuml_ops_smoke"

# defaults inert: no env => ensure_started refuses, no socket, no thread
for var in ("TPUML_OPS_PORT", "TPUML_FLIGHT_DIR", "TPUML_TRACE"):
    os.environ.pop(var, None)
assert opsplane.ensure_started() is False
assert opsplane.address() is None and opsplane.flight_recorder() is None
assert not [t for t in threading.enumerate()
            if t.name.startswith(("tpuml-ops", "tpuml-slo"))]

# live scrape mid-fit: the streamed ingest loop auto-starts the plane;
# the scrape fires from a span sink on the first completed stream.fold,
# so it provably lands while chunks are still folding
os.environ["TPUML_OPS_PORT"] = "0"
os.environ["TPUML_FLIGHT_DIR"] = flight_dir
os.environ["TPUML_SLO_EVAL_MS"] = "60000"  # ticks driven manually below

rng = np.random.default_rng(0)
X = rng.normal(size=(4096, 8)).astype(np.float32)
df = DataFrame({"features": X})

scrapes = []

def get(path):
    host, port = opsplane.address()
    with urllib.request.urlopen(
        f"http://{host}:{port}{path}", timeout=30
    ) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()

def scrape_on_fold(ev, thread_name):
    if ev.get("name") == "stream.fold" and not scrapes:
        t0 = time.perf_counter()
        m = get("/metrics")
        dt = time.perf_counter() - t0
        scrapes.append((m, get("/statusz"), get("/healthz"), dt))

telemetry.add_span_sink(scrape_on_fold)
try:
    KMeans(
        k=4, maxIter=3, seed=0, num_workers=2, streaming=True,
        stream_chunk_rows=256,
    ).setFeaturesCol("features").fit(df)
finally:
    telemetry.remove_span_sink(scrape_on_fold)

assert opsplane.started(), "streamed fit did not auto-start the plane"
assert scrapes, "no scrape landed mid-fit"
(mcode, mctype, mbody), (scode, _, sbody), (hcode, _, hbody), dt = scrapes[0]
assert mcode == 200 and mctype.startswith("text/plain"), (mcode, mctype)
lines = mbody.decode().splitlines()
assert any(l.startswith("# TYPE tpuml_") for l in lines), lines[:5]
for l in lines:
    if l and not l.startswith("#"):
        name = l.split("{", 1)[0].split(" ", 1)[0]
        assert name.startswith("tpuml_"), l
        float(l.rsplit(" ", 1)[1])  # every sample parses as a number
assert hcode == 200 and json.loads(hbody) == {"status": "ok"}
assert scode == 200
st = json.loads(sbody)
assert "stream.ingest" in {s["name"] for s in st["active_spans"]}, st
assert "stream_ingest" in st["heartbeat_ages_s"], st

# forced SLO burn: two violating ticks alert once and trigger the
# one-shot flight dump — a third burning tick must not dump again
ev = opsplane._EVALUATOR
for _ in range(8):
    telemetry.histogram("serve_p99_ms").observe(1e4, model="smoke")
ev.tick(now=1000.0)
burn = ev.tick(now=1001.0)
assert burn["serving_p99_ms"]["alerting"], burn
ev.tick(now=1002.0)
assert telemetry.counter("slo_burn_alerts").value(slo="serving_p99_ms") == 1
shards = [f for f in os.listdir(flight_dir) if f.startswith("flight-")]
assert len(shards) == 1, shards
with open(os.path.join(flight_dir, shards[0])) as f:
    doc = json.load(f)
assert doc["metadata"]["flight"] is True, doc["metadata"]
assert doc["metadata"]["reason"] == "slo_burn", doc["metadata"]
assert opsplane.flight_recorder().dumps == {"slo_burn": 1}
print(f"ops plane smoke OK: {dt * 1e3:.1f} ms mid-fit /metrics scrape, "
      "one-shot burn dump")
EOF

# killed-run crash dump: a streamed fit SIGTERMed mid-flight with
# TPUML_TRACE unset still leaves a loadable rank-tagged flight shard
# (the handler dumps the ring, then chains to the default disposition
# so the exit status stays the conventional -SIGTERM).
rm -rf /tmp/tpuml_flight_smoke
python - <<'EOF'
import json
import os
import signal
import subprocess
import sys

child = r'''
import numpy as np
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import telemetry

def announce(ev, thread_name):
    # announce on the THIRD fold: this sink can run before the flight
    # recorder's for the same event, so earlier folds being announced
    # guarantees at least two are already in the ring when the parent
    # reacts and the SIGTERM lands
    if ev.get("name") == "stream.fold":
        announce.folds += 1
        if announce.folds == 3:
            print("MIDFIT", flush=True)

announce.folds = 0
telemetry.add_span_sink(announce)
rng = np.random.default_rng(0)
X = rng.normal(size=(2048, 8)).astype(np.float32)
df = DataFrame({"features": X})
while True:  # fit until killed
    KMeans(
        k=4, maxIter=50, seed=0, num_workers=2, streaming=True,
        stream_chunk_rows=64,
    ).setFeaturesCol("features").fit(df)
'''

env = dict(os.environ)
for var in ("TPUML_TRACE", "TPUML_OPS_PORT"):
    env.pop(var, None)
env["TPUML_FLIGHT_DIR"] = "/tmp/tpuml_flight_smoke"
env["JAX_PLATFORMS"] = "cpu"
proc = subprocess.Popen(
    [sys.executable, "-c", child], env=env,
    stdout=subprocess.PIPE, text=True,
)
line = proc.stdout.readline()
assert "MIDFIT" in line, line
proc.send_signal(signal.SIGTERM)
rc = proc.wait(timeout=120)
proc.stdout.close()
assert rc == -signal.SIGTERM, rc
shards = [f for f in os.listdir("/tmp/tpuml_flight_smoke")
          if f.startswith("flight-")]
assert len(shards) == 1, shards
with open(os.path.join("/tmp/tpuml_flight_smoke", shards[0])) as f:
    doc = json.load(f)
assert doc["metadata"]["flight"] is True, doc["metadata"]
assert doc["metadata"]["reason"] == "signal", doc["metadata"]
names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
assert "stream.fold" in names, sorted(names)[:20]
print(f"crash-dump smoke OK: {shards[0]} with {len(names)} span sites")
EOF

echo "== serving chaos smoke =="
# Fault-injected serving (docs/serving.md resilience contract): an OOM
# dispatch splits the group and retries halves bit-identically, repeated
# dispatch faults trip the per-model breaker (fast-fail at admission,
# half-open probe closes it again), every future resolves — no hangs —
# and drain() reports a clean flush.
JAX_PLATFORMS=cpu python - <<'EOF'
import concurrent.futures
import os
import time

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.runtime import faults, telemetry
from spark_rapids_ml_tpu.serving import Overloaded, ServingRuntime

rng = np.random.default_rng(23)
X = rng.normal(size=(256, 10)).astype(np.float32)
model = PCA(k=3).fit(DataFrame({"features": X}))

# dispatch 0 = the coalesced 4-request group (oom -> halve), 3/4 = the
# two singleton dispatches after the halves (1/2) -> breaker opens
os.environ["TPUML_FAULT_SPEC"] = (
    "serve:dispatch:0:oom,serve:dispatch:3:raise,serve:dispatch:4:raise"
)
faults.reset_faults()
telemetry.reset_telemetry()
queries = [rng.normal(size=(2, 10)).astype(np.float32) for _ in range(4)]
with ServingRuntime(
    batch_window_us=30_000, max_bucket_rows=64,
    breaker_fails=2, breaker_cooldown_ms=200,
) as rt:
    rt.register("pca", model)
    # one coalesced group; the injected RESOURCE_EXHAUSTED must be
    # absorbed by halving, outputs bit-identical to direct transforms
    futs = [rt.predict_async("pca", q) for q in queries]
    for q, f in zip(queries, futs):
        out = f.result(120)
        direct = model.transform(DataFrame({"features": q}))
        for col, served in out.items():
            assert np.array_equal(served, np.asarray(direct[col])), col
    # two injected dispatch faults -> breaker opens -> typed fast-fail
    for _ in range(2):
        try:
            rt.predict("pca", queries[0])
            raise AssertionError("injected dispatch fault did not surface")
        except RuntimeError as e:
            assert "injected" in str(e).lower(), e
    assert rt.breaker_states() == {"pca": "open"}, rt.breaker_states()
    try:
        rt.predict("pca", queries[0])
        raise AssertionError("open breaker admitted a request")
    except Overloaded as e:
        assert e.reason == "breaker_open", e.reason
    time.sleep(0.3)  # past cooldown: half-open probe succeeds -> closed
    rt.predict("pca", queries[0])
    assert rt.breaker_states() == {"pca": "closed"}, rt.breaker_states()
    report = rt.drain(timeout=30)
    assert report == {"drained": True, "aborted": 0}, report
    done, not_done = concurrent.futures.wait(futs, timeout=0)
    assert not not_done, not_done

snap = telemetry.metrics_snapshot()
inj = {s["labels"]["kind"]: s["value"]
       for s in snap["fault_injections"]["series"]}
assert inj == {"oom": 1, "raise": 2}, inj
assert "serve_breaker_state" in snap, sorted(snap)
shed = {(s["labels"]["model"], s["labels"]["reason"]): s["value"]
        for s in snap["serve_shed_total"]["series"]}
assert shed == {("pca", "breaker_open"): 1}, shed
del os.environ["TPUML_FAULT_SPEC"]
print("serving chaos smoke OK: oom halved bit-identically, breaker "
      "open->half-open->closed, drain clean, zero hung futures")
EOF

echo "== serving overload smoke =="
# Overload contract under trace: offered load past measured capacity
# into a tiny bounded queue must shed (typed, counted) while goodput
# stays positive and the retrace-storm gate holds.
rm -rf /tmp/tpuml_trace_overload
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import time

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.runtime import telemetry
from spark_rapids_ml_tpu.serving import Overloaded, ServingRuntime

rng = np.random.default_rng(29)
X = rng.normal(size=(256, 10)).astype(np.float32)
model = PCA(k=3).fit(DataFrame({"features": X}))
q = rng.normal(size=(8, 10)).astype(np.float32)

os.environ["TPUML_TRACE"] = "/tmp/tpuml_trace_overload"
telemetry.reset_telemetry()
with ServingRuntime(
    batch_window_us=1000, max_bucket_rows=32, queue_limit=4
) as rt:
    rt.register("pca", model)
    # closed-loop capacity probe (stays under the queue bound)
    t0 = time.perf_counter()
    for _ in range(3):
        for f in [rt.predict_async("pca", q) for _ in range(4)]:
            f.result(120)
    capacity_qps = 12 / max(time.perf_counter() - t0, 1e-9)
    offered = 2 * capacity_qps
    ok = shed = 0
    futs = []
    t0 = time.perf_counter()
    for i in range(200):
        lag = t0 + i / offered - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        try:
            futs.append(rt.predict_async("pca", q))
        except Overloaded as e:
            assert e.reason == "queue_full", e.reason
            shed += 1
    for f in futs:
        f.result(120)
        ok += 1
    elapsed = time.perf_counter() - t0

snap = telemetry.metrics_snapshot()
storms = snap.get("retrace_storms")
assert not storms or all(s["value"] == 0 for s in storms["series"]), storms
sheds = {s["labels"]["reason"]: s["value"]
         for s in snap["serve_shed_total"]["series"]}
assert shed > 0 and sheds.get("queue_full") == shed, (shed, sheds)
goodput = ok / elapsed
assert ok > 0 and goodput > 0, (ok, elapsed)
del os.environ["TPUML_TRACE"]
print(f"serving overload smoke OK: {shed}/200 shed at 2x capacity, "
      f"goodput {goodput:.0f} qps, 0 retrace storms")
EOF

echo "== pod-scale router smoke =="
# Fleet contract (docs/serving.md pod-scale section): a 2-replica
# loopback fleet serves a mixed-shape stream bit-identically with zero
# retrace storms, /statusz's fleet section reports both ranks with the
# merged-reservoir p99, and a replica killed mid-stream resolves its
# in-flight futures with typed errors — never a hang — while the
# survivor keeps the fleet serving.
JAX_PLATFORMS=cpu TPUML_OPS_PORT=0 python - <<'EOF'
import json
import urllib.request

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.runtime import opsplane, telemetry
from spark_rapids_ml_tpu.runtime.admission import ShuttingDown
from spark_rapids_ml_tpu.serving import Router

rng = np.random.default_rng(37)
X = rng.normal(size=(256, 10)).astype(np.float32)
model = PCA(k=3).fit(DataFrame({"features": X}))
telemetry.reset_telemetry()
assert opsplane.ensure_started()

with Router(
    replicas=2, policy="p2c",
    runtime_kwargs=dict(batch_window_us=2_000, max_bucket_rows=32),
) as router:
    router.register("pca", model)
    queries = [rng.normal(size=(s, 10)).astype(np.float32)
               for s in (1, 2, 5, 13, 1, 17, 3, 8) * 3]
    futs = [router.predict_async("pca", q) for q in queries]
    for q, f in zip(queries, futs):
        out = f.result(120)
        direct = model.transform(DataFrame({"features": q}))
        for col, served in out.items():
            assert np.array_equal(served, np.asarray(direct[col])), col

    host, port = opsplane.address()
    with urllib.request.urlopen(
        f"http://{host}:{port}/statusz", timeout=30
    ) as r:
        st = json.loads(r.read())
    routers = st["fleet"]["routers"]
    assert len(routers) == 1 and routers[0]["healthy"] == 2, routers
    assert [rep["rank"] for rep in routers[0]["replicas"]] == [0, 1], routers
    assert routers[0]["warmup"]["ready"] is True, routers[0]
    assert routers[0]["p99_ms"].get("pca", 0) > 0, routers[0]

    # chaos: replica 0 dies with requests still in flight — those
    # futures resolve served-or-typed, and the survivor keeps serving
    inflight = [router.replicas[0].predict_async("pca", queries[1])
                for _ in range(4)]
    router.replicas[0].close()
    for f in inflight:
        try:
            f.result(30)  # served before the close landed — fine
        except ShuttingDown:
            pass  # typed, never a hang
    assert router.healthy_count() == 1
    outs = [router.predict("pca", q, timeout=120) for q in queries[:8]]
    assert len(outs) == 8

snap = telemetry.metrics_snapshot()
storms = snap.get("retrace_storms")
assert not storms or all(s["value"] == 0 for s in storms["series"]), storms
picks = {s["labels"]["replica"]: s["value"]
         for s in snap["router_picks_total"]["series"]}
assert picks.get("0", 0) > 0 and picks.get("1", 0) > 0, picks
print("pod-scale router smoke OK: both ranks in /statusz, replica kill "
      "survived, 0 retrace storms")
EOF

echo "== fit scheduler chaos smoke =="
# Multi-tenant fit scheduler (docs/scheduler.md contract): an injected
# sched:dispatch fault fails exactly one tenant while survivors stay
# bitwise equal to their solo fits, a 1 ms quantum preempts a streamed
# fit at checkpoint boundaries and the resumed result matches the
# uninterrupted twin, and drain-under-load resolves every future.
rm -rf /tmp/tpuml_sched_ckpt
JAX_PLATFORMS=cpu python - <<'EOF'
import concurrent.futures
import os

import numpy as np

from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import FitScheduler, faults, telemetry
from spark_rapids_ml_tpu.runtime.faults import InjectedFault

rng = np.random.default_rng(31)
dfs = [
    DataFrame({"features": rng.normal(size=(96 + 16 * i, 3 + i)).astype(np.float32)})
    for i in range(4)
]
make = lambda i: KMeans(k=2 + i % 2, maxIter=5, seed=7 + i, num_workers=4)
solo = [np.asarray(make(i).fit(df).cluster_centers_) for i, df in enumerate(dfs)]

# dispatch order == submit order (no deadlines, equal priority): the
# injected fault at hit index 1 lands on tenant t1 and only t1
os.environ["TPUML_FAULT_SPEC"] = "sched:dispatch:1:raise"
faults.reset_faults()
telemetry.reset_telemetry()
with FitScheduler() as sched:
    futs = [sched.submit(make(i), df, tenant=f"t{i}") for i, df in enumerate(dfs)]
    for i, f in enumerate(futs):
        if i == 1:
            try:
                f.result(300)
                raise AssertionError("injected dispatch fault did not surface")
            except InjectedFault:
                pass
        else:
            assert np.array_equal(np.asarray(f.result(300).cluster_centers_), solo[i]), i
    stats = sched.stats()
assert stats["dispatches"] == 3 and stats["dispatch_errors"] == 1, stats
del os.environ["TPUML_FAULT_SPEC"]
faults.reset_faults()

# quantum preemption: streamed kmeans checkpoints + yields every ~1 ms,
# resumes to the exact uninterrupted result
X = rng.normal(size=(256, 5)).astype(np.float64)
X[:64] += 4.0
stream_df = DataFrame({"features": X})
mk = lambda: KMeans(k=4, maxIter=6, tol=1e-12, seed=5, num_workers=4,
                    streaming=True, stream_chunk_rows=64)
clean = mk().fit(stream_df)
os.environ["TPUML_CKPT_DIR"] = "/tmp/tpuml_sched_ckpt"
os.environ["TPUML_CKPT_EVERY"] = "1"
with FitScheduler(quantum_ms=1.0) as sched:
    model = sched.fit(mk(), stream_df, timeout=300)
    stats = sched.stats()
assert stats["preemptions"] >= 1, stats
assert stats["resumes"] == stats["preemptions"], stats
np.testing.assert_allclose(
    model.cluster_centers_, clean.cluster_centers_, rtol=0, atol=1e-12
)
del os.environ["TPUML_CKPT_DIR"], os.environ["TPUML_CKPT_EVERY"]

# drain under load: every admitted future resolves (model or typed
# ShuttingDown) inside the timeout — zero hangs
sched = FitScheduler()
futs = [sched.submit(make(i % 4), dfs[i % 4], tenant=f"t{i}") for i in range(6)]
report = sched.drain(timeout=120)
done, not_done = concurrent.futures.wait(futs, timeout=0)
assert not not_done, not_done
assert report["aborted"] == sum(1 for f in futs if f.exception() is not None), report
print(f"fit scheduler chaos smoke OK: 1 injected fault isolated, "
      f"{stats['preemptions']} preemptions resumed bit-identically, "
      f"drain {report}")
EOF

echo "== lifecycle hot-swap chaos smoke =="
# Continuous-training lifecycle (docs/serving.md#lifecycle contract):
# a v2 re-fit through the scheduler hot-swaps under live traffic with
# zero typed sheds and exactly one resident version; an injected
# swap:warm fault surfaces as a typed SwapError with v1 untouched and
# still serving; a divergent canary rolls back automatically and the
# version breaker refuses the immediate retry.
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import threading
import time

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.runtime import FitScheduler, faults, telemetry
from spark_rapids_ml_tpu.serving import (
    LifecycleError, ModelLifecycle, ServingRuntime, SwapError,
)

telemetry.reset_telemetry()
faults.reset_faults()
rng = np.random.default_rng(19)
X = rng.normal(size=(512, 8)).astype(np.float32)
df = DataFrame({"features": X})
queries = [rng.normal(size=(s, 8)).astype(np.float32) for s in (3, 17, 33)]

def totals(name):
    s = telemetry.metrics_snapshot().get(name)
    return sum(row["value"] for row in s["series"]) if s else 0

with ServingRuntime(batch_window_us=5000, max_bucket_rows=64) as rt:
    rt.register("pca", PCA(k=4).fit(df))
    with FitScheduler() as sched:
        lc = ModelLifecycle(rt, scheduler=sched)
        # live closed-loop traffic across the whole swap window
        stop, errors = threading.Event(), []
        def client():
            i = 0
            while not stop.is_set():
                try:
                    rt.predict("pca", queries[i % 3], timeout=300)
                except Exception as e:
                    errors.append(e)
                    return
                i += 1
        t = threading.Thread(target=client)
        t.start()
        try:
            # v2 re-fit through the scheduler as a preemptible tenant,
            # handed straight to the swap path
            v2 = sched.submit(
                PCA(k=4), df, tenant="lifecycle", priority=-1,
                aging_ms=600000.0,
            ).result(300)
            entry = lc.swap("pca", model=v2)
            assert entry.version == 2, entry.version
            time.sleep(0.3)
        finally:
            stop.set()
            t.join(60)
        assert not errors, f"typed shed under swap: {errors[0]!r}"
        assert rt.registry.names() == ["pca"], rt.registry.names()
        assert totals("serve_shed_total") == 0
        assert totals("retrace_storms") == 0
        # served output matches the v2 model exactly
        direct = v2.transform(DataFrame({"features": queries[1]}))
        out = rt.predict("pca", queries[1], timeout=300)
        for col in out:
            assert np.array_equal(out[col], np.asarray(direct[col])), col

        # injected mid-swap fault: typed, counted, v2 untouched
        os.environ["TPUML_FAULT_SPEC"] = "swap:warm:0:raise"
        faults.reset_faults()
        try:
            lc.swap("pca", model=PCA(k=4).fit(df))
            raise AssertionError("injected swap:warm fault did not surface")
        except SwapError as e:
            assert e.stage == "warm", e.stage
        del os.environ["TPUML_FAULT_SPEC"]
        faults.reset_faults()
        assert rt.registry.get("pca").version == 2
        assert not rt.registry.swaps_in_progress()
        assert totals("swap_failures_total") == 1
        rt.predict("pca", queries[0], timeout=300)  # still serving

        # divergent canary (fitted on unrelated data — its projection
        # basis disagrees): auto-rollback + version breaker opens
        other = rng.normal(size=(512, 8)).astype(np.float32)
        bad = PCA(k=4).fit(DataFrame({"features": other}))
        lc.start_canary("pca", model=bad, fraction=1.0, min_requests=4)
        for _ in range(8):
            rt.predict("pca", queries[2], timeout=300)
        deadline = time.monotonic() + 30
        while lc.canary_in_progress("pca") and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not lc.canary_in_progress("pca"), "canary never settled"
        assert rt.registry.get("pca").version == 2  # v2 kept serving
        assert totals("canary_rollbacks_total") == 1
        assert totals("canary_promotions_total") == 0
        try:
            lc.swap("pca", model=v2)
            raise AssertionError("version breaker admitted a swap")
        except LifecycleError:
            pass
        lc.drain(timeout=30)
print("lifecycle chaos smoke OK: scheduled re-fit hot-swapped with zero "
      "sheds, injected swap fault typed + rolled past, divergent canary "
      "rolled back with breaker open")
EOF

echo "== lock-witness chaos smoke =="
# The whole stack — serving burst + scheduler re-fit + lifecycle
# hot-swap + canary — under TPUML_LOCK_WITNESS=1: every cataloged lock
# is an instrumented wrapper checking the runtime/lockspec.py rank
# hierarchy on the REAL cross-thread acquisition orders (client
# threads, the dispatcher, the fit loop, canary scoring). The contract:
# zero lock-order violations, zero retrace storms, and the hold-time
# histogram populated for the data-plane locks the burst exercised.
JAX_PLATFORMS=cpu TPUML_LOCK_WITNESS=1 python - <<'EOF'
import threading
import time

import numpy as np

from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.runtime import FitScheduler, lockwitness, telemetry
from spark_rapids_ml_tpu.serving import ModelLifecycle, ServingRuntime

telemetry.reset_telemetry()
lockwitness.reset_lockwitness()
assert lockwitness.active(), "witness not armed"
rng = np.random.default_rng(23)
X = rng.normal(size=(512, 8)).astype(np.float32)
df = DataFrame({"features": X})
queries = [rng.normal(size=(s, 8)).astype(np.float32) for s in (3, 17, 33)]

def totals(name):
    s = telemetry.metrics_snapshot().get(name)
    return sum(row["value"] for row in s["series"]) if s else 0

with ServingRuntime(batch_window_us=5000, max_bucket_rows=64) as rt:
    rt.register("pca", PCA(k=4).fit(df))
    with FitScheduler() as sched:
        lc = ModelLifecycle(rt, scheduler=sched)
        stop, errors = threading.Event(), []
        def client(i0):
            i = i0
            while not stop.is_set():
                try:
                    rt.predict("pca", queries[i % 3], timeout=300)
                except Exception as e:
                    errors.append(e)
                    return
                i += 1
        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        try:
            # a scheduled re-fit hot-swapped under the burst, then a
            # promoting canary — the full cross-subsystem lock surface
            v2 = sched.submit(
                PCA(k=4), df, tenant="lifecycle", priority=-1,
                aging_ms=600000.0,
            ).result(300)
            lc.swap("pca", model=v2)
            lc.start_canary(
                "pca", model=v2, fraction=1.0, min_requests=4
            )
            deadline = time.monotonic() + 60
            while (lc.canary_in_progress("pca")
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert not lc.canary_in_progress("pca"), "canary never settled"
            time.sleep(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join(60)
        assert not errors, f"typed shed under witness: {errors[0]!r}"
        lc.drain(timeout=30)

viol = lockwitness.violations()
assert viol == (), f"lock-order violations on real paths: {viol}"
assert totals("lock_order_violations_total") == 0
assert totals("retrace_storms") == 0
held = {
    row.get("labels", {}).get("lock")
    for row in telemetry.metrics_snapshot()["lock_hold_ms"]["series"]
}
assert "serving.state" in held, held
print("lock-witness chaos smoke OK: serving burst + scheduled re-fit + "
      "hot-swap + canary under TPUML_LOCK_WITNESS=1 — zero lock-order "
      "violations, zero retrace storms, hold histograms for "
      f"{len(held)} lock(s)")
EOF

echo "== measured-autotuner smoke =="
# Autotuner contract (docs/autotune.md): defaults inert (env unset =>
# no cache file, no autotune metric series, fits bit-identical), a cold
# probe search measures real pinned-width fits and persists the winner,
# and the warm re-run answers the resolver's consult from the cache
# with ZERO new probe spans (span-count-asserted under TPUML_TRACE) and
# zero retrace storms.
rm -rf /tmp/tpuml_autotune_smoke /tmp/tpuml_autotune_trace
JAX_PLATFORMS=cpu python - <<'EOF'
import os
import time

import numpy as np

from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.runtime import autotune, telemetry

cache_dir = "/tmp/tpuml_autotune_smoke"
os.makedirs(cache_dir)
rng = np.random.default_rng(7)
X = rng.normal(size=(512, 12)).astype(np.float32)
y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
df = DataFrame({"features": X, "label": y})

def fit():
    return RandomForestClassifier(
        numTrees=8, maxDepth=5, seed=3, num_workers=1
    ).fit(df)

def probe_spans():
    return sum(
        st["count"]
        for name, st in telemetry.span_stats().items()
        if name.startswith("autotune.probe.")
    )

def metric_total(name):
    s = telemetry.metrics_snapshot().get(name)
    return sum(r["value"] for r in s["series"]) if s else 0

# --- defaults inert: no file, no metric series, bit-identical fits ---
for var in ("TPUML_AUTOTUNE", "TPUML_AUTOTUNE_CACHE", "TPUML_TRACE"):
    os.environ.pop(var, None)
os.environ["TPUML_RF_TREE_BATCH"] = "auto"
telemetry.reset_telemetry()
autotune.reset_autotune()
m_a, m_b = fit(), fit()
np.testing.assert_array_equal(m_a._features_arr, m_b._features_arr)
np.testing.assert_array_equal(m_a._thresholds_arr, m_b._thresholds_arr)
assert "autotuned" not in m_a._fit_report, m_a._fit_report
assert not any(
    k.startswith("autotune") for k in telemetry.metrics_snapshot()
)
assert os.listdir(cache_dir) == [], "off mode must not create files"

# --- cold: real measured search over pinned widths, winner persisted ---
os.environ["TPUML_AUTOTUNE"] = "on"
os.environ["TPUML_AUTOTUNE_CACHE"] = cache_dir
os.environ["TPUML_TRACE"] = "/tmp/tpuml_autotune_trace"
# each candidate measure is a full (small) fit: the library's 2 s
# default budget is sized for micro-probes and would truncate the grid
os.environ["TPUML_AUTOTUNE_BUDGET_MS"] = "60000"
telemetry.reset_telemetry()
autotune.reset_autotune()
m_cold = fit()  # heuristic-provenance decision carries the shape key
dec = next(
    d for d in m_cold._fit_report["autotuned"] if d["knob"] == "rf_tree_batch"
)
assert dec["provenance"] == "heuristic", dec

def measure(width):
    os.environ["TPUML_RF_TREE_BATCH"] = str(width)
    os.environ["TPUML_AUTOTUNE"] = "off"  # no recursion inside probes
    try:
        t0 = time.perf_counter()
        fit()
        return time.perf_counter() - t0
    finally:
        os.environ["TPUML_RF_TREE_BATCH"] = "auto"
        os.environ["TPUML_AUTOTUNE"] = "on"

widths = [dec["value"]] + [w for w in (1, 2, 4) if w != dec["value"]]
won = autotune.probe("rf_tree_batch", dec["key"], widths, measure, reps=1)
cold_spans = probe_spans()
assert cold_spans >= len(widths), (cold_spans, widths)
# one SEARCH (probes_total) spanning len(widths) measurements (spans)
assert metric_total("autotune_probes_total") == 1
assert os.path.exists(os.path.join(cache_dir, "autotune-cache.json"))

# --- warm: fresh in-memory state answers from disk, zero new probes ---
autotune.reset_autotune()  # simulate a new process on the same cache
m_warm = fit()
warm = next(
    d for d in m_warm._fit_report["autotuned"] if d["knob"] == "rf_tree_batch"
)
assert warm["provenance"] == "cache_hit", warm
assert warm["value"] == won.value, (warm, won)
assert probe_spans() == cold_spans, "warm cache must probe ZERO times"
assert metric_total("autotune_probes_total") == 1, "no new searches warm"
assert metric_total("autotune_cache_hits") >= 1
storms = telemetry.metrics_snapshot().get("retrace_storms")
assert not storms or all(
    s["value"] == 0 for s in storms["series"]
), storms
for var in ("TPUML_AUTOTUNE", "TPUML_AUTOTUNE_CACHE", "TPUML_TRACE",
            "TPUML_RF_TREE_BATCH", "TPUML_AUTOTUNE_BUDGET_MS"):
    os.environ.pop(var, None)
print(f"autotuner smoke OK: cold search measured {cold_spans} probes "
      f"(winner {won.value}, {won.provenance}), warm consult cache_hit "
      "with zero new probes, 0 retrace storms")
EOF

echo "CI OK"
