"""Multi-process distributed path tests.

The reference treats communicator bootstrap as a first-class tested layer
(``/root/reference/python/src/spark_rapids_ml/common/cuml_context.py:35-147``,
tested by ``python/tests/test_ucx.py:35-99``). The TPU-native analog —
``TpuDistContext`` / ``jax.distributed`` + a global device mesh — gets the
same treatment: a REAL 2-process world (subprocesses with gloo CPU
collectives), each process holding its own data partition, asserting the
distributed fit matches the single-process fit bit-for-bit at f32 tolerance.

The multi-process tests require a jaxlib whose CPU backend implements
multiprocess computations (some builds raise ``INVALID_ARGUMENT:
Multiprocess computations aren't implemented on the CPU backend`` from
the very first ``process_allgather``). That is an environment property,
not a code property, so the tests gate on an explicit capability probe
— a 2-process ``jax.distributed.initialize`` + ``process_allgather``
round-trip in subprocesses — and skip with the probe's failure as the
reason when the build can't do it.
"""

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_DIST_PROBE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    jax.distributed.initialize(
        coordinator_address=os.environ["PROBE_COORD"],
        num_processes=2,
        process_id=int(os.environ["PROBE_ID"]),
    )
    from jax.experimental import multihost_utils
    out = multihost_utils.process_allgather(
        np.array([1 + int(os.environ["PROBE_ID"])], np.int32)
    )
    assert int(out.sum()) == 3, out
    print("DIST_PROBE_OK", flush=True)
    """
)

# None = not probed yet; "" = capable; anything else = the skip reason
_DIST_PROBE_RESULT = None


def _probe_two_process_cpu_world() -> str:
    """Run the minimal primitive every test here depends on: a real
    2-process gloo world doing one allgather on the CPU backend."""
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "dist_probe.py")
        with open(script, "w") as fh:
            fh.write(_DIST_PROBE)
        coord = f"127.0.0.1:{_free_port()}"
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                PROBE_COORD=coord, PROBE_ID=str(pid), JAX_PLATFORMS="cpu"
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, script], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = []
        for p in procs:
            try:
                stdout, _ = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                return "2-process jax.distributed CPU probe timed out"
            outs.append(stdout)
    if all(p.returncode == 0 for p in procs):
        return ""
    bad = next(o for p, o in zip(procs, outs) if p.returncode != 0)
    lines = [ln for ln in bad.strip().splitlines() if ln]
    return (
        "this jaxlib cannot run a 2-process CPU world: "
        + (lines[-1] if lines else "probe produced no output")
    )


def _require_two_process_cpu_world() -> None:
    """Skip (with the probe's diagnosis) unless a real multi-process
    CPU world works here. Probed once per session, cached."""
    global _DIST_PROBE_RESULT
    if _DIST_PROBE_RESULT is None:
        _DIST_PROBE_RESULT = _probe_two_process_cpu_world()
    if _DIST_PROBE_RESULT:
        pytest.skip(_DIST_PROBE_RESULT)

_WORKER = textwrap.dedent(
    """
    import os, sys
    import numpy as np

    # pin CPU (2 virtual devices per worker) before any backend touch
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, {repo!r})
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.clustering import KMeans

    from spark_rapids_ml_tpu.runtime import envspec
    pid = int(envspec.get("TPUML_PROC_ID"))

    # deterministic dataset; each process holds ITS partition only
    # (uneven split: exercises the cross-process shard agreement)
    rng = np.random.default_rng(42)
    X = rng.normal(size=(237, 9)).astype(np.float32) + 3.0
    y = (X @ rng.normal(size=(9,)) > 27.0).astype(np.float32)
    half = 150  # process 0: 150 rows, process 1: 87 rows
    sl = slice(0, half) if pid == 0 else slice(half, None)
    df = DataFrame({{"features": X[sl], "label": y[sl]}})

    # fit spans both processes (4 global devices); mesh bootstrap happens
    # inside make_mesh via ensure_distributed()
    m = PCA(k=3, num_workers=4).fit(df)
    lr = LogisticRegression(num_workers=4, regParam=0.01).fit(df)
    km = KMeans(k=4, seed=3, num_workers=4, maxIter=30).fit(df)

    # class 2 exists ONLY in process 1's partition: n_classes must still
    # resolve globally to 3 on every rank (local label stats would compile
    # mismatched collectives and deadlock)
    y3 = np.zeros(len(X), np.float32)
    y3[100:150] = 1.0
    y3[180:] = 2.0
    lr3 = LogisticRegression(num_workers=4, regParam=0.01).fit(
        DataFrame({{"features": X[sl], "label": y3[sl]}})
    )
    assert lr3.numClasses == 3, lr3.numClasses

    # RF: trees are sharded across the global device mesh; the model must
    # be identical to the single-process fit (same global layout + seeds)
    from spark_rapids_ml_tpu.classification import RandomForestClassifier
    rf = RandomForestClassifier(numTrees=8, maxDepth=4, seed=5, num_workers=4).fit(df)

    # UMAP: single-node fit gathers every process's partition, so all
    # ranks embed the FULL dataset identically
    from spark_rapids_ml_tpu.umap import UMAP
    um = UMAP(n_neighbors=8, random_state=1, init="random").fit(df)
    assert um.raw_data_.shape[0] == len(X), um.raw_data_.shape

    if pid == 0:
        np.savez(
            os.environ["SRMT_TEST_OUT"],
            components=m.components_,
            mean=m.mean_,
            ev=m.explained_variance_,
            coef=lr.coefficientMatrix,
            intercept=lr.interceptVector,
            centers=np.asarray(sorted(km.clusterCenters(), key=lambda c: tuple(c))),
            km_cost=km.trainingCost,
            coef3=lr3.coefficientMatrix,
            rf_features=rf._features_arr,
            rf_thresholds=rf._thresholds_arr,
            umap_emb=um.embedding_,
        )
    """
)


@pytest.mark.slow
def test_two_process_fit_matches_single_process(tmp_path):
    _require_two_process_cpu_world()
    out = str(tmp_path / "result.npz")
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=REPO))

    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            TPUML_COORDINATOR=coord,
            TPUML_NUM_PROCS="2",
            TPUML_PROC_ID=str(pid),
            SRMT_TEST_OUT=out,
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(stdout)
    for p, stdout in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{stdout[-3000:]}"

    res = np.load(out)

    # single-process oracle on the full dataset
    rng = np.random.default_rng(42)
    X = rng.normal(size=(237, 9)).astype(np.float32) + 3.0
    y = (X @ rng.normal(size=(9,)) > 27.0).astype(np.float32)
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.feature import PCA

    from spark_rapids_ml_tpu.clustering import KMeans

    df = DataFrame({"features": X, "label": y})
    m = PCA(k=3, num_workers=4).fit(df)
    lr = LogisticRegression(num_workers=4, regParam=0.01).fit(df)
    km = KMeans(k=4, seed=3, num_workers=4, maxIter=30).fit(df)

    np.testing.assert_allclose(res["mean"], m.mean_, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        res["components"], m.components_, rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        res["ev"], m.explained_variance_, rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        res["coef"], lr.coefficientMatrix, rtol=5e-3, atol=5e-4
    )
    np.testing.assert_allclose(
        res["intercept"], lr.interceptVector, rtol=5e-3, atol=5e-4
    )
    # same-seed k-means||: sampling depends only on global logical rows, so
    # the 2-process and 1-process fits converge to the same optimum
    np.testing.assert_allclose(float(res["km_cost"]), km.trainingCost, rtol=1e-2)

    y3 = np.zeros(len(X), np.float32)
    y3[100:150] = 1.0
    y3[180:] = 2.0
    lr3 = LogisticRegression(num_workers=4, regParam=0.01).fit(
        DataFrame({"features": X, "label": y3})
    )
    np.testing.assert_allclose(
        res["coef3"], lr3.coefficientMatrix, rtol=5e-3, atol=5e-4
    )

    from spark_rapids_ml_tpu.classification import RandomForestClassifier
    from spark_rapids_ml_tpu.umap import UMAP

    rf = RandomForestClassifier(numTrees=8, maxDepth=4, seed=5, num_workers=4).fit(df)
    np.testing.assert_array_equal(res["rf_features"], rf._features_arr)
    np.testing.assert_allclose(res["rf_thresholds"], rf._thresholds_arr, rtol=1e-5)

    um = UMAP(n_neighbors=8, random_state=1, init="random").fit(df)
    np.testing.assert_allclose(res["umap_emb"], um.embedding_, rtol=1e-4, atol=1e-4)


def test_dist_context_noop_single_process():
    """Without launcher env, the context is a no-op and exceptions pass
    through (no distributed runtime to abort)."""
    from spark_rapids_ml_tpu.parallel import TpuDistContext

    with TpuDistContext() as ctx:
        assert ctx.rank == 0 and ctx.nranks == 1
    with pytest.raises(ValueError, match="boom"):
        with TpuDistContext():
            raise ValueError("boom")


def test_distributed_env_detection(monkeypatch):
    from spark_rapids_ml_tpu.parallel import distributed_env_configured

    assert distributed_env_configured() is False
    monkeypatch.setenv("TPUML_COORDINATOR", "127.0.0.1:9")
    monkeypatch.setenv("TPUML_NUM_PROCS", "2")
    assert distributed_env_configured() is True
    monkeypatch.setenv("TPUML_NUM_PROCS", "1")
    assert distributed_env_configured() is False


_KNN_WORKER = textwrap.dedent(
    """
    import os, sys
    import numpy as np

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    from spark_rapids_ml_tpu.runtime import envspec
    pid = int(envspec.get("TPUML_PROC_ID"))
    rng = np.random.default_rng(11)
    Xi = rng.normal(size=(157, 6)).astype(np.float32)
    Xq = rng.normal(size=(63, 6)).astype(np.float32)
    isl = slice(0, 90) if pid == 0 else slice(90, None)
    qsl = slice(0, 40) if pid == 0 else slice(40, None)
    m = NearestNeighbors(k=4, num_workers=4).fit(DataFrame({{"features": Xi[isl]}}))
    _, _, knn_df = m.kneighbors(DataFrame({{"features": Xq[qsl]}}))
    idxs = np.asarray(knn_df.column("indices"))
    dists = np.asarray(knn_df.column("distances"))

    # oracle: brute force over the FULL item set for this rank's queries;
    # auto-generated ids are globally offset, so they equal positions in Xi
    qs = Xq[qsl]
    d2 = ((qs[:, None, :] - Xi[None, :, :]) ** 2).sum(-1)
    exp_idx = np.argsort(d2, axis=1)[:, :4]
    exp_d = np.sqrt(np.take_along_axis(d2, exp_idx, 1))
    assert np.allclose(np.sort(dists, 1), np.sort(exp_d, 1), atol=1e-4)
    assert (np.sort(idxs, 1) == np.sort(exp_idx, 1)).all()

    # exactNearestNeighborsJoin: every joined pair's distance must equal
    # the true pair distance even when the item row lives on the other rank
    out = m.exactNearestNeighborsJoin(DataFrame({{"features": Xq[qsl]}}), distCol="d")
    dj = np.asarray(out.column("d"))
    qf = np.asarray(out.column("query_features"))
    itf = np.asarray(out.column("item_features"))
    assert np.allclose(dj, np.sqrt(((qf - itf) ** 2).sum(1)), atol=1e-4)

    # string ids: the cross-process id exchange and the (index-selective)
    # join must carry str ids byte-exactly; ids of differing widths across
    # ranks exercise the global width agreement
    # rank 1's ids are wider: exercises the global width agreement
    all_sids = np.array(
        ["it_%03d" % i if i < 90 else "it_%03d_r1" % i for i in range(len(Xi))],
        dtype=object,
    )
    qids = np.array(["q_%02d" % i for i in range(len(Xq))], dtype=object)
    m2 = NearestNeighbors(k=3, num_workers=4, idCol="sid").fit(
        DataFrame({{"features": Xi[isl], "sid": all_sids[isl]}})
    )
    _, _, knn2 = m2.kneighbors(
        DataFrame({{"features": Xq[qsl], "sid": qids[qsl]}})
    )
    idx2 = np.asarray(knn2.column("indices"))
    assert idx2.dtype.kind == "U", idx2.dtype
    exp3 = np.argsort(d2, axis=1)[:, :3]
    assert (np.sort(idx2, 1) == np.sort(all_sids[exp3].astype(idx2.dtype), 1)).all()

    out2 = m2.exactNearestNeighborsJoin(
        DataFrame({{"features": Xq[qsl], "sid": qids[qsl]}}), distCol="d"
    )
    dj2 = np.asarray(out2.column("d"))
    qf2 = np.asarray(out2.column("query_features"))
    itf2 = np.asarray(out2.column("item_features"))
    assert np.allclose(dj2, np.sqrt(((qf2 - itf2) ** 2).sum(1)), atol=1e-4)
    assert np.asarray(out2.column("item_sid")).dtype.kind == "U"
    print(f"rank {{pid}} ok", flush=True)
    """
)


@pytest.mark.slow
def test_two_process_knn_exact(tmp_path):
    """Cross-process kNN: each rank owns item and query partitions; results
    must match a full-dataset brute-force oracle (the reference's UCX
    partition exchange contract, ``knn.py:377-379``)."""
    _require_two_process_cpu_world()
    script = tmp_path / "knn_worker.py"
    script.write_text(_KNN_WORKER.format(repo=REPO))
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            TPUML_COORDINATOR=coord,
            TPUML_NUM_PROCS="2",
            TPUML_PROC_ID=str(pid),
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"knn worker failed:\n{stdout[-3000:]}"


_STREAM_WORKER = textwrap.dedent(
    """
    import os, sys
    import numpy as np

    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.regression import LinearRegression
    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.clustering import KMeans

    from spark_rapids_ml_tpu.runtime import envspec
    pid = int(envspec.get("TPUML_PROC_ID"))
    rng = np.random.default_rng(42)
    X = (rng.normal(size=(357, 7)) + 2.0).astype(np.float32)
    w = rng.normal(size=(7,))
    yr = (X @ w + 0.5).astype(np.float32)
    yc = (X @ w > 14.0).astype(np.float32)
    sl = slice(0, 200) if pid == 0 else slice(200, None)

    kw = dict(streaming=True, stream_chunk_rows=64)
    pca = PCA(k=3, **kw).fit(DataFrame({{"features": X[sl]}}))
    lin = LinearRegression(regParam=0.01, **kw).fit(
        DataFrame({{"features": X[sl], "label": yr[sl]}}))
    log = LogisticRegression(regParam=0.01, **kw).fit(
        DataFrame({{"features": X[sl], "label": yc[sl]}}))
    km = KMeans(k=3, seed=5, maxIter=25, **kw).fit(DataFrame({{"features": X[sl]}}))
    if pid == 0:
        np.savez(
            os.environ["SRMT_TEST_OUT"],
            pca=np.asarray(pca.components_),
            lin=np.asarray(lin.coefficients),
            log=np.asarray(log.coefficientMatrix),
            km_cost=km.trainingCost,
        )
    """
)


@pytest.mark.slow
def test_two_process_streaming_matches_single_process(tmp_path):
    """Out-of-core fits across processes: each rank streams ITS partition
    through its own chips; sufficient-statistic partials allreduce — the
    reference's per-worker Arrow stream + NCCL allreduce architecture."""
    _require_two_process_cpu_world()
    out = str(tmp_path / "stream.npz")
    script = tmp_path / "stream_worker.py"
    script.write_text(_STREAM_WORKER.format(repo=REPO))
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            TPUML_COORDINATOR=coord,
            TPUML_NUM_PROCS="2",
            TPUML_PROC_ID=str(pid),
            SRMT_TEST_OUT=out,
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"stream worker failed:\n{stdout[-3000:]}"

    res = np.load(out)
    rng = np.random.default_rng(42)
    X = (rng.normal(size=(357, 7)) + 2.0).astype(np.float32)
    w = rng.normal(size=(7,))
    yr = (X @ w + 0.5).astype(np.float32)
    yc = (X @ w > 14.0).astype(np.float32)
    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.regression import LinearRegression

    kw = dict(streaming=True, stream_chunk_rows=64)
    pca = PCA(k=3, **kw).fit(DataFrame({"features": X}))
    lin = LinearRegression(regParam=0.01, **kw).fit(
        DataFrame({"features": X, "label": yr}))
    log = LogisticRegression(regParam=0.01, **kw).fit(
        DataFrame({"features": X, "label": yc}))
    km = KMeans(k=3, seed=5, maxIter=25, **kw).fit(DataFrame({"features": X}))

    np.testing.assert_allclose(res["pca"], np.asarray(pca.components_), atol=2e-4)
    np.testing.assert_allclose(
        res["lin"], np.asarray(lin.coefficients), rtol=5e-3, atol=5e-4
    )
    np.testing.assert_allclose(
        res["log"], np.asarray(log.coefficientMatrix), rtol=2e-2, atol=2e-3
    )
    np.testing.assert_allclose(float(res["km_cost"]), km.trainingCost, rtol=2e-2)


@pytest.mark.slow
def test_multihost_benchmark_launcher():
    """The cluster-submission analog (reference databricks/run_benchmark.sh):
    N processes, same command line, joined via the TPUML_* bootstrap."""
    _require_two_process_cpu_world()
    r = subprocess.run(
        [os.path.join(REPO, "run_benchmark_multihost.sh"), "2", "cpu", "3000", "16"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "EXTRA_ALGOS": "pca"},
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "multihost benchmark OK" in r.stdout
