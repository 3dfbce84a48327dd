"""Packed-forest inference engine tests: the FIL-style lockstep layout
(``ops/tree_kernels.pack_forest`` + the ``rf_pallas.packed_traverse``
kernel + the model dispatch layer) must be BIT-IDENTICAL to the per-tree
two-hop bins descent — leaf routing is integer comparisons and the
payload reduction replicates the bins path's association exactly, so
equality is exact, not approximate."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.classification import (
    RandomForestClassificationModel,
    RandomForestClassifier,
)
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.regression import (
    RandomForestRegressionModel,
    RandomForestRegressor,
)


def _blobs(n=400, d=10, k=3, seed=0, spread=0.4):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 4
    labels = rng.integers(0, k, size=n)
    X = centers[labels] + spread * rng.normal(size=(n, d))
    return X.astype(np.float32), labels.astype(np.float64)


def _reg_data(n=400, d=6, seed=1):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-2, 2, size=(n, d))
    y = np.sin(X[:, 0]) * 3 + X[:, 1] ** 2 + 0.5 * X[:, 2]
    return X.astype(np.float32), y.astype(np.float64)


def _random_forest(rng, T, depth, d, nb):
    """Heap-ordered (feat, thrb) with consistent leaf structure: children
    of leaves are leaves (the builder's invariant pack_forest relies on)."""
    from spark_rapids_ml_tpu.ops.tree_kernels import max_nodes

    M = max_nodes(depth)
    feat = rng.integers(0, d, size=(T, M)).astype(np.int32)
    thrb = rng.integers(0, nb - 1, size=(T, M)).astype(np.int32)
    for t in range(T):
        for i in range(M):
            p = (i - 1) // 2
            if i >= (1 << depth) - 1 or (i > 0 and feat[t, p] < 0):
                feat[t, i] = -1
            elif rng.random() < 0.2:
                feat[t, i] = -1
    return feat, thrb


# ---------------------------------------------------------------------------
# kernel-level parity
# ---------------------------------------------------------------------------


def test_packed_descent_matches_python_oracle(monkeypatch):
    """pack_forest + forest_apply_packed (interpret-forced kernel) vs a
    per-row python heap walk: identical leaf heap indices across depths
    spanning k2=0 (hop-1-only) and the kernel path, tree counts off the
    pad-of-8 boundary, and a feature width beyond one 64-lane word."""
    import jax

    import spark_rapids_ml_tpu.ops.rf_pallas as rfp
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        forest_apply_packed,
        pack_forest,
    )

    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    rng = np.random.default_rng(17)
    try:
        for depth, T, n, d, nb in [
            (5, 5, 100, 12, 32),    # k2 = 0: no kernel, hop-1 only
            (7, 7, 257, 130, 64),   # k2 = 0 at the k1 cap; d > 128 lanes
            (9, 9, 400, 16, 64),    # k2 = 2: kernel path
            (13, 4, 300, 8, 64),    # k2 = 6: deepest supported subtree
        ]:
            feat, thrb = _random_forest(rng, T, depth, d, nb)
            xb = rng.integers(0, nb, size=(n, d), dtype=np.uint8)

            def descend(t, row):
                i = 0
                while feat[t, i] >= 0:
                    i = 2 * i + 1 + int(xb[row, feat[t, i]] > thrb[t, i])
                return i

            oracle = np.array(
                [[descend(t, r) for r in range(n)] for t in range(T)]
            ).T  # (n, T)
            pf = pack_forest(feat, thrb, max_depth=depth)
            got = np.asarray(
                forest_apply_packed(
                    np.asarray(xb),
                    pf.feat1, pf.thr1, pf.feat2, pf.thr2,
                    k1=pf.k1, k2=pf.k2, max_depth=depth,
                )
            )
            np.testing.assert_array_equal(got[:, :T], oracle)
    finally:
        jax.clear_caches()


def test_packed_eval_bit_identical_to_bins(monkeypatch):
    """rf_eval_packed vs rf_eval_bins on the same forest: the payload
    accumulation replicates the bins path's group-of-8 association, so
    the float sums are bit-identical, not merely close."""
    import jax

    import spark_rapids_ml_tpu.ops.rf_pallas as rfp
    from spark_rapids_ml_tpu.ops.tree_kernels import (
        pack_forest,
        rf_eval_bins,
        rf_eval_packed,
    )

    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    rng = np.random.default_rng(23)
    try:
        for depth, T, n, d, nb in [(9, 9, 400, 16, 64), (5, 5, 100, 12, 32)]:
            feat, thrb = _random_forest(rng, T, depth, d, nb)
            vals = rng.normal(size=feat.shape + (3,)).astype(np.float32)
            xb = rng.integers(0, nb, size=(n, d), dtype=np.uint8)
            ref = np.asarray(
                rf_eval_bins(
                    np.asarray(xb), np.asarray(feat), np.asarray(thrb),
                    np.asarray(vals), max_depth=depth,
                )
            )
            pf = pack_forest(feat, thrb, max_depth=depth)
            got = np.asarray(
                rf_eval_packed(
                    np.asarray(xb),
                    pf.feat1, pf.thr1, pf.feat2, pf.thr2, np.asarray(vals),
                    k1=pf.k1, k2=pf.k2, max_depth=depth,
                )
            )
            np.testing.assert_array_equal(got, ref)
    finally:
        jax.clear_caches()


# ---------------------------------------------------------------------------
# model-level parity
# ---------------------------------------------------------------------------


# The deep/wide shapes cost ~30s each in interpret mode for the same packed
# traversal path as (5, 7); they stay on --runslow to keep tier-1 in budget.
@pytest.mark.parametrize(
    "depth,trees",
    [
        (5, 7),
        pytest.param(9, 9, marks=pytest.mark.slow),
        pytest.param(11, 5, marks=pytest.mark.slow),
    ],
)
def test_rf_transform_packed_matches_bins(monkeypatch, depth, trees):
    """TPUML_RF_APPLY=packed (interpret-forced kernel) must reproduce the
    bins descent bit-for-bit at the model level — every output column,
    classification AND regression. A spy proves the traversal kernel
    actually ran when the depth requires it (else the packed gate could
    silently fall back and this would compare bins against bins)."""
    import jax

    import spark_rapids_ml_tpu.ops.rf_pallas as rfp

    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    calls = []
    real = rfp.packed_traverse

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    # forest_apply_packed resolves packed_traverse from rf_pallas at call
    # time (function-local import), so this patch is seen by the engine
    monkeypatch.setattr(rfp, "packed_traverse", spy)

    X, y = _blobs(seed=depth)
    df = DataFrame({"features": X, "label": y})
    dfq = DataFrame({"features": X})
    try:
        m = RandomForestClassifier(
            numTrees=trees, maxDepth=depth, seed=3, num_workers=1
        ).fit(df)
        monkeypatch.setenv("TPUML_RF_APPLY", "bins")
        out_b = m.transform(dfq)
        monkeypatch.setenv("TPUML_RF_APPLY", "packed")
        assert m._packed_apply_ready()
        out_p = m.transform(dfq)
        needs_kernel = m._ensure_packed().k2 > 0
        assert bool(calls) == needs_kernel, (calls, needs_kernel)
        for c in ("prediction", "probability", "rawPrediction"):
            a, b = np.asarray(out_b[c]), np.asarray(out_p[c])
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b, err_msg=c)

        Xr, yr = _reg_data(seed=depth)
        dfr = DataFrame({"features": Xr, "label": yr})
        mr = RandomForestRegressor(
            numTrees=trees, maxDepth=depth, seed=5, num_workers=1
        ).fit(dfr)
        monkeypatch.setenv("TPUML_RF_APPLY", "bins")
        pb = np.asarray(mr.transform(dfr)["prediction"])
        monkeypatch.setenv("TPUML_RF_APPLY", "packed")
        pp = np.asarray(mr.transform(dfr)["prediction"])
        np.testing.assert_array_equal(pb, pp)
    finally:
        jax.clear_caches()


def test_rf_packed_save_load_roundtrip(monkeypatch, tmp_path):
    """Persistence: saving a model after packing stores the packed SoA
    tensors; a reload is PRE-PACKED (pack_forest never reruns) and its
    packed predictions are bit-identical to the original's."""
    import jax

    import spark_rapids_ml_tpu.models.tree as mt
    import spark_rapids_ml_tpu.ops.rf_pallas as rfp

    monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    X, y = _blobs(seed=31)
    df = DataFrame({"features": X, "label": y})
    dfq = DataFrame({"features": X})
    try:
        m = RandomForestClassifier(
            numTrees=6, maxDepth=9, seed=3, num_workers=1
        ).fit(df)
        monkeypatch.setenv("TPUML_RF_APPLY", "packed")
        out1 = m.transform(dfq)
        assert m._model_attributes.get("packed_feat1") is not None

        path = str(tmp_path / "rf_model")
        m.write().overwrite().save(path)

        import spark_rapids_ml_tpu.ops.tree_kernels as tk

        def boom(*a, **k):
            raise AssertionError("pack_forest reran on a pre-packed reload")

        monkeypatch.setattr(tk, "pack_forest", boom)
        m2 = RandomForestClassificationModel.load(path)
        pf1, pf2 = m._ensure_packed(), m2._ensure_packed()
        assert (pf1.n_trees, pf1.k1, pf1.k2, pf1.max_depth) == (
            pf2.n_trees, pf2.k1, pf2.k2, pf2.max_depth
        )
        np.testing.assert_array_equal(pf1.feat1, pf2.feat1)
        np.testing.assert_array_equal(pf1.thr2, pf2.thr2)
        out2 = m2.transform(dfq)
        for c in ("prediction", "probability", "rawPrediction"):
            np.testing.assert_array_equal(
                np.asarray(out1[c]), np.asarray(out2[c]), err_msg=c
            )
    finally:
        jax.clear_caches()


def test_rf_apply_mode_validation(monkeypatch):
    """Typos in TPUML_RF_APPLY must error, not silently select a path."""
    X, y = _blobs(n=60, seed=2)
    df = DataFrame({"features": X, "label": y})
    m = RandomForestClassifier(numTrees=2, maxDepth=3, seed=1).fit(df)
    monkeypatch.setenv("TPUML_RF_APPLY", "packd")
    with pytest.raises(ValueError, match="TPUML_RF_APPLY"):
        m.transform(df)


def test_rf_finite_input_contract(monkeypatch):
    """Fit rejects non-finite features outright; transform does when the
    opt-in TPUML_RF_CHECK_FINITE=1 boundary check is on (binize would
    otherwise silently route NaN to bin 0)."""
    X, y = _blobs(n=80, seed=4)
    Xbad = X.copy()
    Xbad[3, 2] = np.nan
    with pytest.raises(ValueError, match="NaN/Inf"):
        RandomForestClassifier(numTrees=2, maxDepth=3, seed=1).fit(
            DataFrame({"features": Xbad, "label": y})
        )

    m = RandomForestClassifier(numTrees=2, maxDepth=3, seed=1).fit(
        DataFrame({"features": X, "label": y})
    )
    monkeypatch.setenv("TPUML_RF_APPLY", "bins")
    monkeypatch.setenv("TPUML_RF_CHECK_FINITE", "1")
    with pytest.raises(ValueError, match="NaN/Inf"):
        m.transform(DataFrame({"features": Xbad}))
    # and the guard stays out of the way for clean inputs
    m.transform(DataFrame({"features": X}))


def test_export_random_forest_packed():
    """export.random_forest_packed surfaces the cached SoA layout with
    real-tree metadata (serving integrations read this, not the model's
    private attributes)."""
    from spark_rapids_ml_tpu.export import random_forest_packed

    X, y = _blobs(n=100, seed=8)
    m = RandomForestClassifier(numTrees=5, maxDepth=6, seed=2).fit(
        DataFrame({"features": X, "label": y})
    )
    pk = random_forest_packed(m)
    assert pk["meta"]["n_trees"] == 5
    assert pk["feat1"].shape[0] % 8 == 0
    k1, k2 = pk["meta"]["k1"], pk["meta"]["k2"]
    assert k1 + k2 == m._max_depth_built
    assert pk["feat1"].shape[1] == (1 << k1) - 1
    if k2 == 0:
        assert pk["feat2"].shape == (0, 64)
    else:
        assert pk["feat2"].shape == (pk["feat1"].shape[0] * (1 << k1), 64)
    with pytest.raises(TypeError):
        random_forest_packed(object())
