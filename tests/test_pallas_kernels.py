"""Pallas kernel correctness (interpret mode on the CPU mesh).

The real kernels run only on TPU (`gram_pallas_declined` gates on backend); these
tests run the same kernel bodies through the Pallas interpreter against
numpy oracles, including the last-partial-tile index-validity guard.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.linalg import _shifted_gram_pallas


@pytest.mark.parametrize(
    "n,tile,d",
    [(512, 128, 256), (700, 128, 256), (100, 256, 256), (300, 128, 700), (260, 128, 300)],
    ids=["whole_tiles", "ragged_tail", "one_short_tile", "two_blocks_width_700", "width_300"],
)
def test_shifted_gram_pallas_matches_numpy(n, tile, d):
    """The kernel reads the shard as its transpose (d, n); widths that are no
    lane multiple overhang its one block along d, and past 512 columns the
    accumulator is an upper triangle of blocks mirrored after the call."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32) + 2.0
    mask = (rng.random(n) > 0.1).astype(np.float32)
    mu = X[:64].mean(axis=0)

    G, s = _shifted_gram_pallas(
        jnp.asarray(X).T, jnp.asarray(mask), jnp.asarray(mu),
        tile=tile, interpret=True,
    )

    xs = (X.astype(np.float64) - mu.astype(np.float64)) * mask[:, None]
    G_ref = xs.T @ xs
    s_ref = xs.sum(axis=0)
    scale = np.abs(G_ref).max()
    assert G.shape == (d, d) and s.shape == (d,)
    assert np.abs(np.asarray(G, np.float64) - G_ref).max() / scale < 2e-6   # float32-exact products
    assert np.array_equal(np.asarray(G), np.asarray(G).T)
    assert np.abs(np.asarray(s, np.float64) - s_ref).max() < 1e-2


def test_shifted_gram_pallas_all_masked_tail():
    # padding suffix fully masked: the guard and the mask must compose
    d, n, tile = 256, 384, 128
    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[300:] = 1e30  # padded rows may hold (finite) garbage — must not leak
    mask = (np.arange(n) < 300).astype(np.float32)
    mu = X[:64].mean(axis=0)

    G, s = _shifted_gram_pallas(
        jnp.asarray(X).T, jnp.asarray(mask), jnp.asarray(mu),
        tile=tile, interpret=True,
    )
    assert np.isfinite(np.asarray(G)).all()
    xs = (X[:300].astype(np.float64) - mu.astype(np.float64))
    G_ref = xs.T @ xs
    assert np.abs(np.asarray(G, np.float64) - G_ref).max() / np.abs(G_ref).max() < 2e-6


def _logreg_data_term(X, y, m, multinomial):
    """The XLA data term of ``logreg_fit``: Σ m·logloss at (Aeff, beff)."""
    def ref(a, b):
        logits = X @ a.T + b[None, :]
        if multinomial:
            ll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
                logits, y.astype(jnp.int32)[:, None], axis=1
            )[:, 0]
        else:
            z = logits[:, 0]
            ll = jax.nn.softplus(z) - y * z
        return (ll * m).sum()

    return ref


@pytest.mark.parametrize(
    "multinomial,K,minor_rows",
    [(False, 1, False), (False, 1, True), (True, 3, False)],
    ids=["binary_cols_minor", "binary_rows_minor", "multinomial"],
)
def test_fused_logreg_loss_grad_matches_autodiff(monkeypatch, multinomial, K, minor_rows):
    """The fused Pallas loss+grad (one data pass) must match
    jax.value_and_grad of the reference formulation, including masking and
    the padded-classes guard — the binary pass whichever way the device
    keeps the shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import logreg_pallas
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(logreg_pallas, "rows_minor", lambda *a: minor_rows)
    mesh = make_mesh(8)
    rng = np.random.default_rng(0)
    n, d = 8 * 40, 256
    X = rng.normal(size=(n, d)).astype(np.float32)
    ncls = K if multinomial else 2
    y = rng.integers(0, ncls, size=n).astype(np.float32)
    mask = (np.arange(n) < n - 13).astype(np.float32)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))
    Xd, yd, md = put(X), put(y), put(mask)
    Aeff = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32) * 0.1)
    beff = jnp.asarray(rng.normal(size=(K,)).astype(np.float32) * 0.1)

    f = logreg_pallas.make_fused_data_loss(Xd, yd, md, mesh, K, multinomial, interpret=True)
    loss, (gA, gb) = jax.value_and_grad(
        lambda a, b: f(a, b), argnums=(0, 1)
    )(Aeff, beff)

    rl, (rgA, rgb) = jax.value_and_grad(_logreg_data_term(Xd, yd, md, multinomial), argnums=(0, 1))(Aeff, beff)
    assert abs(float(loss) - float(rl)) < 1e-2
    assert float(jnp.abs(gA - rgA).max() / jnp.abs(rgA).max()) < 1e-4
    assert float(jnp.abs(gb - rgb).max()) < 1e-2


@pytest.mark.parametrize("minor_rows", [False, True], ids=["cols_minor", "rows_minor"])
@pytest.mark.parametrize("n,d", [(4100, 300), (4100, 2176), (1029, 8)])
def test_binary_pass_at_any_width(n, d, minor_rows):
    """The binary pass against value_and_grad of the XLA data term where the
    width is no lane multiple, or above the old kernel's 2048 columns: the
    rows do not divide by the tile (a masked tail of garbage rows), 300 and
    2176 are no multiples of 128, 300 is none of 8 (the rows-minor kernel's
    last sublane group is partial). Float32 sums: 1e-5 of the loss, of the
    largest gradient coordinate, and of Σ|r| for the intercept's."""
    from spark_rapids_ml_tpu.ops.logreg_pallas import binary_loss_grad, binary_tile

    assert n % binary_tile(d, minor_rows)[0]
    rng = np.random.default_rng(n + d)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, size=n).astype(np.float32)
    mask = (rng.random(n) > 0.1).astype(np.float32)
    a = (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)
    b = np.float32(0.3)

    loss, g, gb = binary_loss_grad(X, y, mask, a, b, minor_rows=minor_rows, interpret=True)
    ref = _logreg_data_term(jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask), False)
    rl, (rg, rgb) = jax.value_and_grad(lambda a, b: ref(a[None, :], b[None]), argnums=(0, 1))(jnp.asarray(a), jnp.asarray(b))
    assert abs(float(loss) - float(rl)) / float(rl) < 1e-5
    assert float(jnp.abs(g - rg).max() / jnp.abs(rg).max()) < 1e-5
    assert abs(float(gb) - float(rgb)) / float(mask.sum()) < 1e-5


@pytest.mark.parametrize(
    "d,minor_rows", [(256, False), (256, True), (300, False), (300, True)],
    ids=["d256_cols_minor", "d256_rows_minor", "d300_cols_minor", "d300_rows_minor"],
)
def test_logreg_fit_fused_branch_matches_xla(monkeypatch, d, minor_rows):
    """Run the REAL fused branch inside logreg_fit (gate -> custom_vjp ->
    L-BFGS) via the interpret override and require parity with the XLA
    branch (``mesh=None``) in coefficients, intercept and objective — guards
    the integration wiring (the /n scaling, the standardization
    reparametrization feeding Aeff/beff) at a lane-aligned width and at one
    that is not, whichever way the device keeps the shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import logreg_pallas
    from spark_rapids_ml_tpu.ops.logreg_kernels import logreg_fit
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    rng = np.random.default_rng(2)
    n = 8 * 48
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32) * 0.2
    y = (X @ w > 0).astype(np.float32)
    mask = (np.arange(n) < n - 17).astype(np.float32)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))
    Xd, yd, md = put(X), put(y), put(mask)

    kw = dict(
        n_classes=2, multinomial=False, fit_intercept=True,
        standardization=True, l1=jnp.float32(0.0), l2=jnp.float32(1e-3),
        use_l1=False, max_iter=25, tol=jnp.float32(0.0),
    )
    ref = logreg_fit(Xd, md, yd, mesh=None, **kw)

    monkeypatch.setattr(logreg_pallas, "FORCE_INTERPRET", True)
    monkeypatch.setattr(logreg_pallas, "rows_minor", lambda *a: minor_rows)
    assert logreg_pallas.logreg_pallas_ok(n // 8, d, 1, jnp.float32)
    # FORCE_INTERPRET is read at trace time but is not part of the jit
    # cache key: drop cached executables so this call really traces (and
    # runs) the fused branch, and again afterwards so no interpreted
    # executable leaks into later same-signature calls
    jax.clear_caches()
    try:
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda X, m, y: logreg_fit(X, m, y, mesh=mesh, **kw))(Xd, md, yd))
        fused = logreg_fit(Xd, md, yd, mesh=mesh, **kw)
    finally:
        jax.clear_caches()

    cr = np.asarray(ref["coef_"])
    cf = np.asarray(fused["coef_"])
    assert np.abs(cr - cf).max() / max(np.abs(cr).max(), 1e-9) < 1e-3
    assert abs(float(ref["intercept_"][0]) - float(fused["intercept_"][0])) < 1e-3
    assert abs(float(ref["objective"]) - float(fused["objective"])) < 1e-5 * float(ref["objective"])


@pytest.mark.parametrize(
    "K,d,dtype,tpu,declined",
    [
        (1, 3000, "float32", True, ""),            # the binary pass: any width
        (1, 256, "float32", True, ""),
        (1, 4096, "float32", True, ""),            # above the multinomial kernel's 2048
        (1, 3000, "bfloat16", True, "dtype"),      # a bf16-placed X keeps XLA's passes
        (1, 3000, "float32", False, "backend"),
        (1, 2_000_000, "float32", True, "tile"),   # no block of this width fits VMEM
        (3, 256, "float32", True, ""),
        (3, 256, "bfloat16", True, ""),
        (3, 3000, "float32", True, "d%128,d<=2048"),
        (3, 4096, "float32", True, "d<=2048"),
        (3, 256, "float64", False, "backend,dtype"),
    ],
)
def test_logreg_pallas_gate_terms(monkeypatch, K, d, dtype, tpu, declined):
    """The gate's terms: K = 1 asks for a TPU, f32 X and a tile that fits, at
    any width; K >= 3 keeps the multinomial kernel's own terms."""
    from spark_rapids_ml_tpu.ops import logreg_pallas

    monkeypatch.setattr(logreg_pallas, "FORCE_INTERPRET", tpu)
    assert logreg_pallas.logreg_pallas_declined(4096, d, K, jnp.dtype(dtype)) == declined
    assert logreg_pallas.logreg_pallas_ok(4096, d, K, jnp.dtype(dtype)) == (not declined)


def test_logreg_pallas_gate_rejects_overwide_class_packing():
    # K in 121..127 would make the packed row exceed 128 lanes (Kp=128 + loss)
    from spark_rapids_ml_tpu.ops.logreg_pallas import logreg_pallas_ok

    assert not logreg_pallas_ok(4096, 256, 121, jnp.float32)
    assert not logreg_pallas_ok(4096, 256, 127, jnp.float32)


def test_mean_and_cov_chunked_pallas_branch_matches_scan(monkeypatch):
    """Run the REAL Pallas branch inside mean_and_cov_chunked (gate ->
    shard_map -> kernel -> rank-1 correction) via the interpret override
    and require parity with XLA's blocked pass."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import linalg
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    rng = np.random.default_rng(4)
    n, d, csize = 8 * 3 * 16, 128, 16
    X = (rng.normal(size=(n, d)) + 100.0).astype(np.float32)
    mask = (np.arange(n) < n - 19).astype(np.float32)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))
    Xd, md = put(X), put(mask)

    m1, c1, n1 = linalg.mean_and_cov_chunked(Xd, md, mesh, csize)

    monkeypatch.setattr(linalg, "FORCE_INTERPRET", True)
    assert linalg.gram_pallas_declined(n // 8, d, jnp.float32) == ""
    jax.clear_caches()  # FORCE_INTERPRET is read at trace time, not cached
    try:
        m2, c2, n2 = linalg.mean_and_cov_chunked(Xd, md, mesh, csize)
    finally:
        jax.clear_caches()

    assert float(n1) == float(n2)
    assert np.abs(np.asarray(m1) - np.asarray(m2)).max() < 1e-3
    scale = np.abs(np.asarray(c1)).max()
    assert np.abs(np.asarray(c1) - np.asarray(c2)).max() / scale < 1e-5


def test_logreg_fused_bf16_objective_close_to_f32():
    """bf16 X reads (f32 accumulation) must land within solver noise of
    the f32 fit — the bandwidth-halving bench configuration."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import logreg_pallas
    from spark_rapids_ml_tpu.ops.logreg_kernels import logreg_fit
    from spark_rapids_ml_tpu.parallel.mesh import make_mesh, shard_rows

    rng = np.random.default_rng(0)
    n, d = 512, 128
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d,))
    y = (X @ w > 0).astype(np.float32)
    mesh = make_mesh(2)
    Xd, mask = shard_rows(X, mesh)
    yd, _ = shard_rows(y, mesh)

    logreg_pallas.FORCE_INTERPRET = True
    jax.clear_caches()
    try:
        kw = dict(
            n_classes=2, multinomial=False, fit_intercept=True,
            standardization=True, l1=jnp.float32(0.0), l2=jnp.float32(1e-3),
            use_l1=False, max_iter=30, tol=jnp.float32(0.0), mesh=mesh,
        )
        f32 = logreg_fit(Xd, mask, yd, objective_dtype="float32", **kw)
        b16 = logreg_fit(Xd, mask, yd, objective_dtype="bfloat16", **kw)
    finally:
        logreg_pallas.FORCE_INTERPRET = False
        jax.clear_caches()
    np.testing.assert_allclose(
        np.asarray(b16["coef_"]), np.asarray(f32["coef_"]), rtol=0.05, atol=0.02
    )
    # predictions must agree except at the decision boundary
    agree = np.mean(
        (X @ np.asarray(f32["coef_"]).T[:, 0] > 0)
        == (X @ np.asarray(b16["coef_"]).T[:, 0] > 0)
    )
    assert agree > 0.99, agree


@pytest.mark.parametrize("matmul_dtype", [None, "bfloat16"])
def test_lloyd_step_pallas_matches_xla_chunk_stats(matmul_dtype):
    """The fused Pallas Lloyd pass must reproduce the XLA chunked step's
    (sums, counts, cost) triple — including masked rows, a non-128 k
    (center padding must never win the argmin), and both contraction
    dtypes."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import kmeans_pallas
    from spark_rapids_ml_tpu.ops.kmeans_kernels import _chunk_stats

    md = None if matmul_dtype is None else jnp.bfloat16
    rng = np.random.default_rng(9)
    n, d, k = 4096, 128, 37
    X = rng.normal(size=(n, d)).astype(np.float32)
    mask = np.ones((n,), np.float32)
    mask[-300:] = 0.0  # padding rows must not contribute
    centers = rng.normal(size=(k, d)).astype(np.float32)

    # force the XLA branch for the reference values: on a TPU host the
    # gate would engage Pallas inside _chunk_stats too, and the test
    # would compare the kernel against itself
    orig_ok = kmeans_pallas.kmeans_pallas_ok
    kmeans_pallas.kmeans_pallas_ok = lambda *a: False
    try:
        # single-call reference computation  # tpuml: ignore[TPU003]
        sums_x, counts_x, cost_x = jax.jit(
            lambda X, m, c: _chunk_stats(X, m, c, csize=1024, matmul_dtype=md)
        )(X, mask, centers)
    finally:
        kmeans_pallas.kmeans_pallas_ok = orig_ok

    # TILE must divide n for the gate; shrink it for test scale. _TILE is
    # baked into lloyd_step_pallas's jit trace — drop caches on restore
    # so later same-shape calls don't silently reuse the test tile.
    old_tile = kmeans_pallas._TILE
    kmeans_pallas._TILE = 512
    try:
        sums_p, counts_p, cost_p = kmeans_pallas.lloyd_step_pallas(
            X, mask, centers, matmul_dtype=md, interpret=True
        )
    finally:
        kmeans_pallas._TILE = old_tile
        jax.clear_caches()

    np.testing.assert_array_equal(np.asarray(counts_p), np.asarray(counts_x))
    rtol = 1e-6 if md is None else 1e-2
    np.testing.assert_allclose(
        np.asarray(sums_p), np.asarray(sums_x), rtol=rtol, atol=1e-3
    )
    np.testing.assert_allclose(
        float(cost_p), float(cost_x), rtol=1e-5 if md is None else 1e-2
    )


def test_kmeans_fit_pallas_branch_matches_xla(monkeypatch):
    """Full KMeans fit with the fused Pallas step ENGAGED (interpret +
    TPUML_LANE_PAD, mirroring the on-TPU ingestion) must match the
    XLA-step fit. The spy asserts the branch actually ran — the gate
    silently falling back would make this test vacuous."""
    from spark_rapids_ml_tpu.data import DataFrame
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.ops import kmeans_pallas

    rng = np.random.default_rng(4)
    # 1024 rows / 2 workers -> 512-row shards: divisible by the test TILE
    X = np.concatenate(
        [
            rng.normal(loc=c, scale=0.3, size=(256, 5))
            for c in (-3.0, 0.0, 3.0, 6.0)
        ]
    ).astype(np.float32)
    df = DataFrame({"features": X})
    kw = dict(k=4, maxIter=12, seed=1, initMode="random", num_workers=2)

    m_xla = KMeans(**kw).fit(df)

    calls = []
    orig = kmeans_pallas.lloyd_step_pallas

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    monkeypatch.setenv("TPUML_LANE_PAD", "128")  # on-TPU ingestion shape
    monkeypatch.setattr(kmeans_pallas, "FORCE_INTERPRET", True)
    monkeypatch.setattr(kmeans_pallas, "_TILE", 128)
    monkeypatch.setattr(kmeans_pallas, "lloyd_step_pallas", spy)
    jax.clear_caches()  # FORCE_INTERPRET/_TILE are not jit cache keys
    try:
        m_pl = KMeans(**kw).fit(df)
    finally:
        jax.clear_caches()

    assert calls, "fused Pallas Lloyd step never engaged"
    np.testing.assert_allclose(
        np.sort(np.asarray(m_pl.clusterCenters()), axis=0),
        np.sort(np.asarray(m_xla.clusterCenters()), axis=0),
        rtol=1e-5, atol=1e-5,
    )


class TestKnnPallas:
    def test_fused_pass_matches_xla_ring(self):
        """The fused Pallas distance+top-k pass (interpret mode) must agree
        with the XLA tile path on distances and ids, including item padding
        (ni not a block multiple) and query padding."""
        import spark_rapids_ml_tpu.ops.knn_pallas as kp
        import spark_rapids_ml_tpu.ops.knn_kernels as kk
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()
        rng = np.random.default_rng(5)
        nq, ni, d, k = 96, 600, 128, 8
        Xq = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
        Xi = jnp.asarray(rng.standard_normal((ni, d)), jnp.float32)
        mi = jnp.ones((ni,), jnp.float32).at[-7:].set(0.0)  # masked tail
        ids = jnp.arange(ni, dtype=jnp.int32) * 3 + 1

        d_ref, i_ref = jax.tree.map(
            np.asarray, kk.ring_knn(Xq, Xi, mi, ids, mesh=mesh, k=k)
        )
        kp.FORCE_INTERPRET = True
        calls = []
        real_pass = kp.knn_pallas_pass
        try:
            # fresh jit so the pallas gate re-evaluates; spy proves the
            # fused path was actually traced (not a cache/gate miss)
            import functools

            def spy(*a, **kw):
                calls.append(1)
                return real_pass(*a, **kw)

            kp.knn_pallas_pass = spy
            fresh = jax.jit(
                functools.partial(kk.ring_knn.__wrapped__, mesh=mesh, k=k)
            )
            d_pal, i_pal = jax.tree.map(np.asarray, fresh(Xq, Xi, mi, ids))
        finally:
            kp.FORCE_INTERPRET = False
            kp.knn_pallas_pass = real_pass
        assert calls, "fused Pallas kNN pass was not traced"

        np.testing.assert_allclose(d_pal, d_ref, rtol=1e-5, atol=1e-5)
        # ids may differ only where distances tie; none expected here
        np.testing.assert_array_equal(i_pal, i_ref)
        # masked items never appear
        masked_ids = set(np.asarray(ids[-7:]).tolist())
        assert not (set(i_pal.ravel().tolist()) & masked_ids)

    def test_sort_impl_routes_around_fused_kernel(self):
        """TPUML_KNN_TOPK=sort is the validated escape hatch: it must
        bypass the fused Pallas pass entirely, not just the tile top-k."""
        import functools

        import spark_rapids_ml_tpu.ops.knn_kernels as kk
        import spark_rapids_ml_tpu.ops.knn_pallas as kp
        from spark_rapids_ml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()
        rng = np.random.default_rng(9)
        nq, ni, d, k = 64, 256, 128, 4
        Xq = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
        Xi = jnp.asarray(rng.standard_normal((ni, d)), jnp.float32)
        mi = jnp.ones((ni,), jnp.float32)
        ids = jnp.arange(ni, dtype=jnp.int32)

        kp.FORCE_INTERPRET = True  # pallas gate would otherwise pass
        calls = []
        real_pass = kp.knn_pallas_pass
        try:
            kp.knn_pallas_pass = lambda *a, **kw: calls.append(1) or real_pass(
                *a, **kw
            )
            fresh = jax.jit(
                functools.partial(
                    kk.ring_knn.__wrapped__, mesh=mesh, k=k, topk_impl="sort"
                )
            )
            d_s, i_s = jax.tree.map(np.asarray, fresh(Xq, Xi, mi, ids))
        finally:
            kp.FORCE_INTERPRET = False
            kp.knn_pallas_pass = real_pass
        assert not calls, "sort impl must not trace the fused Pallas pass"
        # and it still returns correct neighbors
        d2 = ((np.asarray(Xq)[:, None, :] - np.asarray(Xi)[None, :, :]) ** 2).sum(-1)
        oracle = np.sort(d2, axis=1)[:, :k]
        np.testing.assert_allclose(np.asarray(d_s), oracle, rtol=1e-4, atol=1e-4)


def test_probe_pallas_lowering_raises_with_kernel_name_and_memoises_success():
    """A kernel the static gate admitted and the compiler refuses is loud:
    the probe raises with the kernel's name and the compiler's message, and
    a refusal is never cached. A success is memoised (compiled once)."""
    from spark_rapids_ml_tpu.ops.linalg import (
        PallasLoweringError,
        probe_pallas_lowering,
    )

    cache: dict = {}
    calls = []

    def refused():
        calls.append("refused")
        raise ValueError("Mosaic failed to compile TPU kernel: bad slice")

    for _ in range(2):  # not negative-cached: the second ask compiles again
        with pytest.raises(PallasLoweringError) as ei:
            probe_pallas_lowering(cache, (256, 1024), refused, "fused Lloyd")
        assert "fused Lloyd" in str(ei.value)
        assert "Mosaic failed to compile TPU kernel: bad slice" in str(ei.value)
        assert isinstance(ei.value.__cause__, ValueError)
    assert calls == ["refused", "refused"] and cache == {}

    def accepted():
        calls.append("accepted")

    assert probe_pallas_lowering(cache, (256, 1024), accepted, "fused Lloyd")
    assert probe_pallas_lowering(cache, (256, 1024), accepted, "fused Lloyd")
    assert calls.count("accepted") == 1 and cache == {(256, 1024): True}
