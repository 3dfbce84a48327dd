"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

The suite runs kernel bodies in interpret mode; that cannot show what the
Mosaic compiler refuses (a slice off the tiling, too much VMEM, a program
that does not fit HBM). These tests hand the kernel functions themselves
(``interpret=False``; the ``*_ok`` gates ask ``jax.default_backend()`` and
would take the CPU branch here) to the installed TPU compiler at the widths
``chip_smoke.py`` runs, plus — compile only, they are off the smoke path —
the RF and UMAP kernels at 131,072 x 256. A compile that passes is
not a chip run.

Rules (on-chip-measurement guide, section 2): the topology is described
inside a module-scoped fixture that skips when it cannot be; nothing touches
it at import time; the persistent compilation cache is off around these
tests (a topology compile is written to it but can never be read back
without a chip); every compile happens in this process; all cases live in
this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROWS, D = 4_194_304, 256          # chip_smoke.py's X
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    """``S(shape, dtype)``: a shape placed on the first described chip."""
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


@pytest.fixture(scope="module")
def four_chips(topo, no_compile_cache):
    """(mesh, rows(shape, dtype)): a shape row-sharded over the 2x2 host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("dp", "mp"))
    sh = NamedSharding(mesh, P("dp"))
    return mesh, lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---- the smoke path, one chip -------------------------------------------


@pytest.mark.parametrize(
    "rows,cols", [(500_000, 3000), (200_000, 4000), (131_072, 300)],
    ids=["reference_shape", "widest_accumulator", "narrow_width"],
)
def test_gram_kernel_compiles(topo, one_chip, rows, cols):
    """The Gram pass reads a rows-minor shard as its transpose (a bitcast),
    with the whole (padded d)² float32 accumulator in VMEM: 37.7 MB at
    ``pca_dbx``'s 3000 → 3072 columns, 67 MB at 4000 → 4096, the widest the
    gate admits beside the frame's blocks. No copy of the frame stands in
    front of the kernel: the program's temporaries are the accumulator's
    mirror and no more."""
    from spark_rapids_ml_tpu.ops import linalg

    assert linalg.rows_minor(topo.devices[0], rows, cols)
    tile, block, need = linalg.gram_tile(cols)
    assert need <= linalg._GRAM_VMEM_LIMIT
    fn = jax.jit(lambda X, m, mu: linalg._shifted_gram_pallas(X.T, m, mu, interpret=False))
    c = fn.lower(one_chip((rows, cols)), one_chip((rows,)), one_chip((cols,))).compile()
    assert _has_kernel(c)
    dp = -(-cols // block) * block
    assert c.memory_analysis().temp_size_in_bytes < 2.5 * dp * dp * 4


def test_pca_fit_reads_the_reference_frame_in_place(topo, no_compile_cache, monkeypatch):
    """The whole ``_pca_fit_kernel`` program at ``pca_dbx``'s shard (500,000 x
    3000 f32 on one described chip): the mean's sample, the Gram kernel, the
    subspace iteration and its host finish — one program over a resident
    frame, which since PR 34 the cell's fit no longer runs (it folds the row
    blocks: the two tests below). PR 33's parent held a 6.9 GB
    row-major copy of the frame for a strided sample and compiled
    ``jnp.linalg.eigh`` for 262 s (PERF.md section 6); this program's
    temporaries stay under one 786 MB block of the frame, by far."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.models.feature import _pca_fit_kernel

    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "mp"))
    rows = lambda shape: jax.ShapeDtypeStruct(shape, F32, sharding=NamedSharding(mesh, P("dp")))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    n, d = 500_000, 3000
    c = _pca_fit_kernel.lower(rows((n, d)), rows((n,)), k=3, mesh=mesh, csize=62_500).compile()
    txt = c.as_text()
    assert "tpu_custom_call" in txt
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes > n * d * 4
    assert mem.temp_size_in_bytes < (128 << 20)


@pytest.mark.parametrize("block_rows", [65_536, 41_248], ids=["whole_block", "tail_block"])
def test_gram_fold_compiles_at_the_cells_block_shapes(topo, one_chip, monkeypatch, block_rows):
    """``pca_dbx``'s frame goes up in seven blocks of 65,536 rows and a tail
    of 41,248, each 3000 wide and rows minor on the device: the fold reads a
    block as its transpose (a bitcast), adds the kernel's triangle into the
    donated accumulators, and holds that triangle and little more — no
    relaid copy of the 786 MB block, which the runtime's peak would not show."""
    from spark_rapids_ml_tpu.ops import linalg

    d = 3000
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    assert linalg.gram_pallas_declined(block_rows, d, F32, topo.devices[0]) == ""
    acc = (one_chip((6, 512, 3072)), one_chip((3072, 128)), one_chip((1,)))
    c = linalg.gram_fold.lower(acc, one_chip((block_rows, d)), one_chip((d,)), one_chip((), I32), pallas=True, block=0).compile()
    assert _has_kernel(c)
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < (80 << 20)
    assert mem.alias_size_in_bytes >= 6 * 512 * 3072 * 4      # the accumulators are added to in place


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "four_chips"])
def test_pca_finish_takes_the_accumulators_not_the_frame(topo, no_compile_cache, chips):
    """What runs after the last block's fold: psum over dp, mirror, rank-one
    correction, the subspace iteration. Its operands are every device's
    triangle, row sum partials and count, and μ̂ — 39 MB a device, nothing of
    the frame's 6 GB; on the 2x2 host (what ``num_workers=4`` runs since PR 34)
    the partials meet in an all-reduce."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.models.feature import _pca_finish

    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips, 1), ("dp", "mp"))
    rows = lambda *shape: jax.ShapeDtypeStruct((chips * shape[0],) + shape[1:], F32, sharding=NamedSharding(mesh, P("dp")))
    mu = jax.ShapeDtypeStruct((3000,), F32, sharding=NamedSharding(mesh, P()))
    c = _pca_finish.lower(rows(6, 512, 3072), rows(3072, 128), rows(1), mu, k=3, mesh=mesh, d=3000, pallas=True).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes < (48 << 20)
    assert mem.temp_size_in_bytes < (256 << 20)
    assert ("all-reduce" in c.as_text()) == (chips > 1)


def test_linreg_fold_and_finish_compile_at_the_cells_shapes(topo, one_chip, monkeypatch):
    """``linreg_dbx``'s fit, two programs: the fold of a 65,536 x 3000 block
    WITH its label — PCA's kernel over the block's transposed view, the
    label's sums beside it, no relaid copy of the 786 MB block — and the
    finish at d = 3000 — psum, mirror, re-centring, the Cholesky factor and
    its triangular solves — every product of which is float32 (HIGHEST),
    whose operands are the accumulators, not the frame, and which holds no
    host transfer (the least-norm fall-back's host ``eigh`` is a program of
    its own: one with a host callback is never written to the persistent
    compilation cache)."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import linalg
    from spark_rapids_ml_tpu.ops.linreg_kernels import _linreg_finish

    d, rows = 3000, 65_536
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    acc = (one_chip((6, 512, 3072)), one_chip((3072, 128)), one_chip((1,)), one_chip((d,)), one_chip((1,)), one_chip((1,)))
    label = (one_chip((rows,)), one_chip(()))
    c = linalg.gram_fold.lower(acc, one_chip((rows, d)), one_chip((d,)), one_chip((), I32), label, pallas=True, block=0).compile()
    assert _has_kernel(c)
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < (80 << 20)
    assert mem.alias_size_in_bytes >= 6 * 512 * 3072 * 4

    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "mp"))
    rows_of = lambda *shape: jax.ShapeDtypeStruct(shape, F32, sharding=NamedSharding(mesh, P("dp")))
    rep = lambda *shape: jax.ShapeDtypeStruct(shape, F32, sharding=NamedSharding(mesh, P()))
    folds = (rows_of(6, 512, 3072), rows_of(3072, 128), rows_of(1), rows_of(d), rows_of(1), rows_of(1))
    lowered = _linreg_finish.lower(
        folds, rep(d), rep(), rep(), mesh=mesh, d=d, pallas=True, fit_intercept=True, standardization=True, solve=True
    )
    assert "is_host_transfer = true" not in lowered.as_text()
    c = lowered.compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes < (48 << 20)
    products = [ln for ln in c.as_text().splitlines() if " convolution(" in ln or " dot(" in ln]
    assert products and all(re.search(r"operand_precision=\{highest,highest\}", ln) for ln in products)


def test_pca_fit_at_a_row_major_shard_takes_xlas_pass(topo, no_compile_cache, monkeypatch):
    """``chip_smoke.py``'s 4,194,304 x 256 lives row-major, where the kernel
    would get a relayout of the whole frame in front of it: the gate says
    ``rows_minor`` and XLA's blocked pass at HIGHEST reads the frame in place."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.models.feature import _pca_fit_kernel
    from spark_rapids_ml_tpu.ops import linalg

    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "mp"))
    rows = lambda shape: jax.ShapeDtypeStruct(shape, F32, sharding=NamedSharding(mesh, P("dp")))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert linalg.gram_pallas_declined(ROWS, D, F32, topo.devices[0]) == "rows_minor"
    c = _pca_fit_kernel.lower(rows((ROWS, D)), rows((ROWS,)), k=3, mesh=mesh, csize=65_536).compile()
    assert "tpu_custom_call" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < (128 << 20)


@pytest.mark.parametrize(
    "k,exact",
    [(1024, False), (1024, True), (4097, False)],
    ids=["lloyd_k1024", "cost_pass_k1024_highest", "kmeans_seeding_candidates_k4097"],
)
def test_lloyd_kernel_compiles(one_chip, k, exact):
    """k=1024 is the Lloyd loop and (exact) its final cost pass; ~4,100 is
    what k-means|| seeding asks of the same kernel (count_closest over
    1 + 2·2k candidates), inside the gate's VMEM arithmetic — all at the
    estimator's default matmul_dtype (None = f32 operands)."""
    from spark_rapids_ml_tpu.ops.kmeans_pallas import lloyd_step_pallas

    c = lloyd_step_pallas.lower(
        one_chip((ROWS, D)), one_chip((ROWS,)), one_chip((k, D)),
        matmul_dtype=None, exact=exact, interpret=False,
    ).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("cost_only", [False, True], ids=["lloyd", "cost_pass_highest"])
def test_lloyd_kernel_compiles_at_the_reference_width(one_chip, cost_only):
    """``kmeans_dbx``'s shard as the estimator places it: 500,000 rows padded
    to 500,118 (123 chunks of 4,066: the rows do not divide by the tile, the
    last block is ragged) x 3072 lane-padded columns, k=1000. The tile rule
    gives 1024 for both passes; at 2048 the cost pass needs 146-158 MiB of
    VMEM and Mosaic refuses it (PERF.md section 6, PR 29)."""
    from spark_rapids_ml_tpu.ops.kmeans_pallas import lloyd_cost_pallas, lloyd_step_pallas, lloyd_tile

    n, d, k = 500_118, 3072, 1000
    assert lloyd_tile(d, k, None, cost_only, not cost_only)[0] == 1024
    fn = lloyd_cost_pallas if cost_only else lloyd_step_pallas
    c = fn.lower(one_chip((n, d)), one_chip((n,)), one_chip((k, d)), interpret=False).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("block_rows", [65_536, 41_248], ids=["whole_block", "tail_block"])
def test_narrow_block_is_written_without_a_second_copy(one_chip, block_rows):
    """``kmeans_dbx``'s frame goes up 3000 columns wide into the 3072-wide
    zero buffer. The block comes up with its rows minor (3000 is no multiple
    of 128), the buffer has its columns minor: written at once, the program
    holds the relaid block besides (a temporary of 805 MB a write, which the
    runtime's memory peak does not show; PERF.md section 6, PR 30). Piece by
    piece it holds nothing."""
    from spark_rapids_ml_tpu.parallel.mesh import _write_block

    c = _write_block.lower(one_chip((500_118, 3072)), one_chip((block_rows, 3000)), one_chip((), I32)).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= 500_118 * 3072 * 4   # the buffer is written in place
    assert mem.temp_size_in_bytes < (32 << 20)


@pytest.mark.parametrize(
    "rows,cols,minor_rows",
    [(ROWS, D, False), (500_000, 3000, True), (5_000, 3000, False), (4_100, 300, True)],
    ids=["smoke_shape", "reference_shape", "unaligned_width_row_major", "width_no_multiple_of_8"],
)
def test_logreg_loss_grad_kernel_compiles(topo, one_chip, rows, cols, minor_rows):
    """The binary pass (float32 on the VPU) reads a shard as the described
    v5e keeps it: ``chip_smoke.py``'s 4,194,304 x 256 row-major, ``logreg_dbx``'s
    500,000 x 3000 with its ROWS minor (as its transpose, a bitcast); 5,000 x
    3000 pads the same either way and stays row-major, with a last lane tile
    of 56 columns. Whichever way, no copy of the frame stands in front of the
    kernel: the program's temporaries stay under 64 MiB."""
    from spark_rapids_ml_tpu.ops import logreg_pallas as lp

    assert lp.rows_minor(topo.devices[0], rows, cols) == minor_rows
    assert lp.binary_tile(cols, minor_rows)[0] > 0
    fn = jax.jit(
        lambda X, y, m, a, b: lp.binary_loss_grad(X, y, m, a, b, minor_rows=minor_rows, interpret=False)
    )
    c = fn.lower(
        one_chip((rows, cols)), one_chip((rows,)), one_chip((rows,)), one_chip((cols,)), one_chip(()),
    ).compile()
    assert _has_kernel(c)
    assert c.memory_analysis().temp_size_in_bytes < (64 << 20)


def test_logreg_multinomial_kernel_compiles(one_chip):
    from spark_rapids_ml_tpu.ops.logreg_pallas import _loss_grad_pallas, _row_tile

    Kp = 8  # three classes, sublane-padded
    fn = jax.jit(
        lambda X, y, m, A, b: _loss_grad_pallas(
            X, y, m, A, b, n_valid_classes=3, tile=_row_tile(D, Kp), interpret=False,
        )
    )
    c = fn.lower(
        one_chip((ROWS, D)), one_chip((ROWS,)), one_chip((ROWS,)),
        one_chip((Kp, D)), one_chip((1, 128)),
    ).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip_500k_rows", "four_chips_1m_rows"])
def test_logreg_fit_reads_the_reference_frame_in_place(topo, no_compile_cache, monkeypatch, chips):
    """The whole ``logreg_fit`` program at ``logreg_dbx``'s shard (500,000 x
    3000 f32 on one described chip; the source's whole 1,000,000 rows over
    four, 250,000 a chip, rows minor too): the fused pass is in it, inside the
    L-BFGS loops, and the program holds no second copy of the frame — a
    relayout in front of the custom call would be one that the runtime's
    memory peak cannot see (PERF.md section 6, PR 30) and that would cost more
    than the pass saves."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.logreg_kernels import logreg_fit

    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(chips, 1), ("dp", "mp"))
    rows = lambda shape: jax.ShapeDtypeStruct(shape, F32, sharding=NamedSharding(mesh, P("dp")))
    scalar = jax.ShapeDtypeStruct((), F32, sharding=NamedSharding(mesh, P()))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    n, d = 500_000 * (1 if chips == 1 else 2), 3000
    c = logreg_fit.lower(
        rows((n, d)), rows((n,)), rows((n,)),
        n_classes=2, multinomial=False, fit_intercept=True, standardization=True,
        l1=scalar, l2=scalar, use_l1=False, max_iter=200, tol=scalar, mesh=mesh,
    ).compile()
    txt = c.as_text()
    assert txt.count("tpu_custom_call") >= 3   # before the loop, in its body, in the line search
    assert chips == 1 or "all-reduce" in txt
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes > n // chips * d * 4
    assert mem.temp_size_in_bytes < (64 << 20)


def test_knn_pass_kernel_compiles(one_chip):
    from spark_rapids_ml_tpu.ops.knn_pallas import _IB, _QB, knn_pallas_pass

    nq, ni, k = 65_536, 1_048_576, 16
    assert nq % _QB == 0 and ni % _IB == 0
    c = knn_pallas_pass.lower(
        one_chip((nq, D)), one_chip((ni, D)), one_chip((1, ni)),
        one_chip((1, ni), I32), one_chip((nq, k)), one_chip((nq, k), I32),
        interpret=False,
    ).compile()
    assert _has_kernel(c)


# ---- the cross-chip path: whole programs over the described 2x2 mesh ----


def test_sharded_gram_step_compiles_for_four_chips(four_chips, monkeypatch):
    """The PCA fit program at num_workers=4 on the source's whole 1,000,000 x
    3000 set: the Gram kernel on every shard (250,000 rows a chip, rows minor
    too) and a psum of the partials, a quarter of X per device."""
    from spark_rapids_ml_tpu.models.feature import _pca_fit_kernel

    mesh, rows = four_chips
    n, d = 1_000_000, 3000
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    c = _pca_fit_kernel.lower(
        rows((n, d)), rows((n,)), k=3, mesh=mesh, csize=62_500
    ).compile()
    txt = c.as_text()
    assert "tpu_custom_call" in txt and "all-reduce" in txt
    per_device = c.memory_analysis().argument_size_in_bytes
    assert per_device < 0.3 * n * d * 4


def test_knn_ring_compiles_for_four_chips(four_chips, monkeypatch):
    from spark_rapids_ml_tpu.ops import knn_pallas
    from spark_rapids_ml_tpu.ops.knn_kernels import ring_knn

    mesh, rows = four_chips
    nq, ni, k = 65_536, 1_048_576, 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the gate's probe compiles for the process's own backend (the CPU here);
    # the kernel's lowering for the chip is test_knn_pass_kernel_compiles
    monkeypatch.setitem(knn_pallas._LOWERING_OK, (D, k), True)
    c = ring_knn.lower(
        rows((nq, D)), rows((ni, D)), rows((ni,)), rows((ni,), I32),
        mesh=mesh, k=k, topk_impl="auto",
    ).compile()
    txt = c.as_text()
    assert "tpu_custom_call" in txt and "collective-permute" in txt


# ---- off the smoke path: compile only, RF / UMAP kernels -----------------


def test_rf_subblock_hist_kernel_compiles(one_chip):
    """131,072 rows x 16 sampled features x 128 bins, 2 stats (bench_rf)."""
    from spark_rapids_ml_tpu.ops.rf_pallas import subblock_hist

    n, k, S = 131_072, 16, 2
    c = subblock_hist.lower(
        one_chip((n, k), I32), one_chip((S, n)), n_bins=128, r_sub=64,
        variance=False, transposed_sw=True, interpret=False,
    ).compile()
    assert _has_kernel(c)


def test_rf_fused_selection_kernel_compiles_at_the_reference_width(one_chip):
    """``rf_dbx``'s histogram kernel: whole rows of 3072 bins (3000 columns
    at the multiple of 512, not 4096) held four to an int32 word, 768 words
    a row, unpacked in VMEM; 64 feature slots (55 sampled), 128 bins, 2
    stats, sub-blocks of 64 rows — two grid blocks, as the lowering probe
    compiles it."""
    from spark_rapids_ml_tpu.ops.rf_pallas import BLOCK_ROWS, rf_hist_sel_declined, subblock_hist_sel

    n, d_pad, k, r_sub, S = 2 * BLOCK_ROWS, 3072, 64, 64, 2
    assert rf_hist_sel_declined(n, d_pad, k, 128, S, r_sub) == "backend"   # every other term holds
    c = subblock_hist_sel.lower(
        one_chip((n, d_pad // 4), I32), one_chip((n // r_sub, k), I32), one_chip((S, n)),
        n_bins=128, r_sub=r_sub, variance=False, interpret=False,
    ).compile()
    assert _has_kernel(c)


def test_rf_build_forest_holds_no_copy_of_a_level(topo, no_compile_cache, monkeypatch):
    """``build_forest`` at ``rf_dbx``'s shape class (500,000 rows of 3072 bins
    four to an int32 word, 55 of 3000 features a node in 64 slots, 128 bins, 2 classes; two
    levels deep, a whole tree compiles for minutes): ONE Mosaic call a level,
    as before a level followed its live rows, now inside the level's loop over
    chunks of 16,384 node-sorted rows and at that one shape on every level.
    Which only the compiler's count shows: the program holds no node-sorted
    copy of a level and no level's partials any more (1.54 + 0.51 GB here,
    2.34 + 0.78 GB at level 12: the parent's temporaries were 2.07 GB at this
    depth and 3.17 GB at 13) but a chunk of rows, its partials and the
    per-node sums — under a fifth of the parent's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import linalg, tree_kernels as tk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gates
    monkeypatch.setattr(linalg, "probe_pallas_lowering", lambda cache, key, fn, name: True)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "mp"))
    rows = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P("dp")))
    n, d, d_pad, depth = 500_000, 3000, 3072, 2
    cfg = tk.ForestConfig(
        max_depth=depth, n_bins=128, n_features=d, n_stats=2, impurity="gini", k_features=55, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True, held_bytes=n * d * 4,
    )
    assert [tk.level_plan(n, d_pad, lv, cfg).strategy for lv in range(depth)] == ["pallas_sel"] * depth
    c = tk.build_forest.lower(
        rows((n, d_pad // 4), I32), rows((n,), F32), rows((n, 2), F32), rows((1, 8, 2), jnp.uint32),
        mesh=mesh, cfg=cfg, gather=False, tree_batch=1,
    ).compile()
    txt = c.as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == depth
    # every call at the chunk's shape: whole rows of words of one chunk in, its sub-blocks' partials out
    chunk = tk._LIVE_CHUNK
    assert txt.count(f"operand_layout_constraints={{s32[1]{{0}}, s32[{chunk},{d_pad // 4}]{{1,0}}") == depth
    assert f"u8[{chunk},{d_pad}]" not in txt
    assert c.memory_analysis().temp_size_in_bytes < (400 << 20)


def test_rf_wide_selection_kernel_compiles_at_the_regressors_width(one_chip):
    """``rf_reg_dbx``'s histogram kernel: one chunk's whole rows (16,384 x 3072
    bins as 768 int32 words a row, a column tile unpacked in VMEM), 1000 features a node in 1024 slots — a grid of 8 slot tiles
    x 32 one-node blocks, a tile's selection a loop over its window of column
    tiles (scalar prefetch) — 128 bins, the three statistics' exact split in
    16 bf16 rows, the level's per-node sums (32 nodes) added to in place: the
    fused selection declines this shape on its one-hot width and its VMEM.
    The gate's VMEM is a tile's, whatever the slots: twice the slots, or 256
    bins, fit too."""
    from spark_rapids_ml_tpu.ops import rf_pallas as rp

    n, d_pad, k, nb, S, nodes = 16_384, 3072, 1024, 128, 3, 32
    assert rp.rf_hist_sel_declined(n, d_pad, k, nb, S, 64) == "backend,width<=8192,vmem"
    assert rp.rf_hist_wide_declined(n, d_pad, k, nb, S) == "backend"      # every other term holds
    assert rp.rf_hist_wide_declined(n, d_pad, 2 * k, nb, S) == rp.rf_hist_wide_declined(n, d_pad, k, 256, S) == "backend"
    blocks = n // rp.WIDE_BLOCK_ROWS
    assert rp.rf_hist_wide_declined(n, 1152, k, nb, S) == "backend,words%128"
    c = rp.subblock_hist_sel_wide.lower(
        one_chip((n, d_pad // 4), I32), one_chip((blocks, k), I32), one_chip((rp.WIDE_STAT_ROWS, n), jnp.bfloat16),
        one_chip((blocks,), I32), one_chip((1,), I32), one_chip((nodes, rp.WIDE_STAT_ROWS, k * nb)),
        n_bins=nb, interpret=False,
    ).compile()
    assert _has_kernel(c)
    # the sums are updated in place: the result aliases the operand, no second copy
    m = c.memory_analysis()
    assert m.alias_size_in_bytes == nodes * rp.WIDE_STAT_ROWS * k * nb * 4 and m.temp_size_in_bytes < (1 << 20)


def test_rf_regressor_forest_gathers_no_subset(topo, no_compile_cache, monkeypatch):
    """``build_forest`` at ``rf_reg_dbx``'s shape (500,000 rows of 3072 bins
    four to an int32 word, 1000 of 3000 features a node, variance, 128 bins;
    two levels deep): ONE Mosaic call a level, on a chunk's whole rows of
    words — no bf16 copy of a chunk (``bf16[16384,3072]``, 100 MB), no
    per-row gather of the 1024 sampled columns (``u8[16384,1024]``, 0.2 s a
    chunk on the chip) and no subset-wide copy of a level; the temporaries
    are a chunk, the level's sums and the gain search's."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import linalg, tree_kernels as tk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(linalg, "probe_pallas_lowering", lambda cache, key, fn, name: True)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dp", "mp"))
    rows = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, P("dp")))
    n, d, d_pad, depth = 500_000, 3000, 3072, 2
    cfg = tk.ForestConfig(
        max_depth=depth, n_bins=128, n_features=d, n_stats=3, impurity="variance", k_features=1000, min_samples_leaf=1,
        min_info_gain=0.0, min_samples_split=2, bootstrap=True, held_bytes=n * d * 4,
    )
    assert [tk.level_plan(n, d_pad, lv, cfg).strategy for lv in range(depth)] == ["pallas_sel_wide"] * depth
    c = tk.build_forest.lower(
        rows((n, d_pad // 4), I32), rows((n,), F32), rows((n, 3), F32), rows((1, 5, 2), jnp.uint32),
        mesh=mesh, cfg=cfg, gather=False, tree_batch=1,
    ).compile()
    txt = c.as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == depth
    chunk = tk._LIVE_CHUNK
    assert f"s32[{chunk},{d_pad // 4}]" in txt and f"bf16[{chunk},{d_pad}]" not in txt
    assert f"u8[{chunk},1024]" not in txt and f"s32[{chunk},1024]" not in txt
    assert c.memory_analysis().temp_size_in_bytes < (600 << 20)


def test_rf_sketch_reads_the_reference_frame_in_place(one_chip):
    """The quantile sketch at ``rf_dbx``'s shard (500,000 x 3000 f32, rows
    minor): 1024 runs of 128 consecutive rows, sorted a column on the device.
    Its temporaries are the sample's (131,072 x 3000 f32 = 1.57 GB, twice:
    3.2 GB), never the frame's: the strided row gather it replaced put a
    relaid copy of the whole 6 GB frame in front of a 2 GB fetch."""
    from spark_rapids_ml_tpu.ops.tree_kernels import quantile_edges

    rows, cols = 500_000, 3000
    c = quantile_edges.lower(one_chip((rows, cols)), one_chip((rows,)), n_bins=128).compile()
    sample = 131_072 * cols * 4
    assert c.memory_analysis().temp_size_in_bytes < 2.5 * sample
    assert c.memory_analysis().output_size_in_bytes < 2 * cols * 127 * 4


@pytest.mark.parametrize(
    "rows,d_pad,packed,rows_minor_bins",
    [(500_000, 3072, True, False), (131_072, 3000, False, True), (106_784, 3000, False, True), (1500, 256, True, True)],
    ids=["fit", "full_batch", "tail_batch", "narrow_fit"],
)
def test_rf_binize_compiles_at_the_cells_shapes(topo, one_chip, monkeypatch, rows, d_pad, packed, rows_minor_bins):
    """The forests' ``binize`` at the shapes ``rf_dbx`` / ``rf_reg_dbx`` run:
    the fit's 500,000 x 3000 frame to 3072 bin columns four to an int32 word
    (768 words a row), a transform batch of 131,072 rows and the tail of
    106,784 to 3000 bytes a row; and a narrow fit (1,500 x 200 to 256
    columns, 64 words a row). Each frame is rows minor; the gate admits it,
    and the one program is the Pallas quantizer reading the frame as its
    transpose (a bitcast) — no f32 operand of the frame's size, so no relaid
    copy of it — and writing the bins at their default layout (row-major
    words at 768, rows minor at 3000 bytes and 64 words: a bitcast of the
    kernel's transposed result) with no pack pass behind it. XLA's unrolled
    compare-count chunks hold 1.85 GB of temporaries at the fit; this
    program holds none of the frame's size."""
    from spark_rapids_ml_tpu.ops import linalg, tree_kernels as tk

    cols = 3000 if rows > 1500 else 200
    width, dt = (d_pad // 4, I32) if packed else (d_pad, jnp.uint8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the gate
    assert linalg.rows_minor(topo.devices[0], rows, cols)
    assert linalg.rows_minor(topo.devices[0], rows, width, dt) == rows_minor_bins
    plan = tk.binize_plan((rows, cols), F32, 127, d_pad, 1, topo.devices[0], packed=packed)
    tile = "1536x64" if d_pad == 256 else "2048x128"
    assert (plan.kernel, plan.declined, plan.tile, plan.rows_minor_out) == ("pallas", "", tile, rows_minor_bins)
    c = tk.binize.lower(one_chip((rows, cols)), one_chip((cols, 127)), d_pad=d_pad, plan=plan).compile()
    assert c.out_info.shape == (rows, width) and c.out_info.dtype == dt
    txt = c.as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') == 1 and "rf_binize_pass" in txt
    assert f"f32[{cols},{rows}]{{1,0" in txt                 # the frame read as its transpose
    assert f"f32[{rows},{cols}]{{1,0" not in txt             # and never relaid row-major
    assert f"u8[{rows},{d_pad}]" not in txt or not packed    # and no byte bins packed after it
    assert c.memory_analysis().temp_size_in_bytes < (16 << 20)


def test_rf_packed_traverse_kernel_compiles(one_chip):
    """Depth 13 (k1=7, k2=6), d=256 (64 packed words), 131,072 rows. The
    kernel unrolls a static loop over every tree and its compile time grows
    faster than the tree count (here: 4 s at 1 tree, 7 s at 2, 80 s at 8 —
    the smallest forest the gate admits — and minutes at 56; see
    CHANGES.md PR 22). Two trees run the lockstep loop twice and keep this
    case inside tier-1's clock."""
    from spark_rapids_ml_tpu.ops.rf_pallas import packed_traverse

    n, t_pad, k1, k2, words = 131_072, 2, 7, 6, 64
    c = packed_traverse.lower(
        one_chip((n, words), I32), one_chip((n, t_pad), I32),
        one_chip((t_pad << k1, 64), I32), one_chip((t_pad << k1, 64), I32),
        k1=k1, k2=k2, d_pad=4 * words, interpret=False,
    ).compile()
    assert _has_kernel(c)


@pytest.mark.xfail(
    strict=True,
    raises=AssertionError,
    reason="jax 0.9.0 pallas/mosaic/lowering.py gather rule: 'assert "
    "indices_aval.shape == in_aval.shape + (1,)' — take_along_axis lowers only "
    "where indices and table have the same shape; the kernel gathers (B*K, C) "
    "rows from an (n_tab, C) table. umap_sgd_pallas_ok rules the compiled "
    "kernel out until the gather is rewritten (CHANGES.md PR 22).",
)
@pytest.mark.parametrize("rng", ["onchip", "xla"])
def test_umap_sgd_kernel_compiles(one_chip, rng):
    """65,536 points (the table VMEM-resident), 2 components, K=24 slots,
    5 negatives: the fit's epoch loop with either randomness source."""
    from spark_rapids_ml_tpu.ops.umap_pallas import umap_sgd_pallas

    n, C, K, R = 65_536, 2, 24, 98_304
    c = umap_sgd_pallas.lower(
        one_chip((n, C)), one_chip((n, C)), one_chip((R,), I32),
        one_chip((R, K), I32), one_chip((R, K)), one_chip((2,), jnp.uint32),
        n_epochs=200, a=1.577, b=0.895, self_table=True, rng=rng,
        interpret=False,
    ).compile()
    assert _has_kernel(c)
