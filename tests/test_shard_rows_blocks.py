"""``parallel/mesh.shard_rows`` in row blocks: a shard larger than one block
(``_PUT_BLOCK_BYTES``) goes up in puts of a bounded size that are assembled
on the device. The array that comes back must be the one a single put of the
padded host array gives — same shape, dtype, sharding and bits — and a shard
of at most one block must not touch the assembly program at all. A shard
that is to be wider on the device than on the host (``cols``: KMeans' lane
padding) is assembled whatever its size, from blocks of the host's own
width: the zero columns are the device's, and the bits are ``np.pad``'s."""

import contextlib

import ml_dtypes
import numpy as np
import pytest

import jax

from spark_rapids_ml_tpu import core
from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.parallel import mesh as mesh_mod
from spark_rapids_ml_tpu.parallel.mesh import make_mesh, pad_rows, row_sharding, shard_rows
from spark_rapids_ml_tpu.runtime import telemetry

COLS = 12


@pytest.fixture
def assembly(monkeypatch):
    """The assembly program with its calls recorded: (device, row0, rows) each."""
    calls = []
    real = mesh_mod._write_block

    def recording(buf, block, row0):
        (dev,) = block.devices()
        calls.append((dev.id, int(row0), block.shape[0]))
        return real(buf, block, row0)

    monkeypatch.setattr(mesh_mod, "_write_block", recording)
    return calls


@contextlib.contextmanager
def _h2d_puts():
    """The attributes of the ``h2d.enqueue`` spans opened inside the block."""
    puts = []
    telemetry.add_span_sink(lambda ev, thread: puts.append(ev["args"]) if ev["name"] == "h2d.enqueue" else None)
    try:
        yield puts
    finally:
        telemetry.reset_telemetry()


def _host(rows, dtype, ndim):
    rng = np.random.default_rng(rows)
    if ndim == 1:
        return rng.integers(0, 1 << 30, size=rows).astype(dtype)
    return rng.normal(size=(rows, COLS)).astype(dtype)


# id: (dp, mp, row_multiple, rows, dtype, ndim, block rows, assembled on the device?)
CASES = {
    "dp1": (1, 1, 1, 1003, np.float32, 2, 16, True),
    "dp2": (2, 1, 1, 1003, np.float32, 2, 16, True),
    "dp8": (8, 1, 1, 1003, np.float32, 2, 16, True),
    "dp2_mp2": (2, 2, 1, 1003, np.float32, 2, 16, True),
    "dp4_mp2": (4, 2, 1, 1003, np.float32, 2, 16, True),
    "dp1_multiple128": (1, 1, 128, 1003, np.float32, 2, 16, True),
    "dp8_multiple128": (8, 1, 128, 1003, np.float32, 2, 16, True),
    # 130 rows on 8 x 128: devices 2..7 hold padding alone
    "dp8_multiple128_shards_of_padding": (8, 1, 128, 130, np.float32, 2, 16, True),
    "tail_block_of_3_rows": (1, 1, 1, 4 * 16 + 3, np.float32, 2, 16, True),
    "whole_blocks_no_tail": (2, 1, 1, 8 * 16, np.float32, 2, 16, True),
    "bf16": (2, 1, 1, 1003, ml_dtypes.bfloat16, 2, 16, True),
    "f64": (2, 1, 8, 1003, np.float64, 2, 16, True),
    "ids_1d_int32": (8, 1, 1, 1003, np.int32, 1, 16, True),
    "block_rows_floor_of_8": (1, 1, 1, 100, np.float32, 2, 8, True),
    "shard_of_exactly_one_block": (8, 1, 1, 8 * 16, np.float32, 2, 16, False),
    "shard_below_one_block": (2, 1, 128, 97, np.float32, 2, 128, False),
}
# id: (a case as above, the columns the shard is to have on the device)
WIDER = {
    "to16_dp1": (CASES["dp1"], 16),
    "to16_dp2": (CASES["dp2"], 16),
    "to128_dp1": (CASES["dp1"], 128),
    "to128_dp2": (CASES["dp2"], 128),
    "to128_dp8": (CASES["dp8"], 128),
    "to128_dp4_mp2": (CASES["dp4_mp2"], 128),
    "to128_dp1_multiple128": (CASES["dp1_multiple128"], 128),
    "to128_dp8_multiple128": (CASES["dp8_multiple128"], 128),
    "to16_dp8_multiple128_shards_of_padding": (CASES["dp8_multiple128_shards_of_padding"], 16),
    "to16_tail_block_of_3_rows": (CASES["tail_block_of_3_rows"], 16),
    "to128_whole_blocks_no_tail": (CASES["whole_blocks_no_tail"], 128),
    "to16_bf16": (CASES["bf16"], 16),
    "to128_bf16": (CASES["bf16"], 128),
    "to16_f64": (CASES["f64"], 16),
    # blocks above _WRITE_PIECE_ROWS are relaid piece by piece: 2 pieces, then 1 piece and a tail of 476 rows
    "to128_pieces_and_tail": ((1, 1, 1, 2048 + 1500, np.float32, 2, 2048, True), 128),
    "to16_dp2_pieces_and_tail": ((2, 1, 1, 2 * (4096 + 1030), np.float32, 2, 4096, True), 16),
    "to128_bf16_pieces_and_tail": ((1, 1, 8, 2048 + 1500, ml_dtypes.bfloat16, 2, 2048, True), 128),
    # a shard of at most one block is assembled all the same: one write a device
    "to128_shard_of_exactly_one_block": ((8, 1, 1, 8 * 16, np.float32, 2, 16, True), 128),
    "to16_shard_below_one_block": ((2, 1, 128, 97, np.float32, 2, 128, True), 16),
    # its own width asked for by name is no pad: the plain put, no assembly
    "own_width_shard_below_one_block": (CASES["shard_below_one_block"], COLS),
    "own_width_dp2": (CASES["dp2"], COLS),
}


@pytest.mark.parametrize("case", [*CASES, *WIDER])
def test_shard_rows_equals_the_padded_host_array(case, monkeypatch, assembly):
    (dp, mp, row_multiple, rows, dtype, ndim, block_rows, assembled), cols = WIDER.get(case, (CASES.get(case), None))
    x = _host(rows, dtype, ndim)
    row_bytes = x.dtype.itemsize * (COLS if ndim == 2 else 1)
    # a byte target of less than the tile's 8 rows still gives 8
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", row_bytes * (block_rows if block_rows > 8 else 1))
    assert mesh_mod._put_block_rows(row_bytes) == block_rows
    mesh = make_mesh(dp, mp=mp)
    with jax.enable_x64(dtype == np.float64):
        with _h2d_puts() as puts:
            xd, md = shard_rows(x, mesh, row_multiple, cols=cols)
        want, want_mask = pad_rows(x, dp * row_multiple)
        if cols is not None:
            want = np.pad(want, ((0, 0), (0, cols - COLS)))
        assert xd.shape == want.shape and xd.dtype == want.dtype
        assert xd.sharding == md.sharding == row_sharding(mesh)
        np.testing.assert_array_equal(np.asarray(xd).view(np.uint8), want.view(np.uint8))
        np.testing.assert_array_equal(np.asarray(md), want_mask)
        assert md.dtype == np.float32
        for shard in xd.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data), want[shard.index])
    (put,) = puts
    assert (put["cols"], put["pad_cols"]) == (want.shape[1] if ndim == 2 else 1, (cols or COLS) - COLS)
    assert put["bytes"] == want.nbytes + want_mask.nbytes
    if not assembled:
        assert assembly == [] and put["blocks"] == 1
        return
    # each device got the valid rows of its shard, block after block
    per_dev = want.shape[0] // dp
    expected = []
    index_map = row_sharding(mesh).addressable_devices_indices_map(want.shape)
    for dev in mesh.devices.flat:
        lo = index_map[dev][0].start or 0
        valid = min(max(rows - lo, 0), per_dev)
        expected += [(dev.id, at, min(block_rows, valid - at)) for at in range(0, valid, block_rows)]
    assert sorted(assembly) == sorted(expected) and put["blocks"] == len(expected)
    # a shard above one block takes several writes, a wider one of at most one block a single write
    assert len(expected) > dp * mp if per_dev > block_rows else 0 < len(expected) <= dp * mp
    assert put["block_bytes"] == min(block_rows, per_dev) * row_bytes
    # the devices take turns: block k of every device before block k + 1 of any
    assert [row0 for _, row0, _ in assembly] == sorted(row0 for _, row0, _ in assembly)


def test_at_most_two_blocks_in_flight(monkeypatch):
    """A put is issued only after the write of the put two before it has run:
    the bound holds for the process, whatever the number of devices."""
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", COLS * 4 * 16)
    events = []
    real_write = mesh_mod._write_block

    class Written:
        def __init__(self, k, scalar):
            self.k, self.scalar = k, scalar

        def block_until_ready(self):
            events.append(("waited", self.k))
            return self.scalar.block_until_ready()

    def write(buf, block, row0):
        k = sum(1 for e in events if e[0] == "issued")
        events.append(("issued", k))
        out, scalar = real_write(buf, block, row0)
        return out, Written(k, scalar)

    monkeypatch.setattr(mesh_mod, "_write_block", write)
    x = _host(2 * 5 * 16, np.float32, 2)
    xd, _ = shard_rows(x, make_mesh(2))
    np.testing.assert_array_equal(np.asarray(xd), x)
    expected = [("issued", 0), ("issued", 1)]
    for k in range(2, 10):
        expected += [("waited", k - 2), ("issued", k)]
    assert events == expected


def test_logreg_fit_is_bitwise_the_same_through_blocks(monkeypatch):
    rng = np.random.default_rng(5)
    rows, cols = 2051, 24
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    w = rng.normal(size=cols).astype(np.float32) / np.sqrt(cols)
    y = (X @ w + rng.normal(size=rows) > 0).astype(np.float32)
    df = DataFrame({"features": X}).withColumn("label", y)

    def fit():
        with _h2d_puts() as puts:
            return LogisticRegression(maxIter=30, regParam=1e-3, num_workers=2).fit(df), puts

    whole, (x_whole, y_whole) = fit()
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", cols * 4 * 64)
    blocked, (x_blocked, y_blocked) = fit()
    for attr in ("coef_", "intercept_"):
        np.testing.assert_array_equal(np.asarray(getattr(whole, attr)), np.asarray(getattr(blocked, attr)))
    assert whole.n_iter_ == blocked.n_iter_ >= 2
    # one odd row is padded: 2052 rows of X and of the mask, one put
    assert (x_whole["blocks"], x_whole["block_bytes"]) == (1, 2052 * cols * 4)
    # 2 devices x ceil(1026 / 64) puts of at most 64 rows
    assert (x_blocked["blocks"], x_blocked["block_bytes"]) == (2 * 17, 64 * cols * 4)
    for key in ("bytes", "arrays", "devices"):
        assert x_blocked[key] == x_whole[key] and y_blocked[key] == y_whole[key]
    assert x_whole["bytes"] == 2052 * (cols * 4 + 4) and x_whole["devices"] == 2


@pytest.mark.parametrize("block_rows", [None, 64], ids=["one_block", "blocks_of_64_rows"])
def test_kmeans_fit_is_bitwise_the_same_with_the_pad_on_the_device(block_rows, monkeypatch):
    """Lane padding (``TPUML_LANE_PAD``, the TPU's default) no longer pads the
    frame on the host: the fit equals, bit for bit, the fit on the frame that
    the parent's path padded with ``np.pad`` before ``shard_rows``."""
    rng = np.random.default_rng(9)
    rows, cols, k = 1501, 10, 4
    X = (rng.normal(size=(rows, cols)) + 6.0 * rng.integers(0, k, size=(rows, 1))).astype(np.float32)
    df = DataFrame({"features": X})
    monkeypatch.setenv("TPUML_LANE_PAD", "128")
    if block_rows:
        monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", cols * 4 * block_rows)

    def fit():
        with _h2d_puts() as puts:
            model = KMeans(k=k, maxIter=20, seed=3, num_workers=2).fit(df)
            return model, np.asarray(model.transform(df).column("prediction")), puts

    def host_padded_shard_rows(x, mesh, row_multiple=1, cols=None):
        return shard_rows(np.pad(x, ((0, 0), (0, cols - x.shape[1]))), mesh, row_multiple)

    with monkeypatch.context() as parent:
        parent.setattr(core, "shard_rows", host_padded_shard_rows)
        want, want_pred, (want_put,) = fit()

    padded_2d, real_pad = [], np.pad

    def spy(array, *args, **kwargs):
        if np.ndim(array) == 2:
            padded_2d.append(np.shape(array))
        return real_pad(array, *args, **kwargs)

    with monkeypatch.context() as watched:
        watched.setattr(np, "pad", spy)
        got, got_pred, (got_put,) = fit()
    assert padded_2d == []
    assert got.cluster_centers_.shape == (k, cols)
    np.testing.assert_array_equal(np.asarray(got.cluster_centers_), np.asarray(want.cluster_centers_))
    assert got.trainingCost == want.trainingCost and got.numIter == want.numIter >= 2
    np.testing.assert_array_equal(got_pred, want_pred)
    # the same array on the devices; the puts are of the host's 10 columns
    assert (want_put["cols"], want_put["pad_cols"], got_put["cols"], got_put["pad_cols"]) == (128, 0, 128, 118)
    assert got_put["bytes"] == want_put["bytes"]
    per_dev = got_put["bytes"] // (2 * (128 * 4 + 4))   # a device's rows, padded to KMeans' chunks
    assert got_put["blocks"] == (2 * -(-(rows + 1) // 2 // block_rows) if block_rows else 2)
    assert got_put["block_bytes"] == (block_rows or per_dev) * cols * 4


# ---- a fold over the blocks as they land -------------------------------------

# id: (a case as above, the columns the shard is to have on the device)
FOLDED = {
    "blocks_dp1": (CASES["dp1"], None),
    "blocks_dp2": (CASES["dp2"], None),
    "blocks_dp4_mp2": (CASES["dp4_mp2"], None),
    "ragged_tail_of_3_rows": (CASES["tail_block_of_3_rows"], None),
    "whole_blocks_no_tail": (CASES["whole_blocks_no_tail"], None),
    "shards_of_padding_get_no_block": (CASES["dp8_multiple128_shards_of_padding"], None),
    "one_put_shard_of_exactly_one_block": (CASES["shard_of_exactly_one_block"], None),
    "one_put_shard_with_padding_rows": (CASES["shard_below_one_block"], None),
    "one_put_shards_of_padding": ((8, 1, 128, 130, np.float32, 2, 128, False), None),
    "width_padded_to128_dp2": (CASES["dp2"], 128),
    "width_padded_to16_one_block": ((2, 1, 128, 97, np.float32, 2, 128, True), 16),
    "f64": (CASES["f64"], None),
}


@pytest.mark.parametrize("case", list(FOLDED))
def test_fold_is_handed_every_block_once_in_row_order(case, monkeypatch, assembly):
    """A fold sees each device's rows once, block after block as the loop
    places them (a one-put shard: whole, its padding rows behind ``valid``),
    at the host's own width; the frame that comes back is bit for bit the one
    built without a fold, and the span counts the folds."""
    (dp, mp, row_multiple, rows, dtype, ndim, block_rows, assembled), cols = FOLDED[case]
    x = _host(rows, dtype, ndim)
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", x.dtype.itemsize * COLS * block_rows)
    mesh = make_mesh(dp, mp=mp)

    def fold(state, block, row0, valid):
        (dev,) = block.devices()
        assert block.shape[1] == COLS and 0 <= valid <= block.shape[0]
        return (state or []) + [(dev.id, row0, valid, np.asarray(block)[:valid])]

    with jax.enable_x64(dtype == np.float64):
        with _h2d_puts() as plain_puts:
            want, want_mask = shard_rows(x, mesh, row_multiple, cols=cols)
        writes_without = list(assembly)
        del assembly[:]
        with _h2d_puts() as puts:
            xd, md, states = shard_rows(x, mesh, row_multiple, cols=cols, fold=fold)
        np.testing.assert_array_equal(np.asarray(xd).view(np.uint8), np.asarray(want).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(md), np.asarray(want_mask))
        assert xd.sharding == want.sharding and xd.dtype == want.dtype
    assert assembly == writes_without and (assembly != []) == assembled     # the same writes, in the same order
    assert "folded_blocks" not in plain_puts[0]
    per_dev = xd.shape[0] // dp
    index_map = row_sharding(mesh).addressable_devices_indices_map(xd.shape)
    assert set(states) == set(mesh.devices.flat)
    calls = 0
    for dev in mesh.devices.flat:
        lo = index_map[dev][0].start or 0
        valid_rows = min(max(rows - lo, 0), per_dev)
        seen = states[dev] or []
        calls += len(seen)
        if assembled:       # a block a call, none for a device that holds padding alone
            assert [(row0, valid) for _, row0, valid, _ in seen] == [
                (at, min(block_rows, valid_rows - at)) for at in range(0, valid_rows, block_rows)
            ]
        else:               # one put: once, on the whole shard
            assert [(row0, valid) for _, row0, valid, _ in seen] == [(0, valid_rows)]
        for dev_id, row0, valid, got in seen:
            assert dev_id == dev.id
            np.testing.assert_array_equal(got, x[lo + row0:lo + row0 + valid])
    assert puts[0]["folded_blocks"] == calls == (puts[0]["blocks"] if assembled else dp * mp)


def test_fold_follows_its_write_with_at_most_two_writes_outstanding(monkeypatch):
    """Block k is folded after the wait for write k − 2 and right after write
    k is issued — never ahead of it, where it would hold back the write that
    the next put waits for: the fold adds no wait and keeps the bound on the
    writes outstanding."""
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", COLS * 4 * 16)
    events = []
    real_write = mesh_mod._write_block

    class Written:
        def __init__(self, k, scalar):
            self.k, self.scalar = k, scalar

        def block_until_ready(self):
            events.append(("waited", self.k))
            return self.scalar.block_until_ready()

    def write(buf, block, row0):
        k = sum(1 for e in events if e[0] == "issued")
        events.append(("issued", k))
        out, scalar = real_write(buf, block, row0)
        return out, Written(k, scalar)

    def fold(state, block, row0, valid):
        events.append(("folded", sum(1 for e in events if e[0] == "folded")))
        return (state or 0) + valid

    monkeypatch.setattr(mesh_mod, "_write_block", write)
    x = _host(2 * 5 * 16 - 7, np.float32, 2)
    xd, _, states = shard_rows(x, make_mesh(2), fold=fold)
    np.testing.assert_array_equal(np.asarray(xd)[:len(x)], x)
    assert sorted(states.values()) == [76, 77]     # 153 rows over two devices, one padding row
    expected = [("issued", 0), ("folded", 0), ("issued", 1), ("folded", 1)]
    for k in range(2, 10):
        expected += [("waited", k - 2), ("issued", k), ("folded", k)]
    assert events == expected
