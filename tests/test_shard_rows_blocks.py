"""``parallel/mesh.shard_rows`` in row blocks: a shard larger than one block
(``_PUT_BLOCK_BYTES``) goes up in puts of a bounded size that are assembled
on the device. The array that comes back must be the one a single put of the
padded host array gives — same shape, dtype, sharding and bits — and a shard
of at most one block must not touch the assembly program at all."""

import ml_dtypes
import numpy as np
import pytest

import jax

from spark_rapids_ml_tpu.classification import LogisticRegression
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


@pytest.mark.parametrize("case", CASES)
def test_shard_rows_equals_the_padded_host_array(case, monkeypatch, assembly):
    dp, mp, row_multiple, rows, dtype, ndim, block_rows, assembled = CASES[case]
    x = _host(rows, dtype, ndim)
    row_bytes = x.dtype.itemsize * (COLS if ndim == 2 else 1)
    # a byte target of less than the tile's 8 rows still gives 8
    monkeypatch.setattr(mesh_mod, "_PUT_BLOCK_BYTES", row_bytes * (block_rows if block_rows > 8 else 1))
    assert mesh_mod._put_block_rows(row_bytes) == block_rows
    mesh = make_mesh(dp, mp=mp)
    with jax.enable_x64(dtype == np.float64):
        xd, md = shard_rows(x, mesh, row_multiple)
        want, want_mask = pad_rows(x, dp * row_multiple)
        assert xd.shape == want.shape and xd.dtype == want.dtype
        assert xd.sharding == md.sharding == row_sharding(mesh)
        np.testing.assert_array_equal(np.asarray(xd).view(np.uint8), want.view(np.uint8))
        np.testing.assert_array_equal(np.asarray(md), want_mask)
        assert md.dtype == np.float32
        for shard in xd.addressable_shards:
            np.testing.assert_array_equal(np.asarray(shard.data), want[shard.index])
    if not assembled:
        assert assembly == []
        return
    # each device got the valid rows of its shard, block after block
    per_dev = want.shape[0] // dp
    expected = []
    index_map = row_sharding(mesh).addressable_devices_indices_map(want.shape)
    for dev in mesh.devices.flat:
        lo = index_map[dev][0].start or 0
        valid = min(max(rows - lo, 0), per_dev)
        expected += [(dev.id, at, min(block_rows, valid - at)) for at in range(0, valid, block_rows)]
    assert sorted(assembly) == sorted(expected) and len(expected) > dp * mp
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
        puts = []
        telemetry.add_span_sink(lambda ev, thread: puts.append(ev["args"]) if ev["name"] == "h2d.enqueue" else None)
        try:
            return LogisticRegression(maxIter=30, regParam=1e-3, num_workers=2).fit(df), puts
        finally:
            telemetry.reset_telemetry()

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
