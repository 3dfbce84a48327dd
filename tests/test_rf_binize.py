"""The forests' quantizer: ``tree_kernels.binize`` gives the plain count
``#{edges <= x}`` bit for bit through both of its passes — the Pallas
kernel ``rf_pallas.rf_binize_pass`` (interpret mode; bins written row-major
or rows minor, as the device keeps them) and XLA's compare-count in aligned
column chunks — at row counts that are and are not a multiple of a tile, at
the widths of the transform (``d_pad`` the width rounded to 4) and the fit
(rounded to 128), and on the values where a count and a search could part:
tied edges, values on an edge, signed zeros, NaN and infinities. The fit's
and the transform's spans say which pass ran and why."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_ml_tpu.ops.rf_pallas as rfp
from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.data import DataFrame
from spark_rapids_ml_tpu.ops import tree_kernels as tk
from spark_rapids_ml_tpu.runtime import telemetry

NB = 128
PATHS = ("xla", "pallas_row_major_bins", "pallas_rows_minor_bins")


def _plain_count(X, edges, d_pad):
    """``(x[:, :, None] >= edges).sum(-1)``, a slab of columns at a time."""
    n, d = X.shape
    out = np.zeros((n, d_pad), np.uint8)
    for c in range(0, d, 64):
        out[:, c:min(c + 64, d)] = (X[:, c:c + 64, None] >= edges[None, c:c + 64]).sum(-1)
    return out


def _binize(X, edges, d_pad, path, monkeypatch):
    if path == "xla":
        plan = tk.binize_plan(X.shape, jnp.float32, edges.shape[1], d_pad)
        assert plan.kernel == "xla" and "backend" in plan.declined
        assert int(plan.tile) % 8 == 0 or int(plan.tile) == X.shape[1]
    else:
        monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
        rows, cols, _ = rfp.binize_tile(X.shape[0])
        plan = tk.BinizePlan("pallas", f"{rows}x{cols}", "", path == "pallas_rows_minor_bins")
    bins = tk.binize(jnp.asarray(X), jnp.asarray(edges), d_pad=d_pad, plan=plan)
    assert bins.shape == (X.shape[0], d_pad) and bins.dtype == jnp.uint8
    return np.asarray(bins)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("align", [4, 128], ids=["transform_width", "fit_width"])
@pytest.mark.parametrize("cols", [300, 1100, 3000])
@pytest.mark.parametrize("rows", [1000, 3337, 4096])
def test_binize_equals_the_plain_count(rows, cols, align, path, monkeypatch):
    rng = np.random.default_rng(rows * 7 + cols)
    X = (rng.standard_normal((rows, cols)) * rng.uniform(0.1, 100.0, cols)).astype(np.float32)
    edges = tk.make_bin_edges(X, NB)
    d_pad = -(-cols // align) * align
    np.testing.assert_array_equal(_binize(X, edges, d_pad, path, monkeypatch), _plain_count(X, edges, d_pad))


def _edge_case(case, rng):
    """(X, edges) of 1000 x 256 with one kind of value a search could miscount."""
    X = rng.standard_normal((1000, 256)).astype(np.float32)
    edges = tk.make_bin_edges(X, NB)
    if case == "constant_column":          # all 127 edges equal: 0 below, 127 at or above
        X[:, 5] = 2.5
        X[::3, 5] = 1.0
        edges[5] = 2.5
    elif case == "on_an_edge":             # every value of a column exactly one of its edges
        X[:, 9] = edges[9, rng.integers(0, NB - 1, 1000)]
        X[:7, 10] = edges[10, :7]
    elif case == "signed_zero":            # -0.0 and +0.0 compare equal, among the edges too
        X[:, 3] = np.where(rng.random(1000) < 0.5, -0.0, 0.0).astype(np.float32)
        edges[3, :64] = -0.0
        edges[3, 64:] = np.linspace(0.0, 1.0, NB - 1 - 64, dtype=np.float32)
        edges[4, 40:80] = 0.0
        X[::2, 4] = -0.0
    elif case == "nan":                    # NaN compares false with every edge: bin 0
        X[::5, 7] = np.nan
        X[3, :] = np.nan
    elif case == "infinities":             # +inf past every edge, -inf before
        X[::4, 11] = np.inf
        X[1::4, 11] = -np.inf
        edges[12, -3:] = np.inf
        X[::2, 12] = np.inf
    return X, edges


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", ["constant_column", "on_an_edge", "signed_zero", "nan", "infinities"])
def test_binize_edge_values_equal_the_plain_count(case, path, monkeypatch):
    X, edges = _edge_case(case, np.random.default_rng(5))
    got = _binize(X, edges, 256, path, monkeypatch)
    np.testing.assert_array_equal(got, _plain_count(X, edges, 256))
    if case == "nan":
        assert (got[3] == 0).all() and (got[::5, 7] == 0).all()
    if case == "infinities":
        assert (got[::4, 11] == NB - 1).all() and (got[1::4, 11] == 0).all()


@pytest.mark.parametrize("kernel", ["xla", "pallas"], ids=["cpu_gate", "tpu_gate_interpret"])
def test_binize_spans_say_which_pass_ran(kernel, monkeypatch):
    """``forest.binize`` (the fit) and ``forest.binize_batch`` (each transform
    batch) carry ``kernel``, ``tile`` and ``declined``: on the CPU the gate
    declines on the backend and XLA's chunk is whole sublane tiles; with the
    gate answered as a TPU's (the kernel in interpret mode) every span says
    ``pallas`` and the grid step — a fit's step counts one byte plane of the
    64 words a row of its 256 bin columns packs into, a batch's step 128
    columns of bytes."""
    if kernel == "pallas":
        monkeypatch.setattr(rfp, "FORCE_INTERPRET", True)
    monkeypatch.setenv("TPUML_RF_APPLY", "bins")
    rng = np.random.default_rng(3)
    n, d = 1500, 130
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    spans = []
    sink = lambda ev, thread: spans.append(ev)  # noqa: E731
    telemetry.add_span_sink(sink)
    try:
        model = RandomForestClassifier(numTrees=2, maxDepth=3, maxBins=NB, seed=1, num_workers=1).fit(
            DataFrame({"features": X, "label": y})
        )
        pred = np.asarray(model.transform(DataFrame({"features": X})).column("prediction"))
    finally:
        telemetry.remove_span_sink(sink)
        jax.clear_caches()
    assert pred.shape == (n,)
    fit = [s["args"] for s in spans if s["name"] == "forest.binize"]
    batches = [s["args"] for s in spans if s["name"] == "forest.binize_batch"]
    assert len(fit) == 1 and batches
    for args in fit + batches:
        assert args["kernel"] == kernel
        if kernel == "pallas":
            assert args["declined"] == "" and args["tile"] == ("1536x64" if args in fit else "1536x128")
        else:
            assert args["declined"] == "backend,rows_minor" and args["tile"] == str(tk._binize_chunk(n, d))
