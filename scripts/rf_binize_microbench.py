"""The forests' quantizer on the chip, at the three shapes the forest cells run.

    python3 scripts/rf_binize_microbench.py [--tiles 1024,2048,4096] [--out FILE]  # one chip

``tree_kernels.binize`` of a 3000-column f32 frame made on the device (so
kept as a staged batch is: rows minor) against the edges the fit's own
sketch gives it (``quantile_edges``, 128 bins), at

* ``fit``: 500,000 rows -> 3072 bin columns (row-major bins);
* ``full_batch``: 131,072 rows -> 3000 (a transform batch: rows-minor bins);
* ``tail_batch``: 106,784 rows -> 3000 (the transform's last batch),

timed three ways: ``parent`` (a copy here of the XLA compare-count with the
chunk of old, ``(1 << 22) // n`` columns: 8, 32 and 39), ``xla_aligned``
(the same program with the chunk in whole 8-column sublane tiles:
``tree_kernels._binize_chunk``) and ``pallas`` (``rf_pallas.rf_binize_pass``
at each rows tile of ``--tiles``). For each: the first call's seconds (the
compile), the median of ten timed calls, the compiler's temporaries, the
values a second (the frame's) and whether the bins equal the parent's, bit
for bit. At the tail's shape a profiler trace of one call of ``parent`` and
``xla_aligned`` names the operations that take its time. One JSON line a
shape on stdout; all of them, as one JSON list, in ``--out`` if given.
On the CPU it rehearses itself at a toy size, the kernel in interpret mode.
"""
import argparse
import functools
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SHAPES = (("fit", 500_000, 3072), ("full_batch", 131_072, 3000), ("tail_batch", 106_784, 3000))


@functools.partial(jax.jit, static_argnames=("d_pad",))
def _parent_binize(X, edges, *, d_pad):
    """The compare-count as it was: a chunk of ``(1 << 22) // n`` columns."""
    n, d = X.shape
    Fc = max(1, min(d, (1 << 22) // max(n, 1)))
    parts = [
        (X[:, c0:c0 + Fc, None] >= edges[None, c0:c0 + Fc, :]).sum(axis=2, dtype=jnp.int32).astype(jnp.uint8)
        for c0 in range(0, d, Fc)
    ]
    bins = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return jnp.pad(bins, ((0, 0), (0, d_pad - d))) if d_pad > d else bins


def _timed(fn, reps=10):
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t)
    return out, first, statistics.median(times)


def _top_ops(fn, top=6):
    """(name, ms) of the operations one call of ``fn`` spends most device time in."""
    from chipbench import trace_reduce as tr

    with tempfile.TemporaryDirectory() as d:
        tr.start(d)
        jax.block_until_ready(fn())
        jax.profiler.stop_trace()
        device_ops, _ = tr.read_planes(tr.find_xplane(d))
    ops = next((o for o in device_ops.values() if o), [])
    by = sorted(tr.self_times(ops).items(), key=lambda kv: -kv[1])[:top]
    return [[name, t * 1e-6] for name, t in by]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default="1024,2048,4096", help="rows a grid step of the kernel")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write every shape's results here, as one JSON list")
    args = ap.parse_args()
    from spark_rapids_ml_tpu.ops import rf_pallas as rp, tree_kernels as tk

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    shapes, d = SHAPES, 3000
    if jax.default_backend() != "tpu":   # a rehearsal of the script itself
        rp.FORCE_INTERPRET = True
        shapes, d = (("fit", 4000, 384), ("full_batch", 1024, 300), ("tail_batch", 872, 300)), 300
    results = []
    for label, n, d_pad in shapes:
        key = jax.random.PRNGKey(args.seed)
        X = jax.jit(lambda k: jax.random.normal(k, (n, d), jnp.float32) * 3.0)(key)
        edges, _ = tk.quantile_edges(X, jnp.ones((n,), jnp.float32), n_bins=128)
        out = {"shape": label, "rows": n, "cols": d, "d_pad": d_pad}
        ref, out["parent_first_s"], out["parent_s"] = _timed(lambda: _parent_binize(X, edges, d_pad=d_pad))
        out["parent_chunk"] = max(1, min(d, (1 << 22) // n))
        out["parent_temp_mb"] = _parent_binize.lower(X, edges, d_pad=d_pad).compile().memory_analysis().temp_size_in_bytes / 1e6
        chunk = tk._binize_chunk(n, d)
        variants = [("xla_aligned", tk.BinizePlan("xla", str(chunk), "", False), None)]
        gated = tk.binize_plan(X.shape, X.dtype, edges.shape[1], d_pad)
        out["gate_declined"] = gated.declined
        if gated.kernel == "pallas":
            variants += [(f"pallas_{t}", gated, t) for t in (int(s) for s in args.tiles.split(","))]
        for name, plan, tile in variants:
            if tile is not None:
                rp.BINIZE_ROWS = tile
                plan = plan._replace(tile="x".join(str(v) for v in rp.binize_tile(n)[:2]))
            run = lambda p=plan: tk.binize(X, edges, d_pad=d_pad, plan=p)  # noqa: E731
            got, out[name + "_first_s"], out[name + "_s"] = _timed(run)
            out[name + "_tile"] = plan.tile
            out[name + "_equal"] = bool(jnp.array_equal(got, ref))
            out[name + "_temp_mb"] = tk.binize.lower(X, edges, d_pad=d_pad, plan=plan).compile().memory_analysis().temp_size_in_bytes / 1e6
            out[name + "_gvalues_per_s"] = n * d / out[name + "_s"] / 1e9
            del got
        out["parent_gvalues_per_s"] = n * d / out["parent_s"] / 1e9
        rp.BINIZE_ROWS = 2048
        if label == "tail_batch" and jax.default_backend() == "tpu":
            out["parent_top_ops_ms"] = _top_ops(lambda: _parent_binize(X, edges, d_pad=d_pad))
            aligned = tk.BinizePlan("xla", str(chunk), "", False)
            out["xla_aligned_top_ops_ms"] = _top_ops(lambda: tk.binize(X, edges, d_pad=d_pad, plan=aligned))
            out["parent_hlo_top"] = [
                line.strip()[:240] for line in _parent_binize.lower(X, edges, d_pad=d_pad).compile().as_text().splitlines()
                if any(f"%{op[0]} " in line for op in out["parent_top_ops_ms"][:3])
            ]
        print(json.dumps(out), flush=True)
        results.append(out)
        del X, ref
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
