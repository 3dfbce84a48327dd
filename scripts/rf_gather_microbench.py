"""The forests' whole-row gather on the chip: uint8 rows against int32 words.

    python3 scripts/rf_gather_microbench.py [--out FILE]   # one chip

A level of a forest fit gathers whole rows of the binned matrix, a chunk of
16,384 node-sorted rows at a time (``tree_kernels._hist_compact``). At the
forest cells' shape, 500,000 rows of 3072 bins, this times that gather and
what follows it, a chunk at a time:

* ``gather_u8``: ``bins[ids]`` of ``uint8`` rows of 3072;
* ``gather_words``: the same rows as 768 ``int32`` words, four bins to a word
  (planar: byte ``b`` of word ``w`` holds column ``b * 768 + w``);
* ``gather_u8_bf16`` / ``gather_words_bf16``: each followed, in XLA, by the
  unpack and the bf16 cast that the wide histogram kernel reads;

each with the ids ascending (a node's run, as the level hands them) and in
random order; and the wide histogram kernel of ``rf_reg_dbx``
(``rf_pallas.subblock_hist_sel_wide``'s grid and windows, a private copy here)
reading a chunk's rows three ways: ``bf16`` rows cast in XLA (the kernel as
it is), ``u8`` rows cast inside the kernel, ``words`` unpacked inside the
kernel — alone and behind the gather that feeds it, as one program a chunk
(``route_*``). Every kernel variant's sums must equal the ``bf16`` one's, bit
for bit. Then the routing's element gather, one bin a row, from the bytes and
from the words. Times are the mean of ``--reps`` calls after a warm one;
rates are rows a second and bytes a second of the gathered rows. One JSON
line on stdout, and in ``--out`` if given. On the CPU it rehearses itself at
a toy size, the kernel in interpret mode.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


def timed(fn, *args, reps=20):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def unpack(words):
    """(m, W) int32 words -> (m, 4W) int32 bins, columns in order."""
    return jnp.concatenate([(words >> (8 * b)) & 0xFF for b in range(4)], axis=1)


def _wide(bq, feats_b, parts, nodes_b, live_blocks, acc, *, n_bins, mode):
    """``rf_pallas.subblock_hist_sel_wide`` with its rows operand as ``mode``:
    ``bf16`` (n_pad, d_pad) rows, ``u8`` (n_pad, d_pad) rows cast in the
    kernel, ``words`` (n_pad, d_pad / 4) int32 unpacked in the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from spark_rapids_ml_tpu.ops import rf_pallas as rp

    n_pad, width = bq.shape
    d_pad = 4 * width if mode == "words" else width
    wt = width // 128
    n_blocks, k = feats_b.shape
    nb, R, T, P = n_bins, rp.WIDE_BLOCK_ROWS, rp.WIDE_SLOTS, rp.WIDE_STAT_ROWS
    G = min(rp._WIDE_BIN_GROUP, nb)
    n_tiles = k // T
    lo, cnt = rp.wide_windows(feats_b, d_pad)
    win = jnp.stack([lo, cnt], axis=-1).reshape(-1).astype(jnp.int32)

    def cols(b_ref, c):
        if mode == "words":
            w = b_ref[:, pl.ds(pl.multiple_of((c % wt) * 128, 128), 128)]
            return ((w >> (8 * (c // wt))) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        x = b_ref[:, pl.ds(pl.multiple_of(c * 128, 128), 128)]
        return x if mode == "bf16" else x.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)

    def kern(live_ref, nodes_ref, win_ref, b_ref, f_ref, p_ref, acc_ref, out_ref):
        t, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i < live_ref[0])
        def _():
            first = (i == 0) | (nodes_ref[i] != nodes_ref[jnp.maximum(i - 1, 0)])
            f_row = f_ref[0]

            def select(c, selected):
                ids = lax.broadcasted_iota(jnp.int32, (128, T), 0) + c * 128
                sel = (ids == f_row).astype(jnp.bfloat16)
                return selected + jnp.dot(cols(b_ref, c), sel, preferred_element_type=jnp.float32)

            w = 2 * (i * n_tiles + t)
            selected = lax.fori_loop(win_ref[w], win_ref[w] + win_ref[w + 1], select, jnp.zeros((R, T), jnp.float32))
            lane_bin = (lax.broadcasted_iota(jnp.int32, (1, G * T), 1) // T).astype(jnp.float32)
            for g in range(nb // G):
                oh = (pltpu.repeat(selected, G, axis=1) == lane_bin + float(g * G)).astype(jnp.bfloat16)
                sl = slice(g * G * T, (g + 1) * G * T)
                base = jnp.where(first, acc_ref[0, :, sl], out_ref[0, :, sl])
                out_ref[0, :, sl] = base + jnp.dot(p_ref[:], oh, preferred_element_type=jnp.float32)

        @pl.when((live_ref[0] == 0) & (i == 0))
        def _():
            out_ref[:] = acc_ref[:]

    spec = lambda shape, f: pl.BlockSpec(shape, f, memory_space=pltpu.VMEM)  # noqa: E731
    sums = lambda t, i, live, nodes, w: (nodes[rp._live_index(i, live)], 0, t)  # noqa: E731
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_tiles, n_blocks),
            in_specs=[
                spec((R, width), lambda t, i, live, nodes, w: (rp._live_index(i, live), 0)),
                spec((1, 1, T), lambda t, i, live, nodes, w: (rp._live_index(i, live), 0, t)),
                spec((P, R), lambda t, i, live, nodes, w: (0, rp._live_index(i, live))),
                spec((1, P, nb * T), sums),
            ],
            out_specs=spec((1, P, nb * T), sums),
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=100 * 1024 * 1024),
        interpret=rp.FORCE_INTERPRET,
        name="rf_hist_sel_pass_wide",
    )(live_blocks.reshape(1), nodes_b.astype(jnp.int32), win, bq, feats_b.reshape(n_blocks, 1, k), parts, acc)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=500_000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    from spark_rapids_ml_tpu.ops import rf_pallas as rp
    from spark_rapids_ml_tpu.utils.platform import enable_compile_cache

    enable_compile_cache(0.0)
    dev = jax.devices()[0]
    out = {"device": dev.device_kind, "platform": dev.platform}
    n, chunk, d_pad, nb, S, n_nodes, k = args.rows, 16_384, 3072, 128, 3, 3, 1024
    if jax.default_backend() != "tpu":  # a rehearsal of the script itself
        rp.FORCE_INTERPRET, n, chunk, nb, n_nodes = True, 4096, 1024, 32, 2
    W = d_pad // 4
    out.update(rows=n, chunk=chunk, d_pad=d_pad)
    key = jax.random.PRNGKey(0)
    # words of four bins under 128 each, made on the device; the bytes are
    # their planar unpacking
    words = jax.lax.bitcast_convert_type(
        jax.random.bits(key, (n, W), jnp.uint32) & jnp.uint32(0x7F7F7F7F), jnp.int32
    )
    u8 = jax.jit(lambda w: unpack(w).astype(jnp.uint8))(words)
    rng = np.random.default_rng(0)
    ids_rand = jnp.asarray(rng.permutation(n)[:chunk].astype(np.int32))
    ids_up = jnp.sort(ids_rand)

    gathers = {
        "gather_u8": (jax.jit(lambda b, i: b[i]), u8, d_pad),
        "gather_words": (jax.jit(lambda b, i: b[i]), words, 4 * W),
        "gather_u8_bf16": (jax.jit(lambda b, i: b[i].astype(jnp.bfloat16)), u8, d_pad),
        "gather_words_bf16": (jax.jit(lambda b, i: unpack(b[i]).astype(jnp.bfloat16)), words, 4 * W),
    }
    for name, (fn, src, row_bytes) in gathers.items():
        for order, ids in (("up", ids_up), ("rand", ids_rand)):
            t = timed(fn, src, ids, reps=args.reps)
            out[f"{name}_{order}_ms"] = 1e3 * t
            out[f"{name}_{order}_mrows_s"] = chunk / t / 1e6
            out[f"{name}_{order}_gb_s"] = chunk * row_bytes / t / 1e9
    np.testing.assert_array_equal(np.asarray(gathers["gather_u8_bf16"][0](u8, ids_up)),
                                  np.asarray(gathers["gather_words_bf16"][0](words, ids_up)))

    # the wide kernel on one chunk of level 5: 32 one-node blocks in runs of
    # 3 nodes, 1000 of 3000 features a node in 1024 slots, ascending
    R = rp.WIDE_BLOCK_ROWS
    blocks = chunk // R
    swT = jnp.asarray((rng.normal(size=(S, chunk)) * 1e3).astype(np.float32))
    parts = rp.split_f32_exact(swT)
    nodes = jnp.asarray((np.arange(blocks) * n_nodes // blocks).astype(np.int32))
    live = jnp.asarray([blocks], jnp.int32)
    feats = np.stack([np.concatenate([np.sort(rng.permutation(3000)[:1000]), np.full(k - 1000, 3000)])
                      for _ in range(n_nodes)]).astype(np.int32)
    feats_b = jnp.asarray(feats)[nodes]
    operand = {
        "bf16": (u8, lambda b, i: b[i].astype(jnp.bfloat16)),
        "u8": (u8, lambda b, i: b[i]),
        "words": (words, lambda b, i: b[i]),
    }
    sums = {}
    for mode, (src, rows_of) in operand.items():
        kern = functools.partial(_wide, feats_b=feats_b, parts=parts, nodes_b=nodes, live_blocks=live, n_bins=nb, mode=mode)
        rows = jax.jit(rows_of)(src, ids_up)
        alone = jax.jit(lambda a, r, kern=kern: kern(r, acc=a), donate_argnums=0)
        route = jax.jit(lambda a, b, i, rows_of=rows_of, kern=kern: kern(rows_of(b, i), acc=a), donate_argnums=0)
        for label, step, extra in (("kernel", alone, (rows,)), ("route", route, (src, ids_up))):
            acc = jax.block_until_ready(step(jnp.zeros((n_nodes, rp.WIDE_STAT_ROWS, k * nb), jnp.float32), *extra))
            t = time.perf_counter()
            for _ in range(args.reps):
                acc = step(acc, *extra)
            jax.block_until_ready(acc)
            out[f"{label}_{mode}_ms"] = 1e3 * (time.perf_counter() - t) / args.reps
            if label == "kernel":
                sums[mode] = np.asarray(step(jnp.zeros_like(acc), *extra))
            del acc
        del rows
    out["kernel_sums_equal"] = all(np.array_equal(sums["bf16"], s) for s in sums.values())

    # the routing's element gather: one bin a row at the row's split feature
    feat = jnp.asarray(rng.integers(0, 3000, chunk).astype(np.int32))
    route_u8 = jax.jit(lambda b, i, f: b[i, f].astype(jnp.int32))
    route_words = jax.jit(lambda b, i, f: (b[i, f % W] >> (8 * (f // W))) & 0xFF)
    out["route_bin_u8_ms"] = 1e3 * timed(route_u8, u8, ids_up, feat, reps=args.reps)
    out["route_bin_words_ms"] = 1e3 * timed(route_words, words, ids_up, feat, reps=args.reps)
    np.testing.assert_array_equal(np.asarray(route_u8(u8, ids_up, feat)), np.asarray(route_words(words, ids_up, feat)))
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if out["kernel_sums_equal"] else 1


if __name__ == "__main__":
    sys.exit(main())
