"""Demonstrate decode/compute overlap of ops.streaming.prefetch_chunks on
the local CPU backend (a machinery check: no host->device link is involved,
so it says nothing about ingest on a chip).

Producer: a generator that sleeps per chunk (GIL-releasing, modeling
I/O-bound parquet decode — a busy-wait would contend with the CPU
backend's compute for the same cores and make the measurement noise on
small hosts). Consumer: the library's streamed accumulation. With the
prefetch thread, producer time hides under device compute; without it,
the two serialize.

Run:  JAX_PLATFORMS=cpu python scripts/streaming_overlap_cpu.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp

from spark_rapids_ml_tpu.data.chunks import Chunk
from spark_rapids_ml_tpu.ops.streaming import (
    StreamGuard, gram2_init, gram2_step, prefetch_chunks, put_chunk,
)
from spark_rapids_ml_tpu.parallel.mesh import make_mesh

N_CHUNKS = 24
CHUNK_ROWS = 8192
D = 512
DECODE_S = 0.02  # simulated per-chunk decode cost


def chunks():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((CHUNK_ROWS, D)).astype(np.float32)
    for i in range(N_CHUNKS):
        time.sleep(DECODE_S)  # I/O-bound "decode" (releases the GIL)
        yield Chunk(X=base * np.float32(1 + i * 1e-6), n_valid=CHUNK_ROWS)


def run(prefetch: bool) -> float:
    mesh = make_mesh()
    mean0 = jnp.zeros((D,), jnp.float32)
    acc = gram2_init(D, jnp.float32, False)
    guard = StreamGuard()
    it = prefetch_chunks(chunks()) if prefetch else chunks()
    t0 = time.perf_counter()
    for chunk in it:
        dev = put_chunk(chunk, mesh, np.float32, need_y=False, need_w=False)
        acc = gram2_step(acc, dev["X"], dev["mask"], mean0)
        guard.tick(dev, acc["G"])
    guard.flush(acc["G"])
    np.asarray(acc["G"])
    return time.perf_counter() - t0


def main():
    run(True)  # warm compiles
    t_serial = run(False)
    t_prefetch = run(True)
    decode_total = N_CHUNKS * DECODE_S
    hidden = t_serial - t_prefetch
    print(f"serial   : {t_serial:.3f}s  (decode {decode_total:.2f}s + compute)")
    print(f"prefetch : {t_prefetch:.3f}s")
    print(f"overlap  : {hidden:.3f}s of producer time hidden "
          f"({100 * hidden / decode_total:.0f}% of decode)")
    if hidden < 0.25 * decode_total:
        # demo, not a CI gate (tests/test_streaming.py holds that line):
        # on a 1-core host the measurement jitters run-to-run
        print("WARNING: prefetch hid <25% of decode on this run — "
              "re-run; persistent low overlap means a regression")
    else:
        print("OK")


if __name__ == "__main__":
    main()
