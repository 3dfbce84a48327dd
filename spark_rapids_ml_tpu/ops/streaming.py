"""Streaming (out-of-core) accumulation kernels.

The reference holds the whole per-worker partition on device and lets cuML
reduce over it (UVM for beyond-HBM datasets,
``/root/reference/python/src/spark_rapids_ml/core.py:699-741``).  The
TPU-native scheme: fixed-shape host chunks stream through a small device
buffer; these jitted steps fold each chunk into replicated accumulator
state.  Chunks are row-sharded over the ``dp`` mesh axis and accumulators
are replicated, so XLA's SPMD partitioner inserts exactly one psum of each
partial per chunk — the same communication the reference's NCCL allreduce
performed, amortized over chunks.

Accumulators are donated (``donate_argnums=0``) so device memory stays
constant across chunks: one chunk slab + O(d²) state, independent of n.

Numerics: means first, centered Gram second (two passes) — the same
center-before-Gram discipline as the in-memory kernels (``ops/linalg.py``),
avoiding the f32 catastrophic cancellation of one-pass covariance.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..data.chunks import Chunk, ChunkSource
from ..parallel.mesh import row_sharding
from ..runtime import autotune, counters, envspec, opsplane, telemetry
from ..runtime.faults import SimulatedPreemption, fault_site
from ..runtime.scheduler import preempt_point
from ..runtime.retry import (
    backoff_schedule,
    is_resource_exhausted,
    resolve_backoff_ms,
    resolve_retries,
)
from ..utils.logging import get_logger

_res_logger = get_logger("streaming.resilience")
_wire_logger = get_logger("streaming.wire")


# ---------------------------------------------------------------------------
# Chunk transfer
# ---------------------------------------------------------------------------

# host-side backpressure period for streaming loops (chunks between syncs);
# 0 disables
_SYNC_EVERY = int(envspec.get("TPUML_STREAM_SYNC_EVERY"))

_release_err_logged = False


def _release_buffers(arrays) -> None:
    """``delete()`` retired chunk buffers (device slabs + the client's
    retained host copies).

    A failed delete is never fatal — results don't depend on it — but a
    swallowed one hides a leak that grows with total bytes shipped: each
    failure bumps the ``wire_release_errors`` counter and the first in the
    process is debug-logged with the exception, so a nonzero bench/test
    delta points straight at the cause.
    """
    global _release_err_logged
    for a in arrays:
        if a is None:
            continue
        try:
            a.delete()
        except Exception as exc:
            counters.bump("wire_release_errors")
            if not _release_err_logged:
                _release_err_logged = True
                _wire_logger.debug(
                    "chunk buffer release failed (first occurrence; further "
                    "ones only bump wire_release_errors): %r", exc,
                )


class StreamGuard:
    """Bounds host (and device) memory of a streaming loop.

    ``device_put`` transfers are async and a host decodes parquet chunks
    far faster than a slow host->device link drains them; with nothing in
    the loop ever synchronizing, pending transfers pin every chunk's host
    buffer (observed: a 100M-row north-star run was OOM-killed on the HOST
    at 130 GB RSS mid-pass). Dropping the Python references was seen not
    to be enough there: the client retained a host-side copy of a
    transferred buffer until that EXACT buffer is deleted — deleting only
    an array derived from it (e.g. the on-device f32 upcast of an f16 wire
    chunk) releases nothing (observed: RSS kept growing at the ingest rate
    when only derived arrays were deleted). ``put_chunk`` therefore hands
    the guard the raw transferred arrays under ``"_wire"``.

    Every ``_SYNC_EVERY`` chunks — and at :meth:`flush`, which every loop
    MUST call at the end (short passes would otherwise never sync at all)
    — the guard (1) host-fetches one accumulator scalar: the accumulator
    depends on every chunk folded so far, so the fetch PROVES all enqueued
    transfers and steps completed (``jax.block_until_ready`` is NOT
    sufficient on remote backends — it can return at dispatch
    acknowledgment, see docs/tpu_kernel_notes.md); then (2) ``delete()``s
    the retired chunk arrays, releasing device buffers and the client's
    host copies.

    The guard holds strong references to up to ``_SYNC_EVERY`` chunks of
    device buffers between syncs (they are freed only once proven
    retired), so the streaming device footprint is ``_SYNC_EVERY`` chunk
    slabs, not one — sized into the default period below.
    """

    def __init__(self) -> None:
        self._pending: list = []
        self._i = 0

    def _sync_and_release(self, acc) -> None:
        with telemetry.span("stream.sync", pending=len(self._pending)):
            leaf = jax.tree_util.tree_leaves(acc)[0]
            np.asarray(jnp.ravel(leaf)[:1])
            _release_buffers(self._pending)
            self._pending.clear()

    def tick(self, dev, acc) -> None:
        for v in dev.values():
            if v is None:
                continue
            if isinstance(v, (list, tuple)):
                self._pending.extend(v)
            else:
                self._pending.append(v)
        self._i += 1
        if _SYNC_EVERY > 0 and self._i % _SYNC_EVERY == 0:
            self._sync_and_release(acc)

    def flush(self, acc) -> None:
        """Sync + release the tail; call after every streaming loop."""
        if self._pending:
            self._sync_and_release(acc)


def prefetch_chunks(it, depth: Optional[int] = None):
    """Background-thread chunk prefetch (double buffering).

    The streaming loops alternate host work (parquet decode / synthetic
    gen in ``iter_chunks``) with device work (transfer + step) and
    periodic StreamGuard syncs that BLOCK the host. Without prefetch the
    host sits idle during those waits and the device sits idle during
    decode — serial. A bounded producer thread decodes chunk i+1 (and
    i+2, ...) while the main thread transfers/folds chunk i, so wall
    time approaches max(decode, device) instead of their sum
    (asserted by ``tests/test_streaming.py`` on a synthetic slow source).

    ``depth`` bounds look-ahead (host memory: depth chunk buffers).
    TPUML_STREAM_PREFETCH=0 disables (returns ``it`` unchanged); the
    env value otherwise sets the default depth (2).

    Early consumer exit (exception mid-loop) sets a cancel flag the
    producer polls between puts, so the daemon thread cannot wedge on a
    full queue holding the source open.
    """
    if depth is None:
        depth = int(envspec.get("TPUML_STREAM_PREFETCH"))
    if depth <= 0:
        yield from it
        return

    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    end = object()
    cancel = threading.Event()
    err: list = []

    def worker():
        try:
            src = iter(it)
            while True:
                # span covers the source's decode of ONE chunk (parquet
                # read / synthetic gen), not the backpressured put
                with telemetry.span("stream.decode"):
                    c = next(src, end)
                if c is end:
                    break
                while not cancel.is_set():
                    try:
                        q.put(c, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if cancel.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            err.append(e)
        finally:
            while not cancel.is_set():
                try:
                    q.put(end, timeout=0.1)
                    break
                except queue.Full:
                    continue

    th = threading.Thread(
        # the bound context parents this thread's decode spans under the
        # caller's ingest span
        target=telemetry.bind_context(worker),
        name="tpuml-chunk-prefetch",
        daemon=True,
    )
    th.start()
    try:
        while True:
            # fail fast, but deliver what was produced: chunks already in
            # the queue predate the failure and are valid; once the queue
            # is empty and the producer has recorded an error, raise
            # immediately instead of waiting for the end sentinel behind
            # `depth` buffered puts
            if err:
                try:
                    c = q.get_nowait()
                except queue.Empty:
                    # the internal Empty is not part of the user's error;
                    # re-raise the worker's exception object WITH the
                    # traceback it captured in the producer thread, so the
                    # failing frame (parquet decode, injected ingest fault,
                    # ...) is visible from the consumer
                    raise err[0].with_traceback(err[0].__traceback__) from None
            else:
                c = q.get()
            if c is end:
                break
            yield c
        if err:
            raise err[0].with_traceback(err[0].__traceback__)
    finally:
        # Callers that abandon the generator early should close() it (the
        # `finally` then runs promptly); an unclosed-but-unreferenced
        # generator only cancels the producer when GC collects it, until
        # which the daemon thread spins on 0.1 s put timeouts.
        cancel.set()


# ---------------------------------------------------------------------------
# Wire formats (TPUML_WIRE_DTYPE) — fewer bytes over the host->device link
# ---------------------------------------------------------------------------

# float8 e4m3 finite max (S.1111.110 -> 448); quantization maps each
# column's observed absmax onto it
_F8_MAX = 448.0

# auto-probe acceptance thresholds: relative RMS reconstruction error of
# the FIRST chunk under each encoding (cost model + derivation:
# docs/streaming_performance.md; dispatch behavior pinned by
# tests/test_streaming_wire.py)
_AUTO_INT8_TOL = 2e-2
_AUTO_F16_TOL = 2e-3


@jax.tree_util.register_pytree_node_class
class QuantizedWire:
    """A streamed chunk living on device in its quantized wire encoding.

    Fold steps accept this in place of the dense ``X`` and call
    :func:`wire_dense` first thing INSIDE their jit: the dequantize (one
    fused multiply-add per element) happens where the step reads the data,
    so the wide matrix never materializes between transfer and fold — the
    only host->device traffic was the narrow buffer plus two O(d) scale
    vectors. Being a pytree, it crosses the jit boundary as its leaves;
    the target dtype rides in the (static) treedef, so each encoding gets
    exactly one fold-step trace.

    ``offset`` is None for the scale-only encoding (f8).
    """

    def __init__(self, q, scale, offset, dtype):
        self.q = q
        self.scale = scale
        self.offset = offset
        self.dtype = jnp.dtype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale, self.offset), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(*children, dtype)

    def dense(self) -> jax.Array:
        x = self.q.astype(self.dtype) * self.scale.astype(self.dtype)
        if self.offset is not None:
            x = x + self.offset.astype(self.dtype)
        return x

    def delete(self) -> None:
        """StreamGuard-compatible release of the underlying buffers."""
        for a in (self.q, self.scale, self.offset):
            if a is not None:
                a.delete()


def wire_dense(X):
    """Resolve a fold-step ``X`` argument to a dense matrix.

    Every jitted fold step calls this on entry: a :class:`QuantizedWire`
    dequantizes HERE — inside the caller's jit — and a plain array passes
    through untouched (zero cost on the default path).
    """
    return X.dense() if isinstance(X, QuantizedWire) else X


def _quantize_int8(
    x: np.ndarray, n_valid: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-chunk-column affine int8: ``x ~ q * scale + offset``.

    Ranges come from the VALID rows only (padding rows quantize to
    whatever clips — every fold step multiplies them away by the mask).
    A constant column gets scale 1 so the reconstruction is exact.
    """
    v = x[:n_valid] if 0 < n_valid < x.shape[0] else x
    lo = v.min(axis=0).astype(np.float32)
    hi = v.max(axis=0).astype(np.float32)
    scale = ((hi - lo) / np.float32(254.0)).astype(np.float32)
    scale = np.where(scale > 0, scale, np.float32(1.0))
    offset = ((hi + lo) * np.float32(0.5)).astype(np.float32)
    # in-place pipeline: this runs per chunk on the ingest-critical path,
    # so avoid stacking several chunk-sized float temporaries
    q = x - offset
    q /= scale
    np.rint(q, out=q)
    np.clip(q, -127, 127, out=q)
    return q.astype(np.int8), scale, offset


@functools.lru_cache(maxsize=1)
def _f8_dtype() -> Optional[np.dtype]:
    """numpy dtype of the e4m3 wire encoding, or None when the toolchain
    lacks it (``ml_dtypes`` ships with jax, but gate rather than assume)."""
    try:
        import ml_dtypes

        return np.dtype(ml_dtypes.float8_e4m3fn)
    except Exception:
        try:
            return np.dtype(jnp.float8_e4m3fn)
        except Exception:
            return None


@functools.lru_cache(maxsize=1)
def _f8_supported() -> bool:
    """True when f8 buffers round-trip through the live backend (the
    dtype exists AND device_put + upcast lower on this platform)."""
    f8 = _f8_dtype()
    if f8 is None:
        return False
    try:
        np.asarray(
            jnp.asarray(np.ones((2,), f8)).astype(jnp.float32)
        )
        return True
    except Exception:
        return False


def _quantize_f8(
    x: np.ndarray, n_valid: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-chunk-column scaled e4m3: ``x ~ q * scale`` with each column's
    absmax mapped to the f8 finite max (no offset: e4m3's ~2 decimal
    digits are spent on relative precision instead)."""
    v = x[:n_valid] if 0 < n_valid < x.shape[0] else x
    amax = np.abs(v).max(axis=0).astype(np.float32)
    scale = np.where(amax > 0, amax / np.float32(_F8_MAX), np.float32(1.0))
    q = (x / scale).astype(_f8_dtype())
    return q, scale


def resolve_wire_dtype() -> str:
    """Parsed+validated ``TPUML_WIRE_DTYPE`` (EnvSpecError on bad values)."""
    return str(envspec.get("TPUML_WIRE_DTYPE"))


def _probe_quant_error(x: np.ndarray, kind: str) -> float:
    """Relative RMS reconstruction error of encoding ``x`` as ``kind``."""
    v = np.asarray(x, np.float32)
    if kind == "int8":
        q, scale, offset = _quantize_int8(v, v.shape[0])
        rec = q.astype(np.float32) * scale + offset
    else:  # f16
        rec = v.astype(np.float16).astype(np.float32)
    rms = float(np.sqrt(np.mean(v * v)))
    return float(np.sqrt(np.mean((rec - v) ** 2))) / max(rms, 1e-12)


def _tune_wire_format(x: np.ndarray, heuristic: str, mesh) -> str:
    """Measured refinement of the ``auto`` wire pick (TPUML_AUTOTUNE).

    Candidates are the encodings AT LEAST as accurate as the heuristic's
    error-probed choice (the accuracy gate stays with the error probe —
    the tuner only ever trades bytes against encode cost among formats
    the tolerance contract already admits), heuristic first. Fitness is
    the measured encode + device_put + on-device upcast-reduce of the
    first chunk — the per-chunk ingest-path cost the knob controls."""
    ladder = ["int8", "f16", "f32"]  # narrowest (most lossy) first
    feasible = ladder[ladder.index(heuristic):]
    if len(feasible) < 2:
        return heuristic
    candidates = [heuristic] + [w for w in feasible if w != heuristic]
    key = autotune.shape_key(
        n=x.shape[0],
        d=x.shape[1] if x.ndim > 1 else 0,
        dtype=x.dtype,
        mesh=mesh,
        storage=str(x.dtype),
    )

    def measure(w: str) -> float:
        t0 = time.perf_counter()
        if w == "int8":
            q, scale, offset = _quantize_int8(x, x.shape[0])
            buf: np.ndarray = q
        elif w == "f16":
            buf = x.astype(np.float16)
        else:
            buf = np.ascontiguousarray(x, np.float32)
        dev = jax.device_put(buf, row_sharding(mesh))
        jnp.sum(jnp.asarray(dev, jnp.float32)).block_until_ready()
        return time.perf_counter() - t0

    tuned = autotune.tune("wire_dtype", key, candidates, measure, reps=2)
    return tuned if tuned in feasible else heuristic


def select_wire_format(
    sample_X: np.ndarray, requested: Optional[str] = None, mesh=None
) -> str:
    """Resolve the wire encoding for one streaming pass (never ``auto``).

    ``requested`` overrides the env (None = read ``TPUML_WIRE_DTYPE``).
    Same dispatch contract as ``TPUML_UMAP_OPT``: ``auto`` gates on a
    probe — the first chunk's quantization error under int8 (then f16)
    against the documented tolerances — and an explicit request that is
    infeasible on this host/backend WARNS and falls back instead of
    failing the fit. Non-float storage always ships as-is (``f32``).

    With ``TPUML_AUTOTUNE`` on and a ``mesh``, the ``auto`` pick is
    further refined by measurement (:func:`_tune_wire_format`) among
    the formats the error tolerances admit; explicit requests
    (including the ``f32`` default) are never second-guessed.
    """
    kind = resolve_wire_dtype() if requested is None else str(requested)
    x = np.asarray(sample_X)
    if x.dtype.kind != "f":
        return "f32"
    if kind == "auto":
        err8 = _probe_quant_error(x, "int8")
        if err8 <= _AUTO_INT8_TOL:
            kind = "int8"
        elif _probe_quant_error(x, "f16") <= _AUTO_F16_TOL:
            kind = "f16"
        else:
            kind = "f32"
        _wire_logger.info(
            "TPUML_WIRE_DTYPE=auto: int8 probe error %.2e -> wire %s",
            err8, kind,
        )
        if autotune.active() and mesh is not None:
            kind = _tune_wire_format(x, kind, mesh)
    if kind == "f8" and not _f8_supported():
        _wire_logger.warning(
            "TPUML_WIRE_DTYPE=f8 requested but float8_e4m3 is unavailable "
            "on this toolchain/backend; falling back to f16"
        )
        kind = "f16"
    return kind


def put_chunk(
    chunk: Chunk, mesh, dtype, *, need_y: bool = True, need_w: bool = True,
    wire: str = "f32",
) -> Dict[str, Optional[jax.Array]]:
    """device_put one host chunk row-sharded over dp.  Transfers are async:
    the next chunk's H2D overlaps the current chunk's accumulation step.

    Wire dtype (``wire``, a RESOLVED ``select_wire_format`` value — never
    ``auto``): ``int8`` / ``f8`` quantize per chunk column on host and ship
    the 1-byte buffer plus O(d) scales, returning ``X`` as a
    :class:`QuantizedWire` the fold step dequantizes inside its jit;
    ``f16`` downcasts wide float storage on host and upcasts on device.
    Independent of the knob, a chunk stored in a float NARROWER than the
    compute dtype (e.g. float16 parquet) ships as-is and upcasts ON DEVICE.
    Fewer wire bytes attack the streaming bottleneck on any interconnect
    (PCIe, multi-host ingest); the default ``f32`` keeps the
    historical byte-identical behavior.

    ``need_y`` / ``need_w``: callers whose accumulation step does not
    consume the label / weight column MUST pass False — the column is then
    never transferred. This both saves wire bytes and preserves the
    StreamGuard invariant that the accumulator fetch proves every enqueued
    transfer completed: an array the step never reads would otherwise sit
    in the guard's pending list with nothing proving its transfer retired
    before ``delete()``."""
    fault_site("ingest:chunk")
    sh = row_sharding(mesh)
    x_host = np.asarray(chunk.X)
    wire_bufs = None
    if wire in ("int8", "f8") and x_host.dtype.kind == "f":
        # every array below is a buffer the client ACTUALLY transferred
        # (and retains a host copy of); they ride along under "_wire" so
        # StreamGuard deletes THEM, not just arrays derived on device
        from ..parallel.mesh import replicated

        rep = replicated(mesh)
        if wire == "int8":
            q, scale, offset = _quantize_int8(x_host, chunk.n_valid)
        else:
            q, scale = _quantize_f8(x_host, chunk.n_valid)
            offset = None
        qd = jax.device_put(q, sh)
        sd = jax.device_put(scale, rep)
        od = None if offset is None else jax.device_put(offset, rep)
        X: Any = QuantizedWire(qd, sd, od, jnp.dtype(dtype))
        wire_bufs = [a for a in (qd, sd, od) if a is not None]
    elif x_host.dtype.kind == "f" and x_host.dtype.itemsize < np.dtype(dtype).itemsize:
        # narrow float STORAGE pass-through (also where wire="f16" lands
        # once the host buffer is already f16)
        narrow = jax.device_put(x_host, sh)
        X = jnp.asarray(narrow, dtype=dtype)
        wire_bufs = narrow
    elif wire == "f16" and x_host.dtype.kind == "f" and x_host.dtype.itemsize > 2:
        narrow = jax.device_put(x_host.astype(np.float16), sh)
        X = jnp.asarray(narrow, dtype=dtype)
        wire_bufs = narrow
    else:
        X = jax.device_put(np.asarray(x_host, dtype=dtype), sh)
    out: Dict[str, Optional[jax.Array]] = {
        "X": X,
        "mask": jax.device_put(chunk.mask(dtype), sh),
        "y": None,
        "w": None,
        "_wire": wire_bufs,
    }
    if need_y and chunk.y is not None:
        out["y"] = jax.device_put(np.asarray(chunk.y, dtype=dtype), sh)
    if need_w and chunk.w is not None:
        out["w"] = jax.device_put(np.asarray(chunk.w, dtype=dtype), sh)
    return out


def _split_chunk(chunk: Chunk, row_mult: int) -> Optional[Tuple[Chunk, Chunk]]:
    """Split a chunk into two row-slabs, each a multiple of ``row_mult``.

    ``row_mult`` is the dp mesh size — the sharding divisibility every
    ``put_chunk`` row dimension must satisfy. Returns None when the chunk
    is already at the minimum splittable size.
    """
    rows = chunk.X.shape[0]
    if rows < 2 * row_mult or rows % row_mult != 0:
        return None
    half = (rows // 2 // row_mult) * row_mult
    half = max(half, row_mult)

    def slab(lo: int, hi: int) -> Chunk:
        return Chunk(
            X=chunk.X[lo:hi],
            n_valid=int(np.clip(chunk.n_valid - lo, 0, hi - lo)),
            y=None if chunk.y is None else chunk.y[lo:hi],
            w=None if chunk.w is None else chunk.w[lo:hi],
        )

    return slab(0, half), slab(half, rows)


def stage_chunks(
    chunk: Chunk, mesh, dtype, *, need_y: bool = True, need_w: bool = True,
    wire: str = "f32",
):
    """Stage ``chunk`` on device, degrading gracefully under failure.

    Yields ``(piece, dev)`` pairs — normally exactly one, the whole chunk.
    With a retry budget (``TPUML_RETRIES`` > 0):

    - a RESOURCE_EXHAUSTED staging failure halves the chunk (at a dp-size
      row multiple, preserving sharding divisibility) and stages the
      halves independently, recursively down to one row-slab per dp rank —
      an allocator-pressure spike degrades throughput instead of killing
      the fit;
    - any other staging failure is retried on the env backoff schedule;
    - :class:`SimulatedPreemption` is terminal, never absorbed.

    With the default env (no budget) this is one ``put_chunk`` call — the
    clean path stays byte-identical. The accumulation steps downstream are
    per-chunk sum-folds, so a split chunk folds to the same result as the
    whole one (halves carry correctly sliced ``n_valid``/labels/weights).
    """
    budget = resolve_retries()
    if budget <= 0:
        with telemetry.span("stream.stage", rows=chunk.X.shape[0]):
            dev = put_chunk(
                chunk, mesh, dtype, need_y=need_y, need_w=need_w, wire=wire
            )
        yield chunk, dev
        return
    import time as _time

    delays = backoff_schedule(budget, resolve_backoff_ms())
    row_mult = max(1, int(mesh.shape.get("dp", 1)))
    attempts = 0
    pending = [chunk]
    while pending:
        piece = pending[0]
        try:
            with telemetry.span("stream.stage", rows=piece.X.shape[0]):
                dev = put_chunk(
                    piece, mesh, dtype, need_y=need_y, need_w=need_w,
                    wire=wire,
                )
        except SimulatedPreemption:
            raise
        except Exception as exc:
            if is_resource_exhausted(exc):
                halves = _split_chunk(piece, row_mult)
                if halves is not None:
                    counters.bump("chunk_halvings")
                    _res_logger.warning(
                        "chunk staging hit RESOURCE_EXHAUSTED (%s); halving "
                        "%d rows -> 2 x %d-row slabs",
                        exc,
                        piece.X.shape[0],
                        halves[0].X.shape[0],
                    )
                    pending[0:1] = list(halves)
                    continue
            if attempts >= budget:
                raise
            counters.bump("retries")
            _res_logger.warning(
                "chunk staging failed (attempt %d/%d): %s — retrying in %.0f ms",
                attempts + 1,
                budget + 1,
                exc,
                delays[attempts],
            )
            _time.sleep(delays[attempts] / 1000.0)
            attempts += 1
            continue
        pending.pop(0)
        yield piece, dev


# provenance of the most recent ingest pipeline in this process (resolved
# wire dtype + ring depths); the estimator layer copies it onto fitted
# models as ``model._ingest_report``
_LAST_INGEST: Dict[str, Any] = {}


def last_ingest_report() -> Dict[str, Any]:
    """Copy of the most recent :func:`iter_device_chunks` configuration."""
    return dict(_LAST_INGEST)


def _staged_chunks(chunks, mesh, dtype, *, need_y, need_w, wire, depth):
    """Device-staging ring stage of the ingest pipeline.

    A background thread pulls decoded chunks, wire-encodes them
    (quantization for int8/f8 is real host CPU work) and issues the async
    ``device_put``, keeping up to ``depth`` staged chunks buffered ahead
    of the consumer. The consumer's fold dispatch — and crucially the
    StreamGuard's periodic BLOCKING syncs — no longer serialize against
    encode+transfer of the next chunks.

    Single producer + FIFO queue: yields ``(chunk, dev)`` strictly in
    source order at any depth. Cancel/error discipline is identical to
    :func:`prefetch_chunks` (same close-promptly caveat).
    """
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    end = object()
    cancel = threading.Event()
    err: list = []

    def worker():
        try:
            for chunk in chunks:
                # span covers wire-encode + async device_put of ONE
                # chunk, not the backpressured put
                with telemetry.span("stream.stage", rows=chunk.X.shape[0]):
                    dev = put_chunk(
                        chunk, mesh, dtype,
                        need_y=need_y, need_w=need_w, wire=wire,
                    )
                while not cancel.is_set():
                    try:
                        q.put((chunk, dev), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if cancel.is_set():
                    return
                # ops-plane liveness: occupancy right after this put
                # plus a heartbeat, so /statusz distinguishes a wedged
                # stage thread from a fold-bound one
                telemetry.gauge("ingest_ring_occupancy").set(q.qsize())
                telemetry.gauge("loop_heartbeat_ts").set(
                    time.monotonic(), loop="stream_stage"
                )
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            err.append(e)
        finally:
            while not cancel.is_set():
                try:
                    q.put(end, timeout=0.1)
                    break
                except queue.Full:
                    continue

    th = threading.Thread(
        # bound context: the ring thread's stage spans nest under the
        # consumer's ingest span
        target=telemetry.bind_context(worker),
        name="tpuml-chunk-stage",
        daemon=True,
    )
    th.start()
    try:
        while True:
            if err:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    raise err[0].with_traceback(err[0].__traceback__) from None
            else:
                item = q.get()
            if item is end:
                break
            yield item
        if err:
            raise err[0].with_traceback(err[0].__traceback__)
    finally:
        cancel.set()


def iter_device_chunks(
    source: ChunkSource,
    mesh,
    chunk_rows: int,
    dtype,
    *,
    need_y: bool = True,
    need_w: bool = True,
    wire: Optional[str] = None,
):
    """The shared multi-stage ingest pipeline of every streaming loop.

    Yields ``(piece, dev)`` pairs in source order. Stages, each a bounded
    ring so host memory stays O(depth) chunk buffers:

    1. **decode** — :func:`prefetch_chunks` runs ``source.iter_chunks``
       (parquet decode / synthetic gen) on a background thread,
       ``TPUML_STREAM_PREFETCH`` deep;
    2. **stage** — :func:`_staged_chunks` wire-encodes and issues the
       async ``device_put`` up to ``TPUML_STREAM_STAGE_DEPTH`` chunks
       ahead, so decode, host->device transfer, and the fold step
       genuinely overlap instead of serializing;
    3. **fold** — the caller accumulates and ``guard.tick``s as before.

    The wire encoding is resolved ONCE from the first chunk
    (:func:`select_wire_format`: env request, ``auto`` probe, fallback)
    and pinned for the whole pass, so every chunk shares one encoding and
    one fold-step trace. Ordering — and therefore every accumulator
    result — is independent of both depths (single producer per stage,
    FIFO rings); ``tests/test_streaming_wire.py`` pins that.

    With a retry budget (``TPUML_RETRIES`` > 0) staging happens on the
    consumer thread where :func:`stage_chunks` can halve/retry
    synchronously — the ring is bypassed (resilience wins over overlap).
    """
    import contextlib
    import itertools

    np_dtype = np.dtype(jnp.dtype(dtype).name)
    # a streamed fit is the long-lived loop the ops plane wants to
    # watch; no-op unless TPUML_OPS_PORT/TPUML_FLIGHT_DIR opted in
    opsplane.ensure_started()
    it = prefetch_chunks(source.iter_chunks(chunk_rows, np_dtype))
    # manual enter/exit: a `with` around a generator body would not
    # survive the consumer abandoning the iterator mid-pass
    ingest_span = telemetry.span("stream.ingest")
    ingest_span.__enter__()
    try:
        first = next(it, None)
        if first is None:
            return
        kind = select_wire_format(first.X, requested=wire, mesh=mesh)
        depth = int(envspec.get("TPUML_STREAM_STAGE_DEPTH"))
        if not envspec.is_set("TPUML_STREAM_STAGE_DEPTH") and autotune.active():
            # consult-only: a ring depth cannot be measured from inside
            # one pipeline pass, so there is no in-situ search; nothing
            # in the tree writes this entry (ROADMAP.md D14)
            depth_key = autotune.shape_key(
                n=first.X.shape[0],
                d=first.X.shape[1] if first.X.ndim > 1 else 0,
                dtype=np_dtype,
                mesh=mesh,
            )
            tuned_depth = autotune.consult("stream_stage_depth", depth_key)
            if isinstance(tuned_depth, int) and 0 <= tuned_depth <= 64:
                depth = tuned_depth
            else:
                autotune.record_heuristic("stream_stage_depth", depth_key, depth)
        _LAST_INGEST.clear()
        _LAST_INGEST.update(
            wire_dtype=kind,
            stage_depth=depth,
            prefetch_depth=int(envspec.get("TPUML_STREAM_PREFETCH")),
        )
        ingest_span.set_attr(wire=kind, stage_depth=depth)
        # staged slabs resident ahead of the fold: the streaming analog
        # of the gang/tree-batch budget gauges
        telemetry.record_hbm_estimate(
            "stream_stage", float(first.X.nbytes) * float(max(1, depth))
        )
        chunks = itertools.chain([first], it)
        if depth > 0 and resolve_retries() <= 0:
            staged = _staged_chunks(
                chunks, mesh, dtype,
                need_y=need_y, need_w=need_w, wire=kind, depth=depth,
            )
        else:
            staged = (
                pair
                for chunk in chunks
                for pair in stage_chunks(
                    chunk, mesh, dtype,
                    need_y=need_y, need_w=need_w, wire=kind,
                )
            )
        with contextlib.closing(staged) as staged_it:
            for i, (piece, dev) in enumerate(staged_it):
                telemetry.gauge("loop_heartbeat_ts").set(
                    time.monotonic(), loop="stream_ingest"
                )
                # the fold span brackets the yield: it measures the
                # CONSUMER's accumulate/dispatch work on this chunk
                fold_span = telemetry.span("stream.fold", chunk=i)
                fold_span.__enter__()
                try:
                    yield piece, dev
                finally:
                    fold_span.__exit__(None, None, None)
    finally:
        it.close()
        ingest_span.__exit__(None, None, None)


# ---------------------------------------------------------------------------
# Pass 1: weighted first moments
# ---------------------------------------------------------------------------


def moments1_init(d: int, dtype, with_y: bool) -> Dict[str, jax.Array]:
    acc = {
        "n": jnp.zeros((), dtype),
        "sum_x": jnp.zeros((d,), dtype),
    }
    if with_y:
        acc["sum_y"] = jnp.zeros((), dtype)
    return acc


@functools.partial(jax.jit, donate_argnums=(0,))
def moments1_step(
    acc: Dict[str, jax.Array],
    X: jax.Array,
    rw: jax.Array,
    y: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """Fold one chunk into (Σw, Σw·x [, Σw·y]).  ``rw`` = mask·weight."""
    X = wire_dense(X)
    out = dict(acc)
    out["n"] = acc["n"] + rw.sum()
    out["sum_x"] = acc["sum_x"] + (X * rw[:, None]).sum(axis=0)
    if y is not None:
        out["sum_y"] = acc["sum_y"] + (y * rw).sum()
    return out


# ---------------------------------------------------------------------------
# Pass 2: centered second moments (Gram / cross / residual)
# ---------------------------------------------------------------------------


def gram2_init(d: int, dtype, with_y: bool, mesh=None) -> Dict[str, jax.Array]:
    """Zero second-moment accumulators. With ``mesh`` (a 2-D mesh whose mp
    extent divides ``d`` — gate via ``ops.linalg.mp_gram_blocks``) the d×d
    Gram is created column-sharded over mp (``LAYOUT.cols()``) from host
    zeros, so each device ever allocates only its (d, d/mp) block; the
    blocked step keeps it there across donated folds."""
    if mesh is not None:
        from jax.sharding import NamedSharding

        from ..parallel.layout import LAYOUT

        cols = NamedSharding(mesh, LAYOUT.cols())
        acc = {"G": jax.device_put(np.zeros((d, d), dtype), cols)}
    else:
        acc = {"G": jnp.zeros((d, d), dtype)}
    if with_y:
        acc["Xy"] = jnp.zeros((d,), dtype)
        acc["yy"] = jnp.zeros((), dtype)
    return acc


@functools.partial(jax.jit, donate_argnums=(0,))
def gram2_step(
    acc: Dict[str, jax.Array],
    X: jax.Array,
    rw: jax.Array,
    mean_x: jax.Array,
    y: Optional[jax.Array] = None,
    mean_y: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """Fold one chunk into G=(Xc√w)'(Xc√w) [, Xy, yy] centered at mean."""
    X = wire_dense(X)
    sw = jnp.sqrt(rw)
    Xc = (X - mean_x[None, :]) * sw[:, None]
    out = dict(acc)
    out["G"] = acc["G"] + Xc.T @ Xc
    if y is not None:
        yc = (y - mean_y) * sw
        out["Xy"] = acc["Xy"] + Xc.T @ yc
        out["yy"] = acc["yy"] + (yc * yc).sum()
    return out


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("mesh",)
)
def gram2_step_blocked(
    acc: Dict[str, jax.Array],
    X: jax.Array,
    rw: jax.Array,
    mean_x: jax.Array,
    y: Optional[jax.Array] = None,
    mean_y: Optional[jax.Array] = None,
    *,
    mesh,
) -> Dict[str, jax.Array]:
    """:func:`gram2_step` with the Gram accumulator pinned column-sharded
    over the mesh's mp axis: the sharding constraint makes GSPMD compute
    each device's ``XcᵀXc`` column panel in place (the SUMMA product of the
    blocked resident scan), so the fold never materializes a full d×d per
    device. Init with ``gram2_init(..., mesh=mesh)``."""
    from jax.sharding import NamedSharding

    from ..parallel.layout import LAYOUT

    X = wire_dense(X)
    sw = jnp.sqrt(rw)
    Xc = (X - mean_x[None, :]) * sw[:, None]
    cols = NamedSharding(mesh, LAYOUT.cols())
    out = dict(acc)
    out["G"] = jax.lax.with_sharding_constraint(acc["G"] + Xc.T @ Xc, cols)
    if y is not None:
        yc = (y - mean_y) * sw
        out["Xy"] = acc["Xy"] + Xc.T @ yc
        out["yy"] = acc["yy"] + (yc * yc).sum()
    return out


# ---------------------------------------------------------------------------
# KMeans chunk steps (streamed Lloyd / seeding)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("matmul_dtype",))
def kmeans_chunk_step(
    acc: Dict[str, jax.Array],
    X: jax.Array,
    mask: jax.Array,
    centers: jax.Array,
    matmul_dtype=None,
) -> Dict[str, jax.Array]:
    """Fold one chunk's assignment statistics into (sums, counts, cost).

    ``matmul_dtype``: see ``kmeans_kernels.pairwise_sq_dists`` — the
    resident kernel's bf16-operand option, same semantics here."""
    from .kmeans_kernels import pairwise_sq_dists, stats_dot

    X = wire_dense(X)
    k = centers.shape[0]
    d2 = pairwise_sq_dists(X, centers, matmul_dtype=matmul_dtype)
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=X.dtype) * mask[:, None]
    return {
        "sums": acc["sums"] + stats_dot(onehot, X, matmul_dtype),
        "counts": acc["counts"] + onehot.sum(axis=0).astype(jnp.int32),
        "cost": acc["cost"] + (jnp.min(d2, axis=1) * mask).sum(),
    }


@jax.jit
def chunk_min_sq_dists(
    X: jax.Array, mask: jax.Array, centers: jax.Array
) -> jax.Array:
    """Per-row min squared distance to any center (padding rows -> 0)."""
    from .kmeans_kernels import pairwise_sq_dists

    return jnp.min(pairwise_sq_dists(wire_dense(X), centers), axis=1) * mask


@functools.partial(jax.jit, donate_argnums=(0,))
def count_closest_chunk_step(
    counts: jax.Array, X: jax.Array, mask: jax.Array, cands: jax.Array
) -> jax.Array:
    """Fold one chunk into per-candidate closest-row counts (k-means||
    candidate weighting).  ``counts`` is int32: a float32 accumulator would
    silently drop small per-chunk increments past ~2²⁴ rows — the exact
    regime the out-of-core path exists for."""
    from .kmeans_kernels import pairwise_sq_dists

    X = wire_dense(X)
    d2 = pairwise_sq_dists(X, cands)
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, cands.shape[0], dtype=X.dtype) * mask[:, None]
    return counts + onehot.sum(axis=0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Logistic-regression chunk steps (streamed L-BFGS objective)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def var_chunk_step(
    acc: jax.Array, X: jax.Array, rw: jax.Array, mean: jax.Array
) -> jax.Array:
    """Fold one chunk into Σ w·(x-mean)² (diagonal-only second moment —
    cheaper than the full Gram when only feature variances are needed)."""
    X = wire_dense(X)
    d = (X - mean[None, :]) * jnp.sqrt(rw)[:, None]
    return acc + (d * d).sum(axis=0)


@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("n_classes", "multinomial", "fit_intercept", "use_center"),
)
def logreg_chunk_vg_step(
    acc: Dict[str, jax.Array],
    X: jax.Array,
    mask: jax.Array,
    y: jax.Array,
    wflat: jax.Array,
    mean: jax.Array,
    inv_std: jax.Array,
    *,
    n_classes: int,
    multinomial: bool,
    fit_intercept: bool,
    use_center: bool,
) -> Dict[str, jax.Array]:
    """Fold one chunk's data log-loss and its gradient w.r.t. the flat
    parameter vector into the accumulator.

    Same objective as the resident kernel (``ops/logreg_kernels.py``):
    standardization is a reparametrization folded into the logits, not a
    data copy. The regularization terms are added once on the host, not
    per chunk.
    """
    X = wire_dense(X)
    dtype = X.dtype
    d = X.shape[1]
    K = n_classes if multinomial else 1
    n_coef = K * d
    yi = y.astype(jnp.int32)
    yf = y.astype(dtype)

    def chunk_loss(wf: jax.Array) -> jax.Array:
        A = wf[:n_coef].reshape(K, d)
        b = wf[n_coef:] if fit_intercept else jnp.zeros((K,), dtype)
        Aeff = A * inv_std[None, :]
        beff = b - (Aeff @ mean if use_center else jnp.zeros((), dtype))
        logits = X @ Aeff.T + beff[None, :]
        if multinomial:
            ll = jax.nn.logsumexp(logits, axis=1) - jnp.take_along_axis(
                logits, yi[:, None], axis=1
            )[:, 0]
        else:
            z = logits[:, 0]
            ll = jax.nn.softplus(z) - yf * z
        return (ll * mask).sum()

    f, g = jax.value_and_grad(chunk_loss)(wflat)
    return {"f": acc["f"] + f, "g": acc["g"] + g}


def streamed_suffstats(
    source: ChunkSource,
    mesh,
    chunk_rows: int,
    dtype,
    *,
    with_y: bool = False,
    fit_intercept: bool = True,
) -> Dict[str, jax.Array]:
    """Two streaming passes -> the same stats dict as
    ``ops.linreg_kernels.linreg_suffstats`` (n, mean_x, mean_y, G, Xy, yy,
    var) / the inputs of ``mean_and_cov`` — so every downstream solver
    (Cholesky OLS/ridge, FISTA elasticnet, eigh PCA) is reused unchanged.
    """
    from ..parallel.mesh import allreduce_sum_host

    d = source.n_features

    acc1 = moments1_init(d, dtype, with_y)
    guard = StreamGuard()
    # closing() so an exception in the loop body tears down the pipeline
    # threads promptly instead of at GC time (caveat on prefetch_chunks).
    with telemetry.span("suffstats.pass", which="moments"):
        with contextlib.closing(
            iter_device_chunks(source, mesh, chunk_rows, dtype, need_y=with_y)
        ) as chunks:
            for _, dev in chunks:
                rw = dev["mask"] if dev["w"] is None else dev["mask"] * dev["w"]
                acc1 = moments1_step(
                    acc1, dev["X"], rw, dev["y"] if with_y else None
                )
                guard.tick(dev, acc1)
        guard.flush(acc1)
    # cross-process allreduce of the first-moment partials (the NCCL
    # allreduce analog; identity single-process)
    if with_y:
        n_h, sx_h, sy_h = allreduce_sum_host(acc1["n"], acc1["sum_x"], acc1["sum_y"])
    else:
        n_h, sx_h = allreduce_sum_host(acc1["n"], acc1["sum_x"])
        sy_h = None
    n = jnp.asarray(n_h, dtype)
    mean_all = jnp.asarray(sx_h, dtype) / n
    if fit_intercept:
        mean_x = mean_all
        mean_y = (jnp.asarray(sy_h, dtype) / n) if with_y else None
    else:
        mean_x = jnp.zeros((d,), dtype)
        mean_y = jnp.zeros((), dtype) if with_y else None

    # blocked (mp-column-sharded) Gram accumulation when the mesh has a
    # model axis and the gate allows it — env resolved here, outside jit
    from .linalg import mp_gram_blocks

    mp = mp_gram_blocks(mesh, d)
    acc2 = gram2_init(d, dtype, with_y, mesh=mesh if mp > 1 else None)
    step = (
        functools.partial(gram2_step_blocked, mesh=mesh)
        if mp > 1
        else gram2_step
    )
    guard = StreamGuard()
    with telemetry.span("suffstats.pass", which="gram"):
        with contextlib.closing(
            iter_device_chunks(source, mesh, chunk_rows, dtype, need_y=with_y)
        ) as chunks:
            for _, dev in chunks:
                rw = dev["mask"] if dev["w"] is None else dev["mask"] * dev["w"]
                acc2 = step(
                    acc2, dev["X"], rw, mean_x,
                    dev["y"] if with_y else None, mean_y,
                )
                guard.tick(dev, acc2)
        guard.flush(acc2)
    mp_report = None
    if mp > 1:
        mp_report = {
            "mp_degree": mp,
            "gram_shard_bytes": int(
                acc2["G"].addressable_shards[0].data.nbytes
            ),
        }
    if with_y:
        G_h, Xy_h, yy_h = allreduce_sum_host(acc2["G"], acc2["Xy"], acc2["yy"])
    else:
        (G_h,) = allreduce_sum_host(acc2["G"])
        Xy_h = yy_h = None
    G = jnp.asarray(G_h, dtype)

    var = jnp.diagonal(G) / n
    if not fit_intercept:
        var = var - mean_all * mean_all
    stats: Dict[str, jax.Array] = {
        "n": n,
        "mean_x": mean_x,
        "mean_all": mean_all,
        "G": G,
        "var": var,
    }
    if with_y:
        stats["mean_y"] = mean_y
        stats["Xy"] = jnp.asarray(Xy_h, dtype)
        stats["yy"] = jnp.asarray(yy_h, dtype)
    if mp_report:
        stats["_mp_report"] = mp_report
    return stats


def streamed_logreg_fit(
    source: ChunkSource,
    mesh,
    chunk_rows: int,
    dtype,
    *,
    n_classes: int,
    multinomial: bool,
    fit_intercept: bool,
    standardization: bool,
    l1: float,
    l2: float,
    max_iter: int,
    tol: float,
    history: int = 10,
    checkpointer=None,
) -> Dict[str, np.ndarray]:
    """Out-of-core logistic regression: host-driven L-BFGS/OWL-QN where each
    objective evaluation streams the dataset through the device in chunks.

    Numerically mirrors the resident kernel (``ops/logreg_kernels.py``):
    same standardization-as-reparametrization, Spark objective
    (1/n)·Σ logloss + λ[(1−α)/2‖β‖₂² + α‖β‖₁] with the penalty on
    standardized coefficients and never on intercepts, same multinomial
    intercept centering. The O(m·p) quasi-Newton math runs on host in f64;
    every line-search trial is one chunked data pass (exactly the
    re-read-per-iteration cost cuML's out-of-core QN pays, reference
    ``classification.py:955-1140``).
    """
    from ..parallel.mesh import allreduce_sum_host

    from .lbfgs import minimize_lbfgs_host

    d = source.n_features
    np_dtype = np.dtype(jnp.dtype(dtype).name)

    # pass 1: n + feature means (partials allreduced across processes)
    acc1 = moments1_init(d, dtype, with_y=False)
    guard = StreamGuard()
    with contextlib.closing(
        iter_device_chunks(
            source, mesh, chunk_rows, dtype, need_y=False, need_w=False
        )
    ) as chunks:
        for _, dev in chunks:
            acc1 = moments1_step(acc1, dev["X"], dev["mask"])
            guard.tick(dev, acc1)
    guard.flush(acc1)
    n_h, sx_h = allreduce_sum_host(acc1["n"], acc1["sum_x"])
    n = float(n_h)
    mean = jnp.asarray(sx_h, dtype) / jnp.asarray(n, dtype)

    if standardization:
        # pass 2: diagonal second moment -> unbiased variance (n-1), the
        # reference's denominator (``classification.py:1024-1026``)
        vacc = jnp.zeros((d,), dtype)
        guard = StreamGuard()
        with contextlib.closing(
            iter_device_chunks(
                source, mesh, chunk_rows, dtype, need_y=False, need_w=False
            )
        ) as chunks:
            for _, dev in chunks:
                vacc = var_chunk_step(vacc, dev["X"], dev["mask"], mean)
                guard.tick(dev, vacc)
        guard.flush(vacc)
        (vacc_h,) = allreduce_sum_host(vacc)
        var = jnp.asarray(vacc_h, dtype) / max(n - 1.0, 1.0)
        std = jnp.sqrt(jnp.maximum(var, 0.0))
        inv_std = jnp.where(std > 0, 1.0 / std, 1.0)
    else:
        inv_std = jnp.ones((d,), dtype)
    use_center = standardization and fit_intercept
    mean_dev = mean if use_center else jnp.zeros((d,), dtype)

    K = n_classes if multinomial else 1
    n_coef = K * d
    p = n_coef + (K if fit_intercept else 0)
    coef_mask = np.concatenate([np.ones(n_coef), np.zeros(p - n_coef)])

    def value_grad(w_np):
        wd = jnp.asarray(w_np, dtype)
        acc = {"f": jnp.zeros((), dtype), "g": jnp.zeros((p,), dtype)}
        guard = StreamGuard()
        with telemetry.span("logreg.objective_pass"):
            with contextlib.closing(
                iter_device_chunks(
                    source, mesh, chunk_rows, dtype, need_w=False
                )
            ) as chunks:
                for _, dev in chunks:
                    acc = logreg_chunk_vg_step(
                        acc, dev["X"], dev["mask"], dev["y"], wd, mean_dev,
                        inv_std,
                        n_classes=n_classes, multinomial=multinomial,
                        fit_intercept=fit_intercept, use_center=use_center,
                    )
                    guard.tick(dev, acc)
            guard.flush(acc)
        # per-evaluation allreduce of (loss, grad) partials — the QN-loop
        # NCCL allreduce of the reference's distributed L-BFGS; every rank
        # then takes identical optimizer steps
        f_h, g_h = allreduce_sum_host(acc["f"], acc["g"])
        coefs = w_np * coef_mask
        f = float(f_h) / n + 0.5 * l2 * float(coefs @ coefs)
        g = np.asarray(g_h, np.float64) / n + l2 * coefs
        return f, g

    res = minimize_lbfgs_host(
        value_grad,
        np.zeros((p,)),
        max_iter=max_iter,
        tol=tol,
        l1_weights=(l1 * coef_mask) if l1 > 0.0 else None,
        history=history,
        checkpointer=checkpointer,
    )

    w = np.asarray(res.w)
    A = w[:n_coef].reshape(K, d)
    b = w[n_coef:] if fit_intercept else np.zeros((K,))
    inv_std_h = np.asarray(inv_std, np.float64)
    mean_h = np.asarray(mean, np.float64)
    coef = A * inv_std_h[None, :]
    intercept = b - (coef @ mean_h if use_center else 0.0)
    if fit_intercept and K > 1:
        intercept = intercept - intercept.mean()
    return {
        "coef_": coef.astype(np_dtype),
        "intercept_": np.asarray(intercept, np_dtype),
        "n_iter": int(res.n_iter),
        "objective": float(res.f),
    }


def streamed_kmeans_lloyd(
    source: ChunkSource,
    mesh,
    chunk_rows: int,
    dtype,
    centers0: np.ndarray,
    *,
    max_iter: int,
    tol: float,
    matmul_dtype=None,
    checkpointer=None,
):
    """Out-of-core Lloyd: one chunked pass per iteration accumulates
    (sums, counts, cost); centroid state stays tiny (k×d). Matches the
    resident ``kmeans_kernels.kmeans_lloyd`` semantics: empty clusters keep
    their previous center (Spark behavior), convergence on max center
    shift² <= tol², plus a final cost pass at the converged centers.
    Returns (centers, cost, n_iter) as host values.

    ``checkpointer`` (a ``runtime.FitCheckpointer``, or None) snapshots
    centers + the last center shift after each Lloyd iteration; resume
    walks the identical centroid sequence (Lloyd is deterministic given
    the centers), including the same termination iteration.
    """
    from ..parallel.mesh import allreduce_sum_host

    k, d = centers0.shape
    centers = jnp.asarray(centers0, dtype)

    def one_pass(cts, mm=matmul_dtype, _it=None):
        acc = {
            "sums": jnp.zeros((k, d), dtype),
            "counts": jnp.zeros((k,), jnp.int32),
            "cost": jnp.zeros((), dtype),
        }
        guard = StreamGuard()
        with telemetry.span("kmeans.lloyd_pass", iteration=_it):
            with contextlib.closing(
                iter_device_chunks(
                    source, mesh, chunk_rows, dtype, need_y=False, need_w=False
                )
            ) as chunks:
                for _, dev in chunks:
                    acc = kmeans_chunk_step(
                        acc, dev["X"], dev["mask"], cts, matmul_dtype=mm
                    )
                    guard.tick(dev, acc)
            guard.flush(acc)
        # per-iteration allreduce of (sums, counts, cost) partials — the
        # Lloyd-loop NCCL allreduce; every rank then updates identically
        s_h, c_h, cost_h = allreduce_sum_host(
            acc["sums"], acc["counts"], acc["cost"]
        )
        return {"sums": s_h, "counts": c_h, "cost": cost_h}

    it = 0
    prev_shift = np.inf
    resumed = checkpointer.load() if checkpointer is not None else None
    if resumed is not None:
        it, arrays, extra = resumed
        centers = jnp.asarray(arrays["centers"], dtype)
        prev_shift = float(extra["prev_shift"])
        counters.bump("resumed_fits")
        counters.note("resumed_from", it)
    while it < max_iter and prev_shift > tol * tol:
        fault_site("sgd:epoch")
        acc = one_pass(centers, _it=it)
        sums = np.asarray(acc["sums"], np.float64)
        counts = np.asarray(acc["counts"])
        safe = np.maximum(counts.astype(np.float64), 1.0)
        new_centers = np.where(
            counts[:, None] > 0, sums / safe[:, None], np.asarray(centers, np.float64)
        )
        prev_shift = float(
            ((new_centers - np.asarray(centers, np.float64)) ** 2).sum(axis=1).max()
        )
        centers = jnp.asarray(new_centers, dtype)
        it += 1
        if checkpointer is not None:
            checkpointer.maybe_save(
                it, {"centers": np.asarray(centers)}, {"prev_shift": prev_shift}
            )
            preempt_point(
                checkpointer, it,
                lambda: {"centers": np.asarray(centers)},
                {"prev_shift": prev_shift},
            )

    # final cost pass always f32 (bf16 distance expansion cancels near
    # centroids — see kmeans_kernels.kmeans_lloyd)
    final = one_pass(centers, mm=None, _it="final")
    if checkpointer is not None:
        checkpointer.clear()
    return np.asarray(centers), float(final["cost"]), it


def streamed_label_stats(
    source: ChunkSource, chunk_rows: int
) -> Dict[str, float]:
    """One host pass over the label stream: max/min, integer check, and
    whether all labels are identical — everything the fit needs to pick
    ``n_classes`` (Spark: max(label)+1) without materializing the dataset.
    Combined across the process world so every rank agrees."""
    from ..parallel.mesh import combine_label_summaries

    y_max = -np.inf
    y_min = np.inf
    all_int = True
    first = None
    all_same = True
    n_seen = 0
    for yv in source.iter_labels(chunk_rows):
        if yv.size == 0:
            continue
        n_seen += yv.size
        y_max = max(y_max, float(yv.max()))
        y_min = min(y_min, float(yv.min()))
        if not np.all(yv == np.floor(yv)):
            all_int = False
        if first is None:
            first = float(yv[0])
        if not np.all(yv == first):
            all_same = False

    local = np.asarray(
        [
            0.0 if n_seen else 1.0,
            y_max,
            y_min,
            1.0 if all_int else 0.0,
            first if first is not None else 0.0,
            1.0 if all_same else 0.0,
            float(n_seen),
        ]
    )
    out = combine_label_summaries(local)
    if out["total"] == 0:
        raise ValueError("Labels column is empty")
    return out


# ---------------------------------------------------------------------------
# Streamed k-means|| seeding passes
# ---------------------------------------------------------------------------


def streamed_rows_at(
    source: ChunkSource, chunk_rows: int, idx: np.ndarray, dtype
) -> np.ndarray:
    """Gather rows by global index in ONE sequential pass (host-side).

    The out-of-core replacement for fancy-indexing the resident matrix:
    chunks arrive in order, so each requested (sorted) index is sliced out
    of the chunk that covers it.
    """
    idx = np.sort(np.asarray(idx, np.int64))
    out = np.empty((len(idx), source.n_features), dtype=dtype)
    pos = 0  # next unsatisfied request
    offset = 0
    for chunk in source.iter_chunks(chunk_rows, dtype):
        hi = offset + chunk.n_valid
        while pos < len(idx) and idx[pos] < hi:
            out[pos] = chunk.X[idx[pos] - offset]
            pos += 1
        offset = hi
        if pos == len(idx):
            break
    if pos != len(idx):
        raise IndexError(f"row index {idx[pos]} out of range ({offset} rows)")
    return out


def streamed_min_sq_dists_update(
    source: ChunkSource,
    mesh,
    chunk_rows: int,
    dtype,
    cands: np.ndarray,
    min_d2: Optional[np.ndarray] = None,
) -> np.ndarray:
    """One chunked pass: per-row min squared distance to ``cands``, folded
    into a host ``min_d2`` array (O(n) host floats — 4 bytes/row, the only
    per-row state k-means|| needs; the dataset itself never materializes).
    """
    cands_dev = jnp.asarray(cands, dtype)
    out = (
        np.full((source.n_rows,), np.inf, np.float64)
        if min_d2 is None
        else min_d2
    )
    offset = 0
    with contextlib.closing(
        iter_device_chunks(
            source, mesh, chunk_rows, dtype, need_y=False, need_w=False
        )
    ) as chunks:
        for piece, dev in chunks:
            d2 = np.asarray(
                chunk_min_sq_dists(dev["X"], dev["mask"], cands_dev),
                np.float64,
            )
            # the d2 fetch above proves the step completed; release the
            # chunk's buffers including the raw wire transfer (StreamGuard
            # rationale — retention otherwise grows with total bytes
            # shipped)
            _release_buffers(dev.values())
            nv = piece.n_valid
            np.minimum(
                out[offset : offset + nv],
                d2[:nv],
                out=out[offset : offset + nv],
            )
            offset += nv
    return out


def streamed_count_closest(
    source: ChunkSource, mesh, chunk_rows: int, dtype, cands: np.ndarray
) -> np.ndarray:
    """One chunked pass: for each candidate, how many rows are closest to it
    (the k-means|| candidate weights)."""
    cands_dev = jnp.asarray(cands, dtype)
    counts = jnp.zeros((cands.shape[0],), jnp.int32)
    guard = StreamGuard()
    with contextlib.closing(
        iter_device_chunks(
            source, mesh, chunk_rows, dtype, need_y=False, need_w=False
        )
    ) as chunks:
        for _, dev in chunks:
            counts = count_closest_chunk_step(
                counts, dev["X"], dev["mask"], cands_dev
            )
            guard.tick(dev, counts)
    guard.flush(counts)
    return np.asarray(counts, np.float64)
