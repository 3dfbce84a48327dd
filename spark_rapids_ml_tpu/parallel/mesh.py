"""Device mesh management — the TPU-native "cluster".

The reference's worker topology is 1 Spark barrier task = 1 GPU, with
NCCL joining them (``/root/reference/python/src/spark_rapids_ml/common/cuml_context.py:35-147``).
TPU-natively the topology is a ``jax.sharding.Mesh``: data parallelism maps
rows onto the ``dp`` axis; feature/model parallelism (used by wide-feature
Gram computations and multi-model fits) maps onto ``mp``. XLA inserts the
collectives (psum/all_gather) that NCCL provided in the reference.

Axis naming convention used across the framework:
  * ``dp`` — data parallel (rows of the design matrix)
  * ``mp`` — model parallel (features / trees / hyper-param sets)
"""

from __future__ import annotations

import collections
import functools
import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
MP_AXIS = "mp"


def default_device_count() -> int:
    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def _cached_mesh(n_dp: int, n_mp: int) -> Mesh:
    devices = np.asarray(jax.devices()[: n_dp * n_mp]).reshape(n_dp, n_mp)
    return Mesh(devices, (DP_AXIS, MP_AXIS))


def device_bytes_limit(cpu_default: float) -> float:
    """Memory limit of one local device, from ``memory_stats()``.

    The CPU backend reports none: it gets ``cpu_default`` (the virtual test
    mesh). On an accelerator a missing ``bytes_limit`` is an error — every
    budget derived from a made-up limit would be wrong in silence."""
    dev = jax.local_devices()[0]
    limit = float((dev.memory_stats() or {}).get("bytes_limit", 0.0))
    if limit > 0.0:
        return limit
    if dev.platform == "cpu":
        return float(cpu_default)
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no bytes_limit "
        "in memory_stats(); cannot size HBM budgets"
    )


def _default_mp_budget() -> float:
    """Default HBM budget for one device's model-axis shard under
    ``TPUML_MESH_MP=auto``: a quarter of the device memory limit (of a
    nominal 16 GB on the CPU test mesh) — the same convention as the
    gang-fit and tree-batch resolvers."""
    return device_bytes_limit(16 << 30) / 4.0


def resolve_mesh_mp(model_bytes: float = 0.0) -> int:
    """Resolved model-parallel degree for :func:`make_mesh` (1 = the 1-D
    row-sharded mesh, bit-identical to the pre-2-D behavior).

    ``TPUML_MESH_MP``: ``off`` (default) keeps mp=1, an integer pins the
    degree (clamped to the device count with a warning), ``auto`` picks
    the smallest power-of-two degree whose per-device model-axis shard
    (``model_bytes / mp`` — the caller's Gram-block / centroid-table /
    IVF-index estimate) fits the HBM budget (``TPUML_MESH_MP_BUDGET``,
    default a quarter of device memory).
    """
    from ..runtime import envspec

    raw = str(envspec.get("TPUML_MESH_MP")).strip().lower()
    if raw == "off":
        return 1
    avail = default_device_count()
    if raw == "auto":
        budget = envspec.get("TPUML_MESH_MP_BUDGET")
        budget = float(budget) if budget else _default_mp_budget()
        mp = 1
        while float(model_bytes) / mp > budget and mp * 2 <= avail:
            mp *= 2
    else:
        try:
            mp = int(raw)
        except ValueError:
            raise envspec.EnvSpecError(
                f"TPUML_MESH_MP={raw!r}: expected 'auto', 'off', or a "
                "positive integer"
            ) from None
        if mp < 1:
            raise envspec.EnvSpecError(
                f"TPUML_MESH_MP={mp}: mp degree must be >= 1"
            )
        if mp > avail:
            from ..utils.logging import get_logger

            get_logger("mesh").warning(
                "TPUML_MESH_MP=%d > %d devices; clamping mp to %d",
                mp, avail, avail,
            )
            mp = avail
    if mp > 1:
        from ..runtime import telemetry

        telemetry.record_hbm_estimate("mesh_mp", float(model_bytes) / mp)
    return mp


def make_mesh(num_workers: Optional[int] = None, mp: Optional[int] = None) -> Mesh:
    """Build a (dp, mp) mesh over the first ``num_workers * mp`` devices.

    ``num_workers`` defaults to all devices — *global* devices when a
    multi-process world is configured. ``mp`` defaults to the
    ``TPUML_MESH_MP`` resolution (:func:`resolve_mesh_mp`; 1 when the env
    is unset). Requesting more workers than devices available clamps down
    with a warning — the reference similarly clamps/validates against the
    cluster's GPU count (``params.py:377-409``).
    """
    from .context import ensure_distributed

    ensure_distributed()
    if mp is None:
        mp = resolve_mesh_mp()
    avail = default_device_count()
    if jax.process_count() > 1:
        # multi-process worlds always span the FULL device world: a mesh
        # that excludes one rank's devices would strand that rank outside
        # every collective (peers would hang, not error)
        full_dp = max(1, avail // mp)
        if num_workers is not None and num_workers != full_dp:
            from ..utils.logging import get_logger

            get_logger("mesh").warning(
                "num_workers=%d ignored in multi-process mode; using all "
                "%d global devices (dp=%d)", num_workers, avail, full_dp,
            )
        return _cached_mesh(full_dp, mp)
    if num_workers is None:
        num_workers = max(1, avail // mp)
    if num_workers * mp > avail:
        from ..utils.logging import get_logger

        get_logger("mesh").warning(
            "Requested %d workers x %d mp > %d devices; clamping dp to %d",
            num_workers, mp, avail, max(1, avail // mp),
        )
        num_workers = max(1, avail // mp)
    return _cached_mesh(num_workers, mp)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 over dp; replicate over mp."""
    return NamedSharding(mesh, P(DP_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def col_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 1 over mp; replicate over dp — the SUMMA column-blocked
    layout of square (d, d) model-axis accumulators."""
    return NamedSharding(mesh, P(None, MP_AXIS))


def block_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 over mp; replicate over dp — feature/centroid/list
    blocks of model-axis state."""
    return NamedSharding(mesh, P(MP_AXIS))


def shard_cols(x: Any, mesh: Mesh) -> jax.Array:
    """``device_put`` a host/device array column-blocked over the mp axis
    (dim 1 of d x d accumulators). Dim 1 must divide by the mesh's mp
    degree; with mp=1 this is a plain replicated placement."""
    n_mp = mesh.shape[MP_AXIS]
    if x.shape[1] % n_mp:
        raise ValueError(
            f"dim 1 ({x.shape[1]}) does not divide the mesh mp degree "
            f"({n_mp}); pad the model axis before sharding"
        )
    return jax.device_put(x, col_sharding(mesh))


def fetch_blocked(arr: jax.Array, mesh: Mesh) -> np.ndarray:
    """Host-fetch a model-axis-blocked global array (column-blocked Gram,
    centroid/list blocks) as the full unsharded value.

    Single-process meshes read the addressable shards directly; a
    multi-host fetch reshards to fully-replicated first (one all_gather)
    so every process can assemble the complete value — the model-axis
    analog of :func:`fetch_global`.
    """
    if jax.process_count() <= 1:
        return np.asarray(arr)
    rep = _replicate_jit(mesh)(arr)
    return np.asarray(rep.addressable_shards[0].data)


def pad_rows(
    x: np.ndarray, multiple: int, pad_value: float = 0.0
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad dim-0 to a multiple of the dp size; returns (padded, mask).

    Static shapes are an XLA requirement: instead of the reference's
    ragged per-task partitions (``PartitionDescriptor``, ``utils.py:163-200``)
    we pad to an even shard and carry a row-validity mask that downstream
    reductions fold in (a masked psum replaces cuML's ragged allreduce).
    """
    n = x.shape[0]
    n_pad = (-n) % multiple
    mask = np.ones((n,), dtype=np.float32)
    if n_pad:
        pad_width = [(0, n_pad)] + [(0, 0)] * (x.ndim - 1)
        x = np.pad(x, pad_width, constant_values=pad_value)
        mask = np.pad(mask, (0, n_pad), constant_values=0.0)
    return x, mask


def _enqueue_span(
    mesh: Mesh, nbytes: int, arrays: int, host_bytes: Optional[int] = None, **attrs: Any
) -> Any:
    """Span around handing host arrays to the runtime. Its opening is where a
    fit's wait for its inputs starts. ``bytes`` is what the arrays take on
    the devices, ``host_bytes`` what the host hands over — what crosses the
    link: the same unless the devices pad (the row-block loop's rows and
    columns of zeros are the device's own fill). A single ``device_put``
    only enqueues, so the span closes long before the bytes are on the
    device. The row-block loop (:func:`_put_row_blocks`) opens an
    ``h2d.put`` around every block's ``device_put`` and write dispatch, an
    ``h2d.wait`` around its one wait (the write of the put
    ``_PUTS_IN_FLIGHT`` before) and an ``h2d.fold`` around a caller's fold
    dispatch, and returns with the last ``_PUTS_IN_FLIGHT`` writes still
    outstanding: the frame has landed when the last run of ``write_program``
    has ended on the device, not when this span closes."""
    from ..runtime import telemetry

    return telemetry.span(
        "h2d.enqueue",
        bytes=nbytes,
        host_bytes=nbytes if host_bytes is None else host_bytes,
        arrays=arrays,
        devices=int(mesh.devices.size),
        **attrs,
    )


# Byte target of one host→device put of shard_rows. One put of a 6 GB frame
# pins the whole host buffer before a byte moves (29.9 s on a v5e); the same
# frame in blocks, two in flight, is on the chip in 0.564 s at this target
# (786 MB blocks at 3000 f32 columns; median of 5), against 0.561 s at 2 GB,
# 0.636 s at 512 MB, 0.677 s at 256 MB, 0.765 s at 128 MB (PERF.md §6, PR 28):
# the smallest of those within 5% of the best.
_PUT_BLOCK_BYTES = 1 << 30
# Blocks handed to the runtime whose write into the shard has not run yet.
# What the runtime charges grows with the bytes it holds pinned at once (all
# 31 blocks of 197 MB at once: 2.2 s and 11.8 GB of HBM, two: 0.68 s), so the
# bound is on the process, not on each device: HBM holds a shard + 2 blocks,
# the host 2 linearized blocks.
_PUTS_IN_FLIGHT = 2


def _put_block_rows(row_bytes: int) -> int:
    """Rows of one put: the largest power of two whose bytes fit the target,
    and at least the 8 rows of the device's tile."""
    return 1 << (max(8, _PUT_BLOCK_BYTES // max(1, row_bytes)).bit_length() - 1)


@functools.partial(jax.jit, static_argnames="shape")
def _fill(value: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """``value`` broadcast to ``shape`` on the device ``value`` is committed to."""
    return jnp.broadcast_to(value, shape)


# Rows of a block that _write_block relays at a time where the block is
# narrower than the buffer: 12 MB at 3000 f32 columns, which the TPU compiler
# keeps in VMEM (no HBM temporary at all; 8192 rows: one of 98 MB; the whole
# 65,536-row block at once: 805 MB a write, two writes outstanding, and
# 0.061 s of device time a frame against 0.048 s; PERF.md §6, PR 30).
_WRITE_PIECE_ROWS = 1024


@functools.partial(jax.jit, donate_argnums=0)
def _write_block(buf: jax.Array, block: jax.Array, row0: Any) -> Tuple[jax.Array, jax.Array]:
    """``buf`` (donated: written in place) with ``block`` at row ``row0``,
    column 0, and a scalar that is ready once the write has run. ``row0`` is
    traced, so one program serves every block of a shape.

    A block narrower than ``buf`` leaves the columns past its own as they
    are (the zeros of :func:`_fill`). On a TPU such a block has another
    layout than the buffer (``f32[65536,3000]`` comes up with its rows minor,
    the 3072-wide buffer with its columns minor), so the write has to relay
    it: piece by piece, or the program holds a second copy of the block."""
    zero = jnp.zeros_like(row0)

    def write(b, rows, at):
        return lax.dynamic_update_slice(b, rows, (at,) + (zero,) * (buf.ndim - 1))

    if block.ndim != 2 or block.shape[1] == buf.shape[1]:
        return write(buf, block, row0), row0 + block.shape[0]
    step = _WRITE_PIECE_ROWS
    pieces, tail = divmod(block.shape[0], step)
    if pieces:
        buf = lax.fori_loop(
            0,
            pieces,
            lambda i, b: write(b, lax.dynamic_slice_in_dim(block, i * step, step), row0 + i * step),
            buf,
        )
    if tail:
        buf = write(buf, block[pieces * step:], row0 + pieces * step)
    return buf, row0 + block.shape[0]


# A fold over the row blocks of a shard as they land on its device:
# ``fold(state, rows, row0, valid) -> state``. ``state`` is that device's
# running state (``None`` before its first block), ``rows`` the block as it
# sits on the device, ``row0`` its first row within the device's shard and
# ``valid`` how many of its leading rows are rows of the host array (all of
# them for a block of the loop; a shard that went up in one put carries its
# padding rows behind them). It dispatches its work and never waits for it.
RowFold = Callable[[Any, jax.Array, int, int], Any]


def _put_row_blocks(
    x: np.ndarray,
    shape: Tuple[int, ...],
    sh: NamedSharding,
    block_rows: int,
    fold: Optional[RowFold] = None,
) -> Tuple[jax.Array, int, Dict[Any, Any]]:
    """Assemble the row-sharded global array of ``shape`` on the devices from
    puts of at most ``block_rows`` rows of ``x``; rows past ``len(x)`` are
    zero, and so are columns past ``x``'s own where ``shape`` is the wider:
    the blocks are slices of ``x`` as it is, written at column 0. Never holds
    a second copy of a shard, on the host or on a device: each device's zero
    buffer is written in place, block by block.

    With a ``fold`` (:data:`RowFold`) every block is handed to it as it sits
    on the device, in row order, right AFTER its write is dispatched: the
    fold's program runs while the next blocks cross the link. The fold only
    dispatches; the loop's one wait stays the write of the put two before,
    and that write must not stand behind the block's fold on the device —
    the next put is issued when it has run, and a put issued 28 ms later
    leaves the link with nothing to send that long (write before fold: the
    frame is up in 0.61 s; fold before write: 0.69 s; no fold: 0.58 s;
    PERF.md section 6, PR 34). A block lives until its fold has run, so
    three are on the device for the length of a fold, not two. Without a
    fold the loop is what it was.

    Returns the array, the puts issued and each device's fold state (``None``
    for a device that got no block, and for every device without a fold).

    Spans, children of the caller's ``h2d.enqueue``: ``h2d.put`` (the host's
    own seconds in handing block ``block`` of ``bytes`` to the runtime and
    dispatching its write), ``h2d.wait`` (the host blocked until the write
    of ``block`` has run) and, with a fold, ``h2d.fold`` (its dispatch)."""
    from ..runtime import telemetry

    bufs, todo = {}, []
    for dev, idx in sh.addressable_devices_indices_map(shape).items():
        lo, hi, _ = idx[0].indices(shape[0])
        zero = jax.device_put(np.zeros((), x.dtype), dev)
        bufs[dev] = _fill(zero, (hi - lo,) + shape[1:])
        valid = x[lo:hi]  # the slice ends with x: what is past it stays zero
        todo.append(
            [(dev, at, valid[at:at + block_rows]) for at in range(0, len(valid), block_rows)]
        )
    # round-robin over the devices, so that their DMA queues run together
    puts = [p for step in itertools.zip_longest(*todo) for p in step if p is not None]
    pending = collections.deque()  # (block, the scalar its write leaves)
    states = dict.fromkeys(bufs)
    for i, (dev, row0, rows) in enumerate(puts):
        if len(pending) == _PUTS_IN_FLIGHT:
            waited, written = pending.popleft()
            with telemetry.span("h2d.wait", block=waited):
                written.block_until_ready()
        with telemetry.span("h2d.put", block=i, bytes=int(rows.nbytes)):
            block = jax.device_put(rows, dev)
            bufs[dev], written = _write_block(bufs[dev], block, np.int32(row0))
        if fold is not None:
            with telemetry.span("h2d.fold", block=i):
                states[dev] = fold(states[dev], block, row0, len(rows))
        pending.append((i, written))
    return jax.make_array_from_single_device_arrays(shape, sh, list(bufs.values())), len(puts), states


def padded_rows(n: int, mesh: Mesh, row_multiple: int = 1) -> int:
    """Rows of the global array ``shard_rows`` makes of ``n`` host rows in
    one process: every device's shard the same, a multiple of ``row_multiple``."""
    return n + (-n) % (mesh.shape[DP_AXIS] * row_multiple)


def shard_rows(
    x: np.ndarray,
    mesh: Mesh,
    row_multiple: int = 1,
    cols: Optional[int] = None,
    fold: Optional[RowFold] = None,
) -> Tuple[Any, ...]:
    """Pad + device_put a host array row-sharded over the dp axis.

    This is the data-plane replacement for the reference's Arrow-batch →
    cupy ingestion inside the barrier task (``core.py:717-741``).
    ``row_multiple`` > 1 additionally aligns each device's shard to that
    multiple (for kernels that scan rows in fixed-size chunks). ``cols`` is
    the width a 2-D ``x`` is to have on the device (default: its own);
    columns past ``x``'s are zero. Returns (sharded_x, sharded_mask).

    A shard of at most one block (``_PUT_BLOCK_BYTES``) goes up in one
    ``device_put`` of the padded array. A larger one goes up in row blocks
    that are assembled on the device (:func:`_put_row_blocks`): the same
    array, bit for bit, without the host's padded copy and without one put
    the size of the frame. A shard that is to be wider than ``x`` is
    assembled on the device whatever its size (a small one from a single
    block): the zero columns are the device's own fill, and the host never
    pads or copies ``x``.

    ``fold`` (:data:`RowFold`) is for a caller whose work is a sum over rows
    (PCA's covariance): it is handed every row block as the block lands on
    its device, so its programs run under the frame's crossing instead of
    after it. A shard that goes up in one put, and a process's one put in
    the multi-process path, call it once a device on the whole shard — one
    contract whatever the number of blocks. The frame is assembled all the
    same, and the return gains a third element: ``{device: state}`` over this
    process's devices. The ``h2d.enqueue`` span then says ``folded_blocks``.
    Without a fold nothing here differs from a call that has no such
    argument.

    Multi-process: ``x`` is this process's local rows (each worker holds
    its partition, as each Spark barrier task held its Arrow batches).
    Processes agree on a common per-device row count via a host allgather
    — the ``PartitionDescriptor.build`` analog (``utils.py:163-200``) —
    pad locally, and assemble one global row-sharded array; the mask marks
    every process's padding rows invalid.
    """
    x = np.asarray(x)
    width = x.shape[1] if x.ndim > 1 else 1
    cols = width if cols is None else int(cols)
    if cols != width and (x.ndim != 2 or cols < width):
        raise ValueError(f"cannot place an array of shape {x.shape} at {cols} columns")
    pad_cols = cols - width
    if jax.process_count() > 1:
        return _shard_rows_multiproc(x, mesh, row_multiple, pad_cols, fold)
    n_dp = mesh.shape[DP_AXIS]
    sh = row_sharding(mesh)
    n = x.shape[0]
    n_padded = padded_rows(n, mesh, row_multiple)
    row_bytes = x.dtype.itemsize * int(np.prod(x.shape[1:]))
    block_rows = _put_block_rows(row_bytes)
    if not pad_cols and n_padded // n_dp <= block_rows:
        xp, mask = pad_rows(x, n_dp * row_multiple)
        with _enqueue_span(
            mesh, xp.nbytes + mask.nbytes, 2, blocks=1, block_bytes=xp.nbytes, cols=cols, pad_cols=0
        ) as span:
            xd = jax.device_put(xp, sh)
            md = jax.device_put(mask, sh)
            states = _fold_whole_shards(fold, xd, n, span) if fold is not None else None
        return (xd, md) if fold is None else (xd, md, states)
    mask = np.zeros((n_padded,), np.float32)
    mask[:n] = 1.0
    shape = (n_padded, cols) if pad_cols else (n_padded,) + x.shape[1:]
    with _enqueue_span(
        mesh,
        x.dtype.itemsize * int(np.prod(shape)) + mask.nbytes,
        2,
        host_bytes=x.nbytes + mask.nbytes,
        block_bytes=min(block_rows, n_padded // n_dp) * row_bytes,
        cols=cols,
        pad_cols=pad_cols,
        write_program=_write_block.__name__,
    ) as span:
        xd, blocks, states = _put_row_blocks(x, shape, sh, block_rows, fold)
        span.set_attr(blocks=blocks)
        md = jax.device_put(mask, sh)
        if fold is not None:
            span.set_attr(folded_blocks=blocks)
    return (xd, md) if fold is None else (xd, md, states)


def _fold_whole_shards(fold: RowFold, xd: jax.Array, n_rows: int, span: Any) -> Dict[Any, Any]:
    """``fold`` once on every local shard of ``xd`` (a frame that went up in
    one put a device or a process), whole: ``{device: state}``. The process's
    ``n_rows`` host rows lead its shards; what follows them is padding."""
    spans = {s.device: s.index[0].indices(xd.shape[0])[:2] for s in xd.addressable_shards}
    first = min(lo for lo, _ in spans.values())
    states = {}
    for shard in xd.addressable_shards:
        lo, hi = spans[shard.device]
        states[shard.device] = fold(None, shard.data, 0, min(max(first + n_rows - lo, 0), hi - lo))
    span.set_attr(folded_blocks=len(states))
    return states


def _local_dp_devices(mesh: Mesh) -> int:
    """This process's dp-axis device count; validates the uniform-devices-
    per-process assumption the global shard layout math relies on (ranks
    must all derive the SAME per-device row count or their collective
    shapes diverge)."""
    nproc = jax.process_count()
    n_total = mesh.devices.size
    pidx = jax.process_index()
    n_local = sum(1 for d in mesh.devices.flat if d.process_index == pidx)
    n_mp = mesh.shape[MP_AXIS]
    if n_local == 0 or n_local % n_mp:
        raise ValueError(
            f"mesh dp axis does not evenly cover process {pidx}'s devices"
        )
    if n_local * nproc != n_total:
        raise ValueError(
            f"multi-process sharding requires a uniform device count per "
            f"process; process {pidx} has {n_local} of {n_total} devices "
            f"across {nproc} processes"
        )
    return n_local // n_mp


def _shard_rows_multiproc(
    x: np.ndarray, mesh: Mesh, row_multiple: int, pad_cols: int = 0, fold: Optional[RowFold] = None
) -> Tuple[Any, ...]:
    from jax.experimental import multihost_utils

    if pad_cols:
        # one put a process: this path still pads on the host (a second copy
        # of the local rows); the single-process path pads on the device
        x = np.pad(x, ((0, 0), (0, pad_cols)))
    local_dp = _local_dp_devices(mesh)
    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray([x.shape[0]]))
    ).ravel()
    if counts.max() == 0:
        raise ValueError("dataset is empty on every process")
    # common per-device shard rows: fits the largest local partition,
    # aligned to row_multiple
    per_dev = -(-int(counts.max()) // local_dp)
    per_dev = -(-per_dev // row_multiple) * row_multiple
    local_rows = per_dev * local_dp
    if x.shape[0] == 0:
        # a legitimately empty local partition contributes all-invalid rows
        xp = np.zeros((local_rows,) + x.shape[1:], x.dtype)
        mask = np.zeros((local_rows,), np.float32)
    else:
        xp, mask = pad_rows(x, local_rows)
    if xp.shape[0] != local_rows:
        raise ValueError(
            f"local rows {x.shape[0]} exceed the agreed shard {local_rows}"
        )
    n_dp = mesh.shape[DP_AXIS]
    global_rows = per_dev * n_dp
    sh = row_sharding(mesh)
    cols = x.shape[1] if x.ndim > 1 else 1
    with _enqueue_span(mesh, xp.nbytes + mask.nbytes, 2, cols=cols, pad_cols=pad_cols) as span:
        xd = jax.make_array_from_process_local_data(sh, xp, (global_rows,) + x.shape[1:])
        md = jax.make_array_from_process_local_data(sh, mask, (global_rows,))
        # one put a process: nothing to run under, the fold sees whole shards
        states = _fold_whole_shards(fold, xd, x.shape[0], span) if fold is not None else None
    return (xd, md) if fold is None else (xd, md, states)


def shard_aligned(v: np.ndarray, mesh: Mesh, total_rows: int) -> jax.Array:
    """Shard a per-process 1-D array (labels/weights) with the same row
    layout as an existing ``shard_rows`` output of global padded length
    ``total_rows`` (padding rows zero-filled)."""
    v = np.asarray(v)
    if jax.process_count() <= 1:
        vp = np.pad(v, (0, total_rows - v.shape[0]))
        with _enqueue_span(mesh, vp.nbytes, 1):
            return jax.device_put(vp, row_sharding(mesh))
    local_rows = total_rows // jax.process_count()
    vp = np.pad(v, (0, local_rows - v.shape[0]))
    with _enqueue_span(mesh, vp.nbytes, 1):
        return jax.make_array_from_process_local_data(
            row_sharding(mesh), vp, (total_rows,)
        )


@functools.lru_cache(maxsize=None)
def _replicate_jit(mesh: Mesh):
    """One compiled reshard-to-replicated program per mesh — building the
    jit per call would retrace on every fetch (the cache keys on the
    callable object)."""
    return jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))


@functools.lru_cache(maxsize=None)
def _gather_replicated_jit(mesh: Mesh):
    return jax.jit(
        lambda a, i: jnp.take(a, i, axis=0),
        out_shardings=NamedSharding(mesh, P()),
    )


def fetch_global(arr: jax.Array, mesh: Mesh) -> np.ndarray:
    """``np.asarray`` that also works for row-sharded multi-host arrays:
    reshard to fully-replicated (one all_gather over ICI/DCN) so every
    process can read the complete value."""
    if jax.process_count() <= 1:
        return np.asarray(arr)
    rep = _replicate_jit(mesh)(arr)
    return np.asarray(rep.addressable_shards[0].data)


def gather_rows_global(x: jax.Array, idx: np.ndarray, mesh: Mesh) -> np.ndarray:
    """Host-fetch selected rows of a (possibly multi-host) row-sharded
    matrix: device-side gather with a replicated output, then one fetch."""
    out = _gather_replicated_jit(mesh)(x, np.asarray(idx))
    if jax.process_count() <= 1:
        return np.asarray(out)
    return np.asarray(out.addressable_shards[0].data)


def global_row_count(n_local: int) -> int:
    """Total valid rows across the process world (local count if single)."""
    if jax.process_count() <= 1:
        return int(n_local)
    from jax.experimental import multihost_utils

    return int(
        np.asarray(multihost_utils.process_allgather(np.asarray([n_local]))).sum()
    )


def combine_label_summaries(local: np.ndarray) -> Dict[str, Any]:
    """Allgather + merge per-rank label-summary vectors.

    ``local`` encodes ``[is_empty, max, min, all_int, first, all_same,
    count]``; one wire format shared by the resident column scan
    (:func:`global_label_summary`) and the streaming label pass
    (``ops.streaming.streamed_label_stats``).
    """
    g = allgather_host(np.asarray(local))
    non_empty = g[g[:, 0] == 0.0]
    if len(non_empty) == 0:
        return {
            "y_max": -np.inf, "y_min": np.inf, "all_int": True,
            "all_same": True, "first": 0.0, "total": 0,
        }
    return {
        "y_max": float(non_empty[:, 1].max()),
        "y_min": float(non_empty[:, 2].min()),
        "all_int": bool(np.all(non_empty[:, 3] == 1.0)),
        "all_same": bool(
            np.all(non_empty[:, 5] == 1.0)
            and np.all(non_empty[:, 4] == non_empty[0, 4])
        ),
        "first": float(non_empty[0, 4]),
        "total": int(g[:, 6].sum()),
    }


def global_label_summary(y_local: np.ndarray) -> Dict[str, Any]:
    """World-wide label statistics from per-process label columns.

    Every rank must agree on label-derived compile-time constants
    (n_classes, degenerate single-label cases) or their collectives
    diverge; empty local partitions are legitimate and excluded.
    Returns ``{y_max, y_min, all_int, all_same, first, total}``.
    """
    y_local = np.asarray(y_local)
    empty = y_local.size == 0
    local = np.asarray(
        [
            1.0 if empty else 0.0,
            -np.inf if empty else float(y_local.max()),
            np.inf if empty else float(y_local.min()),
            1.0 if empty or bool(np.all(y_local == np.floor(y_local))) else 0.0,
            0.0 if empty else float(y_local[0]),
            1.0 if empty or bool(np.all(y_local == y_local[0])) else 0.0,
            float(y_local.size),
        ]
    )
    return combine_label_summaries(local)


def allgather_host(vals: np.ndarray) -> np.ndarray:
    """Host-value allgather across the process world: (k,) per process ->
    (nproc, k). Identity-with-leading-axis single-process. The out-of-band
    metadata exchange of the reference's ``BarrierTaskContext.allGather``
    (``cuml_context.py:75-103``)."""
    vals = np.atleast_1d(np.asarray(vals))
    if jax.process_count() <= 1:
        return vals[None, :]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(vals))


def host_file_shard(
    files: Any,
    *,
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
    mp: int = 1,
    devices_per_process: Optional[int] = None,
) -> List[Any]:
    """This host's round-robin subset of the ingest file list.

    Per-host sharded ingest: with the streaming data plane partition-local
    (see :func:`local_mesh`), N hosts reading the SAME parquet directory
    would each decode every file and N-fold overcount the global
    statistics at the allreduce. Round-robin assignment
    (``files[group::n_groups]``) makes the subsets a disjoint cover, so N
    hosts pull N files concurrently and the existing
    :func:`allreduce_sum_host` of partials is exact. Round-robin (not
    contiguous blocks) keeps per-host byte counts balanced when file sizes
    trend across the directory (time-partitioned writers).

    The shard key is the process's **dp replica group**, not its bare
    rank: on a 2-D mesh where one process owns fewer devices than the mp
    degree, the ``mp // devices_per_process`` consecutive processes
    spanning one dp row replicate the same logical data rows and must
    read the SAME file subset — keying off rank alone would hand them
    disjoint sets that disagree across the model axis. With ``mp=1`` (or
    processes owning whole dp rows) every process is its own group and
    the assignment reduces to the historical ``files[rank::nprocs]``.

    ``process_index`` / ``process_count`` / ``devices_per_process``
    default to the live jax process world; tests and ``dryrun_multichip``
    override them to validate the assignment without a real multi-host
    world. Identity when the world has one group.
    """
    idx = jax.process_index() if process_index is None else int(process_index)
    n = jax.process_count() if process_count is None else int(process_count)
    if n < 1 or not (0 <= idx < n):
        raise ValueError(f"invalid process world: index {idx} of {n}")
    n_mp = int(mp)
    if n_mp < 1:
        raise ValueError(f"invalid mp degree: {n_mp}")
    dpp = (
        jax.local_device_count()
        if devices_per_process is None
        else int(devices_per_process)
    )
    if dpp < 1:
        raise ValueError(f"invalid devices_per_process: {dpp}")
    # processes spanning one dp row (row-major device order: a process
    # owning < mp devices shares its dp row with the next ones)
    procs_per_group = max(1, n_mp // dpp)
    if n % procs_per_group:
        raise ValueError(
            f"process world of {n} does not divide into mp replica groups "
            f"of {procs_per_group} (mp={n_mp}, devices_per_process={dpp})"
        )
    files = list(files)
    n_groups = n // procs_per_group
    if n_groups == 1:
        return files
    return files[idx // procs_per_group :: n_groups]


def local_mesh(mp: int = 1) -> Mesh:
    """A mesh over THIS process's devices only.

    The streaming data plane is partition-local (each worker streams its
    chunks through its own chips, like each reference barrier task streams
    its Arrow batches through its GPU); cross-process combination happens
    at the sufficient-statistics level via :func:`allreduce_sum_host`.
    """
    devs = jax.local_devices()
    n_dp = max(1, len(devs) // mp)
    return Mesh(np.asarray(devs[: n_dp * mp]).reshape(n_dp, mp), (DP_AXIS, MP_AXIS))


def allreduce_sum_host(*arrays: Any) -> Tuple[np.ndarray, ...]:
    """Elementwise-sum each array across the process world (host path).

    The explicit allreduce of per-partition partials — exactly the role
    NCCL allreduce played inside cuML's MG fit. Single-process: identity.
    Sums in float64 for exactness; returns each result in its input dtype.
    """
    if jax.process_count() <= 1:
        return tuple(np.asarray(a) for a in arrays)
    parts = [np.asarray(a) for a in arrays]
    flat = np.concatenate([p.astype(np.float64).ravel() for p in parts])
    total = allgather_host(flat).sum(axis=0)
    out = []
    off = 0
    for p in parts:
        out.append(
            total[off : off + p.size].reshape(p.shape).astype(p.dtype)
        )
        off += p.size
    return tuple(out)


def allgather_host_blobs(blob: bytes) -> List[bytes]:
    """Gather one opaque byte blob per process, rank-ordered.

    The metadata-exchange primitive behind
    ``telemetry.aggregate_metrics``: each rank JSON-encodes its metric
    snapshot, the blobs ride a padded uint8 allgather (counts first, so
    uneven payloads trim exactly), and every rank gets the full list to
    merge locally. Single-process: ``[blob]``.
    """
    a = np.frombuffer(blob, np.uint8)
    if jax.process_count() <= 1:
        return [blob]
    counts = allgather_host(np.asarray([a.shape[0]])).ravel().astype(int)
    maxc = max(int(counts.max()), 1)
    padded = np.zeros((maxc,), np.uint8)
    padded[: a.shape[0]] = a
    gathered = allgather_host(padded)
    return [
        gathered[p][: counts[p]].tobytes() for p in range(len(counts))
    ]


def allgather_ragged_rows(a: np.ndarray) -> np.ndarray:
    """Concatenate every process's rows in rank order (uneven partitions
    padded through a host allgather, then trimmed) — the multi-host analog
    of coalescing a dataset to one node."""
    counts = allgather_host(np.asarray([a.shape[0]])).ravel().astype(int)
    maxc = int(counts.max())
    padded = np.zeros((maxc,) + a.shape[1:], a.dtype)
    padded[: a.shape[0]] = a
    gathered = allgather_host(padded)
    return np.concatenate([gathered[p][: counts[p]] for p in range(len(counts))])


def allgather_ragged_rows_exact(a: np.ndarray) -> np.ndarray:
    """Dtype-exact ragged row gather: moves raw bytes (the plain gather
    rides jax arrays, which canonicalize int64/float64 to 32-bit when x64
    is off) and views them back as the input dtype."""
    a = np.ascontiguousarray(a)
    row_shape = a.shape[1:]
    # explicit widths, not -1: reshape(-1) is ambiguous for 0-row inputs
    # (a rank with an empty partition must still join the collective)
    row_elems = int(np.prod(row_shape, dtype=np.int64)) if row_shape else 1
    flat = a.reshape(a.shape[0], row_elems)
    as_bytes = flat.view(np.uint8).reshape(a.shape[0], row_elems * a.itemsize)
    g = allgather_ragged_rows(as_bytes)
    return (
        np.ascontiguousarray(g).view(a.dtype).reshape((len(g),) + row_shape)
    )


def object_string_kind(a: np.ndarray):
    """np.str_/np.bytes_ for an all-str / all-bytes object array; raises
    TypeError otherwise. Scans EVERY element: a single stray Python int
    would silently stringify (corrupting joins), and ranks sampling
    different prefixes could disagree on raising vs entering a collective
    (deadlock) — so no shortcut sampling."""
    kinds = {type(v) for v in a.ravel()}
    if kinds <= {str, np.str_}:
        return np.str_
    if kinds <= {bytes, np.bytes_}:
        return np.bytes_
    raise TypeError(
        f"cannot exchange object column with element types {kinds}; "
        "use a numeric or string dtype"
    )


def unify_string_width(a: np.ndarray) -> np.ndarray:
    """Cast an object/str/bytes array to a fixed-width dtype whose width is
    agreed across the process world (the byte-moving collectives need every
    rank to view rows at the same itemsize). Numeric arrays pass through."""
    if a.dtype.kind not in "OUS":
        return a
    if a.dtype.kind == "O":
        a = np.asarray(a, dtype=object_string_kind(a))
    else:
        a = np.asarray(a, dtype=np.str_ if a.dtype.kind == "U" else np.bytes_)
    unit = np.dtype(a.dtype.kind + "1").itemsize
    w_local = max(1, a.dtype.itemsize // unit)
    w = int(allgather_host(np.asarray([w_local])).max())
    return a.astype(f"{a.dtype.kind}{w}")


def allgather_ragged_any(a: np.ndarray) -> np.ndarray:
    """:func:`allgather_ragged_rows_exact` that also accepts string/object
    columns (width-unified first so every rank's byte view agrees)."""
    return allgather_ragged_rows_exact(unify_string_width(np.asarray(a)))


def local_row_block(arr: jax.Array) -> np.ndarray:
    """This process's rows of a row-sharded array, assembled from its
    addressable shards in row order — no collective, and no assumption
    that the dp device order is process-contiguous."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards])
