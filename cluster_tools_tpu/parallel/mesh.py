"""Device mesh utilities.

Blocks are the unit of data parallelism (the analog of the reference's
round-robin job assignment, cluster_tasks.py:331): a batch of blocks is stacked
on the leading axis and sharded over a 1d ``data`` mesh; per-block kernels are
vmapped so XLA compiles one program for the whole batch and partitions it over
ICI.  Cross-block reductions (label merges, feature merges) then ride XLA
collectives instead of the reference's filesystem round-trips (SURVEY.md §2.9).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def local_device_count() -> int:
    return jax.local_device_count()


def get_mesh(devices: Optional[Sequence] = None, axis_name: str = "data") -> Mesh:
    """1d mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis_name,))


def shard_batch(batch, mesh: Optional[Mesh] = None, axis_name: str = "data"):
    """Place a [B, ...] stacked block batch with the leading axis sharded over
    the mesh.  B must be divisible by the mesh size (callers pad)."""
    if mesh is None:
        mesh = get_mesh(axis_name=axis_name)
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.device_put(batch, sharding)


# sharding of the most recent ``put_sharded`` placement — introspection hook so
# tests (and the driver dryrun) can assert that the production task path really
# partitioned its batch over the mesh rather than landing everything on device 0
_LAST_BATCH_SHARDING = None


def last_batch_sharding():
    return _LAST_BATCH_SHARDING


def resolve_devices(config: Optional[dict] = None):
    """Devices used for block data parallelism: the ``devices`` config entry
    (indices into ``jax.devices()``, device objects, or the string
    ``"global"`` for every device of every process after
    ``init_distributed``) or all local devices."""
    devices = (config or {}).get("devices")
    if devices == "global":
        return jax.devices()
    if devices:
        all_devices = jax.devices()
        return [all_devices[d] if isinstance(d, int) else d for d in devices]
    return jax.local_devices()


_DISTRIBUTED_INITIALIZED = False


def init_distributed(config: Optional[dict] = None) -> bool:
    """Join the multi-host jax runtime (idempotent).

    Reads ``coordinator_address`` / ``num_processes`` / ``process_id`` from
    the config or the ``CTT_COORDINATOR`` / ``CTT_NUM_PROCESSES`` /
    ``CTT_PROCESS_ID`` environment, and calls ``jax.distributed.initialize``
    — after which ``jax.devices()`` spans all processes and the collective
    kernels run their ppermute/psum over ICI within a host and DCN
    (gRPC/Gloo on CPU) across hosts.  Returns True when a multi-process
    runtime is active.

    MUST run at process startup, before any jax backend initializes
    (``jax.distributed.initialize`` refuses afterwards) — call it from the
    launcher.  Then either drive the ``parallel.sharded*`` kernels directly
    over a ``resolve_devices({"devices": "global"})`` mesh, or run the
    collective tasks through ``build()``: tasks marked
    ``collective = True`` (sharded components / watershed / problem)
    execute their program on EVERY process under the runtime's multi-host
    topology, with process 0 owning the store writes and the status file
    (``runtime.task.SimpleTask``).  The block-task layer stays
    per-process; multi-host here is the comm backend — the role NCCL/MPI
    bootstrap plays in GPU stacks (SURVEY.md §2.9).
    """
    global _DISTRIBUTED_INITIALIZED
    import os

    conf = config or {}

    def _setting(key, env_key, default=None):
        # explicit key-presence checks: 0 is a legitimate process_id and
        # must not fall through to a stale environment value
        if key in conf and conf[key] is not None:
            return conf[key]
        return os.environ.get(env_key, default)

    coord = _setting("coordinator_address", "CTT_COORDINATOR")
    if not coord:
        return False
    if _DISTRIBUTED_INITIALIZED:
        return True
    # None passes through so jax's own auto-detection (TPU pod metadata)
    # still works when only the coordinator is configured
    n_proc = _setting("num_processes", "CTT_NUM_PROCESSES")
    pid = _setting("process_id", "CTT_PROCESS_ID")
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=None if n_proc is None else int(n_proc),
        process_id=None if pid is None else int(pid),
    )
    _DISTRIBUTED_INITIALIZED = True
    return True


def put_global(arr, mesh: Mesh, axis_name: str = "data", dtype=None):
    """Place a host array onto a mesh sharding, multi-process safe.

    Every process passes the SAME full (global-shape) host array;
    ``jax.make_array_from_callback`` materializes only the shards addressable
    by this process, so the call works identically on a single-process mesh
    (where it is just a sharded device_put) and on a multi-host mesh (where
    ``jax.device_put`` would fail on non-addressable devices).

    Device arrays already carrying the target sharding pass through
    untouched (a host round-trip would crash on a global mesh and waste two
    transfers on a single-host one)."""
    sharding = NamedSharding(mesh, P(axis_name))
    if isinstance(arr, jax.Array):
        ok_dtype = dtype is None or arr.dtype == np.dtype(dtype)
        if ok_dtype and arr.sharding.is_equivalent_to(sharding, arr.ndim):
            return arr
    arr = np.asarray(arr)
    if dtype is not None:
        arr = arr.astype(dtype, copy=False)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def put_from_store(ds, mesh: Mesh, axis_name: str = "data", dtype=None,
                   pad_to: Optional[int] = None, transform=None,
                   pad_value=0, box=None):
    """Stream a chunked-store dataset onto the mesh sharding shard-by-shard:
    the placement callback reads each shard's region directly from the
    store, so no full-volume host copy ever exists (the practical bound
    becomes one shard, not the volume — and on a multi-host mesh each
    process reads only its own slab from shared storage).

    ``box``: ``(begin, end)`` of the region to place (an ROI); the array
    then has the box's shape and each shard reads its z-range of the box
    over the box's in-plane window.  None is the whole dataset.

    ``pad_to``: pad the leading axis up to a multiple of this, for meshes
    that do not divide the raw extent — the pad is ``pad_value`` in the
    OUTPUT dtype and never passes through ``transform``.

    ``transform``: host function applied to each shard's real region before
    it crosses to the device.  Narrowing transforms (e.g. thresholding a
    float volume to its bool mask) keep HBM at the narrow dtype — only the
    transformed shard ever leaves the host."""
    if box is None:
        box = ((0,) * len(ds.shape), tuple(ds.shape))
    begin = tuple(int(b) for b in box[0])
    shape = [int(e) - b for b, e in zip(begin, box[1])]
    z = shape[0]
    if pad_to:
        shape[0] = z + ((-z) % pad_to)
    shape = tuple(shape)
    sharding = NamedSharding(mesh, P(axis_name))
    out_dtype = np.dtype(dtype) if dtype is not None else ds.dtype

    def region(sl, b, n):
        return slice(b + (sl.start or 0), b + (n if sl.stop is None
                                               else sl.stop))

    def read(idx):
        sl0 = idx[0]
        start, stop = sl0.start or 0, sl0.stop or shape[0]
        stop_real = min(stop, z)
        block = np.full((stop - start,) + shape[1:], pad_value, dtype=out_dtype)
        if start < z:
            inner = tuple(region(sl, b, n) for sl, b, n in
                          zip(idx[1:], begin[1:], shape[1:]))
            part = np.asarray(ds[(slice(begin[0] + start,
                                        begin[0] + stop_real),) + inner])
            if transform is not None:
                part = transform(part)
            block[: stop_real - start] = part.astype(out_dtype, copy=False)
        return block

    return jax.make_array_from_callback(shape, sharding, read)


def fetch_local(arr, axis: int = 0):
    """Host view of this process's shards of a (possibly multi-host) global
    array: ``(offset, local_block)`` concatenated along ``axis`` in index
    order.  Replicated (or otherwise non-``axis``-sharded) arrays return
    ``(0, full array)`` — duplicate per-device copies are collapsed, not
    concatenated."""
    by_index = {}
    for s in arr.addressable_shards:
        key = tuple((sl.start, sl.stop) for sl in s.index)
        by_index.setdefault(key, s)
        for d, sl in enumerate(s.index):
            if d != axis and (sl.start or 0) != 0:
                raise ValueError(
                    f"fetch_local(axis={axis}) expects sharding along that "
                    f"axis only, found a shard split on axis {d}"
                )
    shards = sorted(
        by_index.values(), key=lambda s: s.index[axis].start or 0
    )
    # contiguity: interleaved device orders would give this process
    # non-adjacent slabs, and a single (offset, block) pair cannot
    # represent them — fail loudly instead of mislabeling coordinates
    for prev, cur in zip(shards, shards[1:]):
        if prev.index[axis].stop != (cur.index[axis].start or 0):
            raise ValueError(
                "fetch_local: this process's shards are not contiguous "
                f"along axis {axis} ({prev.index} then {cur.index}); use a "
                "process-contiguous device order"
            )
    parts = [np.asarray(s.data) for s in shards]
    start = shards[0].index[axis].start or 0
    return start, np.concatenate(parts, axis=axis)


def fetch_global(arr, axis: int = 0):
    """Full host copy of a (possibly multi-host) global array in EVERY
    process: each process contributes its local slab via an allgather.
    Single-process arrays are just np.asarray."""
    if jax.process_count() == 1:
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    _, local = fetch_local(arr, axis)
    return np.asarray(multihost_utils.process_allgather(local, tiled=True))


def put_sharded(arr, config: Optional[dict] = None, axis_name: str = "data"):
    """Place a stacked [B, ...] block batch for compute: with >1 device the
    leading axis is padded (repeating the last block) to divide the 1d mesh and
    sharded over it; single-device falls back to a plain transfer.

    Returns ``(device_array, B)`` where ``B`` is the *unpadded* batch size —
    callers slice results back to ``[:B]``.  This is the production analog of
    the reference's round-robin block→job placement (cluster_tasks.py:331):
    blocks are the unit of data parallelism, and every kernel vmapped over the
    leading axis is partitioned over ICI by XLA.
    """
    global _LAST_BATCH_SHARDING
    b = arr.shape[0]
    # only the tpu target shards; 'local' is the single-device parity oracle
    # (sharding it would make local-vs-tpu comparisons vacuous and compute
    # every block n_dev times through the per-block path)
    if config is not None and config.get("target", "tpu") != "tpu":
        devices = []
    else:
        devices = resolve_devices(config)
    # a batch smaller than the mesh gains nothing from padding to it — run on
    # the first b devices instead of computing (n - b) wasted replicas
    if b < len(devices):
        devices = devices[:b]
    if len(devices) <= 1:
        out = jax.numpy.asarray(arr)
        _LAST_BATCH_SHARDING = out.sharding
        return out, b
    n = len(devices)
    pad = (-b) % n
    if pad:
        arr = np.concatenate(
            [arr, np.broadcast_to(arr[-1:], (pad,) + arr.shape[1:])], axis=0
        )
    out = shard_batch(arr, get_mesh(devices, axis_name), axis_name)
    _LAST_BATCH_SHARDING = out.sharding
    return out, b
