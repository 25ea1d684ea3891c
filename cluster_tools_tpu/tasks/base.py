"""Shared plumbing for volume-to-volume block tasks."""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..obs import metrics as obs_metrics
from ..runtime.task import BlockTask, SimpleTask
from ..utils import store
from ..utils.blocking import Blocking


SCRATCH_STORE_NAME = "data.zarr"


def fusion_wrap(ds, path: str, key: str):
    """Route a dataset through the fused chain's active per-batch read
    cache (ctt-stream) — a no-op outside a chain's read stage."""
    from ..parallel.dispatch import wrap_with_read_cache

    return wrap_with_read_cache(ds, path, key)


def scratch_store_path(tmp_folder: str) -> str:
    """The shared per-tmp-folder scratch store (single source of truth)."""
    return os.path.join(tmp_folder, SCRATCH_STORE_NAME)


class VolumeTask(BlockTask):
    """A block task reading ``input_path/input_key`` and writing
    ``output_path/output_key``.

    The blocking is derived from the input dataset shape (the last ``ndim``
    axes when the input carries leading channel axes).
    """

    output_dtype = None  # subclasses set to create the output dataset
    output_chunks_from_blocks = True
    space_ndim = 3  # spatial rank; inputs may have extra leading channel axes

    def __init__(
        self,
        tmp_folder: str,
        config_dir: Optional[str] = None,
        max_jobs: Optional[int] = None,
        dependencies: Sequence = (),
        input_path: str = None,
        input_key: str = None,
        output_path: Optional[str] = None,
        output_key: Optional[str] = None,
    ):
        super().__init__(tmp_folder, config_dir, max_jobs, dependencies)
        self.input_path = input_path
        self.input_key = input_key
        self.output_path = output_path
        self.output_key = output_key

    # -- datasets ------------------------------------------------------------

    def input_ds(self, mode: str = "r"):
        # ctt-stream seam: inside a fused chain's read stage the thread
        # carries a per-batch BlockReadCache — reads come back as crops of
        # the one shared store read instead of hitting the codec again
        return fusion_wrap(
            store.file_reader(self.input_path, mode)[self.input_key],
            self.input_path, self.input_key,
        )

    def output_ds(self, mode: str = "a"):
        return store.file_reader(self.output_path, mode)[self.output_key]

    def get_shape(self) -> Sequence[int]:
        shape = self.input_ds().shape
        return shape[-self.space_ndim :] if len(shape) > self.space_ndim else shape

    def prepare(self, blocking: Blocking, config: Dict[str, Any]) -> None:
        if self.output_path is None or self.output_dtype is None:
            return
        f = store.file_reader(self.output_path, "a")
        chunks = (
            tuple(blocking.block_shape)
            if self.output_chunks_from_blocks
            else None
        )
        # user-facing outputs keep the reference's gzip default (vanilla
        # n5-java readers lack the blosc plugin); SCRATCH datasets get the
        # fast house codec via create_dataset's "default"
        f.require_dataset(
            self.output_key,
            shape=tuple(blocking.shape),
            dtype=self.output_dtype,
            chunks=chunks,
            compression="gzip",
        )

    # -- ctt-cloud async prefetch ---------------------------------------------

    def prefetch_halo(self, config) -> Sequence[int]:
        """Halo of the regions ``read_batch`` will request — the task
        config's ``halo`` key when it matches the spatial rank, else no
        halo.  An approximate halo is fine: prefetch works at chunk
        granularity and is advisory, so over/under-shoot degrades to a few
        extra (or missed) chunk warms, never to wrong data."""
        halo = config.get("halo")
        if not halo:
            return (0,) * self.space_ndim
        halo = tuple(int(h) for h in halo)
        if len(halo) != self.space_ndim:
            return (0,) * self.space_ndim
        return halo

    def prefetch_batch(self, block_ids, blocking: Blocking, config) -> int:
        """Warm the decoded-chunk LRU with every input chunk the batch's
        read stage will need (the executor's async-prefetch stage issues
        this up to ``pipeline_depth`` batches ahead of the in-order
        compute stage — ctt-cloud).  Consecutive ids prefetch as one
        bounding superslab (each chunk probed once); sparse id runs fall
        back to per-block outer boxes.  Returns the chunk count submitted
        (0 when the dataset has no prefetch support, e.g. hdf5)."""
        from ..parallel.dispatch import batch_outer_boxes

        ds = self.input_ds()
        prefetch = getattr(ds, "prefetch", None)
        if prefetch is None or not block_ids:
            return 0
        halo = self.prefetch_halo(config)
        extra = len(ds.shape) - blocking.ndim
        lead = tuple(slice(0, s) for s in ds.shape[:extra])
        bhs, lo, hi, bbox_ok = batch_outer_boxes(blocking, block_ids, halo)
        if bbox_ok:
            return prefetch(
                lead + tuple(slice(b, e) for b, e in zip(lo, hi))
            )
        return sum(prefetch(lead + bh.outer.slicing) for bh in bhs)

    # -- ctt-stream fusion contract ------------------------------------------

    def fusion_inputs(self, config):
        """Per-block dataset reads of a volume-to-volume task: the input
        (plus the optional mask) — the fused chain's shared-read set."""
        pairs = [(self.input_path, self.input_key)]
        mask_path = getattr(self, "mask_path", None)
        if mask_path:
            pairs.append((mask_path, getattr(self, "mask_key", None)))
        return pairs

    # -- scratch data --------------------------------------------------------

    @property
    def tmp_store_path(self) -> str:
        return scratch_store_path(self.tmp_folder)

    def tmp_store(self):
        return store.file_reader(self.tmp_store_path, "a")

    def tmp_ragged(self, key: str, grid_size: int, dtype):
        return self.tmp_store().create_ragged_dataset(key, (grid_size,), dtype)


def read_ragged_chunks(ds, n_blocks: int, n_threads: int = 1) -> list:
    """Read all per-block ragged chunks, fanned out over a thread pool when
    ``n_threads > 1`` (the reference's ``threads_per_job`` merge pattern,
    write.py:236-243, measures.py:121-127 — chunk decode is gzip-bound, so
    threads overlap IO + decompression).  Returns a list indexed by block id,
    ``None`` where a chunk is absent."""
    from concurrent.futures import ThreadPoolExecutor

    if n_threads <= 1:
        return [ds.read_chunk((bid,)) for bid in range(n_blocks)]
    with ThreadPoolExecutor(n_threads) as pool:
        return list(pool.map(lambda bid: ds.read_chunk((bid,)), range(n_blocks)))


def merge_threads(task) -> int:
    """The ``threads_per_job`` knob of a merge task's config."""
    return max(int(task.get_task_config().get("threads_per_job", 1)), 1)


def read_threads(config) -> int:
    """The ``read_threads`` knob (chunk-read fan-out of a block batch) —
    DEFAULT_TASK_CONFIG owns the default, this helper just clamps."""
    from ..runtime.config import DEFAULT_TASK_CONFIG

    return max(
        int(config.get("read_threads", DEFAULT_TASK_CONFIG["read_threads"])), 1
    )


def count_block_rounds(rounds: Dict[str, Any], n_blocks: int) -> None:
    """Add a block program's round counts to the obs counters.

    ``rounds`` maps ``flood`` / ``flood_tile`` / ``cc`` to lists of per-block
    int32 arrays fetched with the program's labels (one entry per flood or
    CC of the program, None for a CC path that counts none;
    ``ops.watershed.dt_watershed(with_rounds=True)``); only the first
    ``n_blocks`` (the real, unpadded blocks) count.  The readers divide by
    ``blocks.computed``: rounds per block."""

    def total(key):
        return int(sum(np.asarray(r)[:n_blocks].sum()
                       for r in rounds.get(key, ()) if r is not None))

    obs_metrics.inc("blocks.computed", n_blocks)
    obs_metrics.inc("flood.rounds", total("flood"))
    obs_metrics.inc("flood.tile_rounds", total("flood_tile"))
    obs_metrics.inc("cc.rounds", total("cc"))


def resolve_n_blocks(
    config_dir, path: str, key: str, scale: int = 0, space_ndim: int = 3
) -> int:
    """Block count of a dataset under the global block shape.  Called at task
    run time (the dataset may not exist when the DAG is built); leading channel
    axes beyond ``space_ndim`` are dropped, matching ``VolumeTask.get_shape``."""
    from ..runtime import config as cfg

    shape = store.file_reader(path, "r")[key].shape
    if len(shape) > space_ndim:
        shape = shape[-space_ndim:]
    gconf = cfg.global_config(config_dir)
    block_shape = [bs * (2**scale) for bs in gconf["block_shape"]]
    return Blocking(shape, block_shape).n_blocks


class VolumeSimpleTask(SimpleTask):
    """Single-shot reduction task with access to the shared scratch store."""

    def __init__(
        self,
        tmp_folder: str,
        config_dir: Optional[str] = None,
        max_jobs: Optional[int] = None,
        dependencies: Sequence = (),
        **params,
    ):
        super().__init__(tmp_folder, config_dir, max_jobs, dependencies)
        for k, v in params.items():
            setattr(self, k, v)

    @property
    def tmp_store_path(self) -> str:
        return scratch_store_path(self.tmp_folder)

    def tmp_store(self):
        return store.file_reader(self.tmp_store_path, "a")

    def require_output(self, shape, conf, dtype="uint64"):
        """Create/open ``output_path/output_key`` with the house convention
        (block-shape chunks, gzip — user-facing outputs stay on the
        reference's default codec for vanilla n5-java readability; scratch
        data rides the fast blosc default) — one recipe for every
        single-shot task that writes a volume."""
        f = store.file_reader(self.output_path, "a")
        block_shape = conf.get("block_shape")
        return f.require_dataset(
            self.output_key, shape=tuple(shape), dtype=dtype,
            chunks=tuple(block_shape) if block_shape else None,
            compression="gzip",
        )

