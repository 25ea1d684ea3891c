"""Mask-driven ROI restriction (reference masking/ package).

Two tasks:

* ``BlocksFromMaskTask`` — compute the list of blocks intersecting a (possibly
  lower-resolution) mask and write it as a JSON block list, consumed by every
  other task through the global ``block_list_path`` config
  (reference blocks_from_mask.py:22; nearest-neighbor mask upscaling mirrors
  elf's ResizedVolume).
* ``MinfilterTask`` — halo'd minimum filter over a mask so that every block
  whose *receptive field* touches masked-out voxels is excluded (used to guard
  NN inference borders; reference minfilter.py:25).  The filter itself is
  ``lax.reduce_window`` min on device — one batched dispatch per block batch.
"""

from __future__ import annotations

import json
import os
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from ..ops.filters import minimum_filter
from ..parallel.dispatch import read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils import store
from ..utils.blocking import Blocking
from .base import VolumeSimpleTask, VolumeTask, read_threads


def resize_nearest(data: np.ndarray, shape: Sequence[int]) -> np.ndarray:
    """Nearest-neighbor resize via index mapping (the moral equivalent of
    elf's ResizedVolume used by the reference, blocks_from_mask.py:115)."""
    if tuple(data.shape) == tuple(shape):
        return data
    idx = tuple(
        np.minimum(
            (np.arange(ns) * ds / ns).astype(np.int64), ds - 1
        )
        for ns, ds in zip(shape, data.shape)
    )
    return data[np.ix_(*idx)]


class BlocksFromMaskTask(VolumeSimpleTask):
    """Write the JSON list of blocks overlapping the mask
    (reference blocks_from_mask.py:22-133)."""

    task_name = "blocks_from_mask"

    def __init__(
        self,
        *args,
        mask_path: str = None,
        mask_key: str = None,
        shape: Sequence[int] = None,
        output_path: str = None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.mask_path = mask_path
        self.mask_key = mask_key
        self.shape = list(shape) if shape is not None else None
        self.output_path = output_path

    def run_impl(self) -> None:
        from ..runtime import config as cfg

        gconf = cfg.global_config(self.config_dir)
        mask = np.asarray(
            store.file_reader(self.mask_path, "r")[self.mask_key][:]
        ).astype(bool)
        shape = self.shape if self.shape is not None else list(mask.shape)
        mask = resize_nearest(mask, shape)

        blocking = Blocking(shape, gconf["block_shape"])
        # one pass over the grid: a block is kept iff any mask voxel inside
        blocks_in_mask = [
            bid
            for bid in range(blocking.n_blocks)
            if bool(np.any(mask[blocking.block(bid).slicing]))
        ]
        os.makedirs(os.path.dirname(os.path.abspath(self.output_path)),
                    exist_ok=True)
        with open(self.output_path, "w") as f:
            json.dump(blocks_in_mask, f)
        self.log(
            f"{len(blocks_in_mask)}/{blocking.n_blocks} blocks intersect the mask"
        )


@partial(jax.jit, static_argnames=("size",))
def _minfilter_batch(batch, size):
    return jax.vmap(lambda m: minimum_filter(m, size))(batch)


class MinfilterTask(VolumeTask):
    """Halo'd minimum filter over a binary mask (reference minfilter.py:25-119)."""

    task_name = "minfilter"
    output_dtype = "uint8"

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({"filter_shape": [10, 100, 100]})
        return conf

    def _halo(self, config) -> Sequence[int]:
        # halo = half the filter extent, rounded up (reference minfilter.py:83)
        return [fs // 2 + 1 for fs in config["filter_shape"]]

    # -- split batch protocol (three-stage executor pipeline) ---------------

    def read_batch(self, block_ids, blocking: Blocking, config):
        halo = self._halo(config)
        # the device-source tag marks the replicate-pad edit below: the
        # cached upload holds the EDITED batch, so the key must never
        # collide with a plain zero-padded read of the same region
        batch = read_block_batch(self.input_ds(), blocking, block_ids,
                                 halo=halo, n_threads=read_threads(config),
                                 dtype="float32",
                                 device_source=(self.input_path,
                                                self.input_key,
                                                ("minfilter-read",), config))
        if batch.data is None:
            return batch  # device probe hit: the edited batch is resident
        # replicate-pad the static-shape padding: zero fill would leak
        # "masked out" into border blocks through the min window
        full_shape = batch.data.shape[1:]
        for i, bh in enumerate(batch.blocks):
            true_shape = tuple(e - b for b, e in zip(bh.outer.begin, bh.outer.end))
            if true_shape != full_shape:
                arr = batch.data[i][tuple(slice(0, s) for s in true_shape)]
                batch.data[i] = np.pad(
                    arr,
                    [(0, f - s) for f, s in zip(full_shape, true_shape)],
                    mode="edge",
                )
        return batch

    def upload_batch(self, batch, blocking: Blocking, config):
        hbm.batch_device(batch, config)
        return batch

    def stack_payloads(self, payloads, blocking: Blocking, config):
        return hbm.stack_block_batches(payloads, config)

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, out = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(out, counts),
        ))

    def compute_batch(self, batch, blocking: Blocking, config):
        db = hbm.batch_device(batch, config)
        out = _minfilter_batch(
            db.arrays[0], tuple(int(f) for f in config["filter_shape"])
        )
        return batch, np.asarray(out)[:db.n]

    def write_batch(self, result, blocking: Blocking, config):
        batch, out = result
        write_block_batch(
            self.output_ds(), batch, out, cast="uint8",
            n_threads=read_threads(config),
        )

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)
