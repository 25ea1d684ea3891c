"""Threshold task (reference thresholded_components/threshold.py:21).

Per-block: optional gaussian pre-smoothing, then compare against the threshold.
The batch path stacks blocks and runs one jit program for the whole batch.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import filters
from ..parallel.dispatch import read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils.blocking import Blocking
from .base import VolumeTask, read_threads

_MODES = {
    "greater": jnp.greater,
    "less": jnp.less,
    "equal": jnp.equal,
}


@partial(jax.jit, static_argnames=("mode", "sigma"))
def _threshold_batch(batch: jnp.ndarray, threshold: float, mode: str, sigma):
    x = filters.normalize_input(batch) if batch.dtype != jnp.float32 else batch
    if sigma:
        x = jax.vmap(lambda b: filters.gaussian(b, sigma))(x)
    return _MODES[mode](x, threshold).astype(jnp.uint8)


class ThresholdTask(VolumeTask):
    task_name = "threshold"
    output_dtype = "uint8"
    # ctt-stream: fusable chain member; typically the elided head of a
    # threshold → components chain (the mask never leaves HBM)
    fusable = True

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({"threshold": 0.5, "threshold_mode": "greater", "sigma": 0.0})
        return conf

    # -- ctt-stream fusion contract ------------------------------------------

    def fused_compute_batch(self, payload, blocking: Blocking, config,
                            elided=False):
        """Device handoff for in-chain consumers: the uint8 mask stays a
        sharded device array ([B_padded, *block], plus the real batch
        size); when the mask volume is elided the host materialization is
        skipped entirely — the intermediate never leaves HBM.  The input
        upload routes through the warm device-buffer cache (ctt-hbm), so
        a back-to-back fused serve job on the same volume skips it."""
        batch = payload
        sigma = config.get("sigma", 0.0) or 0.0
        if isinstance(sigma, list):
            sigma = tuple(sigma)
        db = hbm.batch_device(batch, config)
        dev = _threshold_batch(
            db.arrays[0], float(config.get("threshold", 0.5)),
            config.get("threshold_mode", "greater"), sigma,
        )
        handoff = {"batch": batch, "labels": dev, "n": db.n}
        result = None if elided else (batch, np.asarray(dev)[:db.n])
        return result, handoff

    def fused_elided_nbytes(self, handoff, blocking: Blocking, config) -> int:
        # the uint8 mask bytes that were neither written nor re-read
        return sum(
            int(np.prod(bh.inner.shape)) for bh in handoff["batch"].blocks
        )

    # -- split batch protocol (three-stage executor pipeline) ---------------

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        mode = config.get("threshold_mode", "greater")
        if mode not in _MODES:
            raise ValueError(f"unsupported threshold_mode {mode!r}")
        # device_source: raw float32 read, no halo — the kernel params
        # (threshold/sigma) run on device, so the upload is shareable
        # across configs and jobs of the same volume
        return read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="float32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("threshold-read",), config),
        )

    def upload_batch(self, batch, blocking: Blocking, config):
        """ctt-hbm transfer stage: the batch crosses to HBM (through the
        warm device-buffer cache) while the previous batch computes."""
        hbm.batch_device(batch, config)
        return batch

    def stack_payloads(self, payloads, blocking: Blocking, config):
        return hbm.stack_block_batches(payloads, config)

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, labels = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(labels, counts),
        ))

    def compute_batch(self, batch, blocking: Blocking, config):
        sigma = config.get("sigma", 0.0) or 0.0
        if isinstance(sigma, list):
            sigma = tuple(sigma)
        db = hbm.batch_device(batch, config)
        result = _threshold_batch(
            db.arrays[0], float(config.get("threshold", 0.5)),
            config.get("threshold_mode", "greater"), sigma,
        )
        return batch, np.asarray(result)[:db.n]

    def write_batch(self, result, blocking: Blocking, config):
        batch, labels = result
        write_block_batch(
            self.output_ds(), batch, labels, cast="uint8",
            n_threads=read_threads(config),
        )

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)
