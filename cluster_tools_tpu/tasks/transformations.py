"""Intensity transformations (reference transformations/linear.py:24).

``a*x + b`` applied block-wise, with either one global ``(a, b)`` pair or a
per-z-slice table ``{z: {"a": .., "b": ..}}``; an optional mask restricts the
transform to mask voxels.

TPU mapping: the transform is a pure elementwise program — a batch of blocks is
one jit dispatch; per-slice coefficients become a gathered ``[Z]`` coefficient
vector broadcast over the block (no per-slice python loop, unlike the
reference's ``_transform_block``).  (The reference's affine task is an empty
stub — transformations/affine.py, 0 LoC — and is intentionally not built.)
"""

from __future__ import annotations

import json
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.dispatch import read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils import store
from ..utils.blocking import Blocking
from .base import VolumeTask, read_threads


def load_transformation(trafo_file: str, n_slices: int) -> Dict[str, Any]:
    """Global {'a','b'} or per-slice {'0': {'a','b'}, ...} spec
    (reference linear.py:125-139)."""
    with open(trafo_file) as f:
        trafo = json.load(f)
    if set(trafo.keys()) == {"a", "b"}:
        return {"a": float(trafo["a"]), "b": float(trafo["b"])}
    if len(trafo) != n_slices:
        raise ValueError(
            f"per-slice transformation has {len(trafo)} entries, volume has "
            f"{n_slices} slices"
        )
    return {int(k): {"a": float(v["a"]), "b": float(v["b"])}
            for k, v in trafo.items()}


@jax.jit
def _linear_batch(batch, a_z, b_z, mask):
    """batch: [B, Z, Y, X]; a_z/b_z: [B, Z] per-slice coefficients;
    mask: [B, Z, Y, X] bool (all-true when no mask)."""
    out = a_z[:, :, None, None] * batch + b_z[:, :, None, None]
    return jnp.where(mask, out, batch)


class LinearTransformationTask(VolumeTask):
    task_name = "linear"

    def __init__(
        self,
        *args,
        transformation: str = None,
        mask_path: Optional[str] = None,
        mask_key: Optional[str] = None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.transformation = transformation
        self.mask_path = mask_path
        self.mask_key = mask_key

    def prepare(self, blocking: Blocking, config: Dict[str, Any]) -> None:
        in_ds = self.input_ds()
        f = store.file_reader(self.output_path, "a")
        f.require_dataset(
            self.output_key,
            shape=tuple(blocking.shape),
            dtype=str(in_ds.dtype),
            chunks=tuple(blocking.block_shape),
            compression="gzip",
        )

    def _coefficients(self, blocking: Blocking, block_ids) -> np.ndarray:
        """Per-block per-slice [B, Z] coefficient arrays."""
        n_slices = blocking.shape[0]
        trafo = load_transformation(self.transformation, n_slices)
        bz = blocking.block_shape[0]
        a = np.empty((len(block_ids), bz), dtype=np.float32)
        b = np.empty((len(block_ids), bz), dtype=np.float32)
        if "a" in trafo and isinstance(trafo["a"], float):
            a[:] = trafo["a"]
            b[:] = trafo["b"]
        else:
            for i, bid in enumerate(block_ids):
                z0 = blocking.block(bid).begin[0]
                for dz in range(bz):
                    entry = trafo.get(min(z0 + dz, n_slices - 1))
                    a[i, dz] = entry["a"]
                    b[i, dz] = entry["b"]
        return a, b

    # -- split batch protocol (three-stage executor pipeline) ---------------

    def read_batch(self, block_ids, blocking: Blocking, config):
        # only the input volume routes through the device-buffer cache —
        # coefficients come from the trafo file and the mask from its own
        # dataset, neither covered by the input's store signature
        batch = read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="float32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("linear-read",), config),
        )
        a, b = self._coefficients(blocking, block_ids)

        full_shape = (len(block_ids),) + tuple(blocking.block_shape)
        if self.mask_path:
            mask_ds = store.file_reader(self.mask_path, "r")[self.mask_key]
            mask = np.zeros(full_shape, dtype=bool)
            for i, bh in enumerate(batch.blocks):
                m = mask_ds[bh.outer.slicing].astype(bool)
                mask[i][tuple(slice(0, s) for s in m.shape)] = m
        else:
            mask = np.ones(full_shape, dtype=bool)
        return batch, a, b, mask

    def upload_batch(self, payload, blocking: Blocking, config):
        batch, a, b, mask = payload
        hbm.batch_device(batch, config)
        return payload

    def stack_payloads(self, payloads, blocking: Blocking, config):
        return (
            hbm.stack_block_batches([p[0] for p in payloads], config),
            np.concatenate([p[1] for p in payloads], axis=0),
            np.concatenate([p[2] for p in payloads], axis=0),
            np.concatenate([p[3] for p in payloads], axis=0),
        )

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, out = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(out, counts),
        ))

    def compute_batch(self, payload, blocking: Blocking, config):
        batch, a, b, mask = payload
        from ..parallel.mesh import put_sharded

        db = hbm.batch_device(batch, config)
        ab, _ = put_sharded(np.asarray(a), config)
        bb, _ = put_sharded(np.asarray(b), config)
        mb, _ = put_sharded(mask, config)
        out = _linear_batch(db.arrays[0], ab, bb, mb)
        return batch, np.asarray(out)[:db.n]

    def write_batch(self, result, blocking: Blocking, config):
        batch, out = result
        out_ds = self.output_ds()
        write_block_batch(
            out_ds, batch, out, cast=out_ds.dtype,
            n_threads=read_threads(config),
        )

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)
