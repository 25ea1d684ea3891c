"""ctt-events task: batched event building over a stack of detector frames.

The input volume is ``(n_frames, h, w)`` — axis 0 is the frame stream, a
block is a contiguous run of WHOLE frames (``block_shape[0]`` frames; the
frame axes must be covered by ``block_shape[1:]``, frames are never split).
One block batch becomes one ``(frames, h, w)`` device dispatch through
``ops.events.build_events``.

Outputs: a uint32 per-frame labels volume at ``output_key`` (the same
consecutive-per-frame contract as the kernel) plus ragged per-block event
tables at ``<output_key>_events`` via the varlen chunk path
(``create_ragged_dataset`` — one ``.npy`` per block holding
``(n_clusters, 1 + N_PROPS)`` float64 rows: global frame index +
:data:`~..ops.events.PROP_FIELDS`).

Speaks the full split protocol + ctt-hbm contract (``read_batch`` /
``upload_batch`` / ``stack_payloads`` / ``unstack_results``), so frame
batches ride the three-stage pipeline, the warm device-buffer cache, and
aggregated ``hbm_stack`` dispatch unchanged.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ..ops import events as events_ops
from ..parallel.dispatch import read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils import store
from ..utils.blocking import Blocking
from .base import VolumeTask, read_threads

EVENTS_SUFFIX = "_events"


class EventBuildingTask(VolumeTask):
    task_name = "events"
    output_dtype = "uint32"

    # ctt-stream/ctt-ingest: frames are independent (no cross-block state,
    # no halo), so the task is fusable as-is — the fusion contract
    # defaults (no carry, compute_batch doubling as fused compute) are
    # exact.  ctt-ingest wraps it in a single-member chain to fold frame
    # batches into event tables as they land.
    fusable = True

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({
            "threshold": 0.0,
            "connectivity": 2,
            "max_clusters": events_ops.DEFAULT_MAX_CLUSTERS,
        })
        return conf

    @property
    def events_key(self) -> str:
        return self.output_key + EVENTS_SUFFIX

    def prepare(self, blocking: Blocking, config: Dict[str, Any]) -> None:
        shape = tuple(self.get_shape())
        if len(shape) != 3:
            raise ValueError(
                f"event building expects an (n_frames, h, w) stack, "
                f"got shape {shape}"
            )
        bs = tuple(blocking.block_shape)
        if bs[1] < shape[1] or bs[2] < shape[2]:
            raise ValueError(
                f"block_shape {bs} splits frames of shape {shape[1:]} — "
                f"frames are independent and must stay whole per block "
                f"(use block_shape [frames_per_block, {shape[1]}, "
                f"{shape[2]}])"
            )
        super().prepare(blocking, config)
        store.file_reader(self.output_path, "a").create_ragged_dataset(
            self.events_key, (blocking.n_blocks,), np.float64
        )

    # -- split batch protocol + ctt-hbm contract -----------------------------

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        # raw float32 frame read, no halo: threshold/connectivity run on
        # device (or in the property pass), so the upload is shareable
        # across configs and jobs of the same stream
        return read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="float32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("events-read",), config),
        )

    def upload_batch(self, batch, blocking: Blocking, config):
        hbm.batch_device(batch, config)
        return batch

    def stack_payloads(self, payloads, blocking: Blocking, config):
        return hbm.stack_block_batches(payloads, config)

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, labels, evc, evp = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(labels, counts),
            hbm.split_stacked(evc, counts),
            hbm.split_stacked(evp, counts),
        ))

    def compute_batch(self, batch, blocking: Blocking, config):
        db = hbm.batch_device(batch, config)
        frames = np.asarray(db.arrays[0])[: db.n]
        B, bf, h, w = frames.shape
        labels, counts, props = events_ops.build_events(
            frames.reshape(B * bf, h, w),
            threshold=float(config.get("threshold", 0.0)),
            connectivity=int(config.get("connectivity", 2)),
            max_clusters=config.get("max_clusters"),
        )
        maxc = props.shape[1]
        return (
            batch,
            labels.reshape(B, bf, h, w),
            counts.reshape(B, bf),
            props.reshape(B, bf, maxc, events_ops.N_PROPS),
        )

    def write_batch(self, result, blocking: Blocking, config):
        batch, labels, counts, props = result
        write_block_batch(
            self.output_ds(), batch, labels, cast="uint32",
            n_threads=read_threads(config),
        )
        ev_ds = store.file_reader(self.output_path, "a")[self.events_key]
        for i, bh in enumerate(batch.blocks):
            # only the block's real frames (the batch pads the frame axis
            # to the static block shape; padded frames carry no clusters
            # by construction but are dropped regardless)
            nf = bh.inner.end[0] - bh.inner.begin[0]
            table = events_ops.event_table(counts[i][:nf], props[i][:nf])
            table[:, 0] += bh.inner.begin[0]  # local -> global frame index
            ev_ds.write_chunk((batch.block_ids[i],), table)

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)


def read_event_tables(output_path: str, output_key: str,
                      n_blocks: int) -> np.ndarray:
    """Concatenate every block's ragged event table (rows sorted by global
    frame index) — the client-side helper tests and the CI smoke use to
    check parity against the scipy oracle."""
    ds = store.file_reader(output_path, "r")[output_key + EVENTS_SUFFIX]
    tables = [ds.read_chunk((bid,)) for bid in range(n_blocks)]
    tables = [t for t in tables if t is not None and len(t)]
    if not tables:
        return np.zeros((0, 1 + events_ops.N_PROPS), np.float64)
    out = np.concatenate(tables, axis=0)
    return out[np.argsort(out[:, 0], kind="stable")]
