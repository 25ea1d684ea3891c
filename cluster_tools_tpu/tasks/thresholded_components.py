"""Distributed connected components over thresholded volumes.

The reference pipeline (SURVEY.md §3.4, thresholded_components/*.py):

  1. block_components  — per block: threshold (+smooth) → CC label → write local
                         labels, record per-block max id
  2. merge_offsets     — exclusive prefix sum of max ids → per-block offsets
  3. block_faces       — per inter-block face: touching (a+off_a, b+off_b) label
                         pairs
  4. merge_assignments — union-find over all pairs → dense assignment table
  5. write             — apply offsets + assignment (tasks/write.py)

Here step 1 is a device-batched jit program (CC is the pointer-jumping kernel,
one dispatch per block batch); steps 2/4 are host reductions (1-job merge tasks
in the reference too); step 3 reads thin face slabs host-side.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import numpy as np

from ..ops import cc as cc_ops
from ..ops import filters
from ..ops.unionfind import merge_assignments_device, merge_assignments_np
from ..parallel.dispatch import read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils.blocking import Blocking
from .base import (
    VolumeSimpleTask, VolumeTask, count_block_rounds, merge_threads,
    read_ragged_chunks, read_threads,
)

MAX_IDS_KEY = "thresholded_components/max_ids"
FACES_KEY = "thresholded_components/faces"
OFFSETS_NAME = "thresholded_components_offsets.npz"
ASSIGNMENTS_NAME = "thresholded_components_assignments.npy"


@partial(
    jax.jit, static_argnames=("mode", "sigma", "connectivity", "coarse_tile")
)
def _components_batch(batch, threshold, mode, sigma, connectivity,
                      coarse_tile=None):
    """The block components program: ``(labels, n, rounds)`` per block of
    the batch, ``rounds`` the CC's int32 round counts
    (``connected_components(with_rounds=True)``).  The threshold runs under
    the named scope ``cc.threshold``, the CC under those of ``ops/cc.py``."""
    with jax.named_scope("cc.threshold"):
        x = batch
        if sigma:
            x = jax.vmap(lambda b: filters.gaussian(b, sigma))(x)
        if mode == "greater":
            mask = x > threshold
        elif mode == "less":
            mask = x < threshold
        else:
            mask = x == threshold
    return jax.vmap(
        lambda m: cc_ops.connected_components(
            m, connectivity, coarse_tile=coarse_tile, with_rounds=True
        )
    )(mask)


class BlockComponentsTask(VolumeTask):
    """Step 1: per-block CC with local consecutive labels
    (reference block_components.py:25)."""

    task_name = "block_components"
    output_dtype = "uint64"

    def __init__(self, *args, mask_path: str = None, mask_key: str = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.mask_path = mask_path
        self.mask_key = mask_key

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "threshold": 0.5,
                "threshold_mode": "greater",
                "sigma": 0.0,
                "connectivity": 1,
                # ctt-cc coarse-to-fine tile (None = CTT_CC_TILE env pin /
                # backend default — see ops/cc.resolve_coarse_tile)
                "coarse_tile": None,
            }
        )
        return conf

    # -- ctt-stream fusion contract ------------------------------------------
    #
    # As a fused-chain member this task (a) consumes the upstream threshold
    # mask as a device handoff — the mask never round-trips through the
    # store — and (b) carries the downstream merge state forward while its
    # labels are still in memory: per-block max ids (the merge-offsets
    # input) and face-edge equivalence tables (the block-faces output, the
    # same (a, b) value-pair format ops/unionfind.merge_value_table
    # resolves device-side for ctt-cc tile faces).  The chain's ``covers``
    # list then stamps MergeOffsetsTask/BlockFacesTask complete without
    # either re-reading one voxel of the labels volume.

    fusable = True

    def fused_read_batch(self, handoffs, block_ids, blocking: Blocking,
                         config):
        """Payload from the upstream threshold handoff: the device mask
        replaces the store read of the mask dataset (which may be elided
        and never exist).  uint8 0/1 values compare against the 0.5
        default threshold exactly like the float32 store read would."""
        h = handoffs[(self.input_path, self.input_key)]
        from ..parallel.dispatch import BlockBatch

        batch = BlockBatch(
            data=h["labels"], valid=None,
            blocks=list(h["batch"].blocks),
            block_ids=list(h["batch"].block_ids),
        )
        if self.mask_path:
            from ..utils import store as _store

            mask_ds = _store.file_reader(self.mask_path, "r")[self.mask_key]
            masks = [
                mask_ds[bh.outer.slicing].astype(bool) for bh in batch.blocks
            ]
        else:
            masks = None
        return batch, masks

    def fusion_carry_init(self, blocking: Blocking, config):
        return {
            "max_ids": np.zeros(blocking.n_blocks, dtype=np.int64),
            "planes": {},  # (block_id, axis) -> the block's last label plane
            "pairs": {},   # block_id -> axis -> (lo_vals, hi_vals) int64
        }

    def fusion_carry_update(self, carry, result, block_ids,
                            blocking: Blocking, config):
        """Per-slab carry: record each block's max id and its upper
        boundary planes; resolve faces against the carried plane of the
        lower neighbor (already processed — block ids stream in ascending
        C-order, so the carry window is one slab of planes).  Pair values
        stay block-local; offsets are applied at finalize, after the last
        slab fixes the global offset table."""
        if result is None:
            return carry
        batch, labels = result
        for i, bid in enumerate(batch.block_ids):
            bh = batch.blocks[i]
            lab = labels[i][bh.inner_local.slicing]
            carry["max_ids"][bid] = int(lab.max())
            for axis in range(blocking.ndim):
                if blocking.neighbor_id(bid, axis, lower=False) is not None:
                    carry["planes"][(bid, axis)] = np.take(
                        lab, lab.shape[axis] - 1, axis=axis
                    ).astype(np.int64)
                nb = blocking.neighbor_id(bid, axis, lower=True)
                if nb is not None:
                    lo = carry["planes"].pop((nb, axis))
                    hi = np.take(lab, 0, axis=axis).astype(np.int64)
                    both = (lo > 0) & (hi > 0)
                    if both.any():
                        carry["pairs"].setdefault(nb, {})[axis] = (
                            lo[both], hi[both]
                        )
        return carry

    def fusion_carry_nbytes(self, carry) -> int:
        n = carry["max_ids"].nbytes
        n += sum(a.nbytes for a in carry["planes"].values())
        n += sum(
            lo.nbytes + hi.nbytes
            for per_axis in carry["pairs"].values()
            for lo, hi in per_axis.values()
        )
        return n

    def fusion_finalize(self, carry, blocking: Blocking, config) -> None:
        """Write the carried merge state in the exact shape the downstream
        tasks would have produced: the offsets npz (MergeOffsetsTask) and
        one FACES_KEY chunk per block (BlockFacesTask) — byte-identical
        pair tables, so MergeAssignmentsTask and WriteTask run unchanged."""
        import os

        if carry is None:
            return
        max_ids = carry["max_ids"]
        offsets = np.roll(np.cumsum(max_ids), 1)
        offsets[0] = 0
        empty_blocks = np.nonzero(max_ids == 0)[0]
        np.savez(
            os.path.join(self.tmp_folder, OFFSETS_NAME),
            offsets=offsets,
            empty_blocks=empty_blocks,
            n_labels=np.int64(max_ids.sum()),
        )
        faces = self.tmp_ragged(FACES_KEY, blocking.n_blocks, np.int64)
        for bid in range(blocking.n_blocks):
            parts = []
            for axis, ngb_id, _face in blocking.iterate_faces(bid, halo=1):
                got = carry["pairs"].get(bid, {}).get(axis)
                if got is None:
                    continue
                lo, hi = got
                a = lo + offsets[bid]
                b = hi + offsets[ngb_id]
                parts.append(np.unique(np.stack([a, b], axis=1), axis=0))
            out = (
                np.concatenate(parts, axis=0).reshape(-1)
                if parts
                else np.array([], dtype=np.int64)
            )
            faces.write_chunk((bid,), out)

    # -- split batch protocol (three-stage executor pipeline) ---------------

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        # the device cache covers ONLY the input upload (masks are applied
        # host-side after compute, so mask freshness never rides the key)
        batch = read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="float32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("components-read",), config),
        )
        if self.mask_path:
            from ..utils import store as _store

            mask_ds = _store.file_reader(self.mask_path, "r")[self.mask_key]
            masks = [
                mask_ds[bh.outer.slicing].astype(bool) for bh in batch.blocks
            ]
        else:
            masks = None
        return batch, masks

    def upload_batch(self, payload, blocking: Blocking, config):
        batch, masks = payload
        hbm.batch_device(batch, config)
        return payload

    def stack_payloads(self, payloads, blocking: Blocking, config):
        masks = None
        if any(p[1] is not None for p in payloads):
            masks = [m for p in payloads for m in (p[1] or [])]
        return hbm.stack_block_batches(
            [p[0] for p in payloads], config
        ), masks

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, labels = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(labels, counts),
        ))

    def compute_batch(self, payload, blocking: Blocking, config):
        batch, masks = payload
        sigma = config.get("sigma", 0.0) or 0.0
        if isinstance(sigma, list):
            sigma = tuple(sigma)
        db = hbm.batch_device(batch, config)
        n = db.n
        coarse_tile = config.get("coarse_tile", None)
        if coarse_tile is not None and not isinstance(coarse_tile, int):
            coarse_tile = tuple(coarse_tile)
        labels, _, rounds = _components_batch(
            db.arrays[0],
            float(config.get("threshold", 0.5)),
            config.get("threshold_mode", "greater"),
            sigma,
            int(config.get("connectivity", 1)),
            coarse_tile,
        )
        # one device-to-host copy for the labels and the round counts
        labels, rounds = jax.device_get((labels[:n], rounds))
        count_block_rounds({"cc": [rounds]}, n)
        labels = np.array(labels)  # writable host copy (mask edit below)
        if masks is not None:
            for i, m in enumerate(masks):
                sl = tuple(slice(0, s) for s in m.shape)
                labels[i][sl] = np.where(m, labels[i][sl], 0)
        return batch, labels

    def write_batch(self, result, blocking: Blocking, config):
        batch, labels = result
        write_block_batch(
            self.output_ds(), batch, labels, cast="uint64",
            n_threads=read_threads(config),
        )
        max_ids = self.tmp_ragged(MAX_IDS_KEY, blocking.n_blocks, np.int64)
        for i, bid in enumerate(batch.block_ids):
            bh = batch.blocks[i]
            inner = labels[i][bh.inner_local.slicing]
            max_ids.write_chunk((bid,), np.array([inner.max()], dtype=np.int64))

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)


class MergeOffsetsTask(VolumeSimpleTask):
    """Step 2: exclusive prefix sum of per-block max ids
    (reference merge_offsets.py:96-125)."""

    task_name = "merge_offsets"

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 **kwargs):
        super().__init__(*args, input_path=input_path, input_key=input_key,
                         **kwargs)

    def run_impl(self) -> None:
        import os

        from .base import resolve_n_blocks

        n_blocks = resolve_n_blocks(self.config_dir, self.input_path, self.input_key)
        max_ids_ds = self.tmp_store()[MAX_IDS_KEY]
        max_ids = np.zeros(n_blocks, dtype=np.int64)
        for bid, chunk in enumerate(
            read_ragged_chunks(max_ids_ds, n_blocks, merge_threads(self))
        ):
            if chunk is not None:
                max_ids[bid] = chunk[0]
        offsets = np.roll(np.cumsum(max_ids), 1)
        offsets[0] = 0
        empty_blocks = np.nonzero(max_ids == 0)[0]
        out = os.path.join(self.tmp_folder, OFFSETS_NAME)
        np.savez(
            out,
            offsets=offsets,
            empty_blocks=empty_blocks,
            n_labels=np.int64(max_ids.sum()),
        )


def load_offsets(tmp_folder: str):
    import os

    with np.load(os.path.join(tmp_folder, OFFSETS_NAME)) as f:
        return f["offsets"], f["empty_blocks"], int(f["n_labels"])


class BlockFacesTask(VolumeTask):
    """Step 3: cross-block label equivalences over 1-voxel-halo faces
    (reference block_faces.py:87-137)."""

    task_name = "block_faces"
    output_dtype = None  # writes only scratch data

    def process_block(self, block_id: int, blocking: Blocking, config):
        labels_ds = self.input_ds()
        offsets, _, _ = load_offsets(self.tmp_folder)
        pairs = []
        for axis, ngb_id, face in blocking.iterate_faces(block_id, halo=1):
            slab = labels_ds[face.slicing]
            lo, hi = np.split(slab, 2, axis=axis)
            both = (lo > 0) & (hi > 0)
            if not both.any():
                continue
            a = lo[both].astype(np.int64) + offsets[block_id]
            b = hi[both].astype(np.int64) + offsets[ngb_id]
            pairs.append(np.unique(np.stack([a, b], axis=1), axis=0))
        faces = self.tmp_ragged(FACES_KEY, blocking.n_blocks, np.int64)
        out = (
            np.concatenate(pairs, axis=0).reshape(-1)
            if pairs
            else np.array([], dtype=np.int64)
        )
        faces.write_chunk((block_id,), out)


class MergeAssignmentsTask(VolumeSimpleTask):
    """Step 4: global union-find over face pairs → dense assignment table
    (reference merge_assignments.py:88-146)."""

    task_name = "merge_assignments"

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 **kwargs):
        super().__init__(*args, input_path=input_path, input_key=input_key,
                         **kwargs)

    def run_impl(self) -> None:
        import os

        from .base import resolve_n_blocks

        n_blocks = resolve_n_blocks(self.config_dir, self.input_path, self.input_key)
        _, _, n_labels = load_offsets(self.tmp_folder)
        faces = self.tmp_store()[FACES_KEY]
        all_pairs = []
        for chunk in read_ragged_chunks(faces, n_blocks, merge_threads(self)):
            if chunk is not None and chunk.size:
                all_pairs.append(chunk.reshape(-1, 2))
        pairs = (
            np.concatenate(all_pairs, axis=0)
            if all_pairs
            else np.zeros((0, 2), dtype=np.int64)
        )
        conf = {**self.global_config(), **self.get_task_config()}
        merge = (
            merge_assignments_device
            if conf.get("target") == "tpu"
            else merge_assignments_np
        )
        assignment, n_new = merge(n_labels + 1, pairs)
        np.save(os.path.join(self.tmp_folder, ASSIGNMENTS_NAME), assignment)
        self.log(f"merged {n_labels} block-local labels into {n_new} components")


def _np_smooth(raw: np.ndarray, sigma) -> np.ndarray:
    from scipy import ndimage as _ndi

    return _ndi.gaussian_filter(raw.astype("float32"), sigma)


def _smoothed_region(ds, sigma, begin, end) -> np.ndarray:
    """``_np_smooth`` of the whole of ``ds``, cropped to ``[begin, end)``,
    from a read of that box grown by the filter's radius (clipped to the
    volume): inside the volume the halo covers the whole kernel, and at its
    border the filter reflects where it would over the whole volume."""
    sig = np.broadcast_to(np.asarray(sigma, dtype=float), (len(begin),))
    # scipy's gaussian_filter radius at its default truncate of 4.0
    radius = [int(4.0 * s + 0.5) for s in sig]
    lo = [max(b - r, 0) for b, r in zip(begin, radius)]
    hi = [min(e + r, n) for e, r, n in zip(end, radius, ds.shape)]
    raw = _np_smooth(ds[tuple(slice(a, b) for a, b in zip(lo, hi))], sigma)
    return raw[tuple(slice(b - a, e - a) for a, b, e in zip(lo, begin, end))]


def _threshold_host(raw: np.ndarray, threshold: float, mode: str) -> np.ndarray:
    if mode == "greater":
        return raw > threshold
    if mode == "less":
        return raw < threshold
    return raw == threshold


class ShardedComponentsTask(VolumeSimpleTask):
    """Connected components of the job's ROI (the global config's
    ``roi_begin``/``roi_end``; the whole volume without one) over the
    device mesh in ONE jit program — the collective alternative to the
    5-step block pipeline above.

    The ROI is z-sharded over the mesh (its z extent padded with background
    to a multiple of the mesh size).  At ``sigma == 0`` (the default) it
    streams from the store shard-by-shard and each shard thresholds on host
    inside the placement callback (``mesh.put_from_store(box=roi,
    transform=...)``) — peak host RAM on the ingest side is one shard and
    only the 1-byte/voxel bool mask reaches HBM; with ``device_threshold``
    the float ROI crosses and thresholds on device; with smoothing the ROI
    plus the filter's halo is gaussian-filtered on host (scipy) first, which
    equals smoothing the whole volume, and the boolean mask crosses whole.
    Labeling is ``parallel.sharded.sharded_connected_components``: local
    fixpoints per shard, then one exchange of the shard faces over ICI — the
    cross-block merge that steps 2-4 route through the filesystem.  Flat ids
    are local to the ROI, so the int32 bound is the ROI's, not the volume's.
    Bounds: the labels round-trip through host for the consecutive relabel
    (int32/voxel), and the mask must fit the mesh's aggregate HBM; the block
    pipeline remains the truly out-of-core path.  Output is a dataset of the
    volume's shape in which only the ROI's chunks are written: consecutive
    uint64 labels within the job (background 0) matching the block
    pipeline's partition over a block-aligned ROI at ``sigma == 0``; with
    smoothing the two differ at block borders by design — the block path
    smooths each halo-less block (truncating the filter at every block
    boundary, as the reference's block_components does), while this path
    smooths seamlessly.  Host spans ``scc:ingest``, ``scc:program``
    (dispatch to fetched labels), ``scc:relabel`` and ``scc:write``; obs
    counters ``scc.shards``, ``scc.local_rounds`` and ``scc.face_pairs``.
    """

    task_name = "sharded_components"
    collective = True

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 output_path: str = None, output_key: str = None,
                 mask_path: str = None, mask_key: str = None, **kwargs):
        super().__init__(
            *args, input_path=input_path, input_key=input_key,
            output_path=output_path, output_key=output_key,
            mask_path=mask_path, mask_key=mask_key, **kwargs,
        )

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {"threshold": 0.5, "threshold_mode": "greater", "sigma": 0.0,
             "connectivity": 1,
             # ctt-stream: threshold on DEVICE, fused into the collective
             # CC program (parallel.sharded.fused_threshold_components) —
             # HBM holds the float volume instead of the bool mask, but
             # the mask never crosses the host boundary.  Only greater-
             # mode, sigma 0, unmasked; other settings keep the
             # host-threshold ingest transform.
             "device_threshold": False}
        )
        return conf

    @staticmethod
    def _roi(conf, shape):
        """``(begin, end)`` of the global config's ROI clipped to
        ``shape``, or of the whole volume."""
        begin, end = conf.get("roi_begin"), conf.get("roi_end")
        if begin is None and end is None:
            return (0,) * len(shape), tuple(int(s) for s in shape)
        if begin is None or end is None:
            raise ValueError(
                "either both or none of roi_begin / roi_end must be given")
        begin = tuple(max(int(b), 0) for b in begin)
        end = tuple(int(s) if e is None else min(int(e), int(s))
                    for e, s in zip(end, shape))
        if any(e <= b for b, e in zip(begin, end)):
            raise ValueError(f"empty ROI {begin}..{end} in volume {shape}")
        return begin, end

    def run_impl(self) -> None:
        import jax

        from ..obs import metrics as obs_metrics
        from ..obs import trace as obs_trace
        from ..parallel.mesh import (
            fetch_global,
            get_mesh,
            put_from_store,
            put_global,
            resolve_devices,
        )
        from ..parallel.sharded import (
            fused_threshold_components,
            sharded_connected_components,
        )
        from ..utils import store as store_mod

        conf = {**self.global_config(), **self.get_task_config()}
        mode = conf.get("threshold_mode", "greater")
        if mode not in ("greater", "less", "equal"):
            raise ValueError(f"unsupported threshold_mode {mode!r}")
        in_ds = store_mod.file_reader(self.input_path, "r")[self.input_key]
        store_mod.set_read_threads(in_ds, read_threads(conf))
        box = self._roi(conf, in_ds.shape)
        roi = tuple(slice(b, e) for b, e in zip(*box))
        z = box[1][0] - box[0][0]
        devices = resolve_devices(conf)
        mesh = get_mesh(devices)
        n_dev = len(devices)
        threshold = float(conf.get("threshold", 0.5))
        sigma = conf.get("sigma", 0.0) or 0.0  # scalar or per-axis sequence
        connectivity = int(conf.get("connectivity", 1))

        device_threshold = (
            bool(conf.get("device_threshold", False))
            and mode == "greater"
            and threshold >= 0
            and not self.mask_path
            and not np.any(np.asarray(sigma) > 0)
        )
        with obs_trace.span("scc:ingest", kind="host_io"):
            if device_threshold:
                # ctt-stream collective fusion: the raw ROI streams to HBM
                # and thresholds there, feeding the CC program directly —
                # the mask intermediate never exists host-side
                x_d = put_from_store(
                    in_ds, mesh, dtype=np.float32, pad_to=n_dev, box=box,
                )
            elif np.any(np.asarray(sigma) > 0):
                # smoothing runs on host (scipy) over the ROI and its halo
                # — the full-copy path; sigma == 0 streams instead (below)
                raw = _smoothed_region(in_ds, sigma, *box)
                mask = _threshold_host(raw, threshold, mode)
                del raw
                if self.mask_path:
                    m = store_mod.file_reader(
                        self.mask_path, "r")[self.mask_key]
                    mask &= m[roi].astype(bool)
                pad = (-z) % n_dev
                if pad:
                    mask = np.pad(
                        mask, ((0, pad),) + ((0, 0),) * (mask.ndim - 1))
                mask_d = put_global(mask, mesh, dtype=bool)
                del mask
            else:
                # stream shard-by-shard from the store, thresholding each
                # shard on host inside the read callback: peak host RAM is
                # one shard and only the 1-byte/voxel bool mask ever crosses
                # to HBM (ADVICE r2; the zero pad slab is bool False by
                # construction, so no pad-foreground guard is needed for
                # any mode)
                mask_d = put_from_store(
                    in_ds, mesh, dtype=bool, pad_to=n_dev, box=box,
                    transform=lambda part: _threshold_host(
                        part.astype("float32"), threshold, mode
                    ),
                )
                if self.mask_path:
                    m_ds = store_mod.file_reader(
                        self.mask_path, "r")[self.mask_key]
                    m_d = put_from_store(
                        m_ds, mesh, dtype=bool, pad_to=n_dev, box=box)
                    mask_d = jax.jit(jax.numpy.logical_and)(mask_d, m_d)

        with obs_trace.span("scc:program", kind="collective"):
            if device_threshold:
                labels_d, stats = fused_threshold_components(
                    x_d, threshold, mesh=mesh, connectivity=connectivity,
                    with_stats=True,
                )
            else:
                labels_d, stats = sharded_connected_components(
                    mask_d, mesh=mesh, connectivity=connectivity,
                    with_stats=True,
                )
            raw_labels = fetch_global(labels_d)[:z]
        if stats is not None:  # the local fallback counts nothing
            # what the program returned beside its labels: the benchmark
            # divides the stage-1 rounds by the shards
            obs_metrics.inc("scc.shards", n_dev)
            obs_metrics.inc("scc.local_rounds",
                            int(fetch_global(stats["rounds"]).sum()))
            obs_metrics.inc("scc.face_pairs",
                            int(fetch_global(stats["face_pairs"]).sum()))
        self._write_labels(raw_labels, conf, n_dev, in_ds.shape, roi)

    def _write_labels(self, raw_labels, conf, n_dev: int, shape, roi) -> None:
        """Relabel the collective CC result of the ROI consecutively and
        write it into the ROI of the volume-shaped output (shared by the
        host-threshold and device-threshold/fused ingest paths)."""
        import jax

        from ..obs import trace as obs_trace
        from ..utils import store as store_mod

        if jax.process_index() != 0:
            return  # process 0 owns the writes

        # consecutive uint64 ids in root order (matches the block pipeline's
        # relabeling up to partition equality); background -1 → 0 first so the
        # shared helper keeps zero
        from ..ops.relabel import relabel_consecutive_np

        with obs_trace.span("scc:relabel"):
            shifted = np.where(
                raw_labels < 0, 0, raw_labels.astype(np.int64) + 1)
            out, n_labels = relabel_consecutive_np(shifted.astype(np.uint64))

        with obs_trace.span("scc:write", kind="host_io"):
            ds = self.require_output(shape, conf)
            # threaded write of the ROI's chunks (store fast path where a
            # chunk lies inside the ROI)
            store_mod.set_read_threads(ds, read_threads(conf))
            ds[roi] = out
            ds.attrs["n_labels"] = int(n_labels)
        self.log(
            f"sharded CC over {n_dev} devices: {n_labels} components"
        )
