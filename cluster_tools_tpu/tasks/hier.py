"""ctt-hier tasks: build the merge hierarchy once, re-cut it in milliseconds.

Pipeline shape mirrors the thresholded-components stack (SURVEY.md §3.4),
with the merge TABLE carried beside the labels:

  1. hierarchy_blocks  — per block, ONE fused device program: the
                         threshold → DT-seed → CC → watershed-flood chain
                         (``ops.watershed.dt_watershed``) plus the block's
                         full-adjacency ``(a, b, saddle)`` merge table
                         (``ops.hier.block_merge_table``) over the flood's
                         working input.  Writes block-LOCAL labels, per-
                         block max ids, and the reduced in-block table.
  2. hierarchy_offsets — exclusive prefix sum of max ids → global id
                         offsets (the merge_offsets idiom).
  3. hierarchy_faces   — per inter-block face: label pairs + saddles over
                         the 1-voxel boundary planes (the block-grain
                         analog of the sharded boundary-plane stitching,
                         parallel/sharded.py), in GLOBAL ids.
  4. hierarchy_build   — concat in-block (+offsets) and face tables,
                         reduce to per-pair min saddle, sort by saddle,
                         persist the hierarchy artifact npz beside the
                         labels volume + the identity assignment for step 5.
  5. write             — the existing WriteTask applies offsets (+identity
                         assignment): the labels volume becomes GLOBAL ids,
                         which is exactly what a re-cut gathers through.

Steps 2 and 3 are *covered* when the workflow's fused chain runs
(ctt-stream): ``hierarchy_blocks`` carries max ids and boundary
label/height planes slab-by-slab and finalizes the offsets npz + face
tables from carry — the labels volume is never re-read for stitching.

:class:`ResegmentTask` is the serve-side consumer: load the artifact,
threshold the saddle column, one value-space union-find pass
(``ops.hier.cut_table``), then gather every labels block batch through
the relabel table — block reads ride the warm ctt-hbm DeviceBufferCache,
so a threshold sweep on a serve daemon re-reads and re-uploads nothing.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as obs_metrics
from ..ops import hier as hier_ops
from ..ops import watershed as ws_ops
from ..parallel.dispatch import BlockBatch, read_block_batch, write_block_batch
from ..runtime import hbm
from ..runtime.executor import run_split_batch
from ..utils import store
from ..utils.blocking import Blocking
from .base import (
    VolumeSimpleTask,
    VolumeTask,
    merge_threads,
    read_ragged_chunks,
    read_threads,
    resolve_n_blocks,
)
from .watershed import _normalize_host

HIER_MAX_IDS_KEY = "hier/max_ids"
HIER_PAIRS_KEY = "hier/pairs"            # per block: (k, 2) int64, flattened
HIER_SADDLES_KEY = "hier/saddles"        # per block: (k,) float32
HIER_FACE_PAIRS_KEY = "hier/face_pairs"  # per block: GLOBAL-id pairs
HIER_FACE_SADDLES_KEY = "hier/face_saddles"
HIER_OFFSETS_NAME = "hier_offsets.npz"
HIER_ASSIGNMENTS_NAME = "hier_assignments.npy"


def default_hierarchy_path(output_path: str, output_key: str) -> str:
    """The artifact's default home: beside the labels volume inside its
    container directory (``<output_path>/<output_key>_hierarchy.npz``)."""
    return os.path.join(output_path, f"{output_key}_hierarchy.npz")


def load_hier_offsets(tmp_folder: str):
    with np.load(os.path.join(tmp_folder, HIER_OFFSETS_NAME)) as f:
        return f["offsets"], int(f["n_labels"])


def _working_heights(raw: np.ndarray, config) -> np.ndarray:
    """The flood's working input as the saddle height field: normalize by
    dtype range, optionally invert — a PER-VOXEL transform of the stored
    volume, so host (face stitching) and device (in-block table) land on
    bit-identical values and the field is globally consistent across
    blocks (a per-block normalization would make face saddles depend on
    which side measured them)."""
    x = _normalize_host(np.asarray(raw))
    if config.get("invert_inputs", False):
        x = 1.0 - x
    return x


@lru_cache(maxsize=16)
def _hier_block_kernel(params_key):
    """One jitted program per config: the fused DT-watershed
    (threshold → DT → seeds → hmap → flood → size filter, exactly the
    WatershedTask kernel) PLUS the block's full-adjacency merge table
    over the working input, vmapped over the stacked block batch.  The
    flood rides ``seeded_watershed``'s own dispatch (tile warm start,
    sweep/Pallas mode pins), so hierarchy labels are bit-identical to a
    plain watershed run of the same config."""
    params = dict(params_key)
    invert = bool(params.get("invert_input", False))
    kernel = partial(ws_ops.dt_watershed, **params)

    def one(x, v):
        lab, _ = kernel(x, valid=v)
        h = 1.0 - x if invert else x  # the flood's working height field
        a, b, s = hier_ops.block_merge_table(lab, h)
        # boundary height planes per axis (first, last): the fused-chain
        # carry stitches block faces from these without re-reading raw
        hplanes = []
        for axis in range(x.ndim):
            hplanes.append(jnp.stack(
                [jnp.take(h, 0, axis=axis),
                 jnp.take(h, x.shape[axis] - 1, axis=axis)]
            ))
        return lab, a, b, s, tuple(hplanes)

    return jax.jit(jax.vmap(one))


class HierarchyBlocksTask(VolumeTask):
    """Step 1: per-block flood + full-adjacency merge table (one fused
    dispatch per block batch).  Labels are block-local consecutive ids
    (offsets applied by the write step); the in-block table is reduced to
    per-pair min saddles host-side and stored as ragged chunks."""

    task_name = "hierarchy_blocks"
    output_dtype = "uint64"
    # ctt-stream: single-member fused chain head — carries max ids +
    # boundary planes so offsets/faces are produced from carry, never by
    # re-reading the labels volume
    fusable = True

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "threshold": 0.5,
                "apply_dt_2d": True,
                "apply_ws_2d": True,
                "sigma_seeds": 2.0,
                "sigma_weights": 2.0,
                "alpha": 0.8,
                "size_filter": 25,
                "invert_inputs": False,
                "non_maximum_suppression": False,
            }
        )
        return conf

    @staticmethod
    def _kernel_params(config) -> Dict[str, Any]:
        return dict(
            threshold=float(config["threshold"]),
            apply_dt_2d=bool(config.get("apply_dt_2d", True)),
            apply_ws_2d=bool(config.get("apply_ws_2d", True)),
            sigma_seeds=float(config.get("sigma_seeds", 2.0)),
            sigma_weights=float(config.get("sigma_weights", 2.0)),
            alpha=float(config.get("alpha", 0.8)),
            size_filter=int(config.get("size_filter", 25)),
            invert_input=bool(config.get("invert_inputs", False)),
            non_maximum_suppression=bool(
                config.get("non_maximum_suppression", False)
            ),
        )

    # -- split batch protocol ------------------------------------------------

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        return read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="float32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("hier-read",), config),
        )

    def upload_batch(self, batch, blocking: Blocking, config):
        hbm.batch_device(batch, config)
        return batch

    def stack_payloads(self, payloads, blocking: Blocking, config):
        return hbm.stack_block_batches(payloads, config)

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, labels, tables, hplanes = result
        hps, off = [], 0
        for c in counts:
            # per-axis plane shapes differ, so hplanes is a tuple of
            # per-axis [B, 2, *plane] arrays sliced along the batch axis
            hps.append(tuple(arr[off: off + c] for arr in hplanes))
            off += c
        return [
            (b, lab, tab, hp)
            for b, lab, tab, hp in zip(
                hbm.split_block_batch(batch, counts),
                hbm.split_stacked(labels, counts),
                hbm.split_stacked(tables, counts),
                hps,
            )
        ]

    def compute_batch(self, batch, blocking: Blocking, config):
        db = hbm.batch_device(batch, config)
        n = db.n
        kernel = _hier_block_kernel(
            tuple(sorted(self._kernel_params(config).items()))
        )
        valid = _valid_masks(batch, blocking)
        vb, _ = _put(valid, config)
        lab, a, b, s, hplanes = kernel(db.arrays[0], vb)
        labels = np.asarray(lab)[:n].astype(np.int64)
        tables = np.stack(
            [np.asarray(a)[:n], np.asarray(b)[:n], np.asarray(s)[:n]],
            axis=1,
        )  # [B, 3, E] raw columns (float64 holds the ids exactly);
        #    reduced to per-pair min saddles per block in write_batch
        hp = tuple(
            np.asarray(p)[:n] for p in hplanes
        )  # per axis: [B, 2, *plane] (first, last) working-height planes
        return batch, labels, tables, hp

    def write_batch(self, result, blocking: Blocking, config):
        batch, labels, tables, _hplanes = result
        write_block_batch(
            self.output_ds(), batch, labels, cast="uint64",
            n_threads=read_threads(config),
        )
        max_ids = self.tmp_ragged(HIER_MAX_IDS_KEY, blocking.n_blocks, np.int64)
        pairs_ds = self.tmp_ragged(HIER_PAIRS_KEY, blocking.n_blocks, np.int64)
        sad_ds = self.tmp_ragged(
            HIER_SADDLES_KEY, blocking.n_blocks, np.float32
        )
        for i, bid in enumerate(batch.block_ids):
            bh = batch.blocks[i]
            inner = labels[i][bh.inner_local.slicing]
            max_ids.write_chunk((bid,), np.array([inner.max()], np.int64))
            pairs, saddles = hier_ops.reduce_merge_table(
                tables[i][0], tables[i][1], tables[i][2]
            )
            pairs_ds.write_chunk((bid,), pairs.reshape(-1))
            sad_ds.write_chunk((bid,), saddles)
            obs_metrics.inc("hier.tables_built")

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)

    # -- ctt-stream fusion carry (covers offsets + faces) --------------------
    #
    # The carry is the hier analog of BlockComponentsTask's: per-block max
    # ids plus the block's boundary label AND height planes, resolved
    # against the lower neighbor's carried planes as blocks stream through
    # in ascending C-order (one slab of planes in memory).  Heights ride
    # the kernel's own working-input planes, so a warm serve job whose
    # read stage skipped the host read entirely still stitches correctly.

    def fusion_carry_init(self, blocking: Blocking, config):
        return {
            "max_ids": np.zeros(blocking.n_blocks, dtype=np.int64),
            "planes": {},  # (block_id, axis) -> (label_plane, height_plane)
            "faces": {},   # block_id -> axis -> (pairs, saddles) LOCAL ids
        }

    def fusion_carry_update(self, carry, result, block_ids,
                            blocking: Blocking, config):
        if result is None:
            return carry
        batch, labels, _tables, hplanes = result
        for i, bid in enumerate(batch.block_ids):
            bh = batch.blocks[i]
            lab = labels[i][bh.inner_local.slicing]
            carry["max_ids"][bid] = int(lab.max())
            for axis in range(blocking.ndim):
                first, last = hplanes[axis][i]
                size = tuple(e - b for b, e in zip(bh.inner.begin, bh.inner.end))
                crop = tuple(
                    slice(0, s) for d, s in enumerate(size) if d != axis
                )
                if blocking.neighbor_id(bid, axis, lower=False) is not None:
                    carry["planes"][(bid, axis)] = (
                        np.take(lab, lab.shape[axis] - 1, axis=axis),
                        last[crop],
                    )
                nb = blocking.neighbor_id(bid, axis, lower=True)
                if nb is not None:
                    lo_lab, lo_h = carry["planes"].pop((nb, axis))
                    hi_lab = np.take(lab, 0, axis=axis)
                    hi_h = first[crop]
                    pairs, saddles = hier_ops.merge_face_pairs(
                        lo_lab, hi_lab, lo_h, hi_h
                    )
                    if pairs.size:
                        carry["faces"].setdefault(nb, {})[axis] = (
                            pairs, saddles
                        )
        return carry

    def fusion_carry_nbytes(self, carry) -> int:
        n = carry["max_ids"].nbytes
        n += sum(
            la.nbytes + h.nbytes for la, h in carry["planes"].values()
        )
        n += sum(
            p.nbytes + s.nbytes
            for per_axis in carry["faces"].values()
            for p, s in per_axis.values()
        )
        return n

    def fusion_finalize(self, carry, blocking: Blocking, config) -> None:
        """Write the offsets npz (HierarchyOffsetsTask's output) and the
        GLOBAL-id face tables (HierarchyFacesTask's chunks) from carry —
        the covered tasks are stamped complete without re-reading one
        voxel of the labels volume."""
        if carry is None:
            return
        max_ids = carry["max_ids"]
        offsets = np.roll(np.cumsum(max_ids), 1)
        offsets[0] = 0
        np.savez(
            os.path.join(self.tmp_folder, HIER_OFFSETS_NAME),
            offsets=offsets,
            n_labels=np.int64(max_ids.sum()),
        )
        fp = self.tmp_ragged(
            HIER_FACE_PAIRS_KEY, blocking.n_blocks, np.int64
        )
        fs = self.tmp_ragged(
            HIER_FACE_SADDLES_KEY, blocking.n_blocks, np.float32
        )
        for bid in range(blocking.n_blocks):
            parts_p, parts_s = [], []
            for axis, ngb_id, _face in blocking.iterate_faces(bid, halo=1):
                got = carry["faces"].get(bid, {}).get(axis)
                if got is None:
                    continue
                pairs, saddles = got
                glob = pairs + np.array(
                    [[offsets[bid], offsets[ngb_id]]], np.int64
                )
                parts_p.append(glob)
                parts_s.append(saddles)
            if parts_p:
                pairs = np.concatenate(parts_p, axis=0)
                saddles = np.concatenate(parts_s)
            else:
                pairs = np.zeros((0, 2), np.int64)
                saddles = np.zeros((0,), np.float32)
            fp.write_chunk((bid,), pairs.reshape(-1))
            fs.write_chunk((bid,), saddles)


def _put(arr: np.ndarray, config):
    from ..parallel.mesh import put_sharded

    return put_sharded(arr, config)


def _valid_masks(batch: BlockBatch, blocking: Blocking) -> np.ndarray:
    """Boolean valid masks of a (possibly edge-clipped) halo-less block
    batch, built from geometry alone — a warm device-cache probe hit
    (``batch.data is None``) must not force a host read just for masks."""
    full = tuple(blocking.block_shape)
    out = np.zeros((len(batch.blocks),) + full, dtype=bool)
    for i, bh in enumerate(batch.blocks):
        size = tuple(e - b for b, e in zip(bh.outer.begin, bh.outer.end))
        out[i][tuple(slice(0, s) for s in size)] = True
    return out


class HierarchyOffsetsTask(VolumeSimpleTask):
    """Step 2: exclusive prefix sum of per-block max ids (the
    merge_offsets idiom over the hier scratch keys)."""

    task_name = "hierarchy_offsets"

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 **kwargs):
        super().__init__(*args, input_path=input_path, input_key=input_key,
                         **kwargs)

    def run_impl(self) -> None:
        n_blocks = resolve_n_blocks(
            self.config_dir, self.input_path, self.input_key
        )
        max_ids_ds = self.tmp_store()[HIER_MAX_IDS_KEY]
        max_ids = np.zeros(n_blocks, dtype=np.int64)
        for bid, chunk in enumerate(
            read_ragged_chunks(max_ids_ds, n_blocks, merge_threads(self))
        ):
            if chunk is not None:
                max_ids[bid] = chunk[0]
        offsets = np.roll(np.cumsum(max_ids), 1)
        offsets[0] = 0
        np.savez(
            os.path.join(self.tmp_folder, HIER_OFFSETS_NAME),
            offsets=offsets,
            n_labels=np.int64(max_ids.sum()),
        )


class HierarchyFacesTask(VolumeTask):
    """Step 3: cross-block hierarchy edges over 1-voxel faces, in GLOBAL
    ids — the labels slab comes from the blocks volume, the saddle
    heights from the raw volume under the same per-voxel transform the
    kernel used (``heights_path/key``)."""

    task_name = "hierarchy_faces"
    output_dtype = None  # writes only scratch ragged chunks

    def __init__(self, *args, heights_path: str = None,
                 heights_key: str = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.heights_path = heights_path
        self.heights_key = heights_key

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({"invert_inputs": False})
        return conf

    def process_block(self, block_id: int, blocking: Blocking, config):
        labels_ds = self.input_ds()
        heights_ds = store.file_reader(self.heights_path, "r")[
            self.heights_key
        ]
        offsets, _ = load_hier_offsets(self.tmp_folder)
        parts_p, parts_s = [], []
        for axis, ngb_id, face in blocking.iterate_faces(block_id, halo=1):
            slab = labels_ds[face.slicing].astype(np.int64)
            h_slab = _working_heights(heights_ds[face.slicing], config)
            lo, hi = np.split(slab, 2, axis=axis)
            h_lo, h_hi = np.split(h_slab, 2, axis=axis)
            pairs, saddles = hier_ops.merge_face_pairs(lo, hi, h_lo, h_hi)
            if pairs.size:
                parts_p.append(pairs + np.array(
                    [[offsets[block_id], offsets[ngb_id]]], np.int64
                ))
                parts_s.append(saddles)
        fp = self.tmp_ragged(HIER_FACE_PAIRS_KEY, blocking.n_blocks, np.int64)
        fs = self.tmp_ragged(
            HIER_FACE_SADDLES_KEY, blocking.n_blocks, np.float32
        )
        if parts_p:
            pairs = np.concatenate(parts_p, axis=0)
            saddles = np.concatenate(parts_s)
        else:
            pairs = np.zeros((0, 2), np.int64)
            saddles = np.zeros((0,), np.float32)
        fp.write_chunk((block_id,), pairs.reshape(-1))
        fs.write_chunk((block_id,), saddles)


class BuildHierarchyTask(VolumeSimpleTask):
    """Step 4: globalize + persist.  In-block tables get their block's
    offset, concat with the (already global) face tables, reduce to the
    per-pair minimum saddle, sort by saddle, save the artifact npz beside
    the labels volume plus the identity assignment the write step
    applies."""

    task_name = "hierarchy_build"

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 hierarchy_path: str = None, **kwargs):
        super().__init__(*args, input_path=input_path, input_key=input_key,
                         **kwargs)
        self.hierarchy_path = hierarchy_path

    def run_impl(self) -> None:
        n_blocks = resolve_n_blocks(
            self.config_dir, self.input_path, self.input_key
        )
        gconf = self.global_config()
        offsets, n_labels = load_hier_offsets(self.tmp_folder)
        tmp = self.tmp_store()
        threads = merge_threads(self)
        pairs_chunks = read_ragged_chunks(
            tmp[HIER_PAIRS_KEY], n_blocks, threads
        )
        sad_chunks = read_ragged_chunks(
            tmp[HIER_SADDLES_KEY], n_blocks, threads
        )
        fp_chunks = read_ragged_chunks(
            tmp[HIER_FACE_PAIRS_KEY], n_blocks, threads
        )
        fs_chunks = read_ragged_chunks(
            tmp[HIER_FACE_SADDLES_KEY], n_blocks, threads
        )
        all_pairs, all_saddles = [], []
        for bid in range(n_blocks):
            p = pairs_chunks[bid]
            if p is not None and p.size:
                all_pairs.append(p.reshape(-1, 2) + offsets[bid])
                all_saddles.append(sad_chunks[bid])
            fpc = fp_chunks[bid]
            if fpc is not None and fpc.size:
                all_pairs.append(fpc.reshape(-1, 2))
                all_saddles.append(fs_chunks[bid])
        if all_pairs:
            pairs = np.concatenate(all_pairs, axis=0)
            saddles = np.concatenate(all_saddles)
            pairs, saddles = hier_ops.reduce_merge_table(
                pairs[:, 0], pairs[:, 1], saddles
            )
        else:
            pairs = np.zeros((0, 2), np.int64)
            saddles = np.zeros((0,), np.float32)
        shape = store.file_reader(self.input_path, "r")[
            self.input_key
        ].shape
        hier_ops.save_hierarchy(
            self.hierarchy_path, pairs, saddles, n_labels,
            shape, gconf["block_shape"],
        )
        # identity assignment: the write step's dense lookup (global id ->
        # global id) — the hierarchy renames nothing at build time
        np.save(
            os.path.join(self.tmp_folder, HIER_ASSIGNMENTS_NAME),
            np.arange(n_labels + 1, dtype=np.uint64),
        )
        obs_metrics.inc("hier.edges", int(pairs.shape[0]))
        self.log(
            f"hierarchy: {n_labels} regions, {pairs.shape[0]} saddle edges "
            f"-> {self.hierarchy_path}"
        )


@jax.jit
def _recut_batch(labels, vals, roots):
    """One gather per block batch: the whole re-segmentation dispatch."""
    return hier_ops.recut_labels(labels, vals, roots)


class ResegmentTask(VolumeTask):
    """Re-segment a hierarchy-built labels volume at one merge threshold:
    load the sorted artifact, select + union-find the edges ≤ threshold
    ONCE (``prepare``), then every block batch is one relabel gather.

    The input labels read carries a ctt-hbm ``device_source``: on a warm
    serve daemon a threshold sweep probes the SAME (volume, blocks,
    dtype) cache lines every job, so after the first job neither host
    reads nor HBM uploads happen — only the gather and the output write.

    ``write_volume: false`` (the interactive-sweep mode) skips the volume
    pass entirely and persists the resolved RELABEL TABLE instead
    (``<output_key>_cut.npz`` beside the hierarchy artifact,
    ``ops.hier.save_cut_table``): a proofreading client applies the table
    to whatever view it holds (``ops.hier.apply_cut_np`` / one device
    gather), so a sweep step costs one searchsorted + one union-find pass
    over the selected edges — milliseconds — while the full-volume gather
    stays one volume-mode job away for the threshold the user commits to.
    """

    task_name = "resegment"
    output_dtype = "uint64"

    # ids at/above this overflow the device gather's int32 — class-level
    # so tests can fake a tiny limit to exercise the host fallback
    INT32_LIMIT = int(np.iinfo(np.int32).max)

    def __init__(self, *args, hierarchy_path: str = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.hierarchy_path = hierarchy_path
        self._cut = None
        self._cut_ready = False
        self._n_labels = 0
        self._host_relabel = False

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({"threshold": 0.5, "write_volume": True})
        return conf

    def cut_table_path(self) -> str:
        return os.path.join(
            self.output_path, f"{self.output_key}_cut.npz"
        )

    def get_block_list(self, blocking, gconf):
        tconf = self.get_task_config()
        if not tconf.get("write_volume", True):
            return []  # table mode: no volume pass at all
        return super().get_block_list(blocking, gconf)

    def _resolve_cut(self, art, threshold: float):
        """Pick the cut path from the hierarchy size: device value-space
        union-find (int32 gather) below :attr:`INT32_LIMIT`, host int64
        union-find + numpy gather at/above it — a LOUD downgrade, never a
        silent wrong answer (int32 ids past 2^31 wrap negative)."""
        self._n_labels = int(art["n_labels"])
        self._host_relabel = self._n_labels >= self.INT32_LIMIT
        if self._host_relabel:
            import warnings

            msg = (
                f"hierarchy holds {self._n_labels} regions (>= "
                f"{self.INT32_LIMIT}): int32 device gather would "
                "overflow — downgrading to the HOST relabel path "
                "(int64 numpy gather, no HBM cache)"
            )
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
            self.log(f"resegment: {msg}")
            return hier_ops.cut_table_np(
                art["a"], art["b"], art["saddle"], threshold
            )
        return hier_ops.cut_table(
            art["a"], art["b"], art["saddle"], threshold
        )

    def prepare(self, blocking: Blocking, config) -> None:
        if config.get("write_volume", True):
            super().prepare(blocking, config)  # the output dataset
        art = hier_ops.load_hierarchy(self.hierarchy_path)
        threshold = float(config["threshold"])
        self._cut = self._resolve_cut(art, threshold)
        self._cut_ready = True
        k = int(np.searchsorted(
            art["saddle"], np.float32(threshold), side="right"
        ))
        obs_metrics.inc("hier.cut_edges", k)
        self.log(
            f"resegment @ t={threshold}: {k}/{art['saddle'].size} edges "
            "selected"
        )

    def finalize(self, blocking: Blocking, config, block_ids) -> None:
        if config.get("write_volume", True):
            return
        hier_ops.save_cut_table(
            self.cut_table_path(), float(config["threshold"]),
            self._cut, self._n_labels,
        )

    def _require_cut(self, config):
        # per-block fallback / local target reach compute without the
        # blockwise run() having called prepare on THIS instance state
        if not self._cut_ready:
            art = hier_ops.load_hierarchy(self.hierarchy_path)
            self._cut = self._resolve_cut(art, float(config["threshold"]))
            self._cut_ready = True
        return self._cut

    # -- split batch protocol ------------------------------------------------

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        self._require_cut(config)  # mode decided before the read dtype
        if self._host_relabel:
            # int64 ids, no device_source: the host path never uploads
            return read_block_batch(
                self.input_ds(), blocking, block_ids, dtype="int64",
                n_threads=read_threads(config),
            )
        return read_block_batch(
            self.input_ds(), blocking, block_ids, dtype="int32",
            n_threads=read_threads(config),
            device_source=(self.input_path, self.input_key,
                           ("hier-labels",), config),
        )

    def upload_batch(self, batch, blocking: Blocking, config):
        if not self._host_relabel:
            hbm.batch_device(batch, config)
        return batch

    def stack_payloads(self, payloads, blocking: Blocking, config):
        if self._host_relabel:
            if len(payloads) == 1:
                return payloads[0]
            return BlockBatch(
                data=np.concatenate([p.data for p in payloads], axis=0),
                valid=np.concatenate([p.valid for p in payloads], axis=0),
                blocks=[bh for p in payloads for bh in p.blocks],
                block_ids=[i for p in payloads for i in p.block_ids],
            )
        return hbm.stack_block_batches(payloads, config)

    def unstack_results(self, result, counts, blocking: Blocking, config):
        batch, labels = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(labels, counts),
        ))

    def compute_batch(self, batch, blocking: Blocking, config):
        import jax.numpy as jnp

        cut = self._require_cut(config)
        if self._host_relabel:
            labels = np.asarray(batch.data, np.int64)
            if cut is None:
                return batch, labels
            vals, roots = cut
            return batch, hier_ops.apply_cut_np(labels, vals, roots)
        db = hbm.batch_device(batch, config)
        labels = db.arrays[0]
        if cut is None:  # identity cut: nothing below the threshold
            return batch, np.asarray(labels)[:db.n]
        vals, roots = cut
        out = _recut_batch(
            labels, jnp.asarray(vals), jnp.asarray(roots)
        )
        return batch, np.asarray(out)[:db.n]

    def write_batch(self, result, blocking: Blocking, config):
        batch, labels = result
        write_block_batch(
            self.output_ds(), batch, labels, cast="uint64",
            n_threads=read_threads(config),
        )

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)
