"""Watershed tasks — the north-star hot path.

Reference watershed/watershed.py:39-394 and two_pass_watershed.py:32-99:
per halo'd block, run the DT-watershed, crop the inner box, re-close labels by
CC, add the block's id offset (``block_id * prod(block_shape)``), write.  The
two-pass variant runs checkerboard halves so pass-2 blocks can seed from their
already-written pass-1 neighbors, giving boundary-consistent labels without a
stitching step.

TPU design: the whole per-block pipeline is one fused jit program
(``ops.watershed.dt_watershed``), vmapped over a stacked block batch; IO,
offsets and uint64 conversion stay on the host.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import watershed as ws_ops
from ..ops.cc import connected_components_labels
from ..parallel.mesh import put_sharded
from ..runtime.executor import run_split_batch
from ..utils import store
from ..utils.blocking import Blocking, make_checkerboard_block_lists
from .base import (
    VolumeSimpleTask, VolumeTask, count_block_rounds, read_threads,
)

MAX_IDS_KEY = "watershed/max_ids"


@lru_cache(maxsize=32)
def _fused_ws_kernel(params_key, block_shape, with_mask: bool, crop_cc: bool,
                     coarse_tile=None):
    """One jitted program per config: flood → per-block dynamic-slice crop to
    the inner box → CC re-close (reference watershed.py:329-333), vmapped
    over the stacked block batch.  Returns ``(labels, rounds)``: the
    per-block int32 round counts of ``dt_watershed(with_rounds=True)``, the
    re-close's CC rounds among ``rounds["cc"]`` (see ``count_block_rounds``).
    The re-close runs under the named scope ``ws.reclose``.

    Fusing the crop+CC into the flood dispatch removes two host↔device
    round-trips of the full batch per stage and runs the CC on the cropped
    extent only — 2×halo fewer voxels per axis than the padded outer shape.  The crop window is
    the static ``block_shape`` anchored at each block's inner-local origin;
    for edge blocks the window tail covers zero padding (masked out of the
    flood by ``valid``), which the partition-CC ignores as background."""
    from jax import lax

    kernel = partial(ws_ops.dt_watershed, **dict(params_key))
    bs = tuple(block_shape)

    def one(x, v, start, m):
        if with_mask:
            lab, _, rounds = kernel(x, mask=m, valid=v, with_rounds=True)
        else:
            lab, _, rounds = kernel(x, valid=v, with_rounds=True)
        if crop_cc:
            with jax.named_scope("ws.reclose"):
                lab = lax.dynamic_slice(
                    lab, (start[0], start[1], start[2]), bs
                )
                # re-close through the ctt-cc kernel: the same
                # connected_components() dispatch as every other CC call
                # site (coarse_tile config knob > CTT_CC_TILE pin > backend
                # default)
                lab, _, cc_rounds = connected_components_labels(
                    lab, coarse_tile=coarse_tile, with_rounds=True
                )
            rounds = dict(rounds, cc=rounds["cc"] + [cc_rounds])
        return lab, rounds

    if with_mask:
        return jax.jit(jax.vmap(one))
    return jax.jit(jax.vmap(lambda x, v, s: one(x, v, s, None)))


def _read_input_block(ds, bb, config):
    """Read a (possibly multi-channel) block, normalize integer dtypes to [0,1]
    and agglomerate channels (reference ``_read_data``, watershed.py:268-283
    incl. vu.normalize)."""
    if ds.ndim == 4:
        c0 = config.get("channel_begin", 0)
        c1 = config.get("channel_end", None)
        data = ds[(slice(c0, c1),) + bb]
        data = _normalize_host(data)
        agglo = config.get("agglomerate_channels", "mean")
        if agglo == "max":
            data = data.max(axis=0)
        else:
            data = data.mean(axis=0)
        return data
    return _normalize_host(ds[bb])


def _pad_block(arr: np.ndarray, full_shape, mode: str = "edge") -> np.ndarray:
    """Pad a clipped edge-block read up to the static batch shape.

    ``mode='edge'`` (data, masks) replicates border values — constant
    background padding would inject fake boundaries into the distance
    transform at volume borders (the reference reads clipped arrays and lets
    vigra reflect at edges).  Label/seed arrays pad with zeros instead
    (``mode='zero'``): replicated labels would invent seeds."""
    pad = [(0, fs - s) for fs, s in zip(full_shape, arr.shape)]
    if not any(p for _, p in pad):
        return arr
    if mode == "zero":
        return np.pad(arr, pad)
    return np.pad(arr, pad, mode=mode)


def _normalize_host(data: np.ndarray) -> np.ndarray:
    """uint8/uint16 → [0,1] by dtype range; other dtypes cast to float32
    (integer boundary maps would otherwise be thresholded meaninglessly)."""
    if data.dtype == np.uint8:
        return data.astype(np.float32) / 255.0
    if data.dtype == np.uint16:
        return data.astype(np.float32) / 65535.0
    return data.astype(np.float32)


class WatershedTask(VolumeTask):
    task_name = "watershed"
    output_dtype = "uint64"
    # ctt-stream: fusable chain member reading the raw boundary map — in a
    # fused chain it shares the head's store read (its halo'd outer boxes
    # ARE the chain's shared read; smaller-halo members get crops)
    fusable = True

    def __init__(self, *args, mask_path: str = None, mask_key: str = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.mask_path = mask_path
        self.mask_key = mask_key

    def fusion_halo(self, config):
        return tuple(config.get("halo") or [0, 0, 0])

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        # mirrors the reference's knobs (watershed.py:50-61)
        conf.update(
            {
                "threshold": 0.5,
                "apply_dt_2d": True,
                "apply_ws_2d": True,
                "pixel_pitch": None,
                "sigma_seeds": 2.0,
                "sigma_weights": 2.0,
                "size_filter": 25,
                "alpha": 0.8,
                "halo": [0, 0, 0],
                "invert_inputs": False,
                "channel_begin": 0,
                "channel_end": None,
                "agglomerate_channels": "mean",
                "non_maximum_suppression": False,
                # ctt-cc tile for the halo-crop CC re-close (None =
                # CTT_CC_TILE env pin / backend default)
                "coarse_tile": None,
            }
        )
        return conf

    # -- kernel dispatch -----------------------------------------------------

    @staticmethod
    def _kernel_params(config) -> Dict[str, Any]:
        pitch = config.get("pixel_pitch")
        return dict(
            threshold=float(config["threshold"]),
            apply_dt_2d=bool(config.get("apply_dt_2d", True)),
            apply_ws_2d=bool(config.get("apply_ws_2d", True)),
            pixel_pitch=tuple(pitch) if pitch else None,
            sigma_seeds=float(config.get("sigma_seeds", 2.0)),
            sigma_weights=float(config.get("sigma_weights", 2.0)),
            alpha=float(config.get("alpha", 0.8)),
            size_filter=int(config.get("size_filter", 25)),
            invert_input=bool(config.get("invert_inputs", False)),
            non_maximum_suppression=bool(config["non_maximum_suppression"]),
        )

    def _load_mask_batch(self, batch, full_shape) -> Optional[np.ndarray]:
        if not self.mask_path:
            return None
        from .base import fusion_wrap

        mask_ds = fusion_wrap(
            store.file_reader(self.mask_path, "r")[self.mask_key],
            self.mask_path, self.mask_key,
        )
        return np.stack([
            _pad_block(mask_ds[bh.outer.slicing].astype(bool), full_shape)
            for bh in batch.blocks
        ])

    # -- split batch protocol (three-stage executor pipeline) ---------------

    def _read_tag(self, config):
        """Device-cache transform tag: everything that changes the bytes
        ``read_batch`` uploads (channel window + agglomeration; the
        normalization is dtype-determined)."""
        return (
            "ws-read",
            str(config.get("channel_begin", 0)),
            str(config.get("channel_end")),
            str(config.get("agglomerate_channels", "mean")),
        )

    def read_batch(self, block_ids: List[int], blocking: Blocking, config):
        """Stage 1: read (channel-agglomerated) halo'd blocks + masks.
        With the warm device-buffer cache armed (ctt-hbm) and the batch's
        upload still HBM-resident from a previous job, the host read is
        skipped entirely — the payload carries only geometry + masks."""
        from ..parallel.dispatch import BlockBatch
        from ..runtime import hbm

        in_ds = self.input_ds()
        halo = config.get("halo") or [0, 0, 0]
        full_shape = tuple(
            bs + 2 * h for bs, h in zip(blocking.block_shape, halo)
        )
        blocks = [blocking.block_with_halo(bid, halo) for bid in block_ids]
        source = hbm.dataset_source(
            in_ds, self.input_path, self.input_key, blocking,
            list(block_ids), halo, self._read_tag(config), config,
        )
        if source is not None:
            dc = hbm.cache()
            hit = dc.get(source) if dc is not None else None
            if hit is not None:
                from ..obs import metrics as obs_metrics

                obs_metrics.inc("device.uploads_skipped")
                batch = BlockBatch(
                    data=None, valid=None, blocks=blocks,
                    block_ids=list(block_ids), source=source, device=hit,
                )
                return batch, None, self._load_mask_batch(batch, full_shape)
        datas, valids = [], []
        for bh in blocks:
            arr = _read_input_block(in_ds, bh.outer.slicing, config)
            datas.append(_pad_block(arr, full_shape))
            v = np.ones(arr.shape, dtype=bool)
            valids.append(_pad_block(v, full_shape, mode="zero"))
        batch_arr = np.stack(datas)
        valid_arr = np.stack(valids)

        batch = BlockBatch(
            data=batch_arr, valid=None, blocks=blocks,
            block_ids=list(block_ids), source=source,
        )
        return batch, valid_arr, self._load_mask_batch(batch, full_shape)

    def _device_payload(self, batch, valid_arr, config):
        """(data, valid, starts) on device through the warm buffer cache —
        all three are deterministic functions of the signed store region
        plus geometry, so they ride one cache entry; the mask (its own
        dataset, its own freshness) is uploaded uncached per compute."""
        from ..runtime import hbm

        def build():
            data = hbm.require_data(batch)
            starts = np.array(
                [bh.inner_local.begin for bh in batch.blocks], dtype=np.int32
            )
            xb, n = put_sharded(data, config)
            vb, _ = put_sharded(valid_arr, config)
            sb, _ = put_sharded(starts, config)
            return hbm.DeviceBatch(
                arrays=(xb, vb, sb), n=n,
                nbytes=int(data.nbytes + valid_arr.nbytes + starts.nbytes),
            )

        return hbm.batch_device(batch, config, build=build)

    def upload_batch(self, payload, blocking: Blocking, config):
        """ctt-hbm transfer stage: batch k+1 crosses to HBM while batch
        k's flood runs."""
        batch, valid_arr, _mask = payload
        self._device_payload(batch, valid_arr, config)
        return payload

    def stack_payloads(self, payloads, blocking: Blocking, config):
        from ..runtime import hbm

        batch = hbm.stack_block_batches([p[0] for p in payloads], config)
        valids = [p[1] for p in payloads]
        valid = (
            np.concatenate(valids, axis=0)
            if all(v is not None for v in valids) else None
        )
        masks = [p[2] for p in payloads]
        mask = (
            np.concatenate(masks, axis=0)
            if all(m is not None for m in masks) else None
        )
        return batch, valid, mask

    def unstack_results(self, result, counts, blocking: Blocking, config):
        from ..runtime import hbm

        batch, labels = result
        return list(zip(
            hbm.split_block_batch(batch, counts),
            hbm.split_stacked(labels, counts),
        ))

    def compute_batch(self, payload, blocking: Blocking, config):
        """Stage 2: ONE fused dispatch — flood → inner-box crop → CC
        re-close (the former three-dispatch sequence with host round-trips
        in between) — materialized back to host."""
        batch, valid_arr, mask = payload
        halo = config.get("halo") or [0, 0, 0]
        params = self._kernel_params(config)
        has_halo = any(h > 0 for h in halo)
        coarse_tile = config.get("coarse_tile", None)
        if coarse_tile is not None and not isinstance(coarse_tile, int):
            coarse_tile = tuple(coarse_tile)
        fused = _fused_ws_kernel(
            tuple(sorted(params.items())),
            tuple(blocking.block_shape),
            mask is not None,
            has_halo,
            coarse_tile,
        )
        db = self._device_payload(batch, valid_arr, config)
        xb, vb, sb = db.arrays
        n_real = db.n
        if mask is None:
            labels, rounds = fused(xb, vb, sb)
        else:
            mb, _ = put_sharded(mask, config)
            labels, rounds = fused(xb, vb, sb, mb)
        # one device-to-host copy for the labels and the round counts
        labels, rounds = jax.device_get((labels, rounds))
        count_block_rounds(rounds, n_real)
        return batch, labels[:n_real].astype(np.uint64)

    def write_batch(self, result, blocking: Blocking, config):
        """Stage 3: apply block-id offsets, record per-block max ids, write
        the inner boxes."""
        batch, labels = result
        out_ds = self.output_ds()
        halo = config.get("halo") or [0, 0, 0]
        has_halo = any(h > 0 for h in halo)
        offset_unit = int(np.prod(blocking.block_shape))
        max_ids = self.tmp_ragged(MAX_IDS_KEY, blocking.n_blocks, np.int64)
        for i, (bid, bh) in enumerate(zip(batch.block_ids, batch.blocks)):
            lab = labels[i]
            if has_halo:
                # fused output is inner-origin at the static block shape;
                # trim the zero tail of edge blocks
                size = tuple(e - b for b, e in zip(bh.inner.begin, bh.inner.end))
                lab = lab[tuple(slice(0, s) for s in size)]
            else:
                lab = lab[bh.inner_local.slicing]
            off = np.uint64(bid * offset_unit)
            lab = np.where(lab > 0, lab + off, 0).astype(np.uint64)
            max_ids.write_chunk((bid,), np.array([lab.max()], dtype=np.int64))
            out_ds[bh.inner.slicing] = lab

    def _run_batch(self, block_ids, blocking, config):
        run_split_batch(self, block_ids, blocking, config)

    def process_block(self, block_id, blocking, config):
        self._run_batch([block_id], blocking, config)

    def process_block_batch(self, block_ids, blocking, config):
        self._run_batch(block_ids, blocking, config)


class WatershedFromSeedsTask(VolumeTask):
    """Seeded watershed from a given (global-id) seed volume
    (reference watershed/watershed_from_seeds.py:25).

    ``input_path/key`` is the boundary/height map, ``seeds_path/key`` a label
    volume whose non-zero ids become the seeds.  Because the seed ids are
    global, the output is boundary-consistent across blocks without a stitching
    step (halo'd floods agree where they overlap up to flood-order ties).
    """

    task_name = "watershed_from_seeds"
    output_dtype = "uint64"

    def __init__(self, *args, seeds_path: str = None, seeds_key: str = None,
                 mask_path: str = None, mask_key: str = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.seeds_path = seeds_path
        self.seeds_key = seeds_key
        self.mask_path = mask_path
        self.mask_key = mask_key

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "sigma_weights": 2.0,
                "halo": [2, 8, 8],
                "invert_inputs": False,
                "apply_ws_2d": False,
                "size_filter": 0,
                "channel_begin": 0,
                "channel_end": None,
                "agglomerate_channels": "mean",
            }
        )
        return conf

    def process_block(self, block_id: int, blocking: Blocking, config):
        in_ds = self.input_ds()
        out_ds = self.output_ds()
        seeds_ds = store.file_reader(self.seeds_path, "r")[self.seeds_key]
        halo = config.get("halo") or [0, 0, 0]
        bh = blocking.block_with_halo(block_id, halo)

        x = _read_input_block(in_ds, bh.outer.slicing, config)
        if config.get("invert_inputs", False):
            x = 1.0 - x
        seeds = seeds_ds[bh.outer.slicing].astype(np.uint64)

        mask = None
        if self.mask_path:
            mask_ds = store.file_reader(self.mask_path, "r")[self.mask_key]
            mask = mask_ds[bh.outer.slicing].astype(bool)

        sigma = float(config.get("sigma_weights", 2.0))
        per_slice = bool(config.get("apply_ws_2d", False)) and x.ndim == 3
        hmap = jnp.asarray(x)
        if sigma > 0:
            from ..ops.filters import gaussian

            sig = (0.0,) + (sigma,) * (x.ndim - 1) if per_slice else sigma
            hmap = gaussian(hmap, sig)

        # flood over compact ids (int32-safe on device), map back after
        uniq = np.unique(seeds)
        uniq = uniq[uniq > 0]
        compact = np.searchsorted(uniq, seeds) + 1
        compact = np.where(seeds > 0, compact, 0).astype(np.int32)
        labels = ws_ops.seeded_watershed(
            hmap,
            jnp.asarray(compact),
            mask=None if mask is None else jnp.asarray(mask),
            per_slice=per_slice,
        )
        size_filter = int(config.get("size_filter", 0))
        if size_filter > 0:
            labels = ws_ops.apply_size_filter(
                labels, hmap, size_filter, int(uniq.size + 2),
                mask=None if mask is None else jnp.asarray(mask),
                per_slice=per_slice,
            )
        labels = np.asarray(labels).astype(np.int64)
        lookup = np.concatenate([[np.uint64(0)], uniq]).astype(np.uint64)
        out = lookup[labels[bh.inner_local.slicing]]
        out_ds[bh.inner.slicing] = out


class AgglomerateTask(VolumeTask):
    """Per-block agglomeration of watershed fragments
    (reference watershed/agglomerate.py:33): build the block's RAG with mean
    boundary-evidence edge weights and merge fragments below the threshold
    (mala clustering semantics).  Fragment ids stay in the block's offset
    namespace, so downstream stitching/relabel tasks apply unchanged.
    """

    task_name = "agglomerate"
    output_dtype = "uint64"

    def __init__(self, *args, labels_path: str = None, labels_key: str = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        # ``input_path/key`` = boundary map; ``labels_path/key`` = watershed
        self.labels_path = labels_path
        self.labels_key = labels_key

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "threshold": 0.9,
                "use_mala_agglomeration": True,
                "channel_begin": 0,
                "channel_end": None,
                "agglomerate_channels": "mean",
                "invert_inputs": False,
            }
        )
        return conf

    def process_block(self, block_id: int, blocking: Blocking, config):
        from ..ops.multicut import agglomerative_clustering
        from ..ops.rag import boundary_edge_features

        bb = blocking.block(block_id).slicing
        seg = store.file_reader(self.labels_path, "r")[self.labels_key][bb]
        seg = seg.astype(np.uint64)
        out_ds = self.output_ds()
        uniq = np.unique(seg)
        uniq = uniq[uniq > 0]
        if uniq.size == 0:
            out_ds[bb] = seg
            return
        x = _read_input_block(self.input_ds(), bb, config)
        if config.get("invert_inputs", False):
            x = 1.0 - x
        edges, feats = boundary_edge_features(seg, x.astype(np.float64))
        if edges.shape[0] == 0:
            out_ds[bb] = seg
            return
        # compact node ids for the local clustering problem
        uv = np.searchsorted(uniq, edges).astype(np.int64)
        clusters = agglomerative_clustering(
            uniq.size,
            uv,
            feats[:, 0],                      # mean boundary evidence
            float(config.get("threshold", 0.9)),
            edge_sizes=feats[:, 9],           # face size
        )
        # merged fragments take the smallest member id — stays in the block's
        # offset namespace (reference agglomerate.py relabels w/ block offset)
        rep = np.full(int(clusters.max()) + 1, np.iinfo(np.int64).max, np.int64)
        np.minimum.at(rep, clusters, np.arange(uniq.size, dtype=np.int64))
        mapped = uniq[rep[clusters]]
        lookup = np.concatenate([[np.uint64(0)], mapped]).astype(np.uint64)
        dense = np.searchsorted(uniq, seg) + 1
        dense = np.where(seg > 0, dense, 0)
        out_ds[bb] = lookup[dense]


class TwoPassWatershedTask(WatershedTask):
    """One pass of the checkerboard two-pass watershed
    (reference two_pass_watershed.py:32-99).

    ``pass_id`` 0 processes the white half normally; ``pass_id`` 1 processes the
    black half seeding from the already-written neighbors inside the halo.
    """

    task_name = "two_pass_watershed"
    # pass 2 reads labels its own dispatch writes — never stream-fusable
    fusable = False

    def __init__(self, *args, pass_id: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        self.pass_id = pass_id

    @classmethod
    def default_task_config(cls):
        conf = super().default_task_config()
        # the two-pass variant defaults NMS on (reference
        # two_pass_watershed.py:54) where plain watershed defaults it off
        conf["non_maximum_suppression"] = True
        return conf

    @property
    def identifier(self) -> str:
        return f"{self.task_name}_pass{self.pass_id}"

    @property
    def pipeline_safe(self) -> bool:
        # pass 2 reads halo'd out_ds regions that same-color *diagonal*
        # neighbors write: concurrent batches would make the visible neighbor
        # labels timing-dependent.  One batch reads everything before writing
        # anything, so serial batches are fully deterministic.
        return self.pass_id == 0

    def get_block_list(self, blocking, gconf):
        base = super().get_block_list(blocking, gconf)
        white, black = make_checkerboard_block_lists(blocking, base)
        return white if self.pass_id == 0 else black

    def _run_batch(self, block_ids, blocking, config):
        if self.pass_id == 0:
            return super()._run_batch(block_ids, blocking, config)
        # pass 2: flood from written pass-1 labels in the halo + own seeds.
        # Blocks of one checkerboard color are independent, so the whole device
        # part (threshold → DT → seeds → flood → size filter) is ONE fused
        # kernel (ops.watershed.two_pass_flood) vmapped over the stacked batch;
        # only the global↔compact id mapping stays on the host.  Written ids
        # are compacted to 1..k per block so the device arrays stay int32-safe
        # and no per-block count leaks into the trace as a static value.
        in_ds = self.input_ds()
        out_ds = self.output_ds()
        halo = config.get("halo") or [0, 0, 0]
        if not any(h > 0 for h in halo):
            raise ValueError(
                "two-pass watershed requires a non-zero halo — pass 2 seeds from "
                "pass-1 neighbors inside the halo (set 'halo' in the task config)"
            )
        params = self._kernel_params(config)
        offset_unit = int(np.prod(blocking.block_shape))
        max_ids = self.tmp_ragged(MAX_IDS_KEY, blocking.n_blocks, np.int64)

        full_shape = tuple(
            bs + 2 * h for bs, h in zip(blocking.block_shape, halo)
        )
        xs, compacts, valids, uniqs, blocks = [], [], [], [], []
        for bid in block_ids:
            bh = blocking.block_with_halo(bid, halo)
            x = _read_input_block(in_ds, bh.outer.slicing, config)
            written = out_ds[bh.outer.slicing].astype(np.int64)
            uniq_written = np.unique(written)
            uniq_written = uniq_written[uniq_written > 0]
            compact = np.searchsorted(uniq_written, written) + 1
            compact = np.where(written > 0, compact, 0).astype(np.int32)
            xs.append(_pad_block(x, full_shape))
            compacts.append(_pad_block(compact, full_shape, mode="zero"))
            valids.append(
                _pad_block(np.ones(x.shape, dtype=bool), full_shape, mode="zero")
            )
            uniqs.append(uniq_written)
            blocks.append(bh)

        from ..parallel.dispatch import BlockBatch

        batch_arr = np.stack(xs)
        batch = BlockBatch(
            data=batch_arr, valid=None, blocks=blocks, block_ids=list(block_ids)
        )
        mask = self._load_mask_batch(batch, full_shape)

        # tight size-filter bincount bound: own-seed CC ids are consecutive
        # (≤ N/2) and written ids only occupy the halo shell (pass-1 neighbors
        # write disjoint inner boxes)
        n_outer = int(np.prod(full_shape))
        shell = n_outer - int(np.prod(blocking.block_shape))
        kernel = partial(
            ws_ops.two_pass_flood,
            num_segments=n_outer // 2 + shell + 2,
            **params,
        )
        xb, n_real = put_sharded(batch_arr, config)
        wb, _ = put_sharded(np.stack(compacts), config)
        vb, _ = put_sharded(np.stack(valids), config)
        if mask is None:
            labels, _ = jax.vmap(
                lambda x, w, v: kernel(x, w, valid=v)
            )(xb, wb, vb)
        else:
            mb, _ = put_sharded(mask, config)
            labels, _ = jax.vmap(
                lambda x, w, m, v: kernel(x, w, mask=m, valid=v)
            )(xb, wb, mb, vb)
        labels = np.asarray(labels).astype(np.int64)[:n_real]

        for i, bid in enumerate(block_ids):
            bh = blocks[i]
            k = uniqs[i].size
            lab = labels[i][bh.inner_local.slicing]
            # map back: 1..k → written global ids, k+1.. → block's namespace
            lookup = np.concatenate([[0], uniqs[i]])
            is_written = lab <= k
            written_part = lookup[np.where(is_written, lab, 0)]
            new_part = lab - k + bid * offset_unit
            lab = np.where(lab == 0, 0, np.where(is_written, written_part, new_part))
            lab = lab.astype(np.uint64)
            out_ds[bh.inner.slicing] = lab
            max_ids.write_chunk((bid,), np.array([lab.max()], dtype=np.int64))


def run_sharded_ws_kernel(x_d, config, mesh, z_valid: int):
    """Collective-watershed kernel dispatch shared by ShardedWatershedTask
    and ShardedWsProblemTask: the per-slice (2d) embarrassingly-parallel
    kernel when ``apply_dt_2d`` AND ``apply_ws_2d`` (the block pipeline's
    CREMI default), the 3d cross-shard collective when both are False;
    mixed settings are refused."""
    from ..parallel.sharded_watershed import (
        sharded_dt_watershed,
        sharded_dt_watershed_2d,
    )

    dt_2d = bool(config.get("apply_dt_2d", False))
    ws_2d = bool(config.get("apply_ws_2d", False))
    if dt_2d != ws_2d:
        raise ValueError(
            "the collective watershed supports apply_dt_2d == apply_ws_2d "
            "only (use the block pipeline for mixed 2d/3d modes)"
        )
    pitch = config.get("pixel_pitch")
    common = dict(
        mesh=mesh,
        threshold=float(config["threshold"]),
        sigma_seeds=float(config.get("sigma_seeds", 2.0)),
        sigma_weights=float(config.get("sigma_weights", 2.0)),
        alpha=float(config.get("alpha", 0.8)),
        size_filter=int(config.get("size_filter", 25)),
        invert_input=bool(config.get("invert_inputs", False)),
        z_valid=z_valid,
    )
    if dt_2d:
        if pitch:
            raise ValueError("pixel_pitch requires the 3d collective mode")
        return sharded_dt_watershed_2d(x_d, **common)
    return sharded_dt_watershed(
        x_d, pixel_pitch=tuple(pitch) if pitch else None, **common
    )


class ShardedWatershedTask(VolumeSimpleTask):
    """Whole-volume DT-watershed over the device mesh in collective form
    (``parallel.sharded_watershed.sharded_dt_watershed``) — the alternative
    to per-block watershed + stitching when the volume fits the mesh's
    aggregate HBM: no block offsets, no halos, no boundary inconsistencies,
    one globally-consistent fragmentation.

    Two collective modes, selected by the block pipeline's own knobs:
    ``apply_dt_2d=True, apply_ws_2d=True`` (the CREMI default) runs the
    per-slice kernel embarrassingly parallel over the z-shards — NO
    collectives at all, bit-exact with the single-device 2d kernel; both
    False runs the 3d collective (cross-shard EDT/flood fixpoints).  Mixed
    2d/3d settings are refused (the block path supports them; the
    collective formulations do not).  Masks are not supported yet — use
    the block pipeline for masked volumes.  ``collective``: under a
    multi-process runtime every process enters the program together
    (``devices: "global"``); process 0 owns the store writes.
    """

    task_name = "sharded_watershed"
    collective = True

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "threshold": 0.5,
                "pixel_pitch": None,
                "sigma_seeds": 2.0,
                "sigma_weights": 2.0,
                "size_filter": 25,
                "alpha": 0.8,
                "invert_inputs": False,
                # collective kernel selection (defaults keep the round-4
                # behavior: the 3d collective)
                "apply_dt_2d": False,
                "apply_ws_2d": False,
            }
        )
        return conf

    def run_impl(self) -> None:
        import jax as _jax

        from ..ops.relabel import relabel_consecutive_np
        from ..parallel.mesh import get_mesh, resolve_devices

        config = {**self.global_config(), **self.get_task_config()}
        in_ds = store.file_reader(self.input_path, "r")[self.input_key]
        if in_ds.ndim != 3:
            raise ValueError(
                "sharded_watershed supports 3d volumes (channel inputs go "
                "through the block pipeline)"
            )
        store.set_read_threads(in_ds, read_threads(config))
        devices = resolve_devices(config)
        mesh = get_mesh(devices)
        n_dev = len(devices)
        invert = bool(config.get("invert_inputs", False))

        # stream shard-by-shard: peak host RAM on ingest is one shard.
        # Pad slabs sit on the foreground side of the threshold AFTER the
        # kernel's inversion, exactly like the host-pad path.  The upload
        # rides the warm device-buffer cache (ctt-hbm): a back-to-back
        # serve job on the same volume skips the transfer entirely
        from ..runtime import hbm

        x_d = hbm.cached_put_from_store(
            in_ds, mesh, source_path=self.input_path,
            source_key=self.input_key,
            tag=("sharded-ws-input", bool(invert)),
            dtype=np.float32, pad_to=n_dev,
            pad_value=1.0 if invert else 0.0,
            transform=_normalize_host,
        )

        labels, n_seeds = run_sharded_ws_kernel(
            x_d, config, mesh, z_valid=int(in_ds.shape[0])
        )
        if _jax.process_index() != 0:
            return  # process 0 owns the writes
        out, n_labels = relabel_consecutive_np(labels.astype(np.uint64))
        ds = self.require_output(in_ds.shape, config)
        # threaded chunk-aligned whole-volume write (store fast path):
        # every chunk encodes straight from the label array, in parallel
        store.set_read_threads(ds, read_threads(config))
        ds[:] = out
        self.log(
            f"sharded DT-watershed over {n_dev} devices: {n_labels} fragments"
        )
