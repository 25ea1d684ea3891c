"""Edge-feature accumulation over boundary or affinity maps.

Reference features/{block_edge_features,merge_edge_features}.py via
nifty.distributed accumulators (SURVEY.md §2.3).  10 features per edge
(mean, var, min, q10..q90, max, count); the cross-block merge is exact for
the moment statistics, and quantiles merge through a per-edge HIST_BINS-bin
histogram sketch carried in the block partials (exact up to one bin width;
out-of-range or legacy 10-column partials degrade to count-weighted
averaging — ops/rag.py doc).

Scratch layout:
  features/ids     ragged per block: global edge ids
  features/vals    ragged per block: flattened [k,10] partial features
  features/hists   ragged per block: flattened [k, HIST_BINS] uint32 sketches
  features/edges   [m,10] merged feature matrix
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..ops.rag import (
    N_FEATURES,
    affinity_edge_features,
    boundary_edge_features,
    filter_edge_features,
    merge_edge_features,
    merge_edge_features_multi,
    HIST_BINS,
)
from ..runtime import config as cfg
from ..utils.blocking import Blocking
from .base import VolumeSimpleTask, VolumeTask, merge_threads, read_ragged_chunks, read_threads, resolve_n_blocks
from .graph import read_block_with_upper_halo, load_graph

def quantile_plan(config):
    """(exact, sketch) from quantile_mode × path — shared by the block task
    (what partials to write) and the merge task (what the partials must
    support), so the two sides cannot silently disagree.  "sketch" and
    "approx" on the filter path both mean approx (filter responses escape
    the sketch's [0,1] bin domain)."""
    mode = config.get("quantile_mode", "auto")
    if mode not in ("auto", "exact", "sketch", "approx"):
        raise ValueError(f"unknown quantile_mode {mode!r}")
    filters = config.get("filters") is not None
    exact = mode == "exact" or (mode == "auto" and filters)
    sketch = not exact and not filters and mode != "approx"
    return exact, sketch


FEATURE_IDS_KEY = "features/ids"
FEATURE_VALS_KEY = "features/vals"
FEATURE_HISTS_KEY = "features/hists"
FEATURE_SAMPLES_KEY = "features/samples"
FEATURES_KEY = "features/edges"


class BlockEdgeFeaturesTask(VolumeTask):
    """Per-block edge features (reference block_edge_features.py:21).

    ``input_path/key`` is the boundary/affinity map; ``labels_path/key`` the
    segmentation whose RAG was extracted.
    """

    task_name = "block_edge_features"
    output_dtype = None

    def __init__(self, *args, labels_path: str = None, labels_key: str = None,
                 **kwargs):
        super().__init__(*args, **kwargs)
        self.labels_path = labels_path
        self.labels_key = labels_key

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update(
            {
                "offsets": None,  # affinity offsets, None → boundary map
                # filter-bank accumulation (reference
                # block_edge_features.py:40-41,151-238): a bank of device
                # filters (ops/filters) × sigmas, 9 stats per response
                # channel + one trailing count column
                "filters": None,
                "sigmas": None,
                "halo": [0, 0, 0],
                "apply_in_2d": False,
                "channel_agglomeration": "mean",
                # quantile merge strategy: "auto" (sketch for the 10-column
                # default path, exact raw-sample partials for the filter
                # bank), "exact" (raw samples everywhere — zero drift vs a
                # single-shot recompute), "sketch" (histogram sketch; filter
                # responses leave the sketch's [0,1] domain so the filter
                # path degrades to "approx"), or "approx" (count-weighted
                # quantile averaging — smallest partials, largest drift)
                "quantile_mode": "auto",
                # fused device accumulator (ops/rag.boundary_edge_features_tpu)
                # for boundary-map blocks without halos; numpy path otherwise.
                # Off by default: wins on TPU (hardware sort), loses on XLA-CPU
                "device_accumulation": False,
                "max_edges_per_block": 16384,
            }
        )
        return conf

    def labels_ds(self):
        from ..utils import store

        return store.file_reader(self.labels_path, "r")[self.labels_key]

    def _quantile_plan(self, config):
        return quantile_plan(config)

    def _filter_responses(self, blocking: Blocking, block_id: int, config):
        """Halo'd read → device filter bank → per-channel responses cropped
        to the inner(+1-upper-halo) region (reference
        block_edge_features.py:172-238 via vu.apply_filter).

        Unlike the reference's per-block min-max ``vu.normalize`` this uses
        the task's deterministic normalization (uint8 → /255, floats raw), so
        blocked responses equal a single-shot whole-volume recompute wherever
        the halo covers the filter support."""
        import jax.numpy as jnp

        from ..ops import filters as F

        block = blocking.block(block_id)
        shape = blocking.shape
        halo = [int(h) for h in (config.get("halo") or [0, 0, 0])]
        # the accumulated region carries a +1 upper halo (cross-block faces
        # are owned by the lower block), so the upper read extends halo + 1:
        # even the +1-slab voxels then see the full filter support
        ob = [max(b - h, 0) for b, h in zip(block.begin, halo)]
        oe = [min(e + h + 1, s) for e, h, s in zip(block.end, halo, shape)]
        bb = tuple(slice(b, e) for b, e in zip(ob, oe))
        data_ds = self.input_ds()
        if len(data_ds.shape) == 4:
            # agglomerate over ALL channels (the reference hardcodes the
            # first three, block_edge_features.py:214-215 — a marked TODO
            # there; silent truncation is worse than the divergence)
            data = self._normalize(data_ds[(slice(None),) + bb])
            agglo = config.get("channel_agglomeration") or "mean"
            data = getattr(np, agglo)(data, axis=0)
        else:
            data = self._normalize(data_ds[bb])
        ie = [min(e + 1, s) for e, s in zip(block.end, shape)]
        local = tuple(
            slice(b - o, e - o) for b, o, e in zip(block.begin, ob, ie)
        )
        if not config.get("sigmas"):
            raise ValueError(
                "filter-bank accumulation needs 'sigmas' (a list of filter "
                "scales) alongside 'filters' in the block_edge_features "
                "config (reference block_edge_features.py:312)"
            )
        responses = []
        x = jnp.asarray(data.astype(np.float32))
        in_2d = bool(config.get("apply_in_2d", False))
        for name in config["filters"]:
            for sigma in config["sigmas"]:
                resp = np.asarray(
                    F.apply_filter(x, name, sigma, apply_in_2d=in_2d),
                    dtype=np.float64,
                )
                if resp.ndim == 4:  # multichannel filters: channels last
                    responses.extend(
                        resp[..., c][local] for c in range(resp.shape[-1])
                    )
                else:
                    responses.append(resp[local])
        return responses

    def process_block(self, block_id: int, blocking: Blocking, config):
        seg = read_block_with_upper_halo(
            self.labels_ds(), blocking, block_id
        ).astype(np.uint64)
        data_ds = self.input_ds()
        offsets = config.get("offsets")
        block = blocking.block(block_id)
        end = tuple(min(e + 1, s) for e, s in zip(block.end, blocking.shape))
        bb = tuple(slice(b, e) for b, e in zip(block.begin, end))
        exact, sketch = self._quantile_plan(config)
        hist_bins = HIST_BINS if sketch else 0
        hists = samples = None
        if config.get("filters") is not None:
            if offsets is not None:
                raise ValueError(
                    "filters and offsets are mutually exclusive "
                    "(reference block_edge_features.py:311)"
                )
            responses = self._filter_responses(blocking, block_id, config)
            out = filter_edge_features(
                seg, responses, owner_shape=block.shape, return_samples=exact
            )
            edges, feats = out[0], out[1]
            if exact:
                samples = out[2]
        elif offsets is not None:
            data = self._normalize(data_ds[(slice(0, len(offsets)),) + bb])
            out = affinity_edge_features(
                seg, data, offsets, hist_bins=hist_bins,
                owner_shape=block.shape, return_samples=exact,
            )
            edges, feats = out[0], out[1]
            if exact:
                samples = out[2]
            elif sketch:
                hists = out[2]
        elif config.get("device_accumulation") and not exact:
            from ..ops.rag import boundary_edge_features_tpu

            data = self._normalize(data_ds[bb])
            edges, feats, hists = boundary_edge_features_tpu(
                seg, data, hist_bins=HIST_BINS, owner_shape=block.shape,
                max_edges=int(config.get("max_edges_per_block", 16384)),
            )
            if not sketch:
                hists = None
        else:
            data = self._normalize(data_ds[bb])
            out = boundary_edge_features(
                seg, data, hist_bins=hist_bins, owner_shape=block.shape,
                return_samples=exact,
            )
            edges, feats = out[0], out[1]
            if exact:
                samples = out[2]
            elif sketch:
                hists = out[2]

        store = self.tmp_store()
        nodes, gedges = load_graph(store)
        ids_out = self.tmp_ragged(FEATURE_IDS_KEY, blocking.n_blocks, np.int64)
        vals_out = self.tmp_ragged(FEATURE_VALS_KEY, blocking.n_blocks, np.float64)
        hists_out = self.tmp_ragged(FEATURE_HISTS_KEY, blocking.n_blocks, np.uint32)
        # keep the samples dataset in lockstep even when this run does not
        # produce samples: a previous exact-mode run's stale chunks must not
        # poison this run's merge (empty chunk ⇒ merge rejects exact path)
        samples_out = (
            self.tmp_ragged(FEATURE_SAMPLES_KEY, blocking.n_blocks, np.float64)
            if (samples is not None or FEATURE_SAMPLES_KEY in store)
            else None
        )
        if edges.shape[0] == 0:
            ids_out.write_chunk((block_id,), np.array([], dtype=np.int64))
            vals_out.write_chunk((block_id,), np.array([], dtype=np.float64))
            hists_out.write_chunk((block_id,), np.array([], dtype=np.uint32))
            if samples_out is not None:
                samples_out.write_chunk(
                    (block_id,), np.array([], dtype=np.float64)
                )
            return
        pairs = np.searchsorted(nodes, edges).astype(np.int64)
        keys = gedges[:, 0] * (nodes.size + 1) + gedges[:, 1]
        want = pairs[:, 0] * (nodes.size + 1) + pairs[:, 1]
        ids = np.searchsorted(keys, want)
        valid = keys[np.clip(ids, 0, keys.size - 1)] == want
        ids_out.write_chunk((block_id,), ids[valid].astype(np.int64))
        vals_out.write_chunk((block_id,), feats[valid].reshape(-1))
        hists_out.write_chunk(
            (block_id,),
            hists[valid].reshape(-1) if hists is not None
            else np.array([], dtype=np.uint32),
        )
        if samples_out is not None:
            if samples is None:
                samples_out.write_chunk(
                    (block_id,), np.array([], dtype=np.float64)
                )
            else:
                counts = feats[:, -1].astype(np.int64)
                total = int(counts.sum())
                n_groups = (feats.shape[1] - 1) // 9
                keep = np.repeat(valid, counts)
                kept = (
                    samples.reshape(n_groups, total)[:, keep].reshape(-1)
                    if total
                    else samples
                )
                samples_out.write_chunk((block_id,), kept)

    @staticmethod
    def _normalize(data: np.ndarray) -> np.ndarray:
        if data.dtype == np.uint8:
            return data.astype(np.float64) / 255.0
        return data.astype(np.float64)


class MergeEdgeFeaturesTask(VolumeSimpleTask):
    """Merge per-block partial features (reference merge_edge_features.py:17)."""

    task_name = "merge_edge_features"

    def __init__(self, *args, labels_path: str = None, labels_key: str = None,
                 **kwargs):
        super().__init__(*args, labels_path=labels_path, labels_key=labels_key,
                         **kwargs)

    def run_impl(self) -> None:
        n_blocks = resolve_n_blocks(self.config_dir, self.labels_path, self.labels_key)
        store = self.tmp_store()
        n_edges = store["graph/edges"].attrs["n_edges"]
        ids_ds = store[FEATURE_IDS_KEY]
        vals_ds = store[FEATURE_VALS_KEY]
        ids_list, feats_list, hists_list, samples_list = [], [], [], []
        n_thr = merge_threads(self)
        all_ids = read_ragged_chunks(ids_ds, n_blocks, n_thr)
        all_vals = read_ragged_chunks(vals_ds, n_blocks, n_thr)
        # sketches live in their own uint32 ragged dataset; absent for scratch
        # written before the histogram merge existed (legacy fallback)
        if FEATURE_HISTS_KEY in store:
            all_hists = read_ragged_chunks(store[FEATURE_HISTS_KEY], n_blocks, n_thr)
        else:
            all_hists = [None] * n_blocks
        # raw sorted samples: only written in exact quantile mode
        if FEATURE_SAMPLES_KEY in store:
            all_samples = read_ragged_chunks(
                store[FEATURE_SAMPLES_KEY], n_blocks, n_thr
            )
        else:
            all_samples = [None] * n_blocks
        for ids, vals, hists, samples in zip(
            all_ids, all_vals, all_hists, all_samples
        ):
            if ids is None or ids.size == 0:
                continue
            ids_list.append(ids)
            feats_list.append(vals.reshape(ids.size, -1))
            hists_list.append(
                hists.reshape(ids.size, -1)
                if hists is not None and hists.size
                else None
            )
            samples_list.append(samples)
        n_cols = next(
            (f.shape[1] for f in feats_list if f.shape[0]), N_FEATURES
        )
        widths = {f.shape[1] for f in feats_list if f.shape[0]}
        if len(widths) > 1:
            raise ValueError(
                f"mixed per-block feature widths {sorted(widths)} — stale "
                "partials from a config switch; rerun block_edge_features "
                "over all blocks"
            )
        # exact merge only when EVERY nonempty block shipped a size-consistent
        # sample partial (stale/empty chunks from a mode switch disqualify)
        n_groups = (n_cols - 1) // 9
        exact = bool(samples_list) and all(
            s is not None and s.size == n_groups * int(f[:, -1].sum())
            for s, f in zip(samples_list, feats_list)
        )
        # never silently downgrade a configured exact merge: partials from a
        # sketch-mode run (e.g. mode switched without rerunning the blocks)
        # lack usable samples
        bconf = cfg.read_config(self.config_dir, "block_edge_features")
        wants_exact, _ = quantile_plan(bconf)
        if wants_exact and not exact and ids_list:
            raise ValueError(
                "quantile_mode requests the exact merge but the block "
                "partials carry no usable sample arrays — rerun "
                "block_edge_features (clear its status) so the blocks "
                "write exact-mode partials"
            )
        if n_cols == N_FEATURES and not exact:
            merged = merge_edge_features(
                ids_list, feats_list, n_edges, hists_list
            )
        else:
            merged = merge_edge_features_multi(
                ids_list, feats_list, n_edges,
                samples_list if exact else None,
            )
        ds = store.create_dataset(
            FEATURES_KEY,
            data=merged,
            chunks=(max(merged.shape[0], 1), merged.shape[1]),
            exist_ok=True,
        )
        ds.attrs["n_features"] = int(merged.shape[1])
        self.log(
            f"merged {merged.shape[1]}-column features for {n_edges} edges"
        )


class ShardedProblemTask(VolumeSimpleTask):
    """Whole-problem RAG extraction + 10-feature accumulation in ONE
    collective program over the device mesh
    (``parallel.sharded_rag.sharded_boundary_edge_features``) — the
    collective replacement for the InitialSubGraphs→MergeSubGraphs→MapEdgeIds
    + BlockEdgeFeatures→MergeEdgeFeatures chain when the volume fits the
    mesh's aggregate HBM.  Both volumes stream shard-by-shard from the
    store (``mesh.put_from_store``) with per-slab label compaction and
    normalization in the read callbacks, so peak host RAM on ingest is one
    slab plus the global node table; HBM holds the int32 compact labels and
    float32 data.  Writes the standard problem scratch layout
    (graph/nodes, graph/edges + attrs, features/edges) so every downstream
    consumer (costs, global multicut solve, postprocess graph tasks) runs
    unchanged.

    ``input_path/key`` = boundary map, ``labels_path/key`` = segmentation.
    """

    task_name = "sharded_problem"
    collective = True

    def __init__(self, *args, input_path: str = None, input_key: str = None,
                 labels_path: str = None, labels_key: str = None, **kwargs):
        super().__init__(
            *args, input_path=input_path, input_key=input_key,
            labels_path=labels_path, labels_key=labels_key, **kwargs,
        )

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        conf.update({"max_edges": 16384})
        return conf

    def run_impl(self) -> None:
        from ..parallel.mesh import get_mesh, put_from_store, resolve_devices
        from ..parallel.sharded_rag import sharded_boundary_edge_features
        from ..utils import store

        conf = {**self.global_config(), **self.get_task_config()}
        seg_ds = store.file_reader(self.labels_path, "r")[self.labels_key]
        data_ds = store.file_reader(self.input_path, "r")[self.input_key]
        store.set_read_threads(seg_ds, read_threads(conf))
        store.set_read_threads(data_ds, read_threads(conf))
        if len(data_ds.shape) != len(seg_ds.shape):
            raise ValueError(
                "sharded_problem supports 3d boundary maps only — affinity "
                "(4d) inputs go through the block pipeline "
                "(sharded_problem=False with block_edge_features offsets)"
            )

        devices = resolve_devices(conf)
        mesh = get_mesh(devices)
        n_dev = len(devices)
        z = int(seg_ds.shape[0])

        # pass 1 (host, slab-wise): the global node table — peak host RAM
        # is one slab plus the accumulating uniques.  Slab height follows
        # the store's z-chunking so no chunk is decompressed twice.  The
        # same pass counts boundary face rows per z-plane, from which the
        # per-shard sample-compaction cap is sized (shard_sample_cap needs
        # the whole volume; here only per-plane counts accumulate).
        zc = int((seg_ds.chunks or (8,))[0]) or 8
        zp = z + (-z) % n_dev  # padded extent (pad planes count 0)
        c_in = np.zeros(zp, np.int64)   # in-plane pairs of plane zi
        c_z = np.zeros(zp, np.int64)    # pairs between planes zi and zi+1
        prev_last = None
        slabs = []
        from ..ops.rag import plane_face_counts

        for z0 in range(0, z, zc):
            # cast BEFORE unique: signed ignore labels (e.g. -1) must wrap
            # to their uint64 identity exactly as the full-volume cast did,
            # or the node table silently drops/disorders them
            slab = np.asarray(seg_ds[z0 : z0 + zc]).astype(np.uint64)
            slabs.append(np.unique(slab))
            s_in, s_z, boundary, prev_last = plane_face_counts(
                slab, prev_last
            )
            c_in[z0 : z0 + slab.shape[0]] += s_in
            c_z[z0 : z0 + slab.shape[0]] += s_z
            if z0:
                c_z[z0 - 1] += boundary
        nodes = np.unique(np.concatenate(slabs)) if slabs else np.zeros(
            0, np.uint64
        )
        nodes = nodes[nodes > 0]
        # shard i owns planes [i*h, (i+1)*h) plus the z-pair into the next
        # shard's first plane (mesh-edge shard: ppermute zero-fill)
        h = zp // n_dev
        worst = 1
        for i in range(n_dev):
            zo, z1 = i * h, (i + 1) * h
            cnt = int(c_in[zo:z1].sum() + c_z[zo:z1].sum())
            worst = max(worst, cnt)
        from ..ops.rag import sample_capacity

        sample_cap = sample_capacity(worst)

        # pass 2: stream both volumes shard-by-shard; compaction to
        # 1..n node ids and the block path's normalization convention
        # (uint8 → /255, other dtypes raw) run per shard in the callbacks
        def compact_slab(s):
            s = s.astype(np.uint64)
            c = np.searchsorted(nodes, s) + 1
            return np.where(s > 0, c, 0)  # label 0: no pairs in the pad

        def normalize_slab(d):
            if d.dtype == np.uint8:
                return d.astype(np.float32) / 255.0
            return np.asarray(d, dtype=np.float32)

        # compact labels depend on the run-local node table, so they stay
        # uncached; the boundary-map upload routes through the warm
        # device-buffer cache (ctt-hbm) — a back-to-back serve job on the
        # same volume reuses the HBM-resident float32 array
        from ..runtime import hbm

        compact_d = put_from_store(
            seg_ds, mesh, dtype=np.int32, pad_to=n_dev, transform=compact_slab
        )
        data_d = hbm.cached_put_from_store(
            data_ds, mesh, source_path=self.input_path,
            source_key=self.input_key, tag=("problem-data",),
            dtype=np.float32, pad_to=n_dev, transform=normalize_slab,
        )

        edges_c, feats = sharded_boundary_edge_features(
            compact_d, data_d, mesh=mesh,
            max_edges=int(conf.get("max_edges", 16384)),
            # compact ids are 1..nodes.size (searchsorted+1): the exact
            # bound gates the packed single-key sort without touching the
            # (possibly multi-host global) device array
            max_id=int(nodes.size),
            max_samples=sample_cap,
        )
        import jax as _jax

        if _jax.process_index() != 0:
            return  # process 0 owns the scratch-store writes
        self._write_problem_scratch(nodes, edges_c, feats)
        self.log(
            f"sharded problem over {len(devices)} devices: "
            f"{nodes.size} nodes, {edges_c.shape[0]} edges"
        )

    def _write_problem_scratch(self, nodes, edges_c, feats):
        """Write the standard problem scratch layout (graph/nodes,
        graph/edges + attrs, features/edges) from compact-id edges —
        shared by the collective problem tasks."""
        from .graph import EDGES_KEY, NODES_KEY

        dense = (edges_c - 1).astype(np.int64)  # compact id → node index
        out = self.tmp_store()
        out.create_dataset(
            NODES_KEY, data=nodes, chunks=(max(nodes.size, 1),), exist_ok=True
        )
        out.create_dataset(
            EDGES_KEY, data=dense,
            chunks=(max(dense.shape[0], 1), 2), exist_ok=True,
        )
        g = out[EDGES_KEY]
        g.attrs["n_nodes"] = int(nodes.size)
        g.attrs["n_edges"] = int(dense.shape[0])
        out.create_dataset(
            FEATURES_KEY, data=feats.astype(np.float64),
            chunks=(max(feats.shape[0], 1), N_FEATURES), exist_ok=True,
        )


class ShardedWsProblemTask(ShardedProblemTask):
    """Device-resident watershed → RAG+features: ONE collective session for
    the whole front of the multicut pipeline (VERDICT r4 item 3 — "keep the
    volume device-resident across watershed→graph→features").

    The split pipeline moves the volume across the host↔device boundary
    five times: the block watershed uploads halo'd blocks and fetches
    labels per batch, writes them, then the problem task re-reads BOTH
    volumes from the store and re-uploads them.  Here the boundary map is
    uploaded ONCE and stays device-resident: the sharded DT-watershed
    consumes it, its labels come down once (the size filter and the ws
    store write need them on host anyway), and the compact relabeling goes
    back up for the collective RAG, which reuses the SAME device-resident
    boundary array.  Per run that removes one full boundary re-read +
    re-upload, one label store re-read + re-upload, the per-block halo'd
    reads, and the slab-wise node-table pass (the host relabel already
    yields it).

    Writes the ws dataset (``output_path/output_key``, compact consecutive
    ids — same contract as ``ShardedWatershedTask``) AND the standard
    problem scratch, so every downstream consumer (costs, global solve,
    write) runs unchanged, and resume/checkpoint semantics stay store-based.

    The watershed mode follows ``apply_dt_2d``/``apply_ws_2d`` in the task
    config exactly like ``ShardedWatershedTask`` (both default False → the
    3d collective; both True → the zero-collective per-slice kernel, the
    block pipeline's CREMI default — ``run_sharded_ws_kernel`` dispatches).
    Masked volumes go through the block pipeline.
    """

    task_name = "sharded_ws_problem"
    collective = True

    @classmethod
    def default_task_config(cls) -> Dict[str, Any]:
        conf = super().default_task_config()
        from .watershed import ShardedWatershedTask

        ws_conf = ShardedWatershedTask.default_task_config()
        conf.update({
            k: v for k, v in ws_conf.items() if k not in conf
        })
        return conf

    def run_impl(self) -> None:
        import jax as _jax

        from ..ops.relabel import relabel_consecutive_np
        from ..parallel.mesh import get_mesh, put_global, resolve_devices
        from ..parallel.sharded_rag import sharded_boundary_edge_features
        from ..utils import store
        from .watershed import _normalize_host, run_sharded_ws_kernel

        conf = {**self.global_config(), **self.get_task_config()}
        in_ds = store.file_reader(self.input_path, "r")[self.input_key]
        if in_ds.ndim != 3:
            raise ValueError(
                "sharded_ws_problem supports 3d boundary maps only"
            )
        if np.dtype(in_ds.dtype) == np.uint16:
            # the device-resident array serves BOTH stages, but the split
            # pipeline normalizes them differently for uint16 (watershed
            # /65535, features raw) — reusing one array would silently
            # change the features; keep exact parity by refusing
            raise ValueError(
                "sharded_ws does not support uint16 boundary maps (the "
                "watershed and feature stages disagree on uint16 "
                "normalization) — use sharded_ws=False"
            )
        store.set_read_threads(in_ds, read_threads(conf))
        devices = resolve_devices(conf)
        mesh = get_mesh(devices)
        n_dev = len(devices)
        z = int(in_ds.shape[0])
        invert = bool(conf.get("invert_inputs", False))

        import time as _time

        def timed(phase, fn):
            # sequential phases under the breakdown's "batch_*" convention
            # so bench_e2e_lib.task_breakdown attributes the fused wall
            t0 = _time.perf_counter()
            r = fn()
            self.record_timing(f"batch_{phase}", 1, _time.perf_counter() - t0)
            return r

        # ONE upload; the array stays resident through watershed AND RAG —
        # and, through the shared device-buffer cache (ctt-hbm), across
        # back-to-back jobs on the same volume: this task's "uploaded
        # ONCE, stays resident" pattern is exactly what the cache
        # generalizes, so the upload is no longer an ad-hoc one-off (the
        # timing record keeps the batch_* breakdown contract)
        from ..runtime import hbm

        x_d = timed("upload", lambda: hbm.cached_put_from_store(
            in_ds, mesh, source_path=self.input_path,
            source_key=self.input_key,
            tag=("ws-problem-input", bool(invert)),
            dtype=np.float32, pad_to=n_dev,
            pad_value=1.0 if invert else 0.0,
            transform=_normalize_host,
        ))

        labels, _ = timed("watershed", lambda: run_sharded_ws_kernel(
            x_d, conf, mesh, z_valid=z
        ))
        compact, n_labels = relabel_consecutive_np(labels.astype(np.uint64))
        compact32 = compact.astype(np.int32)
        pad = (-z) % n_dev
        if pad:  # pad slab: label 0 → contributes no RAG pairs
            compact32 = np.pad(compact32, ((0, pad), (0, 0), (0, 0)))
        compact_d = put_global(compact32, mesh, dtype=np.int32)

        from ..parallel.sharded_rag import shard_sample_cap

        edges_c, feats = timed("rag", lambda: sharded_boundary_edge_features(
            compact_d, x_d, mesh=mesh,
            max_edges=int(conf.get("max_edges", 16384)),
            max_id=int(n_labels),
            # the padded compact labels are on host anyway — size the
            # per-shard compaction cap from them
            max_samples=shard_sample_cap(compact32, n_dev),
        ))

        if _jax.process_index() != 0:
            return  # process 0 owns the store writes
        ds = self.require_output(in_ds.shape, conf)
        # threaded chunk-aligned whole-volume write (store fast path)
        store.set_read_threads(ds, read_threads(conf))
        timed("write", lambda: ds.__setitem__(slice(None), compact))
        # ws ids ARE 1..n_labels consecutive — the node table is implied
        nodes = np.arange(1, n_labels + 1, dtype=np.uint64)
        self._write_problem_scratch(nodes, edges_c, feats)
        self.log(
            f"sharded ws+problem over {n_dev} devices: {n_labels} fragments, "
            f"{edges_c.shape[0]} edges, boundary volume device-resident"
        )
