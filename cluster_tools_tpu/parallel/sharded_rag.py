"""RAG edge-feature accumulation over the device mesh.

The block pipeline accumulates 10 features per RAG edge block-by-block and
merges the partials through the scratch store (tasks/features.py); this is
the collective form for a z-sharded whole volume (SURVEY.md §2.9: "feature
merges ride all_gather/psum instead of files"):

  1. per shard: face-pair samples (one +z neighbor plane via ``ppermute``
     owns the cross-shard pairs; each pair is owned by exactly one shard) →
     3-key sort → segment reduction into a fixed-size SUFFICIENT-STATISTICS
     table: (u, v, count, sum, sum², min, max, histogram-sketch row) — the
     mergeable form of the 10 features;
  2. ``lax.all_gather`` of the per-shard tables (kilobytes — tables, not
     samples) → lexicographic argsort by (u, v) → one more segment reduction
     merges the partial statistics of edges spanning shards;
  3. finalize: mean/variance from the moments, quantiles from the merged
     histogram sketch (the same convention as the host merge,
     ops/rag._histogram_quantiles — exact to one bin width).

Count/mean/min/max columns match the host oracle exactly; the five quantile
columns are sketch-accurate (≤ 1/HIST_BINS drift), the identical contract the
block pipeline's cross-block merge provides (tests/test_sharded_rag.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import trace as obs_trace
from ..ops.rag import HIST_BINS, QUANTILES
from .mesh import get_mesh, put_global
from .sharded import _neighbor_planes

_BIG_ID = np.int32(np.iinfo(np.int32).max)


def _edge_segments(u, v, max_edges):
    """Shared segment machinery over (u, v)-sorted keys: validity mask,
    per-edge segment ids (invalid rows → the overflow bucket), the distinct
    count, and a reducer bound to those segments."""
    valid = u != _BIG_ID
    first = jnp.concatenate(
        [valid[:1], (u[1:] != u[:-1]) | (v[1:] != v[:-1])]
    ) & valid
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    seg = jnp.where(valid, seg, max_edges)
    n_distinct = first.sum()

    def red(x, op=jax.ops.segment_sum):
        return op(x, seg, num_segments=max_edges + 1)[:max_edges]

    return valid, seg, n_distinct, red


def _local_stats_table(lab, val, lab_hi, val_hi, max_edges, hist_bins,
                       packed=False, max_samples=None):
    """Per-shard samples → sorted sufficient-statistics table (fixed size).

    ``packed`` (static): single-int32-key sort ``u*65536 + v`` when every
    global label id ≤ 32766 (caller-gated) — same order-preserving packing
    as ops/rag._boundary_edge_features_device_impl, same bit-identical
    results, one sort stream fewer.

    ``max_samples`` (static): pre-sort compaction of the shard's valid face
    rows to a fixed cap, exactly like the single-device kernel — at
    CREMI-like boundary densities ~3/4 of the rows are sentinels that cost
    the same to sort as real samples.  The cap must bound EVERY shard's
    valid count (callers size it host-side); the true per-shard count is
    returned so the caller can fail loudly on overflow."""
    lab_e = jnp.concatenate([lab, lab_hi[None]], 0)
    val_e = jnp.concatenate([val, val_hi[None]], 0)

    us, vs, ss = [], [], []
    # axis 0 pairs over the +z-extended arrays (owns the cross-shard pairs;
    # the mesh-edge shard's received plane is ppermute zero-fill → label 0 →
    # those pairs are invalid automatically)
    for arrs, axis in (((lab_e, val_e), 0), ((lab, val), 1), ((lab, val), 2)):
        l0 = jnp.moveaxis(arrs[0], axis, 0)
        v0 = jnp.moveaxis(arrs[1], axis, 0)
        lo, hi = l0[:-1].reshape(-1), l0[1:].reshape(-1)
        vlo, vhi = v0[:-1].reshape(-1), v0[1:].reshape(-1)
        sel = (lo != hi) & (lo != 0) & (hi != 0)
        a = jnp.where(sel, jnp.minimum(lo, hi), _BIG_ID)
        b = jnp.where(sel, jnp.maximum(lo, hi), _BIG_ID)
        us += [a, a]
        vs += [b, b]
        ss += [vlo, vhi]
    u = jnp.concatenate(us)
    v = jnp.concatenate(vs)
    s = jnp.concatenate(ss).astype(jnp.float32)

    n_true = (u != _BIG_ID).sum()
    if max_samples is not None:
        from ..ops.rag import compact_valid_rows

        u, v, s = compact_valid_rows(u, v, s, max_samples, _BIG_ID)

    if packed:
        from ..ops.rag import pack_uv, unpack_uv

        p = pack_uv(u, v, _BIG_ID)
        p, s = lax.sort((p, s), num_keys=2)
        # segment machinery straight off the packed key: one diff per
        # boundary, and endpoints recovered by ONE edge-level reduction +
        # unpack — no per-sample div/mod (mirrors ops/rag's packed path)
        valid = p != _BIG_ID
        first = jnp.concatenate([valid[:1], p[1:] != p[:-1]]) & valid
        seg = jnp.cumsum(first.astype(jnp.int32)) - 1
        seg = jnp.where(valid, seg, max_edges)
        n_local = first.sum()

        def red(x, op=jax.ops.segment_sum):
            return op(x, seg, num_segments=max_edges + 1)[:max_edges]

        e_p = red(jnp.where(valid, p, _BIG_ID), op=jax.ops.segment_min)
        e_u, e_v = unpack_uv(e_p, _BIG_ID)
    else:
        u, v, s = lax.sort((u, v, s), num_keys=3)
        valid, seg, n_local, red = _edge_segments(u, v, max_edges)
        e_u = red(jnp.where(valid, u, _BIG_ID), op=jax.ops.segment_min)
        e_v = red(jnp.where(valid, v, _BIG_ID), op=jax.ops.segment_min)
    ones = valid.astype(jnp.float32)

    count = red(ones)
    ssum = red(s * ones)
    ssum2 = red(s * s * ones)
    smin = red(jnp.where(valid, s, jnp.inf), op=jax.ops.segment_min)
    smax = red(jnp.where(valid, s, -jnp.inf), op=jax.ops.segment_max)
    bins = jnp.clip((s * hist_bins).astype(jnp.int32), 0, hist_bins - 1)
    flat = jnp.where(valid, seg * hist_bins + bins, max_edges * hist_bins)
    hist = jax.ops.segment_sum(
        ones, flat, num_segments=max_edges * hist_bins + 1
    )[: max_edges * hist_bins].reshape(max_edges, hist_bins)
    return e_u, e_v, count, ssum, ssum2, smin, smax, hist, n_local, n_true


def _hist_quantile(hist, cum, counts, q):
    """jnp port of ops/rag._histogram_quantiles (same convention — the
    sharded result must match what the block pipeline's merge would say)."""
    n_bins = hist.shape[1]
    target = q * (counts - 1.0)
    idx = (cum <= target[:, None]).sum(axis=1)
    idx = jnp.minimum(idx, n_bins - 1)
    rows = jnp.arange(hist.shape[0])
    below = jnp.where(idx > 0, cum[rows, jnp.maximum(idx - 1, 0)], 0.0)
    in_bin = jnp.maximum(hist[rows, idx], 1.0)
    frac = jnp.clip((target - below + 0.5) / in_bin, 0.0, 1.0)
    return (idx + frac) / n_bins


@partial(
    jax.jit,
    static_argnames=(
        "max_edges", "hist_bins", "axis_name", "mesh", "packed",
        "max_samples",
    ),
)
def _sharded_rag(labels, values, max_edges, hist_bins, axis_name, mesh,
                 packed=False, max_samples=None):
    def local_fn(lab, val):
        lab_hi = _neighbor_planes(lab[0], axis_name, -1)  # +z neighbor plane
        val_hi = _neighbor_planes(val[0], axis_name, -1)
        (e_u, e_v, count, ssum, ssum2, smin, smax, hist,
         n_local, n_true) = _local_stats_table(
            lab, val, lab_hi, val_hi, max_edges, hist_bins, packed,
            max_samples,
        )
        # a local table that truncated (> max_edges distinct edges in one
        # shard) silently drops the lexicographic tail IDENTICALLY on every
        # shard, so the merged count cannot detect it — report the max local
        # count so the host can fail loudly; same for the sample cap
        n_local_max = lax.pmax(n_local, axis_name)
        n_true_max = lax.pmax(n_true, axis_name)

        def gather(x):
            g = lax.all_gather(x, axis_name)
            return g.reshape((-1,) + g.shape[2:])

        u = gather(e_u)
        v = gather(e_v)
        count = gather(count)
        ssum = gather(ssum)
        ssum2 = gather(ssum2)
        smin = gather(smin)
        smax = gather(smax)
        hist = gather(hist)

        # lexicographic (u, v) order: one argsort of the packed key when
        # the id space fits, else two stable argsorts
        if packed:
            from ..ops.rag import pack_uv

            perm = jnp.argsort(pack_uv(u, v, _BIG_ID), stable=True)
        else:
            perm = jnp.argsort(v, stable=True)
            perm = perm[jnp.argsort(u[perm], stable=True)]
        u, v = u[perm], v[perm]
        count, ssum, ssum2 = count[perm], ssum[perm], ssum2[perm]
        smin, smax, hist = smin[perm], smax[perm], hist[perm]

        valid, seg, n_edges, red = _edge_segments(u, v, max_edges)

        m_count = red(count)
        m_sum = red(ssum)
        m_sum2 = red(ssum2)
        m_min = red(jnp.where(valid, smin, jnp.inf), op=jax.ops.segment_min)
        m_max = red(jnp.where(valid, smax, -jnp.inf), op=jax.ops.segment_max)
        m_hist = red(hist)
        m_u = red(jnp.where(valid, u, _BIG_ID), op=jax.ops.segment_min)
        m_v = red(jnp.where(valid, v, _BIG_ID), op=jax.ops.segment_min)

        present = m_count > 0
        safe = jnp.maximum(m_count, 1.0)
        mean = m_sum / safe
        var = jnp.maximum(m_sum2 / safe - mean**2, 0.0)
        cum = jnp.cumsum(m_hist, axis=1)
        qcols = [
            jnp.where(present, _hist_quantile(m_hist, cum, m_count, q), 0.0)
            for q in QUANTILES
        ]
        feats = jnp.stack(
            [
                jnp.where(present, mean, 0.0),
                jnp.where(present, var, 0.0),
                jnp.where(present, m_min, 0.0),
                *qcols,
                jnp.where(present, m_max, 0.0),
                m_count,
            ],
            axis=1,
        )
        return m_u, m_v, feats, m_hist, n_edges, n_local_max, n_true_max

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )(labels, values)


def shard_sample_cap(labels_host: np.ndarray, n_shards: int) -> int:
    """Static per-shard compaction capacity from a HOST label volume
    (padded z divisible by ``n_shards``): the max over shards of the
    shard's valid face rows — in-slab pairs plus the +z cross-shard plane
    the shard owns — with ``sample_capacity``'s headroom/bucketing.  The
    extended-slab count includes the borrowed plane's in-plane pairs too
    (not owned), so it upper-bounds the kernel's count — safe for a cap."""
    from ..ops.rag import count_boundary_samples, sample_capacity

    z = labels_host.shape[0]
    h = z // n_shards
    worst = 1
    for i in range(n_shards):
        z0, z1 = i * h, (i + 1) * h
        ext = labels_host[z0 : min(z1 + 1, z)]  # +z neighbor plane if any
        worst = max(worst, count_boundary_samples(ext))
    return sample_capacity(worst)


@obs_trace.traced(kind="collective")
def sharded_boundary_edge_features(
    labels,
    values,
    mesh=None,
    axis_name: str = "data",
    max_edges: int = 16384,
    hist_bins: int = HIST_BINS,
    max_id=None,
    max_samples=None,
):
    """10 RAG edge features of a z-sharded volume in one collective program.

    ``max_id``: the largest label id, when the caller knows it (e.g. the
    compact node count) — gates the packed single-key sort without touching
    the (possibly multi-host global) labels array.

    ``labels``: int32 compact ids (0 = background), z-extent divisible by the
    mesh size.  Returns host arrays ``(edges [n,2] int64, feats [n,10])`` in
    lexicographic edge order — the same contract as
    ``ops.rag.boundary_edge_features``; count/mean/min/max exact, quantiles
    within one histogram bin (the block pipeline's own merge tolerance).
    """
    mesh = mesh if mesh is not None else get_mesh(axis_name=axis_name)
    n = mesh.shape[axis_name]
    if labels.shape[0] % n:
        raise ValueError(
            f"z extent {labels.shape[0]} not divisible by mesh size {n}"
        )
    lab = put_global(labels, mesh, axis_name, dtype=np.int32)
    val = put_global(values, mesh, axis_name, dtype=np.float32)
    # single-key packed sorts whenever the global id space fits 15 bits.
    # The bound must come from the caller (max_id) or a HOST array: an
    # eager labels.max() on a multi-host global jax.Array would crash
    # (non-addressable shards) and adds a blocking reduction otherwise.
    from ..ops.rag import PACK_MAX_ID

    if max_id is None and isinstance(labels, np.ndarray) and labels.size:
        max_id = int(labels.max())
    packed = max_id is not None and 0 <= int(max_id) <= PACK_MAX_ID
    # pre-sort compaction: size the per-shard cap from the host labels when
    # available; device-resident callers pass max_samples themselves
    if max_samples is None and isinstance(labels, np.ndarray) and labels.size:
        max_samples = shard_sample_cap(labels, n)
    if max_samples is not None:
        # skip compaction that cannot shrink the sort (small or
        # boundary-dense shards) — same guard as the single-device wrapper
        h, y, x_ = lab.shape[0] // n, lab.shape[1], lab.shape[2]
        raw_rows = 2 * (h * y * x_ + h * (y - 1) * x_ + h * y * (x_ - 1))
        if int(max_samples) >= raw_rows:
            max_samples = None
    e_u, e_v, feats, _, n_edges, n_local_max, n_true_max = _sharded_rag(
        lab, val, int(max_edges), int(hist_bins), axis_name, mesh,
        packed=bool(packed),
        max_samples=None if max_samples is None else int(max_samples),
    )
    n_edges = int(n_edges)
    if int(n_local_max) > max_edges or n_edges > max_edges:
        raise RuntimeError(
            f"edge table overflow (local max {int(n_local_max)}, merged "
            f"{n_edges} vs max_edges={max_edges}); raise the bound"
        )
    if max_samples is not None and int(n_true_max) > int(max_samples):
        raise RuntimeError(
            f"sample compaction overflow ({int(n_true_max)} valid rows in "
            f"one shard vs max_samples={int(max_samples)}) — a dropped row "
            "would corrupt features; raise the cap"
        )
    edges = np.stack(
        [np.asarray(e_u)[:n_edges], np.asarray(e_v)[:n_edges]], axis=1
    ).astype(np.int64)
    return edges, np.asarray(feats)[:n_edges]
