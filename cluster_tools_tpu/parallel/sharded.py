"""Sharded whole-volume kernels: XLA collectives over the device mesh.

The block runtime scales by *data parallelism* — independent halo'd blocks
ride a `NamedSharding` and never talk to each other; every cross-block merge
goes through the chunked store.  This module is the other half of the
SURVEY.md §2.8/§2.9 mapping: when one volume is larger than a chip's HBM, the
volume itself is sharded over the mesh (blocks = "sequence shards") and
neighbor communication rides **ICI collectives inside one jit program** —
`lax.ppermute` boundary exchange along the sharded axis, `lax.all_gather`
of the cross-shard tables, `lax.psum` convergence votes — instead of
filesystem round-trips.  This is the spatial analog of ring attention's
neighbor exchange (SURVEY.md §5 "long-context").

Kernels:

  * ``halo_exchange`` — pad a z-sharded array with its neighbors' boundary
    planes (the reference's overlapping block reads, volume_utils
    getBlockWithHalo, as an ICI ring exchange).
  * ``sharded_connected_components`` — global CC of a z-sharded volume,
    with no collective rounds: each shard labels its slab to its local
    fixpoint (ops.cc), one ``ppermute`` of the boundary planes and one
    ``all_gather`` of the face pairs build the whole cross-shard
    equivalence table, and a union-find over it, replicated on every
    shard, relabels the shard (phases ``scc.local``, ``scc.exchange``,
    ``scc.resolve``).  The cross-shard merge that the block pipeline does
    via face files + union-find (ThresholdedComponents steps 3-4) happens
    entirely on the mesh.
  * ``sharded_seeded_watershed`` — the seeded flood of a z-sharded volume:
    per-shard sweeps, ``ppermute``'d boundary relaxation and ``psum``
    convergence votes inside ``lax.while_loop``s.

Tested on the 8-virtual-device CPU mesh against the scipy oracle
(tests/test_sharded.py); the same program runs unchanged on a real ICI mesh.
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import faults
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..ops.cc import (
    _coarse_cc_core,
    _min_sweep,
    _min_sweep_seq,
    _shift,
    boundary_cross_offsets,
    neighbor_offsets,
    resolve_coarse_tile,
)
from .mesh import get_mesh, put_global


class CollectiveInitError(RuntimeError):
    """Collective setup (mesh/device resolution) failed — the entry kernels
    degrade to the single-device local kernel instead of failing the run
    (``sharded.fallback_local`` obs counter + warning, never silent)."""


def _collective_mesh(mesh, axis_name: str):
    """Resolve the mesh for a collective entry kernel; every failure —
    injected (``collective.init`` fault site) or real (driver/device init)
    — surfaces as :class:`CollectiveInitError` so callers can fall back."""
    try:
        faults.check("collective.init")
        return mesh if mesh is not None else get_mesh(axis_name=axis_name)
    except Exception as e:
        raise CollectiveInitError(f"collective init failed: {e}") from e


def _note_local_fallback(what: str, err: Exception) -> None:
    """Record a sharded→local degradation — loud (warning + obs counter),
    and refused outright on a multi-process runtime, where one host
    computing locally while peers enter the collective would deadlock the
    program or silently split the answer."""
    if jax.process_count() > 1:
        raise err
    obs_metrics.inc("sharded.fallback_local")
    warnings.warn(
        f"{what}: {err} — falling back to the single-device local kernel "
        "(same result, no ICI parallelism)",
        RuntimeWarning,
        stacklevel=3,
    )


def _neighbor_planes(plane, axis_name, direction):
    """Every shard receives ``plane`` from its -z neighbor (direction=+1) or
    +z neighbor (direction=-1) along the mesh ring; shards with no such
    neighbor receive zeros (lax.ppermute semantics), which callers mask out
    via the exchanged mask plane."""
    n = lax.axis_size(axis_name)
    if direction > 0:
        perm = [(i, i + 1) for i in range(n - 1)]
    else:
        perm = [(i + 1, i) for i in range(n - 1)]
    return lax.ppermute(plane, axis_name, perm=perm)


def halo_exchange(x, halo: int, axis_name: str, fill=0):
    """Extend a z-sharded array with ``halo`` boundary planes from its mesh
    neighbors (call inside ``shard_map``).  Beyond-the-volume planes (outer
    shards) pad with ``fill``.

    A halo deeper than one shard chains ppermutes — hop k forwards the block
    received at hop k-1, so shard i accumulates shards i∓1..i∓hops — and
    slices the nearest ``halo`` planes.  Returns shape (Zl + 2*halo, ...):
    the ICI equivalent of the reference's overlapping chunk reads
    (SURVEY.md §2.8.2).
    """
    z_local = x.shape[0]
    hops = -(-halo // z_local)  # ceil
    idx = lax.axis_index(axis_name)
    n = lax.axis_size(axis_name)

    def gather(direction):
        if hops == 1:
            # common case: one hop moves only the needed boundary planes
            plane = x[-halo:] if direction > 0 else x[:halo]
            got = _neighbor_planes(plane, axis_name, direction)
            missing = (idx < 1) if direction > 0 else (idx >= n - 1)
            return jnp.where(missing, jnp.full_like(got, fill), got)
        # shallow shards: chain full blocks (hop h forwards hop h-1's block,
        # so shard i accumulates shards i∓1..i∓hops), then slice
        parts = []
        block = x
        for h in range(1, hops + 1):
            block = _neighbor_planes(block, axis_name, direction)
            missing = (idx < h) if direction > 0 else (idx >= n - h)
            block = jnp.where(missing, jnp.full_like(block, fill), block)
            # keep global z order: lo side grows downward (farthest first),
            # hi side grows upward (nearest first)
            if direction > 0:
                parts.insert(0, block)
            else:
                parts.append(block)
        stacked = jnp.concatenate(parts, axis=0)
        # nearest `halo` planes: the trailing ones on the lo side, the
        # leading ones on the hi side
        return stacked[-halo:] if direction > 0 else stacked[:halo]

    lo = gather(+1)  # from the -z side
    hi = gather(-1)  # from the +z side
    return jnp.concatenate([lo, x, hi], axis=0)


def _exchange_planes(arrs, axis_name):
    """The shard-boundary exchange both sharded kernels share: every array's
    last plane goes to the +z neighbor, first plane to the -z neighbor.
    Returns ``(from_below, from_above)`` plane tuples (zero-filled at the
    mesh edges — combiners guard via exchanged mask/label planes)."""
    lo = tuple(_neighbor_planes(a[-1], axis_name, +1) for a in arrs)
    hi = tuple(_neighbor_planes(a[0], axis_name, -1) for a in arrs)
    return lo, hi


def _update_boundary(state, combine, lo, hi, z_local):
    """Apply a cross-boundary ``combine`` to every volume in ``state``:
    first planes against the -z neighbor's contribution ``lo``, last planes
    against the +z neighbor's ``hi``.  A one-plane shard is both boundary
    planes, so both contributions combine into the same plane.

    ``combine(own_planes, got_planes, plane_idx) -> new_planes`` where
    ``plane_idx`` is 0 or -1 (for indexing side data in the closure).
    """
    first = combine(tuple(v[0] for v in state), lo, 0)
    if z_local == 1:
        first = combine(first, hi, 0)
        return tuple(f[None] for f in first)
    last = combine(tuple(v[-1] for v in state), hi, -1)
    return tuple(
        jnp.concatenate([f[None], v[1:-1], l[None]], 0)
        for f, v, l in zip(first, state, last)
    )


def _local_relax(label, mask, offsets, axes, size, shard_offset, local_size):
    """One round of per-shard relaxation: min-label propagation (directional
    axis sweeps — log-depth ``_min_sweep`` on the assoc path, the ctt-cc
    sequential-carry ``_min_sweep_seq`` otherwise, the same CTT_SWEEP_MODE
    switch every sweep kernel honors; diagonal offsets keep one-voxel
    shifts), then two pointer jumps (only labels rooted inside this shard
    can be jumped locally)."""
    from ..ops import _backend

    sentinel = jnp.int32(size)
    new = label
    sweep_fn = _min_sweep if _backend.use_assoc() else _min_sweep_seq
    prop = [o for o in offsets if sum(c != 0 for c in o) > 1]
    for axis in axes:
        for reverse in (False, True):
            new = sweep_fn(new, mask, None, axis, reverse, sentinel)
    if prop:
        best = new
        for off in prop:
            neigh = _shift(new, off, sentinel)
            best = jnp.minimum(best, jnp.where(mask, neigh, sentinel))
        new = jnp.where(mask, best, sentinel)

    def jump(lab):
        flat = lab.reshape(-1)
        idx = flat - shard_offset
        local = (idx >= 0) & (idx < local_size)
        safe = jnp.clip(idx, 0, local_size - 1)
        jumped = jnp.where(local, flat[safe], flat)
        return jnp.where(mask, jumped.reshape(lab.shape), sentinel)

    return jump(jump(new))


@partial(jax.jit, static_argnames=("connectivity", "axis_name", "mesh"))
def _sharded_cc(mask, connectivity, axis_name, mesh):
    """Coarse-to-fine CC at shard granularity (ctt-cc, the shard-level
    instance of ops/cc.py's tile scheme), in three phases, each under its
    named scope:

    * ``scc.local``: each shard labels its slab to its LOCAL fixpoint in
      global-id space, with no collectives (the coarse kernel's tile
      fixpoints and tile-face merge, ``cc.tiles``/``cc.merge`` nested, or
      the flat relaxation loop);
    * ``scc.exchange``: ONE ``ppermute`` of the shard-boundary planes and
      one ``all_gather`` of the resulting face pairs, so every shard holds
      the complete cross-shard equivalence table;
    * ``scc.resolve``: the compact value union-find over that table,
      replicated on every shard, applied to the shard's labels with one
      search of every voxel (``merge_value_table``/``apply_value_roots``).

    Returns ``(labels, rounds, face_pairs)``: int32 labels (background -1,
    each component its minimal global flat index), and per shard (one
    int32 each, sharded like the labels) the stage-1 round count and the
    number of live face pairs the shard contributed to the table."""
    shape = mask.shape
    size = int(np.prod(shape))
    if size >= np.iinfo(np.int32).max:
        raise ValueError("volume too large for int32 flat label ids")
    n_shards = mesh.shape[axis_name]
    z_local = shape[0] // n_shards
    local_size = z_local * int(np.prod(shape[1:]))
    offsets = neighbor_offsets(3, connectivity)
    # cross-boundary offsets, expressed as in-plane shifts of the received
    # neighbor plane (dz = ±1 face/diagonal connections) — the ONE shared
    # derivation in ops/cc.py, so connectivity semantics cannot drift
    cross = boundary_cross_offsets(3, connectivity)
    from ..ops import _backend
    from ..ops.unionfind import apply_value_roots, merge_value_table

    local_shape = (z_local,) + shape[1:]
    coarse = _backend.use_coarse_cc()
    tile = resolve_coarse_tile(local_shape, None) if coarse else None

    def local_fn(m):
        sentinel = jnp.int32(size)

        # -- stage 1: shard-local fixpoint, global-id labels ---------------
        with jax.named_scope("scc.local"):
            offset = lax.axis_index(axis_name) * local_size
            if coarse:
                label, stats = _coarse_cc_core(
                    m, offset, size, connectivity, None, False, tile
                )
                rounds = stats["fixpoint_iters"]
            else:
                gids = (
                    jnp.arange(local_size, dtype=jnp.int32)
                    .reshape(local_shape) + offset
                )
                init = jnp.where(m, gids, sentinel)

                def body(state):
                    lab, _, n = state
                    new = _local_relax(
                        lab, m, offsets, (0, 1, 2), size, offset, local_size
                    )
                    return new, jnp.any(new != lab), n + 1

                label, _, rounds = lax.while_loop(
                    lambda s: s[1], body,
                    (init, jnp.bool_(True), jnp.int32(0)),
                )

        pairs = jnp.int32(0)
        if n_shards > 1:
            # -- stage 2: one all-gathered boundary table ------------------
            # each shard contributes its +z face: own last plane against the
            # +z neighbor's first plane (zero-filled mask past the mesh
            # edge, so the last shard contributes only self-loop padding)
            with jax.named_scope("scc.exchange"):
                _, hi = _exchange_planes((label, m), axis_name)
                hi_lab, hi_msk = hi
                own_lab, own_msk = label[-1], m[-1]
                a_parts, b_parts = [], []
                for off in cross:
                    g_lab = _shift(hi_lab, off, sentinel)
                    g_msk = _shift(hi_msk, off, False)
                    ok = own_msk & g_msk & (g_lab < sentinel)
                    pairs = pairs + jnp.sum(ok, dtype=jnp.int32)
                    a_parts.append(
                        jnp.where(ok, own_lab, sentinel).reshape(-1))
                    b_parts.append(jnp.where(ok, g_lab, sentinel).reshape(-1))
                a = lax.all_gather(
                    jnp.concatenate(a_parts), axis_name).reshape(-1)
                b = lax.all_gather(
                    jnp.concatenate(b_parts), axis_name).reshape(-1)
            with jax.named_scope("scc.resolve"):
                vals, root_vals = merge_value_table(a, b)
                label = apply_value_roots(label, vals, root_vals)
        with jax.named_scope("scc.resolve"):
            label = jnp.where(m, label, jnp.int32(-1))
            return label, rounds.reshape(1), pairs.reshape(1)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        # the ops/cc.py sweeps seed their scan carries from shape constants,
        # which the varying-manual-axes check sees as replicated values
        # meeting per-shard ones; every value here is per-shard
        check_vma=False,
    )
    return fn(mask)


@partial(jax.jit, static_argnames=("axis_name", "mesh"))
def _sharded_flood(hmap, seeds, mask, axis_name, mesh):
    from ..ops import _backend
    from ..ops.watershed import (
        _BIG,
        _sweep_altitude_assoc,
        _sweep_altitude_seq,
        _sweep_assign_assoc,
        _sweep_assign_seq,
    )

    if _backend.use_assoc():
        sweep_alt, sweep_asg = _sweep_altitude_assoc, _sweep_assign_assoc
    else:
        sweep_alt, sweep_asg = _sweep_altitude_seq, _sweep_assign_seq
    big_dist = jnp.int32(np.iinfo(np.int32).max - 1)
    n_shards = mesh.shape[axis_name]
    z_local = hmap.shape[0] // n_shards

    def local_fn(h, s, m):
        s = jnp.where(m, s, 0)
        is_seed = s > 0

        # -- phase 1: altitude ---------------------------------------------
        def alt_boundary(alt):
            lo, hi = _exchange_planes((alt, m), axis_name)

            def comb(own, got, plane_idx):
                (own_alt,) = own
                got_a, got_m = got
                cand = jnp.maximum(got_a, h[plane_idx])
                ok = m[plane_idx] & ~is_seed[plane_idx] & got_m
                return (jnp.where(ok, jnp.minimum(own_alt, cand), own_alt),)

            (out,) = _update_boundary((alt,), comb, lo, hi, z_local)
            return out

        def alt_body(state):
            alt, _ = state
            new = alt
            for axis in (0, 1, 2):
                for rev in (False, True):
                    new = sweep_alt(new, h, is_seed, m, axis, rev)
            new = alt_boundary(new)
            changed = lax.psum(
                jnp.any(new != alt).astype(jnp.int32), axis_name
            ) > 0
            return new, changed

        alt0 = jnp.where(is_seed, h, _BIG)
        alt, _ = lax.while_loop(
            lambda st: st[1], alt_body, (alt0, jnp.bool_(True))
        )

        # -- phase 2: assignment -------------------------------------------
        alt_masked = jnp.where(m, alt, _BIG)
        (alt_lo,), (alt_hi,) = _exchange_planes((alt_masked,), axis_name)
        # mesh-edge shards received zeros: overwrite with BIG (no edge)
        idx = lax.axis_index(axis_name)
        alt_lo = jnp.where(idx == 0, jnp.full_like(alt_lo, _BIG), alt_lo)
        alt_hi = jnp.where(
            idx == n_shards - 1, jnp.full_like(alt_hi, _BIG), alt_hi
        )
        def asg_boundary(dist, label):
            lo, hi = _exchange_planes((dist, label), axis_name)

            def comb(own, got, plane_idx):
                d, l = own
                got_d, got_l = got
                # the neighbor altitude belongs to the SIDE the contribution
                # came from (a one-plane shard combines both sides into the
                # same plane, so the side can't be derived from plane_idx)
                got_a = alt_lo if got is lo else alt_hi
                edge_ok = alt[plane_idx] == jnp.maximum(got_a, h[plane_idx])
                cand = got_d + 1
                valid = (
                    m[plane_idx] & ~is_seed[plane_idx] & edge_ok & (got_l > 0)
                )
                better = valid & (
                    (cand < d) | ((cand == d) & ((l == 0) | (got_l < l)))
                )
                return (
                    jnp.where(better, cand, d),
                    jnp.where(better, got_l, l),
                )

            return _update_boundary((dist, label), comb, lo, hi, z_local)

        def asg_body(state):
            dist, label, _ = state
            d, l = dist, label
            for axis in (0, 1, 2):
                for rev in (False, True):
                    d, l = sweep_asg(d, l, alt, h, is_seed, m, axis, rev)
            d, l = asg_boundary(d, l)
            changed = lax.psum(
                jnp.any((d != dist) | (l != label)).astype(jnp.int32),
                axis_name,
            ) > 0
            return d, l, changed

        dist0 = jnp.where(is_seed, 0, big_dist)
        _, label, _ = lax.while_loop(
            lambda st: st[2], asg_body, (dist0, s, jnp.bool_(True))
        )
        return jnp.where(m, label, 0)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        # the reused sweep kernels build scan carries from shape constants,
        # which the varying-manual-axes tracker sees as replicated values
        # meeting varying ones — semantically fine here (every value is
        # per-shard), so disable the strict check
        check_vma=False,
    )
    return fn(hmap, seeds, mask)


@obs_trace.traced(kind="collective")
def sharded_seeded_watershed(
    hmap,
    seeds,
    mask=None,
    mesh=None,
    axis_name: str = "data",
) -> jnp.ndarray:
    """Seeded 3d flood of a z-sharded volume over the device mesh — the
    flagship kernel's collective form: per-shard directional sweeps
    (ops.watershed, honoring CTT_SWEEP_MODE) + ppermute'd boundary-plane
    relaxation + psum convergence votes, both flood phases inside one jit.

    Computes the SAME lexicographic (pass-height, hops, label) fixpoint as
    ``ops.watershed.seeded_watershed(..., per_slice=False)`` — exact label
    equality (tested) — for volumes whose z-extent is divisible by the mesh
    size.  Seeds are global int32 ids (0 = unlabeled); voxels outside
    ``mask`` stay 0.

    When collective setup fails (``CollectiveInitError`` — a wedged device
    runtime, or the ``collective.init`` fault site), the kernel degrades to
    the single-device ``ops.watershed.seeded_watershed`` fixpoint, which
    computes the SAME labels (the equality claimed above); the degradation
    is recorded (``sharded.fallback_local`` counter + warning), and refused
    under a multi-process runtime.
    """
    try:
        mesh = _collective_mesh(mesh, axis_name)
    except CollectiveInitError as e:
        _note_local_fallback("sharded_seeded_watershed", e)
        from ..ops.watershed import seeded_watershed

        return seeded_watershed(
            jnp.asarray(np.asarray(hmap, dtype=np.float32)),
            jnp.asarray(np.asarray(seeds, dtype=np.int32)),
            mask=None if mask is None else jnp.asarray(
                np.asarray(mask, dtype=bool)
            ),
            per_slice=False,
        )
    n = mesh.shape[axis_name]
    if hmap.shape[0] % n:
        raise ValueError(
            f"z extent {hmap.shape[0]} not divisible by mesh size {n}"
        )
    if mask is None:
        mask = np.ones(hmap.shape, dtype=bool)  # host: no device round-trip
    # put_global: multi-process-safe placement (each process materializes
    # only its addressable shards)
    hmap = put_global(hmap, mesh, axis_name, dtype=np.float32)
    seeds = put_global(seeds, mesh, axis_name, dtype=np.int32)
    mask = put_global(mask, mesh, axis_name, dtype=bool)
    faults.check("collective.execute")
    return _sharded_flood(hmap, seeds, mask, axis_name, mesh)


@obs_trace.traced(kind="collective")
def sharded_connected_components(
    mask,
    mesh=None,
    axis_name: str = "data",
    connectivity: int = 1,
    with_stats: bool = False,
):
    """Global connected components of a volume z-sharded over the device mesh.

    Returns int32 labels where background = -1 and each component carries the
    minimal *global* flat index of its voxels (compose with
    ``ops.relabel.relabel_consecutive`` or host ``np.unique`` for 1..N ids —
    root order matches the single-device ``connected_components_raw``, so the
    consecutive renumbering is identical).  The volume's z-extent must divide
    by the mesh size.

    One jit program (``_sharded_cc``): each shard labels its slab to its
    local fixpoint, one boundary-plane ``ppermute`` and one ``all_gather``
    give every shard the cross-shard face pairs, and a replicated
    union-find over them relabels each shard with one search of every
    voxel — no host round-trips and no rounds of collectives.

    ``with_stats`` returns ``(labels, stats)``: ``stats`` maps ``rounds``
    and ``face_pairs`` to the program's per-shard int32 counts (stage-1
    fixpoint rounds, live face pairs contributed), computed in every call
    alike; None on the local fallback below.

    When collective setup fails (``CollectiveInitError`` — a wedged device
    runtime, or the ``collective.init`` fault site), the kernel degrades to
    the single-device ``ops.cc.connected_components_raw``, which carries the
    IDENTICAL label contract (min global flat index per component,
    background -1) — same values, no ICI parallelism; the degradation is
    recorded (``sharded.fallback_local`` counter + warning), and refused
    under a multi-process runtime.
    """
    try:
        mesh = _collective_mesh(mesh, axis_name)
    except CollectiveInitError as e:
        _note_local_fallback("sharded_connected_components", e)
        from ..ops.cc import connected_components_raw

        labels = connected_components_raw(
            jnp.asarray(np.asarray(mask, dtype=bool)),
            connectivity=connectivity,
        )
        return (labels, None) if with_stats else labels
    n = mesh.shape[axis_name]
    if mask.shape[0] % n:
        raise ValueError(
            f"z extent {mask.shape[0]} not divisible by mesh size {n}"
        )
    mask = put_global(mask, mesh, axis_name, dtype=bool)
    faults.check("collective.execute")
    labels, rounds, pairs = _sharded_cc(mask, connectivity, axis_name, mesh)
    if with_stats:
        return labels, {"rounds": rounds, "face_pairs": pairs}
    return labels


def fused_threshold_components(
    x,
    threshold: float,
    mesh=None,
    axis_name: str = "data",
    connectivity: int = 1,
    with_stats: bool = False,
):
    """ctt-stream under the sharded collective: threshold + global CC as
    one device-resident sequence — the boolean mask is born on device and
    flows straight into the collective label program, never crossing to
    host (the collective analog of the fused block chain's elided
    threshold intermediate).

    ``x`` is the z-sharded raw volume (``mesh.put_from_store`` placement;
    pad slabs must be 0.0).  Only ``greater``-mode with ``threshold >= 0``
    is supported: zero pad slabs then threshold to background, preserving
    the host-threshold path's pad contract — callers with other modes keep
    the host-side transform.  Labels match ``sharded_connected_components``
    on the host-thresholded mask exactly; ``with_stats`` as there.
    """
    if threshold < 0:
        raise ValueError(
            "fused_threshold_components requires threshold >= 0 (pad "
            "slabs are 0.0 and must stay background)"
        )
    mask = jax.jit(lambda v: v > threshold)(x)
    return sharded_connected_components(
        mask, mesh=mesh, axis_name=axis_name, connectivity=connectivity,
        with_stats=with_stats,
    )
