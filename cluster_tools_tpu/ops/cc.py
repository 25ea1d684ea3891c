"""Connected components as an XLA program.

Replaces skimage.morphology.label / vigra.labelVolumeWithBackground
(reference thresholded_components/block_components.py:143-182,
watershed/watershed.py:206,331).

Algorithm (TPU-friendly, no data-dependent shapes): iterative *min-label
propagation* over the neighborhood, accelerated by *pointer jumping* — after each
local propagation every voxel re-gathers the label of the voxel its label points to,
so label information travels exponentially per iteration (O(log diameter)
iterations instead of O(diameter)).  This is the same union-find-by-minimum idea a
parallel CC on GPUs uses (coarse-to-fine CCL literature), expressed as pure
gather/min ops inside a ``lax.while_loop``.
"""

from __future__ import annotations

from functools import partial
from itertools import product
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import _backend


def neighbor_offsets(
    ndim: int, connectivity: int, per_slice: bool = False
) -> np.ndarray:
    """All neighbor offsets with 1 ≤ #nonzero-coords ≤ connectivity
    (connectivity=1 → faces, ndim → full Moore neighborhood).  ``per_slice``
    drops offsets crossing axis 0, so each z-slice is an independent domain
    (the reference's 2d watershed/labeling modes)."""
    offs = [
        o
        for o in product((-1, 0, 1), repeat=ndim)
        if 0 < sum(c != 0 for c in o) <= connectivity
    ]
    if per_slice:
        offs = [o for o in offs if o[0] == 0]
    return np.array(offs, dtype=np.int32)


def _shift(x: jnp.ndarray, offset, fill) -> jnp.ndarray:
    """x shifted so out[p] = x[p + offset], `fill` outside."""
    out = x
    for axis, o in enumerate(offset):
        if o == 0:
            continue
        out = jnp.roll(out, -o, axis=axis)
        idx = [slice(None)] * x.ndim
        # out[p] = x[p+o] is invalid where p+o leaves the axis: the first |o|
        # entries for o<0, the last o entries for o>0
        idx[axis] = slice(0, -o) if o < 0 else slice(x.shape[axis] - o, None)
        out = out.at[tuple(idx)].set(fill)
    return out


def boundary_cross_offsets(
    ndim: int, connectivity: int, per_slice: bool = False
):
    """In-plane shifts of every neighbor offset that crosses an axis-0
    boundary plane: the ONE derivation of cross-plane connectivity, shared
    by the sharded CC collective (parallel/sharded.py) and the per-slice
    merge paths, so connectivity semantics can't drift between kernels.
    Both dz signs map to the same in-plane shift, deduped."""
    offs = neighbor_offsets(ndim, connectivity, per_slice)
    return sorted({tuple(int(c) for c in o[1:]) for o in offs if o[0] != 0})


def _canonical_offsets(ndim: int, connectivity: int, per_slice: bool):
    """The lexicographically-positive half of the neighborhood: each
    unordered adjacency {p, p+o} appears under exactly one canonical o."""
    out = []
    for o in neighbor_offsets(ndim, connectivity, per_slice):
        nz = [int(c) for c in o if c != 0]
        if nz and nz[0] > 0:
            out.append(tuple(int(c) for c in o))
    return out


def _use_assoc() -> bool:
    return _backend.use_assoc()


def _min_sweep_seq(label, mask, partition, axis, reverse, sentinel):
    """Sequential-carry variant of ``_min_sweep``: the same Gauss–Seidel
    min-label conduction as one ``lax.scan`` over planes — O(n) work, n
    dependent steps, the work-bound-backend winner (the CC analog of
    watershed's ``_sweep_altitude_seq``; both compute the identical
    fixpoint).  Before ctt-cc the seq path had NO sweep at all (one-voxel
    shift propagation), which is why the flat kernel needed ~7x the rounds
    on the CPU mesh."""

    def mv(x):
        x = jnp.moveaxis(x, axis, 0)
        return jnp.flip(x, axis=0) if reverse else x

    l_v = mv(label)
    m_v = mv(mask)
    p_v = mv(partition) if partition is not None else None
    plane = l_v.shape[1:]

    def step(carry, x):
        c_lab, c_m, c_p = carry
        if p_v is not None:
            l, m, p = x
            conduct = m & c_m & (p == c_p)
        else:
            l, m = x
            p = c_p
            conduct = m & c_m
        new = jnp.where(conduct, jnp.minimum(l, c_lab), l)
        return (jnp.where(m, new, sentinel), m, p), new

    xs = (l_v, m_v) if p_v is None else (l_v, m_v, p_v)
    init_p = (
        jnp.zeros(plane, p_v.dtype) if p_v is not None
        else jnp.zeros(plane, jnp.int32)
    )
    _, out = lax.scan(
        step,
        (jnp.full(plane, sentinel), jnp.zeros(plane, bool), init_p),
        xs,
    )
    if reverse:
        out = jnp.flip(out, axis=0)
    return jnp.moveaxis(out, 0, axis)


def _min_sweep(label, mask, partition, axis, reverse, sentinel):
    """Min-label propagation along one axis in log depth: the carry chain is
    a composition of clamp transfers c → min(u, max(c, l)) (the same family
    as the watershed sweeps), so a whole straight run collapses to its
    minimum in one ``lax.associative_scan`` instead of one voxel per round."""

    def mv(x):
        x = jnp.moveaxis(x, axis, 0)
        return jnp.flip(x, axis=0) if reverse else x

    l_v = mv(label)
    m_v = mv(mask)
    # conduction across the edge (i-1, i): both in mask, same partition
    prev_m = jnp.concatenate([jnp.zeros_like(m_v[:1]), m_v[:-1]], axis=0)
    conduct = m_v & prev_m
    if partition is not None:
        p_v = mv(partition)
        prev_p = jnp.concatenate([p_v[:1], p_v[:-1]], axis=0)
        conduct &= p_v == prev_p

    u = jnp.where(m_v, l_v, sentinel)
    low = jnp.where(conduct, jnp.int32(-1), sentinel)

    def combine(f, g):  # f earlier, g later
        uf, lf = f
        ug, lg = g
        return jnp.minimum(ug, jnp.maximum(uf, lg)), jnp.maximum(lf, lg)

    u_inc, _ = lax.associative_scan(combine, (u, low), axis=0)
    carry_in = jnp.concatenate(
        [jnp.full_like(u_inc[:1], sentinel), u_inc[:-1]], axis=0
    )
    out = jnp.where(conduct, jnp.minimum(l_v, carry_in), l_v)
    if reverse:
        out = jnp.flip(out, axis=0)
    return jnp.moveaxis(out, 0, axis)


def _axis_conduct(mask, partition, axis):
    """Loop-invariant conduction masks for one axis, in scan layout (the
    axis moved to front): ``c_f[i]`` conducts the edge (i-1, i), ``c_r[i]``
    the edge (i, i+1).  Hoisting these out of the fixpoint loop is a large
    part of the ctt-cc flat-path win — the per-sweep formulation re-derived
    the mask/partition transposes and the edge predicate every round."""
    m_v = jnp.moveaxis(mask, axis, 0)
    c_f = m_v & jnp.concatenate([jnp.zeros_like(m_v[:1]), m_v[:-1]], axis=0)
    if partition is not None:
        p_v = jnp.moveaxis(partition, axis, 0)
        c_f &= p_v == jnp.concatenate([p_v[:1], p_v[:-1]], axis=0)
    c_r = jnp.concatenate([c_f[1:], jnp.zeros_like(c_f[:1])], axis=0)
    return c_f, c_r


def _assoc_sweep_dir(l_v, cond, sentinel, reverse):
    """One clamp-transfer ``associative_scan`` sweep along the leading axis
    (the ``_min_sweep`` recurrence on a precomputed conduction mask);
    labels keep the off-mask == sentinel invariant, so no masking is
    needed beyond ``cond``."""
    if reverse:
        return jnp.flip(
            _assoc_sweep_dir(
                jnp.flip(l_v, 0), jnp.flip(cond, 0), sentinel, False
            ),
            0,
        )
    low = jnp.where(cond, jnp.int32(-1), sentinel)

    def combine(f, g):  # f earlier, g later
        uf, lf = f
        ug, lg = g
        return jnp.minimum(ug, jnp.maximum(uf, lg)), jnp.maximum(lf, lg)

    u_inc, _ = lax.associative_scan(combine, (l_v, low), axis=0)
    carry = jnp.concatenate(
        [jnp.full_like(u_inc[:1], sentinel), u_inc[:-1]], axis=0
    )
    return jnp.where(cond, jnp.minimum(l_v, carry), l_v)


def _axis_sweep_pair(l_v, c_f, c_r, sentinel):
    """Forward then backward min-conduction along the leading axis (a
    Gauss–Seidel pair: the backward pass consumes the forward result, so
    one call resolves every straight run to its minimum).  The backend
    sweep mode picks the formulation: log-depth ``associative_scan`` or
    the sequential-carry ``lax.scan`` (native ``reverse=True``, no flips).
    The two-op step relies on the labels' off-mask == sentinel invariant:
    conduction is false off-mask, so no re-masking is needed per plane."""
    if _use_assoc():
        out = _assoc_sweep_dir(l_v, c_f, sentinel, False)
        return _assoc_sweep_dir(out, c_r, sentinel, True)

    plane = l_v.shape[1:]

    def step(carry, x):
        l, cond = x
        new = jnp.where(cond, jnp.minimum(l, carry), l)
        return new, new

    _, out = lax.scan(step, jnp.full(plane, sentinel), (l_v, c_f))
    _, out = lax.scan(
        step, jnp.full(plane, sentinel), (out, c_r), reverse=True
    )
    return out


# rounds run unconditionally before the stability-gated loop: volumes that
# need fewer rounds pay at most one redundant (cheap, already-converged)
# round, while every realistic volume skips the stability test for rounds
# that cannot pass it
_FLAT_PRE_ROUNDS = 2


def _flat_cc(
    mask: jnp.ndarray,
    connectivity: int,
    partition: Optional[jnp.ndarray],
    per_slice: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Whole-volume min-propagation fixpoint (the flat kernel); returns
    ``(raw_labels, fixpoint_iters)`` — see ``connected_components_raw`` for
    the label contract.  ``fixpoint_iters`` counts WORK rounds: the loop
    terminates on an explicit edge-stability test instead of re-running a
    full round just to observe "nothing changed".

    Per round: one moveaxis per axis with a fused forward+backward sweep
    pair over precomputed conduction masks (``_axis_conduct``), diagonal
    shift-propagation only for connectivity > 1, then one pointer jump.
    Termination: labels are a fixpoint iff every conducting edge is
    label-equal — sweep-stable labels are constant per component, and the
    component's minimal voxel pins that constant to the minimal flat id —
    which costs three shifted compares instead of a full verification
    round.  The lane-most-first axis order and the single jump are the
    measured winners on the CPU fallback (bench.py cc config)."""
    shape = mask.shape
    size = int(np.prod(shape))
    sentinel = jnp.int32(size)
    flat_ids = jnp.arange(size, dtype=jnp.int32).reshape(shape)
    init = jnp.where(mask, flat_ids, sentinel)
    axes = tuple(range(mask.ndim))
    if per_slice:
        axes = axes[1:]
    # lane axis first: its (expensive, strided) transpose then overlaps
    # the cheap outer-axis moves instead of serializing after them
    order = tuple(reversed(axes))
    conds = {a: _axis_conduct(mask, partition, a) for a in order}
    offsets = neighbor_offsets(mask.ndim, connectivity, per_slice)
    # face-neighbor conduction is exactly axis conduction, so the sweep
    # path needs no shift-propagation for connectivity=1 at all; higher
    # connectivities keep shifts for the diagonal offsets
    prop_offsets = [o for o in offsets if sum(c != 0 for c in o) > 1]

    def propagate(label):
        best = label
        for off in prop_offsets:
            neigh = _shift(label, off, sentinel)
            ok = mask
            if partition is not None:
                same = _shift(partition, off, jnp.asarray(-1, partition.dtype)) == partition
                ok = ok & same
            best = jnp.minimum(best, jnp.where(ok, neigh, sentinel))
        return jnp.where(mask, best, sentinel)

    def jump(label):
        # label[p] <- label[label[p]]: pointer jumping through the flat
        # volume.  On-mask labels always index in bounds (every label is
        # some voxel's flat id) and off-mask voxels are re-pinned by the
        # where, so the gather needs no appended sentinel row (the old
        # formulation copied the whole volume per jump for a self-loop).
        flat = label.reshape(-1)
        jumped = flat[flat].reshape(label.shape)
        return jnp.where(mask, jumped, sentinel)

    def one_round(label):
        for a in order:
            l_v = jnp.moveaxis(label, a, 0)
            l_v = _axis_sweep_pair(l_v, conds[a][0], conds[a][1], sentinel)
            label = jnp.moveaxis(l_v, 0, a)
        if prop_offsets:
            label = propagate(label)
        return jump(label)

    # stability predicate over the canonical half-neighborhood (equality
    # is symmetric, so each unordered edge is tested once); conduction
    # masks are loop constants
    stab = []
    for off in _canonical_offsets(mask.ndim, connectivity, per_slice):
        ok = mask & _shift(mask, off, False)
        if partition is not None:
            ok &= (
                _shift(partition, off, jnp.asarray(-1, partition.dtype))
                == partition
            )
        stab.append((off, ok))

    def unstable(label):
        u = jnp.bool_(False)
        for off, ok in stab:
            u |= jnp.any(ok & (label != _shift(label, off, sentinel)))
        return u

    label = init
    for _ in range(_FLAT_PRE_ROUNDS):
        label = one_round(label)
    label, iters = lax.while_loop(
        lambda s: unstable(s[0]),
        lambda s: (one_round(s[0]), s[1] + 1),
        (label, jnp.int32(_FLAT_PRE_ROUNDS)),
    )
    return jnp.where(mask, label, jnp.int32(-1)), iters


@partial(jax.jit, static_argnames=("connectivity", "per_slice"))
def connected_components_raw(
    mask: jnp.ndarray,
    connectivity: int = 1,
    partition: Optional[jnp.ndarray] = None,
    per_slice: bool = False,
) -> jnp.ndarray:
    """Label foreground components of ``mask`` with the flat (whole-volume)
    fixpoint kernel.  See ``_flat_cc`` for the algorithm; the coarse-to-fine
    path (``connected_components_coarse_raw``) computes identical labels in
    far fewer, tile-bounded rounds and is the default behind
    ``connected_components``.

    Returns int32 labels where background = -1 and each component carries the
    *minimal flat index* of its voxels — not consecutive; compose with
    ``relabel.relabel_consecutive`` (or host np.unique) for 1..N labels.

    With ``partition`` (an int array), voxels only merge when their partition
    values are equal — i.e. CC *within* existing labels, the equivalent of
    vigra.labelMultiArrayWithBackground on a segmentation (used to re-close
    labels after halo cropping, reference watershed.py:329-333).
    """
    with jax.named_scope("cc.flat"):
        return _flat_cc(mask, connectivity, partition, per_slice)[0]


@partial(jax.jit, static_argnames=("connectivity", "per_slice"))
def connected_components_raw_with_iters(
    mask: jnp.ndarray,
    connectivity: int = 1,
    partition: Optional[jnp.ndarray] = None,
    per_slice: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``connected_components_raw`` plus its fixpoint round count — the
    bench/CI instrumentation hook for the flat-vs-coarse iteration contract
    (tools/ci_check.sh asserts coarse < flat on the serpentine fixture)."""
    with jax.named_scope("cc.flat"):
        return _flat_cc(mask, connectivity, partition, per_slice)


# ---------------------------------------------------------------------------
# coarse-to-fine CC (ctt-cc): tile-local fixpoints + compact boundary merge
# ---------------------------------------------------------------------------
#
# The flat kernel's fixpoint runs O(log volume-diameter) rounds (worst case
# O(#bends of the longest corridor)) and every round gathers over the ENTIRE
# volume, even when only labels near component boundaries still change.  The
# coarse-to-fine path (the shape of arXiv:1712.09789) instead:
#
#   1. labels fixed-size tiles independently — the fixpoint is bounded by the
#      structure INSIDE one tile, and a per-tile live mask drops converged
#      tiles (uniform background regions) out after one round;
#   2. resolves only the tile-face label equivalences with a union-find
#      whose table is O(tile-boundary area), not O(volume);
#   3. applies the resolved roots with one gather.
#
# Labels are flat indices of the block plus the caller's ``id_offset`` (0
# for a block, the shard's offset in parallel/sharded.py), so steps 2-3
# index that dense range directly (ops.unionfind.merge_dense_values): a
# presence scatter and a prefix count number the face values, and the
# roots map back through one table of size+1 entries.  The sorted-table
# merge (merge_value_table, binary searches over every voxel) stays for
# id spaces the program cannot bound: the hierarchical flood's tables and
# the sharded CC's all-gathered shard faces.
#
# Tile-local labels live in TILE-LOCAL id space during the fixpoint (pointer
# jumping becomes a per-tile take_along_axis) and translate to flat indices
# afterwards by arithmetic (tile origin plus in-tile coordinates): within
# one tile, tile-row-major order and block-row-major order are
# order-isomorphic, so min-label semantics survive the translation exactly.

_TILE_ENV = "CTT_CC_TILE"


def default_coarse_tile(ndim: int) -> Tuple[int, ...]:
    """Built-in tile shape: 64 along the two trailing (lane-friendly) axes,
    8 along every leading axis — the bench's tile sweep records whether a
    different pin wins on a given chip (deploy via CTT_CC_TILE)."""
    if ndim <= 2:
        return (64,) * ndim
    return (8,) * (ndim - 2) + (64, 64)


def parse_tile_spec(spec, ndim: int) -> Optional[Tuple[int, ...]]:
    """Parse a CTT_CC_TILE value ("8,64,64" or a single int for a cube) into
    an ndim tile tuple; a spec longer than ndim keeps its trailing entries, a
    shorter one left-pads with its first entry (one env var serves the 3d
    volumes and the 2d seed masks alike).  Invalid specs return None (the
    caller falls back to the default and warns — malformed env must not
    crash a run, the bench.py deadline-parsing idiom)."""
    try:
        parts = [int(p) for p in str(spec).split(",") if p.strip() != ""]
    except (TypeError, ValueError):
        return None
    if not parts or any(p < 1 for p in parts):
        return None
    if len(parts) == 1:
        parts = parts * ndim
    if len(parts) >= ndim:
        return tuple(parts[-ndim:])
    return tuple([parts[0]] * (ndim - len(parts)) + parts)


def resolve_coarse_tile(shape, coarse_tile=None) -> Tuple[int, ...]:
    """Tile-shape precedence: explicit ``coarse_tile`` (int = cube, sequence
    = per-axis) > CTT_CC_TILE env / chip_modes.json pin > built-in default —
    clipped per-axis to ``shape``.  Read at TRACE time like every mode
    switch (ops/_backend.py): compiled shapes keep their tile until the jit
    caches clear."""
    ndim = len(shape)
    if coarse_tile is None:
        pin = _backend.pinned_value(_TILE_ENV)
        tile = parse_tile_spec(pin, ndim) if pin is not None else None
        if pin is not None and tile is None:
            import warnings

            warnings.warn(
                f"invalid {_TILE_ENV}={pin!r}; using the default tile",
                RuntimeWarning,
                stacklevel=2,
            )
        if tile is None:
            tile = default_coarse_tile(ndim)
    elif isinstance(coarse_tile, (int, np.integer)):
        tile = (int(coarse_tile),) * ndim
    else:
        tile = tuple(int(t) for t in coarse_tile)
        if len(tile) != ndim:
            raise ValueError(
                f"coarse_tile {coarse_tile!r} does not match ndim {ndim}"
            )
    return tuple(max(1, min(int(t), int(s))) for t, s in zip(tile, shape))


def _tile_grid(shape, tile) -> Tuple[int, ...]:
    return tuple(-(-int(s) // int(t)) for s, t in zip(shape, tile))


def tile_stack(x: jnp.ndarray, tile, fill) -> jnp.ndarray:
    """Pad ``x`` to tile multiples with ``fill`` and reshape to
    ``(n_tiles, *tile)`` (tiles in row-major grid order).  Shared by the
    coarse CC and the hierarchical flood (ops/watershed.py)."""
    shape = x.shape
    grid = _tile_grid(shape, tile)
    padded = tuple(g * t for g, t in zip(grid, tile))
    if padded != tuple(shape):
        x = jnp.pad(
            x,
            [(0, p - s) for p, s in zip(padded, shape)],
            constant_values=fill,
        )
    x = x.reshape(tuple(v for gt in zip(grid, tile) for v in gt))
    ndim = len(shape)
    perm = tuple(2 * i for i in range(ndim)) + tuple(
        2 * i + 1 for i in range(ndim)
    )
    return x.transpose(perm).reshape((-1,) + tuple(tile))


def tile_unstack(xt: jnp.ndarray, shape, tile, crop: bool = True):
    """Inverse of ``tile_stack``; ``crop=False`` keeps the padded extent."""
    grid = _tile_grid(shape, tile)
    ndim = len(shape)
    x = xt.reshape(tuple(grid) + tuple(tile))
    perm = tuple(
        v for pair in zip(range(ndim), range(ndim, 2 * ndim)) for v in pair
    )
    x = x.transpose(perm).reshape(tuple(g * t for g, t in zip(grid, tile)))
    if crop:
        x = x[tuple(slice(0, int(s)) for s in shape)]
    return x


def tile_crossing_take(arrs, off, tile, grid):
    """For one canonical neighbor offset ``off``: the voxel slabs where the
    adjacency (p, p+off) crosses a tile boundary, for every array in
    ``arrs`` (pass pre-shifted companions alongside the originals).  Yields
    one tuple of flattened slabs per crossing axis; a diagonal offset
    crossing two axes yields its corner pairs twice — harmless for the
    union-find.  Slab positions are static (tile-grid planes), so every
    shape stays data-independent."""
    out = []
    for ax, o_a in enumerate(off):
        if o_a == 0 or grid[ax] == 1:
            continue
        t_a = int(tile[ax])
        s_a = int(arrs[0].shape[ax])
        idx = np.arange(t_a - 1 if o_a > 0 else 0, s_a, t_a)
        out.append(
            tuple(jnp.take(a, idx, axis=ax).reshape(-1) for a in arrs)
        )
    return out


def _tile_boundary_pairs(
    L, partition, tile, connectivity, per_slice, sentinel
):
    """Label equivalence pairs across tile faces of a label volume ``L``
    (values: component ids, ``sentinel`` on background).  Returns
    ``(a_vals, b_vals, n_valid)`` with invalid slots set to ``sentinel`` on
    both sides (self-loops), or ``None`` when the tiling has no interior
    boundaries (single tile)."""
    shape = L.shape
    grid = _tile_grid(shape, tile)
    sent = jnp.int32(sentinel)
    a_parts, b_parts, n_valid = [], [], jnp.int32(0)
    for off in _canonical_offsets(len(shape), connectivity, per_slice):
        if all(o == 0 or grid[ax] == 1 for ax, o in enumerate(off)):
            continue
        nei = _shift(L, off, sent)
        arrs = [L, nei]
        if partition is not None:
            same = (
                _shift(partition, off, jnp.asarray(-1, partition.dtype))
                == partition
            )
            arrs.append(same)
        for slabs in tile_crossing_take(arrs, off, tile, grid):
            a_v, b_v = slabs[0], slabs[1]
            ok = (a_v < sent) & (b_v < sent)
            if partition is not None:
                ok &= slabs[2]
            a_parts.append(jnp.where(ok, a_v, sent))
            b_parts.append(jnp.where(ok, b_v, sent))
            n_valid = n_valid + jnp.sum(ok.astype(jnp.int32))
    if not a_parts:
        return None
    return jnp.concatenate(a_parts), jnp.concatenate(b_parts), n_valid


def _coarse_cc_core(
    mask: jnp.ndarray,
    id_offset,
    sentinel: int,
    connectivity: int,
    partition: Optional[jnp.ndarray],
    per_slice: bool,
    tile: Tuple[int, ...],
):
    """The coarse-to-fine labeling core (traced; see the section comment).

    Every voxel's component-id candidate is ``id_offset`` plus its flat
    index in ``mask`` (offset 0 here, the shard's offset in
    parallel/sharded.py; an int or a traced scalar); ``sentinel`` must lie
    outside ``[id_offset, id_offset + mask.size)``.  Returns ``(labels,
    stats)`` where ``labels[p]`` is the minimal id of p's component
    (``sentinel`` on background) and ``stats`` carries int32 scalars
    ``fixpoint_iters`` (tile-fixpoint rounds), ``live_tile_rounds`` (Σ live
    tiles per round) and ``merge_pairs`` (valid tile-face equivalences).
    Its two stages run under the named scopes ``cc.tiles`` and
    ``cc.merge``, which a device trace shows in each operation's name."""
    with jax.named_scope("cc.tiles"):
        L, stats = _tile_fixpoints(
            mask, id_offset, sentinel, connectivity, partition, per_slice,
            tile,
        )
    with jax.named_scope("cc.merge"):
        pairs = _tile_boundary_pairs(
            L,
            partition,
            tile,
            connectivity,
            per_slice,
            sentinel,
        )
        if pairs is not None:
            from .unionfind import merge_dense_values

            a_vals, b_vals, n_valid = pairs
            L = merge_dense_values(
                L, a_vals, b_vals, id_offset, int(np.prod(mask.shape)),
                sentinel,
            )
            stats["merge_pairs"] = n_valid
    return L, stats


def _tile_fixpoints(mask, id_offset, sentinel, connectivity, partition,
                    per_slice, tile):
    """The tile stage of ``_coarse_cc_core``: every tile's min-label
    fixpoint in tile-local id space, translated to ``id_offset`` plus the
    block's flat index; returns ``(labels, stats)`` before the tile-face
    merge."""
    shape = mask.shape
    ndim = mask.ndim
    grid = _tile_grid(shape, tile)
    n_tiles = int(np.prod(grid))
    ts = int(np.prod(tile))
    sent_l = jnp.int32(ts)

    mask_t = tile_stack(mask, tile, False)
    part_t = (
        tile_stack(partition, tile, -1) if partition is not None else None
    )
    iota = jnp.arange(ts, dtype=jnp.int32).reshape(tile)
    init = jnp.where(mask_t, jnp.broadcast_to(iota, mask_t.shape), sent_l)

    offsets = neighbor_offsets(ndim, connectivity, per_slice)
    axes = tuple(range(1, ndim + 1))
    if per_slice:
        axes = axes[1:]
    sweep_fn = _min_sweep if _use_assoc() else _min_sweep_seq
    prop_offsets = [o for o in offsets if sum(c != 0 for c in o) > 1]

    def tjump(lab):
        # per-tile pointer jump in local id space: one take_along_axis,
        # sentinel self-loops via the appended column
        flat = jnp.concatenate(
            [
                lab.reshape(n_tiles, ts),
                jnp.full((n_tiles, 1), sent_l, jnp.int32),
            ],
            axis=1,
        )
        jumped = jnp.take_along_axis(
            flat, lab.reshape(n_tiles, ts), axis=1
        ).reshape(lab.shape)
        return jnp.where(mask_t, jumped, sent_l)

    def one_round(lab):
        new = lab
        for axis in axes:
            for reverse in (False, True):
                new = sweep_fn(new, mask_t, part_t, axis, reverse, sent_l)
        if prop_offsets:
            best = new
            for off in prop_offsets:
                soff = (0,) + tuple(off)
                neigh = _shift(new, soff, sent_l)
                ok = mask_t
                if part_t is not None:
                    same = _shift(part_t, soff, jnp.asarray(-1, part_t.dtype))
                    ok = ok & (same == part_t)
                best = jnp.minimum(best, jnp.where(ok, neigh, sent_l))
            new = jnp.where(mask_t, best, sent_l)
        return tjump(tjump(new))

    def cond(state):
        return jnp.any(state[1])

    def body(state):
        lab, live, it, live_rounds = state
        new = one_round(lab)
        # live-mask early-exit: a tile whose labels stopped changing is
        # converged forever (tiles are independent) and drops out
        new = jnp.where(live.reshape((n_tiles,) + (1,) * ndim), new, lab)
        changed = jnp.any((new != lab).reshape(n_tiles, ts), axis=1)
        return (
            new,
            changed,
            it + 1,
            live_rounds + jnp.sum(live.astype(jnp.int32)),
        )

    lab_t, _, iters, live_rounds = lax.while_loop(
        cond,
        body,
        (
            init,
            jnp.ones((n_tiles,), bool),
            jnp.int32(0),
            jnp.int32(0),
        ),
    )

    # translate tile-local labels to flat indices of the block (see section
    # comment: the two orders are isomorphic within a tile, so min survives):
    # the tile's origin plus the label's in-tile coordinates, row-major over
    # the block; a label always points at a masked voxel, inside the block
    strides = np.cumprod((1,) + tuple(shape[:0:-1]))[::-1]
    origins = np.stack(
        np.unravel_index(np.arange(n_tiles), grid), axis=1
    ) * np.asarray(tile)
    base = jnp.asarray((origins @ strides).astype(np.int32))
    glab = base.reshape((n_tiles,) + (1,) * ndim) + id_offset
    rem = lab_t
    for ax in reversed(range(ndim)):
        glab = glab + (rem % tile[ax]) * int(strides[ax])
        rem = rem // tile[ax]
    glab = jnp.where(lab_t == sent_l, jnp.int32(sentinel), glab)

    L = tile_unstack(glab, shape, tile)
    stats = {
        "fixpoint_iters": iters,
        "live_tile_rounds": live_rounds,
        "merge_pairs": jnp.int32(0),
    }
    return L, stats


@partial(jax.jit, static_argnames=("connectivity", "per_slice", "tile"))
def connected_components_coarse_raw(
    mask: jnp.ndarray,
    connectivity: int = 1,
    partition: Optional[jnp.ndarray] = None,
    per_slice: bool = False,
    tile: Optional[Tuple[int, ...]] = None,
):
    """Coarse-to-fine labeling with the exact ``connected_components_raw``
    contract (min flat index per component, background -1), plus the kernel
    stats dict (``fixpoint_iters``, ``live_tile_rounds``, ``merge_pairs``).
    ``tile=None`` resolves CTT_CC_TILE / the default at trace time."""
    shape = mask.shape
    tile = resolve_coarse_tile(shape, tile)
    size = int(np.prod(shape))
    lab, stats = _coarse_cc_core(
        mask, 0, size, connectivity, partition, per_slice, tile
    )
    with jax.named_scope("cc.merge"):
        return jnp.where(mask, lab, jnp.int32(-1)), stats


def connected_components_coarse(
    mask,
    connectivity: int = 1,
    partition=None,
    per_slice: bool = False,
    coarse_tile=None,
):
    """Host-side wrapper over the coarse kernel: consecutive ``(labels, n)``
    like ``connected_components``, and emits the ``cc.*`` obs counters
    (fixpoint_iters / live_tiles / merge_pairs — obs/registry.py).  Metric
    emission must stay outside jit (CTT001/CTT002), which is why the jitted
    dispatch path cannot do it; bench.py and the CI smoke call this."""
    from ..obs import metrics as obs_metrics

    mask = jnp.asarray(mask).astype(bool)
    tile = resolve_coarse_tile(mask.shape, coarse_tile)
    raw, stats = connected_components_coarse_raw(
        mask, connectivity, partition, per_slice, tile
    )
    size = int(np.prod(mask.shape))
    labels, n = consecutive_from_flat_roots(raw.reshape(-1), size)
    obs_metrics.inc("cc.fixpoint_iters", int(stats["fixpoint_iters"]))
    obs_metrics.inc("cc.live_tiles", int(stats["live_tile_rounds"]))
    obs_metrics.inc("cc.merge_pairs", int(stats["merge_pairs"]))
    return labels.reshape(mask.shape), n


@partial(jax.jit, static_argnames=("tile", "connectivity", "per_slice"))
def merge_tiled_labels(
    mask: jnp.ndarray,
    glabels: jnp.ndarray,
    tile: Tuple[int, ...],
    connectivity: int = 1,
    per_slice: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Consecutive volume CC from tile-local minimal-flat-index labels
    (−1 background): resolve the tile-face equivalences with the dense
    flat-index union-find (``merge_dense_values``), then rank.  Generalizes
    ``merge_slice_labels`` (tiles = whole slices) to arbitrary tile grids;
    shared with the tiled Pallas kernel (ops/pallas_cc.py)."""
    shape = mask.shape
    size = int(np.prod(shape))
    sent = jnp.int32(size)
    L = jnp.where(glabels < 0, sent, glabels)
    pairs = _tile_boundary_pairs(
        L, None, tile, connectivity, per_slice, size
    )
    if pairs is not None:
        from .unionfind import merge_dense_values

        a_vals, b_vals, _ = pairs
        L = merge_dense_values(L, a_vals, b_vals, 0, size, sent)
    flat = jnp.where(mask.reshape(-1), L.reshape(-1), -1)
    labels, n = consecutive_from_flat_roots(flat, size)
    return labels.reshape(shape), n


def merge_slice_labels(
    mask: jnp.ndarray, sliced: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Volume CC from per-slice minimal-flat-index labels (−1 background):
    one device pointer-jumping union-find over the z-face equivalences, then
    consecutive ranking.  Shared by the Pallas per-slice kernel
    (ops/pallas_cc.py) and the XLA ``slices`` CC mode — valid for
    connectivity 1 only (z-diagonal adjacency would need more edges)."""
    from .unionfind import merge_labels_device

    n, h, w = mask.shape
    size = n * h * w
    # z-face equivalences (self-loops where either side is background pad
    # the static edge table)
    up = sliced[:-1].reshape(-1)
    dn = sliced[1:].reshape(-1)
    both = (up >= 0) & (dn >= 0)
    edges = jnp.stack(
        [jnp.where(both, up, 0), jnp.where(both, dn, 0)], axis=1
    )
    parent = jnp.arange(size, dtype=jnp.int32)
    roots = merge_labels_device(parent, edges)
    flat = jnp.where(
        mask.reshape(-1),
        roots[jnp.clip(sliced.reshape(-1), 0, size - 1)],
        -1,
    )
    labels, n_comp = consecutive_from_flat_roots(flat, size)
    return labels.reshape(mask.shape), n_comp


@partial(
    jax.jit,
    static_argnames=("connectivity", "per_slice", "coarse_tile", "with_rounds"),
)
def connected_components(
    mask: jnp.ndarray,
    connectivity: int = 1,
    partition: Optional[jnp.ndarray] = None,
    per_slice: bool = False,
    coarse_tile: Optional[Tuple[int, ...]] = None,
    with_rounds: bool = False,
):
    """Consecutive component labeling: background 0, components 1..n.

    Returns ``(labels, n_components)``.  Consecutive ids come from ranking the
    component roots (minimal flat indices) with a cumsum — no dynamic shapes.
    See ``connected_components_raw`` for ``partition`` / ``per_slice``.
    ``with_rounds`` appends the int32 round count of the fixpoint loop
    (the coarse kernel's ``fixpoint_iters``, the flat kernel's rounds;
    None on the Pallas paths, which count none) as a third output of the
    same program.

    Mode switches (read at trace time, ops/_backend.py):
      * ``CTT_CC_MODE=coarse`` — the coarse-to-fine tiled kernel
        (``connected_components_coarse_raw``): tile-local fixpoints + one
        compact boundary union-find; ``coarse_tile`` overrides the tile
        shape per call (and forces this path), CTT_CC_TILE /
        chip_modes.json per deployment.  The unpinned default on non-CPU
        backends (``_backend.use_coarse_cc``);
      * ``CTT_CC_MODE=flat`` — the whole-volume fixpoint kernel (the
        unpinned default on the work-bound CPU fallback, where the ctt-cc
        seq sweeps converge in a handful of rounds and the merge-table
        relabel costs more than the saved rounds — measured in bench.py);
      * ``CTT_CC_MODE=pallas`` — VMEM-resident per-slice kernel + z-merge
        (ops/pallas_cc.py) on eligible volumes (3d, connectivity 1, no
        partition, lane-aligned slices, TPU backend); slices too large for
        whole-slice VMEM residency take the tiled Pallas variant;
      * ``CTT_CC_MODE=slices`` — the same slices+z-merge STRUCTURE in plain
        XLA: per-slice 2d sweeps converge in far fewer rounds than
        whole-volume 3d propagation (a 3d component can wind through z),
        and the z-faces merge in one log-depth union-find.
    All paths produce identical labels (bit-exact, tests/test_cc_coarse.py).
    """
    from . import _backend

    def out(labels, n, rounds):
        return (labels, n, rounds) if with_rounds else (labels, n)

    if partition is None:
        from .pallas_cc import (
            pallas_cc_available,
            pallas_cc_tile,
            pallas_cc_tiled_available,
            pallas_connected_components,
            pallas_connected_components_tiled,
        )

        if pallas_cc_available(mask.shape, connectivity, per_slice):
            return out(*pallas_connected_components(mask), None)
        if pallas_cc_tiled_available(mask.shape, connectivity, per_slice):
            return out(*pallas_connected_components_tiled(
                mask, pallas_cc_tile(mask.shape)
            ), None)
        if (
            _backend.use_slices_cc()
            and not per_slice and mask.ndim == 3 and connectivity == 1
        ):
            sliced, rounds = connected_components_raw_with_iters(
                mask, connectivity, None, per_slice=True
            )
            return out(*merge_slice_labels(mask, sliced), rounds)
    size = int(np.prod(mask.shape))
    if _backend.use_coarse_cc() or coarse_tile is not None:
        tile = resolve_coarse_tile(mask.shape, coarse_tile)
        raw, stats = connected_components_coarse_raw(
            mask, connectivity, partition, per_slice, tile
        )
        rounds = stats["fixpoint_iters"]
    else:
        raw, rounds = connected_components_raw_with_iters(
            mask, connectivity, partition, per_slice
        )
    with jax.named_scope("cc.rank"):
        labels, n = consecutive_from_flat_roots(raw.reshape(-1), size)
        labels = labels.reshape(mask.shape)
    return out(labels, n, rounds)


def rank_of_flat_roots(flat: jnp.ndarray, size: int):
    """Prefix-count rank table over flat-index roots: ``rank[i]`` is the
    1-based consecutive id of the root at flat index i (valid where a root
    exists).  Shared by every consumer that must number components in
    minimal-flat-index order."""
    is_root = flat == jnp.arange(size, dtype=jnp.int32)
    root_rank = jnp.cumsum(is_root.astype(jnp.int32))
    n = root_rank[-1] if size > 0 else jnp.int32(0)
    return root_rank, n.astype(jnp.int32)


def consecutive_from_flat_roots(
    flat: jnp.ndarray, size: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rank flat-index component roots into consecutive ids 1..n (background
    stays 0, marked by negative entries).  Shared by the XLA and Pallas CC
    paths so their numbering stays in lockstep."""
    root_rank, n = rank_of_flat_roots(flat, size)
    safe = jnp.clip(flat, 0, size - 1)
    labels = jnp.where(flat >= 0, root_rank[safe], 0)
    return labels.astype(jnp.int32), n


def connected_components_labels(
    labels: jnp.ndarray,
    connectivity: int = 1,
    per_slice: bool = False,
    coarse_tile: Optional[Tuple[int, ...]] = None,
    with_rounds: bool = False,
):
    """Split a label image into its connected pieces (CC within equal labels,
    background 0) — vigra.labelMultiArrayWithBackground equivalent;
    ``with_rounds`` as in ``connected_components``."""
    return connected_components(
        labels > 0, connectivity, partition=labels, per_slice=per_slice,
        coarse_tile=coarse_tile, with_rounds=with_rounds,
    )


def connected_components_np(mask: np.ndarray, connectivity: int = 1):
    """Host oracle via scipy (used by tests and the local parity path)."""
    from scipy import ndimage

    structure = ndimage.generate_binary_structure(mask.ndim, connectivity)
    labels, n = ndimage.label(mask, structure=structure)
    return labels.astype(np.int32), int(n)


def serpentine_mask(shape) -> np.ndarray:
    """Adversarial CC fixture: ONE corridor snaking through every other row
    and turning at alternating ends, so the component's graph diameter is
    Θ(H·W) with a bend every band — the worst case for propagation-style
    labeling (each fixpoint round resolves one straight segment).  3d shapes
    replicate the serpentine in every z-slice.  Shared by the parity tests
    (tests/test_cc_coarse.py), the bench iteration contract (bench.py), and
    the CI smoke (tools/ci_check.sh asserts the coarse kernel needs strictly
    fewer rounds than the flat one here)."""
    h, w = int(shape[-2]), int(shape[-1])
    m2 = np.zeros((h, w), dtype=bool)
    m2[::2, :] = True
    for i, r in enumerate(range(1, h, 2)):
        m2[r, w - 1 if i % 2 == 0 else 0] = True
    if len(shape) == 2:
        return m2
    return np.broadcast_to(m2, tuple(shape)).copy()
