"""Seeded watershed and seed detection as XLA programs.

Replaces vigra.analysis.watershedsNew / localMaxima3D and
elf.segmentation.watershed (reference watershed/watershed.py:164-250).

Seeded watershed is inherently a priority-flood; the TPU formulation is the
equivalent *lexicographic shortest-path relaxation*: every voxel takes the label
of the seed reachable with the lexicographically smallest path cost

    ( pass height = max h along the path,  hop count,  seed label )

The default 6-connectivity path runs *directional raster sweeps* (the chamfer /
Gauss–Seidel scheme) along ±z, ±y, ±x, so each sweep carries flood fronts
across the whole axis instead of one voxel — the outer ``lax.while_loop`` then
converges in O(#bends of the steepest path) rounds (typically < 10) instead of
O(longest flood path) sweeps.  Each sweep's carry chain evaluates either
sequentially (``lax.scan``, work-bound backends) or in log depth
(``lax.associative_scan`` over closed transfer-function compositions,
dispatch-bound TPUs) — ops/_backend.py picks, both compute the identical
fixpoint (tested).  Monotone label-correcting relaxation is exact: every state
is witnessed by a real path from a seed (induction over updates), states only
decrease, and the unique fixpoint is the lexicographic minimum over all paths —
the same fixpoint the neighbor-sweep kernel (``_seeded_watershed_sweep``, kept
for connectivity > 1) reaches.  Ties resolve to the smaller label id;
voxel-exact boundaries can differ from vigra's sequential flood order, which is
why parity is defined on Rand/VoI, not voxel equality (SURVEY.md §7 #1).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import _backend
from .cc import (
    _canonical_offsets,
    _shift,
    _tile_grid,
    connected_components,
    neighbor_offsets,
    parse_tile_spec,
    resolve_coarse_tile,
    tile_crossing_take,
    tile_stack,
    tile_unstack,
)
from .filters import gaussian, maximum_filter, normalize

# numpy scalar, NOT jnp: a module-level jnp constant would initialize the
# device backend at import time (breaking imports in processes without a
# usable accelerator, e.g. batch-scheduler workers)
_BIG = np.float32(3.0e38)


def _axis_views(arrs, axis, reverse):
    """Move ``axis`` to the front (flipped when ``reverse``) for a raster scan."""

    def mv(x):
        x = jnp.moveaxis(x, axis, 0)
        return jnp.flip(x, axis=0) if reverse else x

    return tuple(mv(x) for x in arrs)


def _axis_unview(x, axis, reverse):
    if reverse:
        x = jnp.flip(x, axis=0)
    return jnp.moveaxis(x, 0, axis)


def _sweep_altitude_assoc(alt, hmap, is_seed, mask, axis, reverse):
    """Gauss–Seidel raster sweep of the flood-altitude field along one axis:
    A'(p) = min(A(p), max(A(prev plane), h(p))).

    The carry chain is a composition of per-element *clamp* transfers
    c → min(u, max(c, l)), a family closed under composition
    (u₂₁ = min(u₂, max(u₁, l₂)), l₂₁ = max(l₁, l₂)) — so the whole
    sequential sweep evaluates exactly via ``lax.associative_scan`` in
    log(n) full-array steps instead of n sequential plane steps (the scan
    version is dispatch-bound on TPU: 256 tiny steps per sweep)."""
    h_v, a_v, sd_v, mk_v = _axis_views((hmap, alt, is_seed, mask), axis, reverse)

    # per-element transfer (u, l): carry' = min(u, max(carry, l))
    #   outside mask: constant _BIG (doesn't conduct)
    #   seed:         constant a (its own fixed altitude)
    #   interior:     min(a_old, max(carry, h))
    conduct = mk_v & ~sd_v
    u = jnp.where(mk_v, a_v, _BIG)
    l = jnp.where(conduct, h_v, u)

    def combine(f, g):  # f earlier, g later along the sweep
        uf, lf = f
        ug, lg = g
        return jnp.minimum(ug, jnp.maximum(uf, lg)), jnp.maximum(lf, lg)

    u_inc, _ = lax.associative_scan(combine, (u, l), axis=0)
    # exclusive prefix applied to the initial carry _BIG gives just u
    carry_in = jnp.concatenate(
        [jnp.full_like(u_inc[:1], _BIG), u_inc[:-1]], axis=0
    )
    n_alt = jnp.where(
        conduct, jnp.minimum(a_v, jnp.maximum(carry_in, h_v)), a_v
    )
    return _axis_unview(n_alt, axis, reverse)



def _sweep_altitude_seq(alt, hmap, is_seed, mask, axis, reverse):
    """Sequential-carry variant of the altitude sweep (``lax.scan`` over
    planes).  O(n) work but n dependent steps — faster on work-bound
    backends (XLA-CPU), slower on dispatch-latency-bound TPUs, where
    ``_sweep_altitude_assoc`` wins."""
    h_v, a_v, sd_v, mk_v = _axis_views((hmap, alt, is_seed, mask), axis, reverse)
    plane_shape = h_v.shape[1:]

    def step(carry, x):
        h, o_alt, sd, mk = x
        cand = jnp.maximum(carry, h)
        better = mk & ~sd & (cand < o_alt)
        n_alt = jnp.where(better, cand, o_alt)
        # voxels outside the mask must not conduct: carry _BIG past them
        return jnp.where(mk, n_alt, _BIG), n_alt

    _, alts = lax.scan(step, jnp.full(plane_shape, _BIG), (h_v, a_v, sd_v, mk_v))
    return _axis_unview(alts, axis, reverse)


def _sweep_assign_seq(dist, label, alt, hmap, is_seed, mask, axis, reverse):
    """Sequential-carry variant of the assignment sweep (see
    ``_sweep_altitude_seq`` for the backend trade-off)."""
    big_dist = jnp.int32(np.iinfo(np.int32).max - 1)
    h_v, a_v, d_v, l_v, sd_v, mk_v = _axis_views(
        (hmap, alt, dist, label, is_seed, mask), axis, reverse
    )
    plane_shape = h_v.shape[1:]

    def step(carry, x):
        c_alt, c_dist, c_lab = carry
        h, o_alt, o_dist, o_lab, sd, mk = x
        edge_ok = o_alt == jnp.maximum(c_alt, h)
        cand_dist = c_dist + 1
        valid = (c_lab > 0) & mk & ~sd & edge_ok
        better = valid & (
            (cand_dist < o_dist)
            | ((cand_dist == o_dist) & ((o_lab == 0) | (c_lab < o_lab)))
        )
        n_dist = jnp.where(better, cand_dist, o_dist)
        n_lab = jnp.where(better, c_lab, o_lab)
        return (
            jnp.where(mk, o_alt, _BIG),
            n_dist,
            jnp.where(mk, n_lab, 0),
        ), (n_dist, n_lab)

    init = (
        jnp.full(plane_shape, _BIG),
        jnp.full(plane_shape, big_dist),
        jnp.zeros(plane_shape, jnp.int32),
    )
    _, (dists, labs) = lax.scan(step, init, (h_v, a_v, d_v, l_v, sd_v, mk_v))
    return (
        _axis_unview(dists, axis, reverse),
        _axis_unview(labs, axis, reverse),
    )


def _use_assoc() -> bool:
    return _backend.use_assoc()


def _minlex(d1, l1, d2, l2):
    """Min over (dist, label) lexicographic order where label 0 = +inf
    (the original sweep's tie-breaking: smaller hop count, then smaller
    label; unlabeled states never win)."""
    take1 = (l1 > 0) & ((l2 == 0) | (d1 < d2) | ((d1 == d2) & (l1 < l2)))
    return jnp.where(take1, d1, d2), jnp.where(take1, l1, l2)


def _sweep_assign_assoc(dist, label, alt, hmap, is_seed, mask, axis, reverse):
    """Gauss–Seidel raster sweep of the (hops, label) assignment along one
    axis, restricted to optimal-prefix edges q→p (A(p) == max(A(q), h(p))).

    The carry chain composes per-element transfers
        f(d, l) = minlex((D, L), (d + k, l) if pass ∧ l>0 else ∞)
    which are closed under composition (pass' = pass_f ∧ pass_g,
    k' = k_f + k_g, const' = minlex(const_g, const_f + k_g if pass_g)),
    so the sweep evaluates exactly via ``lax.associative_scan`` in log(n)
    full-array steps.  The edge-feasibility test A(p) == max(A(q), h(p))
    only involves the *fixed* altitudes of adjacent elements, so it is
    per-element data, not part of the recurrence state."""
    big_dist = jnp.int32(np.iinfo(np.int32).max - 1)
    h_v, a_v, d_v, l_v, sd_v, mk_v = _axis_views(
        (hmap, alt, dist, label, is_seed, mask), axis, reverse
    )

    # previous element's (masked) altitude — data, shifted along the axis
    alt_masked = jnp.where(mk_v, a_v, _BIG)
    prev_alt = jnp.concatenate(
        [jnp.full_like(alt_masked[:1], _BIG), alt_masked[:-1]], axis=0
    )
    edge_ok = a_v == jnp.maximum(prev_alt, h_v)
    can_update = mk_v & ~sd_v & edge_ok

    # per-element transfer: constant part = own pre-sweep state (masked to
    # (big, 0) outside the mask so it never conducts), pass-through iff the
    # optimal-prefix edge into this element exists
    const_d = jnp.where(mk_v, d_v, big_dist)
    const_l = jnp.where(mk_v, l_v, 0)
    step = jnp.ones_like(d_v)

    def combine(f, g):  # f earlier, g later
        fd, fl, fk, fp = f
        gd, gl, gk, gp = g
        cand_d = fd + gk
        cand_l = jnp.where(gp, fl, 0)
        d, l = _minlex(gd, gl, cand_d, cand_l)
        return d, l, fk + gk, fp & gp

    d_inc, l_inc, _, _ = lax.associative_scan(
        combine, (const_d, const_l, step, can_update), axis=0
    )
    # exclusive prefix applied to the initial carry (big, 0): the pass-through
    # candidate has l=0, so the result is just the composed constant part
    carry_d = jnp.concatenate(
        [jnp.full_like(d_inc[:1], big_dist), d_inc[:-1]], axis=0
    )
    carry_l = jnp.concatenate(
        [jnp.zeros_like(l_inc[:1]), l_inc[:-1]], axis=0
    )

    cand_dist = carry_d + 1
    better = can_update & (carry_l > 0) & (
        (cand_dist < d_v)
        | ((cand_dist == d_v) & ((l_v == 0) | (carry_l < l_v)))
    )
    n_dist = jnp.where(better, cand_dist, d_v)
    n_lab = jnp.where(better, carry_l, l_v)
    return (
        _axis_unview(n_dist, axis, reverse),
        _axis_unview(n_lab, axis, reverse),
    )


def _flood_scan_impl(
    hmap, seeds, mask, max_iter, per_slice, tile, warm=None
):
    """Directional-sweep flood (6-connectivity), two monotone phases:

      1. flood altitude A(p) = min over paths of (max h along path) by ±axis
         raster relaxation — a min–max problem where Gauss–Seidel sweeps are
         exact, converging in O(#bends of the steepest path) rounds;
      2. (hops, label) BFS over optimal-prefix edges (A(p) == max(A(q), h(p)))
         with min-label tie-breaking — also monotone under sweeps.

    The split matters: the combined (alt, hops, label) relaxation is NOT
    monotone (max() can keep a stale alt while hops/label change beneath it),
    which is why the neighbor-sweep kernel recomputes states from scratch.
    Each phase alone is monotone, so every fixpoint state has an exact witness
    chain → regions are connected, labels reach their seeds.

    ``tile`` (ctt-cc hierarchy reuse) warm-starts each phase from a
    tile-local fixpoint on independent ``tile_stack``-ed tiles, so the
    global loops only resolve cross-tile structure and their round count
    drops to O(#cross-tile bends) while the fixpoint stays bit-identical
    (tests/test_cc_coarse.py asserts both).  Exactness is an
    over-approximation argument per phase: a warm state below the fixpoint
    could never be corrected upward (relaxation only decreases), so each
    warm state must be witnessed by a REAL feasible path —

      * phase 1: in-tile relaxations are a subset of the global ones, so
        tile-local altitudes are min-max passes of real paths (≥ fixpoint),
        and a sweep-stable over-approximation with pinned seeds IS the
        fixpoint (induction along an optimal path);
      * phase 2 MUST warm-start against the GLOBAL altitude field, after
        global phase 1: any path of globally-feasible edges
        (A(p) == max(A(q), h(p))) is prefix-optimal, so in-tile (hops,
        label) states over those edges are ≥ the fixpoint.  Running tile
        phase 2 against the TILE-local altitudes instead would be wrong:
        a tile path can be pass-optimal without being prefix-optimal, and
        its smaller hop count would survive to a different label
        tie-break.

    ``warm`` injects an externally computed altitude warm state under the
    same phase-1 witness contract (the tiled Pallas flood,
    ops/pallas_flood.py — alt only, for exactly the phase-2 reason above).

    Returns ``(label, alt, stats)`` with int32 round counters
    ``flood_tile_iters`` / ``flood_alt_iters`` / ``flood_assign_iters``.
    """
    hmap = hmap.astype(jnp.float32)
    seeds = jnp.where(mask, seeds.astype(jnp.int32), 0)
    is_seed = seeds > 0
    big_dist = jnp.int32(np.iinfo(np.int32).max - 1)
    ndim = hmap.ndim
    axes = tuple(range(ndim))
    if per_slice:
        axes = axes[1:]  # z-slices independent: never sweep across axis 0

    if _use_assoc():
        _sweep_altitude = _sweep_altitude_assoc
        _sweep_assign = _sweep_assign_assoc
    else:
        _sweep_altitude = _sweep_altitude_seq
        _sweep_assign = _sweep_assign_seq

    def cond(state):
        return state[-2] if max_iter == 0 else state[-2] & (state[-1] < max_iter)

    tile_iters = jnp.int32(0)
    alt0 = jnp.where(is_seed, hmap, _BIG)
    label0 = seeds
    dist0 = jnp.where(is_seed, 0, big_dist)

    if warm is not None:
        alt0 = jnp.minimum(alt0, warm)  # injected phase-1 warm altitudes

    shape = hmap.shape
    h_t = m_t = sd_t = None
    t_axes = tuple(a + 1 for a in axes)
    if tile is not None:
        h_t = tile_stack(hmap, tile, _BIG)
        m_t = tile_stack(mask, tile, False)
        sd_t = tile_stack(is_seed, tile, False)

        # -- tile-local phase-1 warm start ---------------------------------
        def t_alt_body(state):
            alt, _, it = state
            prev = alt
            for axis in t_axes:
                for reverse in (False, True):
                    alt = _sweep_altitude(alt, h_t, sd_t, m_t, axis, reverse)
            return alt, jnp.any(alt != prev), it + 1

        alt_t, _, it_a = lax.while_loop(
            cond, t_alt_body,
            (tile_stack(alt0, tile, _BIG), jnp.bool_(True), jnp.int32(0)),
        )
        alt0 = tile_unstack(alt_t, shape, tile)
        tile_iters = tile_iters + it_a

    # -- phase 1: altitude ---------------------------------------------------
    def alt_body(state):
        alt, _, it = state
        prev = alt
        for axis in axes:
            for reverse in (False, True):
                alt = _sweep_altitude(alt, hmap, is_seed, mask, axis, reverse)
        return alt, jnp.any(alt != prev), it + 1

    alt, _, alt_iters = lax.while_loop(
        cond, alt_body, (alt0, jnp.bool_(True), jnp.int32(0))
    )

    if tile is not None:
        # -- tile-local phase-2 warm start against the GLOBAL altitude -----
        # (see the docstring: tile-local altitudes would break exactness)
        a_t = tile_stack(alt, tile, _BIG)

        def t_asg_body(state):
            dist, label, _, it = state
            prev_d, prev_l = dist, label
            for axis in t_axes:
                for reverse in (False, True):
                    dist, label = _sweep_assign(
                        dist, label, a_t, h_t, sd_t, m_t, axis, reverse
                    )
            changed = jnp.any((dist != prev_d) | (label != prev_l))
            return dist, label, changed, it + 1

        dist_t, label_t, _, it_s = lax.while_loop(
            cond, t_asg_body,
            (
                tile_stack(dist0, tile, big_dist),
                tile_stack(label0, tile, 0),
                jnp.bool_(True),
                jnp.int32(0),
            ),
        )
        dist0 = tile_unstack(dist_t, shape, tile)
        label0 = tile_unstack(label_t, shape, tile)
        tile_iters = tile_iters + it_s

    # -- phase 2: assignment -------------------------------------------------
    def assign_body(state):
        dist, label, _, it = state
        prev_d, prev_l = dist, label
        for axis in axes:
            for reverse in (False, True):
                dist, label = _sweep_assign(
                    dist, label, alt, hmap, is_seed, mask, axis, reverse
                )
        changed = jnp.any((dist != prev_d) | (label != prev_l))
        return dist, label, changed, it + 1

    _, label, _, asg_iters = lax.while_loop(
        cond,
        assign_body,
        (dist0, label0, jnp.bool_(True), jnp.int32(0)),
    )
    stats = {
        "flood_tile_iters": tile_iters,
        "flood_alt_iters": alt_iters,
        "flood_assign_iters": asg_iters,
    }
    return jnp.where(mask, label, 0), alt, stats


@partial(jax.jit, static_argnames=("max_iter", "per_slice"))
def _seeded_watershed_scan(
    hmap: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: jnp.ndarray,
    max_iter: int = 0,
    per_slice: bool = False,
) -> jnp.ndarray:
    """Flood labels of ``_flood_scan_impl`` (the documented kernel), with
    no tile warm start."""
    return _flood_scan_impl(hmap, seeds, mask, max_iter, per_slice, None)[0]


@partial(jax.jit, static_argnames=("per_slice", "tile"))
def flood_with_stats(
    hmap: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: jnp.ndarray,
    per_slice: bool = False,
    tile: Optional[Tuple[int, ...]] = None,
):
    """``(labels, alt, stats)`` of the sweep flood — the bench/CI hook for
    the hierarchical-flood round contract (stats carries the tile/global
    fixpoint round counters; ops/cc.py is the CC analog)."""
    return _flood_scan_impl(hmap, seeds, mask, 0, per_slice, tile)


_FLOOD_TILE_ENV = "CTT_FLOOD_TILE"


def resolve_flood_tile(shape, coarse_tile=None):
    """Flood warm-start tile precedence: explicit ``coarse_tile`` >
    CTT_FLOOD_TILE env / chip_modes.json pin > None (= no tile warm start —
    unlike CC the flood default stays flat, because the production floods
    converge in <10 global rounds and the warm start pays off only where a
    global round is expensive relative to tile rounds; the ws e2e bench
    records both round counts so a chip pin can opt in)."""
    if coarse_tile is None:
        pin = _backend.pinned_value(_FLOOD_TILE_ENV)
        if pin is None:
            return None
        tile = parse_tile_spec(pin, len(shape))
        if tile is None:
            import warnings

            warnings.warn(
                f"invalid {_FLOOD_TILE_ENV}={pin!r}; tile warm start off",
                RuntimeWarning,
                stacklevel=2,
            )
            return None
        return tuple(max(1, min(int(t), int(s))) for t, s in zip(tile, shape))
    return resolve_coarse_tile(shape, coarse_tile)


@partial(jax.jit, static_argnames=("connectivity", "per_slice", "tile"))
def flood_merge_table(
    labels: jnp.ndarray,
    heights: jnp.ndarray,
    tile: Tuple[int, ...],
    connectivity: int = 1,
    per_slice: bool = False,
):
    """Tile-face region-merge table of a flooded labeling: for every
    adjacency (p, p+off) crossing a tile face, the label pair and the edge's
    saddle height max(heights[p], heights[p+off]).  Returns static-shape
    ``(a, b, saddle)`` flat arrays; slots that are not a real inter-region
    edge (background, same label, non-crossing) carry ``(0, 0, _BIG)``.

    This is the ctt-cc hierarchy hook for multi-threshold hierarchical
    segmentation (arXiv:2410.08946's merge-tree shape): thresholding
    ``saddle`` and resolving ``(a, b)`` with ops.unionfind.merge_value_table
    yields the segmentation at any coarser level WITHOUT re-flooding —
    deliberately returned raw (min-reduction per pair is the later PR's
    job).  Pass the flood's height map for basin saddles, or its altitude
    field (``flood_with_stats``) for seed-relative pass heights."""
    shape = labels.shape
    grid = _tile_grid(shape, tile)
    a_parts, b_parts, s_parts = [], [], []
    for off in _canonical_offsets(len(shape), connectivity, per_slice):
        if all(o == 0 or grid[ax] == 1 for ax, o in enumerate(off)):
            continue
        nei_l = _shift(labels, off, jnp.int32(0))
        nei_h = _shift(heights, off, _BIG)
        for slabs in tile_crossing_take(
            (labels, nei_l, heights, nei_h), off, tile, grid
        ):
            a_v, b_v, h_a, h_b = slabs
            ok = (a_v > 0) & (b_v > 0) & (a_v != b_v)
            a_parts.append(jnp.where(ok, a_v, 0))
            b_parts.append(jnp.where(ok, b_v, 0))
            s_parts.append(jnp.where(ok, jnp.maximum(h_a, h_b), _BIG))
    if not a_parts:
        z = jnp.zeros((0,), jnp.int32)
        return z, z, jnp.zeros((0,), jnp.float32)
    return (
        jnp.concatenate(a_parts),
        jnp.concatenate(b_parts),
        jnp.concatenate(s_parts),
    )


def seeded_watershed_hier(
    hmap: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    coarse_tile=None,
    per_slice: bool = False,
):
    """Hierarchical seeded flood: tile-warm-started sweep flood (labels are
    bit-identical to ``seeded_watershed``) plus the tile-face merge table of
    the result over the height map — ``(labels, (a, b, saddle), stats)``.
    The merge table + stats are the multi-threshold-segmentation and bench
    hooks; ``coarse_tile`` defaults through CTT_FLOOD_TILE then the CC
    default tile (this entry point always tiles — it IS the hierarchy)."""
    mask_arr = (
        jnp.ones(hmap.shape, dtype=bool) if mask is None
        else mask.astype(bool)
    )
    tile = resolve_flood_tile(hmap.shape, coarse_tile)
    if tile is None:
        tile = resolve_coarse_tile(hmap.shape, None)
    labels, _, stats = flood_with_stats(
        hmap, seeds, mask_arr, per_slice=per_slice, tile=tile
    )
    table = flood_merge_table(
        labels, hmap.astype(jnp.float32), tile, per_slice=per_slice
    )
    return labels, table, stats


def _flood_rounds(stats, tiled: bool):
    """A flood's round counts for ``with_rounds`` callers, from
    ``_flood_scan_impl``'s stats: the global altitude and assignment loops
    (``flood``) and, where the flood was warm-started from tiles, the tile
    loops (``flood_tile``)."""
    rounds = {"flood": stats["flood_alt_iters"] + stats["flood_assign_iters"]}
    if tiled:
        rounds["flood_tile"] = stats["flood_tile_iters"]
    return rounds


@partial(
    jax.jit,
    static_argnames=(
        "connectivity", "max_iter", "per_slice", "coarse_tile", "with_rounds",
    ),
)
def seeded_watershed(
    hmap: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    connectivity: int = 1,
    max_iter: int = 0,
    per_slice: bool = False,
    coarse_tile: Optional[Tuple[int, ...]] = None,
    with_rounds: bool = False,
):
    """Flood ``seeds`` (int32, 0 = unlabeled) over height map ``hmap``.

    Voxels outside ``mask`` stay 0 and do not conduct floods.  ``max_iter=0``
    iterates to the fixpoint.  ``per_slice`` floods each z-slice independently
    (the reference's 2d watershed mode, watershed.py:120-137).
    ``coarse_tile`` (or a CTT_FLOOD_TILE pin) warm-starts the sweep flood
    from tile-local fixpoints — identical labels, fewer global rounds (see
    ``_flood_scan_impl``); only the fixpoint scan path tiles (``max_iter``
    caps count global rounds, so a warm start would change their meaning).
    ``with_rounds`` returns ``(labels, rounds)``: the int32 round counts of
    ``_flood_rounds``, outputs of the same program (empty on the Pallas
    whole-slice, capped and neighbor-sweep paths, which count none).
    """
    if mask is None:
        mask_arr = jnp.ones(hmap.shape, dtype=bool)
    else:
        mask_arr = mask.astype(bool)
    rounds = {}
    if connectivity == 1:
        tile = resolve_flood_tile(hmap.shape, coarse_tile)
        if max_iter == 0:
            from .pallas_flood import (
                flood_slices,
                flood_tiles_warm,
                pallas_flood_available,
                pallas_flood_tiled_available,
            )

            if pallas_flood_available(hmap.shape, per_slice):
                # whole-slice flood in VMEM (opt-in, CTT_FLOOD_MODE=pallas)
                labels = flood_slices(hmap, seeds, mask_arr)
            elif tile is not None and pallas_flood_tiled_available(
                hmap.shape, per_slice, tile
            ):
                # tile-local altitude fixpoints in VMEM as the phase-1 warm
                # state; the XLA loops finish the cross-tile structure
                warm = flood_tiles_warm(hmap, seeds, mask_arr, tile[1:])
                labels, _, stats = _flood_scan_impl(
                    hmap, seeds, mask_arr, 0, per_slice, tile, warm=warm
                )
                rounds = _flood_rounds(stats, tiled=True)
            else:
                labels, _, stats = flood_with_stats(
                    hmap, seeds, mask_arr, per_slice=per_slice, tile=tile
                )
                rounds = _flood_rounds(stats, tiled=tile is not None)
        else:
            labels = _seeded_watershed_scan(
                hmap, seeds, mask_arr, max_iter=max_iter, per_slice=per_slice
            )
    else:
        labels = _seeded_watershed_sweep(
            hmap, seeds, mask_arr, connectivity, max_iter, per_slice
        )
    return (labels, rounds) if with_rounds else labels


@partial(jax.jit, static_argnames=("connectivity", "max_iter", "per_slice"))
def _seeded_watershed_sweep(
    hmap: jnp.ndarray,
    seeds: jnp.ndarray,
    mask: jnp.ndarray,
    connectivity: int = 1,
    max_iter: int = 0,
    per_slice: bool = False,
) -> jnp.ndarray:
    """Neighbor-sweep Bellman–Ford flood (any connectivity): one-voxel
    propagation per sweep, recomputed from neighbors (see module docstring)."""
    hmap = hmap.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones(hmap.shape, dtype=bool)
    else:
        mask = mask.astype(bool)
    seeds = jnp.where(mask, seeds.astype(jnp.int32), 0)
    offsets = neighbor_offsets(hmap.ndim, connectivity, per_slice)
    is_seed = seeds > 0

    big_dist = jnp.int32(np.iinfo(np.int32).max - 1)
    label0 = seeds
    alt0 = jnp.where(is_seed, hmap, _BIG)
    dist0 = jnp.where(is_seed, 0, big_dist)

    def cond(state):
        _, _, _, changed, it = state
        return changed if max_iter == 0 else changed & (it < max_iter)

    def body(state):
        label, alt, dist, _, it = state
        # recompute purely from neighbors — own state is NOT a candidate, so
        # stale ("ghost") states cannot survive once their witness disappears
        best_alt = jnp.where(is_seed, alt0, _BIG)
        best_dist = jnp.where(is_seed, dist0, big_dist)
        best_label = jnp.where(is_seed, seeds, 0)
        for off in offsets:
            n_label = _shift(label, off, jnp.int32(0))
            n_alt = _shift(alt, off, _BIG)
            n_dist = _shift(dist, off, big_dist)
            valid = n_label > 0
            cand_alt = jnp.where(valid, jnp.maximum(n_alt, hmap), _BIG)
            cand_dist = jnp.where(valid, n_dist + 1, big_dist)
            better = (
                (cand_alt < best_alt)
                | ((cand_alt == best_alt) & (cand_dist < best_dist))
                | (
                    (cand_alt == best_alt)
                    & (cand_dist == best_dist)
                    & valid
                    & ((best_label == 0) | (n_label < best_label))
                )
            )
            better = better & ~is_seed
            best_alt = jnp.where(better, cand_alt, best_alt)
            best_dist = jnp.where(better, cand_dist, best_dist)
            best_label = jnp.where(better, n_label, best_label)
        best_label = jnp.where(mask, best_label, 0)
        best_alt = jnp.where(mask, best_alt, _BIG)
        best_dist = jnp.where(mask, best_dist, big_dist)
        changed = jnp.any(
            (best_label != label) | (best_alt != alt) | (best_dist != dist)
        )
        return best_label, best_alt, best_dist, changed, it + 1

    label, _, _, _, _ = lax.while_loop(
        cond, body, (label0, alt0, dist0, jnp.bool_(True), jnp.int32(0))
    )
    return label


@partial(jax.jit, static_argnames=("per_slice", "pixel_pitch"))
def suppress_seeds(
    maxima: jnp.ndarray,
    dt: jnp.ndarray,
    per_slice: bool = False,
    pixel_pitch: Optional[Tuple[float, ...]] = None,
) -> jnp.ndarray:
    """Distance-based non-maximum suppression of seed maxima, as one separable
    XLA program (the role of nifty.filters.nonMaximumDistanceSuppression in
    the reference seed path, watershed.py:22,200-204).

    A maximum p is suppressed iff a stronger maximum q covers it with its
    parabola: dt(q)² − ‖p−q‖² > dt(p)².  The cover field
    G(p) = max_q over maxima of (dt(q)² − ‖p−q‖²) is a separable max-parabola
    transform — the same tiled min-plus kernel as the EDT with the sign
    flipped — so the whole test is O(n·side) fully-parallel work, no pairwise
    point matrix and no data-dependent point extraction.

    Equal maxima never suppress each other (the inequality is strict), so
    plateaus survive intact and are merged by the CC pass downstream.  The
    greedy sequential semantics of the reference differ in chains of
    overlapping maxima (a suppressed point cannot suppress others there);
    parity is defined on Rand/VoI, not seed identity (SURVEY.md §7 #1).

    ``pixel_pitch`` keeps the units consistent with an anisotropic distance
    transform: dt values are then in physical units, so ‖p−q‖ must be too.
    """
    from .dt import _parabola_pass

    pitch = (1.0,) * dt.ndim if pixel_pitch is None else tuple(pixel_pitch)
    d = dt.astype(jnp.float32)
    d2 = d * d
    f = jnp.where(maxima, -d2, _BIG)  # min-form: G = -min(-f + dist²)
    axes = tuple(range(dt.ndim))
    if per_slice:
        axes = axes[1:]
    g = f
    for axis in axes:
        g = jnp.moveaxis(g, axis, -1)
        g = _parabola_pass(g, pitch[axis], 32)
        g = jnp.moveaxis(g, -1, axis)
    cover = -g
    return maxima & (cover <= d2 * (1.0 + 1e-5) + 1e-5)


@partial(
    jax.jit,
    static_argnames=("sigma", "per_slice", "nms", "pixel_pitch", "with_rounds"),
)
def dt_seeds(
    dt: jnp.ndarray,
    sigma: float = 2.0,
    per_slice: bool = False,
    nms: bool = False,
    pixel_pitch: Optional[Tuple[float, ...]] = None,
    with_rounds: bool = False,
):
    """Seeds from a distance transform: smooth → local maxima (plateaus merged by
    full-connectivity CC over the maxima mask) → consecutive labels.

    Mirrors reference ``_make_seeds`` (watershed.py:180-208): gaussian(dt) then
    localMaxima with allowAtBorder/allowPlateaus.  ``per_slice`` detects maxima
    and labels seeds within each z-slice independently (2d seed mode).
    ``nms`` additionally suppresses maxima dominated by stronger nearby maxima
    (reference ``non_maximum_suppression`` config knob, watershed.py:182-204).
    ``with_rounds`` appends the seed CC's int32 round count.
    """
    if sigma and sigma > 0:
        # per-slice mode smooths within slices only (reference 2d seed path)
        sig = (0.0,) + (sigma,) * (dt.ndim - 1) if per_slice else sigma
        smoothed = gaussian(dt, sig)
    else:
        smoothed = dt
    window = (1,) + (3,) * (dt.ndim - 1) if per_slice else 3
    local_max = (maximum_filter(smoothed, window) == smoothed) & (dt > 0)
    if nms:
        local_max = suppress_seeds(
            local_max, dt, per_slice=per_slice, pixel_pitch=pixel_pitch
        )
    return connected_components(
        local_max, connectivity=dt.ndim, per_slice=per_slice,
        with_rounds=with_rounds,
    )


@partial(
    jax.jit,
    static_argnames=(
        "threshold",
        "apply_dt_2d",
        "apply_ws_2d",
        "pixel_pitch",
        "sigma_seeds",
        "sigma_weights",
        "alpha",
        "size_filter",
        "invert_input",
        "non_maximum_suppression",
        "with_rounds",
    ),
)
def dt_watershed(
    input_: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    threshold: float = 0.25,
    apply_dt_2d: bool = True,
    apply_ws_2d: bool = True,
    pixel_pitch: Optional[Tuple[float, ...]] = None,
    sigma_seeds: float = 2.0,
    sigma_weights: float = 2.0,
    alpha: float = 0.8,
    size_filter: int = 25,
    invert_input: bool = False,
    non_maximum_suppression: bool = False,
    valid: Optional[jnp.ndarray] = None,
    with_rounds: bool = False,
):
    """The full per-block DT-watershed — one fused XLA program.

    threshold → distance transform (2d or 3d) → smoothed-maxima seeds
    (optionally NMS-suppressed, see ``suppress_seeds``) → height map
    α·input + (1-α)·(1-dt) → seeded flood → size filter.  Mirrors the
    reference hot loop ``_ws_block`` (watershed.py:286-344) minus IO and offsets
    (applied host-side).  Returns ``(labels int32, n_seeds)``.

    ``valid`` marks real voxels of an edge-replicate-padded block (clipped
    at volume borders, padded to the static batch shape).  The replicated
    data keeps the DT/seed/hmap fields border-faithful, but the flood and the
    size filter are restricted to ``valid``: labels never occupy padding, so
    segment voxel counts match the clipped computation — replicated copies of
    a small border fragment must not carry it over ``size_filter``.

    Each phase runs under a named scope that a device trace shows in its
    operations' names: ``ws.dt`` (threshold, distance transform),
    ``ws.seeds``, ``ws.hmap``, ``ws.flood`` and ``ws.size_filter``.
    ``with_rounds`` appends the phases' int32 round counts as outputs of the
    same program: ``{"flood": [...], "flood_tile": [...], "cc": [...]}``, one
    entry per flood (the flood, the size filter's re-flood) whose path
    counts its rounds, and the seed CC's (None where it counts none).
    """
    from .dt import _distance_transform, distance_transform_2d_stack

    if pixel_pitch is not None and apply_dt_2d:
        # mirror the reference's assertion (watershed.py:149-153): anisotropic
        # pitch only applies to the 3d distance transform
        raise ValueError("pixel_pitch requires apply_dt_2d=False")

    with jax.named_scope("ws.dt"):
        x = input_.astype(jnp.float32)
        if invert_input:
            x = 1.0 - x
        fg = x < threshold
        if mask is not None:
            fg = fg & mask.astype(bool)

        if apply_dt_2d and x.ndim == 3:
            dt = distance_transform_2d_stack(fg, pixel_pitch=None)
        else:
            dt = _distance_transform(fg, pixel_pitch)

    per_slice_seeds = apply_ws_2d and x.ndim == 3
    with jax.named_scope("ws.seeds"):
        seeds, n_seeds, seed_rounds = dt_seeds(
            dt, sigma_seeds, per_slice=per_slice_seeds,
            nms=non_maximum_suppression, pixel_pitch=pixel_pitch,
            with_rounds=True,
        )
    with jax.named_scope("ws.hmap"):
        hmap = make_hmap(
            x, dt, alpha, sigma_weights, per_slice=per_slice_seeds
        )
    with jax.named_scope("ws.flood"):
        flood_mask = fg if valid is None else fg & valid.astype(bool)
        labels, flood = seeded_watershed(
            hmap, seeds, mask=flood_mask, per_slice=per_slice_seeds,
            with_rounds=True,
        )
    floods = [flood]
    if size_filter > 0:
        num_segments = int(np.prod(x.shape)) // 2 + 2
        with jax.named_scope("ws.size_filter"):
            labels, reflood = apply_size_filter(
                labels, hmap, size_filter, num_segments, mask=flood_mask,
                per_slice=per_slice_seeds, with_rounds=True,
            )
        floods.append(reflood)
    if not with_rounds:
        return labels, n_seeds
    rounds = {
        key: [f[key] for f in floods if key in f]
        for key in ("flood", "flood_tile")
    }
    rounds["cc"] = [seed_rounds]
    return labels, n_seeds, rounds


@partial(
    jax.jit,
    static_argnames=(
        "threshold",
        "apply_dt_2d",
        "apply_ws_2d",
        "pixel_pitch",
        "sigma_seeds",
        "sigma_weights",
        "alpha",
        "size_filter",
        "invert_input",
        "non_maximum_suppression",
        "num_segments",
    ),
)
def two_pass_flood(
    input_: jnp.ndarray,
    written: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    valid: Optional[jnp.ndarray] = None,
    threshold: float = 0.25,
    apply_dt_2d: bool = True,
    apply_ws_2d: bool = True,
    pixel_pitch: Optional[Tuple[float, ...]] = None,
    sigma_seeds: float = 2.0,
    sigma_weights: float = 2.0,
    alpha: float = 0.8,
    size_filter: int = 25,
    invert_input: bool = False,
    non_maximum_suppression: bool = False,
    num_segments: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pass 2 of the checkerboard two-pass watershed as one fused XLA program
    (reference two_pass_watershed.py:96-99 + ``_apply_watershed_with_seeds``,
    watershed.py:128).

    ``written`` carries the already-written pass-1 neighbor labels compacted to
    1..k (0 = unwritten); this block's own DT seeds are appended *above* k on
    device, so the per-block seed count never becomes a static trace value —
    one compile serves every block, and the whole pass-2 pipeline (threshold →
    DT → seeds → hmap → flood → size filter) is a single dispatch, vmappable
    over a stacked block batch.  Returns ``(labels, k)``: flood labels where
    1..k continue written neighbor ids and values > k are new seeds in the
    block's own namespace (the host maps both back to global ids).

    ``num_segments`` (static) bounds the size-filter bincount length; the
    caller can pass a tight bound (own-seed CC ids ≤ N/2 plus written halo-
    shell voxels), default is the always-safe 2·N + 2.
    """
    from .dt import _distance_transform, distance_transform_2d_stack

    if pixel_pitch is not None and apply_dt_2d:
        # mirror dt_watershed / the reference assertion (watershed.py:149-153)
        raise ValueError("pixel_pitch requires apply_dt_2d=False")

    x = input_.astype(jnp.float32)
    if invert_input:
        x = 1.0 - x
    fg = x < threshold
    if mask is not None:
        # reference pass-2 masking (two_pass_watershed.py:236-241):
        # masked-out input is set above threshold = background for the DT
        fg = fg & mask.astype(bool)

    if apply_dt_2d and x.ndim == 3:
        dt = distance_transform_2d_stack(fg, pixel_pitch=None)
    else:
        dt = _distance_transform(fg, pixel_pitch)

    per_slice = apply_ws_2d and x.ndim == 3
    written = written.astype(jnp.int32)
    k = written.max()
    if per_slice:
        # 2d path parity: no own maxima at written voxels — the reference
        # zeroes the dt there before seed-making AND hmap construction
        # (two_pass_watershed.py:144-146)
        dt = jnp.where(written > 0, 0.0, dt)
    own_seeds, _ = dt_seeds(
        dt, sigma_seeds, per_slice=per_slice,
        nms=non_maximum_suppression, pixel_pitch=pixel_pitch,
    )
    seeds = jnp.where(
        written > 0, written, jnp.where(own_seeds > 0, own_seeds + k, 0)
    )
    hmap = make_hmap(x, dt, alpha, sigma_weights, per_slice=per_slice)
    # flood/size-filter restricted to real voxels of a padded edge block —
    # see dt_watershed's ``valid`` note
    flood_mask = fg if valid is None else fg & valid.astype(bool)
    labels = seeded_watershed(hmap, seeds, mask=flood_mask, per_slice=per_slice)
    if size_filter > 0:
        if num_segments is None:
            # always-safe bound: k ≤ #written voxels and #own seeds ≤ #fg
            # voxels, which may overlap — labels ≥ the bincount length would
            # be silently dropped (= wrongly size-filtered)
            num_segments = 2 * int(np.prod(x.shape)) + 2
        # written (initial-seed) regions are exempt from the size filter —
        # continuation labels must survive however small their overlap with
        # this block is (reference run_watershed ``exclude=initial_seed_ids``,
        # two_pass_watershed.py:166-167,205-209)
        labels = apply_size_filter(
            labels, hmap, size_filter, num_segments, mask=flood_mask,
            per_slice=per_slice, protect_upto=k,
        )
    return labels, k


@partial(jax.jit, static_argnames=("alpha", "sigma", "per_slice"))
def make_hmap(
    input_: jnp.ndarray,
    dt: jnp.ndarray,
    alpha: float,
    sigma: float = 0.0,
    per_slice: bool = False,
) -> jnp.ndarray:
    """Height map α·input + (1-α)·(1 - normalize(dt))
    (reference ``_make_hmap``, watershed.py:164-170).  ``per_slice`` normalizes
    the distances and smooths within each z-slice (2d mode)."""
    dtn = jax.vmap(normalize)(dt) if per_slice else normalize(dt)
    hmap = alpha * input_ + (1.0 - alpha) * (1.0 - dtn)
    if sigma and sigma > 0:
        sig = (0.0,) + (sigma,) * (dt.ndim - 1) if per_slice else sigma
        hmap = gaussian(hmap, sig)
    return hmap


@partial(
    jax.jit,
    static_argnames=(
        "size_filter", "num_segments", "connectivity", "per_slice",
        "with_rounds",
    ),
)
def apply_size_filter(
    labels: jnp.ndarray,
    hmap: jnp.ndarray,
    size_filter: int,
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    connectivity: int = 1,
    per_slice: bool = False,
    protect_upto: Optional[jnp.ndarray] = None,
    with_rounds: bool = False,
):
    """Remove segments smaller than ``size_filter`` voxels and re-flood the freed
    voxels from the surviving segments (reference ``_apply_watershed``
    size-filter step, watershed.py:242-250).

    ``num_segments`` is the *exclusive* upper bound on label values, i.e.
    max_label + 1 (pass ``n + 1`` for labels 1..n from dt_seeds).
    ``protect_upto`` (traced scalar) exempts labels ≤ it from the filter
    (the reference ``exclude=`` seam for two-pass continuation labels).
    ``with_rounds`` returns ``(labels, rounds)`` of the re-flood, as
    ``seeded_watershed`` does."""
    counts = jnp.bincount(labels.reshape(-1), length=num_segments)
    too_small = counts[labels] < size_filter
    if protect_upto is not None:
        too_small = too_small & (labels > protect_upto)
    kept = jnp.where(too_small, 0, labels)
    return seeded_watershed(
        hmap, kept, mask=mask, connectivity=connectivity, per_slice=per_slice,
        with_rounds=with_rounds,
    )


def fit_to_hmap(
    objs: np.ndarray,
    hmap: np.ndarray,
    erode_by: int,
    erode_3d: bool = True,
) -> np.ndarray:
    """Refit (possibly resampled) objects to a boundary height map: erode each
    object, then re-grow all of them with a seeded watershed on a DT-blended
    height map (reference volume_utils.fit_to_hmap:336-357).

    Host wrapper: labels are compacted to int32 for the device flood and mapped
    back, so uint64 ids survive.  The per-object erosion is the min==max window
    test (a voxel is interior iff its whole window carries one label); the
    background seed is the eroded background.  Returns the refit uint64 labels.
    """
    from .dt import distance_transform
    from .filters import minimum_filter

    uniq = np.unique(objs)
    if uniq[0] != 0:
        uniq = np.concatenate([[0], uniq])
    local = np.searchsorted(uniq, objs).astype(np.int32)
    bg_id = np.int32(uniq.size)

    size = 2 * int(erode_by) + 1
    win = size if erode_3d else (1, size, size)
    labels = jnp.asarray(local)
    mn = minimum_filter(labels, win)
    mx = maximum_filter(labels, win)
    interior = (mn == mx) & (labels > 0)
    seeds = jnp.where(interior, labels, 0)
    seeds = jnp.where(mx == 0, bg_id, seeds)

    h = normalize(jnp.asarray(hmap, jnp.float32))
    dt = distance_transform(h > 0.3)
    h = 0.8 * h + 0.2 * (1.0 - normalize(dt))

    fitted_local = np.array(seeded_watershed(h, seeds))
    fitted_local[fitted_local == bg_id] = 0
    return uniq[fitted_local].astype(np.uint64)
