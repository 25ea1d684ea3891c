"""Per-slice seeded flood as a Pallas TPU kernel.

The XLA flood (`ops.watershed._seeded_watershed_scan`) runs each directional
sweep as its own full-array program under a `lax.while_loop`: every sweep round
trips through HBM for each state array.  This kernel instead keeps one
z-slice's whole flood state (height map, altitude, hops, labels) resident in
VMEM (a 256x256 f32 slice is 256 KB — a dozen such fields fit in ~16 MB) and
runs BOTH phases to their fixpoint inside a single kernel instance, so the
only HBM traffic is one read of (hmap, seeds, mask) and one write of the
labels per slice.  Grid = slices: independent floods per z-slice is exactly
the reference's 2d watershed mode (reference watershed/watershed.py:120-137),
which is also its production default (`apply_ws_2d: True`).

Semantics are identical to the XLA path (same lexicographic
(pass-height, hops, label) relaxation, same tie-breaking — see
ops/watershed.py module docstring); equivalence is asserted by
tests/test_pallas_flood.py against `_seeded_watershed_scan` in interpret
mode.  Sweeps use the same log-depth transfer-function doubling as the
`assoc` XLA mode: a directional sweep composes per-element clamp transfers
c -> min(u, max(c, l)) by repeated shift-and-compose (log2(n) steps), so no
sequential per-lane carry chain exists anywhere in the kernel.  Reverse-
direction sweeps shift from the opposite side instead of flipping the data —
no data reorientation anywhere.

Activation: `CTT_FLOOD_MODE=pallas` opts the per-slice flood into this kernel
on the TPU backend for lane-aligned slice shapes (H multiple of 8, W multiple
of 128) that fit in VMEM; everything else falls back to the XLA path.  Off by
default.  Mosaic lowering cannot be exercised on the CPU interpreter:
tests/test_tpu_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .watershed import _minlex  # one source of truth for the tie-break rule

_BIG = np.float32(3.0e38)
_NEG = np.float32(-3.0e38)
_BIG_DIST = np.int32(np.iinfo(np.int32).max - 1)
# Largest slice the whole-slice kernel fits in VMEM on a TPU v5e, from AOT
# compiles: 320x512 compiles, 384x512 runs out of VMEM
_MAX_SLICE_ELEMS = 320 * 512


def _shift(x, d, axis, reverse, fill):
    """The value of the element ``d`` steps *earlier* along the sweep:
    earlier = lower index for a forward sweep, higher index for reverse.
    Static-size slice + constant pad (no flips, no rolls)."""
    if d >= x.shape[axis]:
        return jnp.full_like(x, fill)
    if axis == 0:
        pad = jnp.full_like(x[:d, :], fill)
        if reverse:
            return jnp.concatenate([x[d:, :], pad], axis=0)
        return jnp.concatenate([pad, x[:-d, :]], axis=0)
    pad = jnp.full_like(x[:, :d], fill)
    if reverse:
        return jnp.concatenate([x[:, d:], pad], axis=1)
    return jnp.concatenate([pad, x[:, :-d]], axis=1)


def _sweep_altitude(alt, hmap, is_seed, mask, axis, reverse):
    """One Gauss-Seidel altitude sweep A'(p) = min(A(p), max(carry, h(p))) by
    doubling the clamp-transfer composition (u, l): log2(n) shift+compose
    steps — the in-VMEM mirror of ops.watershed._sweep_altitude_assoc."""
    conduct = mask & ~is_seed
    u = jnp.where(mask, alt, _BIG)
    l = jnp.where(conduct, hmap, u)

    n = alt.shape[axis]
    for k in range(int(np.ceil(np.log2(max(n, 2))))):
        # compose the earlier window's transfer (shifted) before our own;
        # identity transfer (BIG, NEG) pads past the boundary
        uf = _shift(u, 1 << k, axis, reverse, _BIG)
        lf = _shift(l, 1 << k, axis, reverse, _NEG)
        u = jnp.minimum(u, jnp.maximum(uf, l))
        l = jnp.maximum(lf, l)

    # exclusive prefix applied to the initial carry BIG is just the composed u
    carry_in = _shift(u, 1, axis, reverse, _BIG)
    return jnp.where(conduct, jnp.minimum(alt, jnp.maximum(carry_in, hmap)), alt)


def _sweep_assign(dist, label, alt, hmap, is_seed, mask, axis, reverse):
    """One (hops, label) BFS sweep over optimal-prefix edges
    (A(p) == max(A(q), h(p))) by doubling the (const_d, const_l, step, pass)
    transfer composition — mirror of ops.watershed._sweep_assign_assoc."""
    alt_masked = jnp.where(mask, alt, _BIG)
    prev_alt = _shift(alt_masked, 1, axis, reverse, _BIG)
    edge_ok = alt == jnp.maximum(prev_alt, hmap)
    can_update = mask & ~is_seed & edge_ok

    cd = jnp.where(mask, dist, _BIG_DIST)
    cl = jnp.where(mask, label, 0)
    step = jnp.ones_like(dist)
    # pass-through flag as int32 0/1, not i1: Mosaic cannot concatenate/pad
    # i1 vregs (invalid bitcast_vreg i1->i32 on real hardware), so every
    # value that flows through _shift must be a full-width dtype
    pas = can_update.astype(jnp.int32)

    n = dist.shape[axis]
    for k in range(int(np.ceil(np.log2(max(n, 2))))):
        fd = _shift(cd, 1 << k, axis, reverse, _BIG_DIST)
        fl = _shift(cl, 1 << k, axis, reverse, jnp.int32(0))
        fk = _shift(step, 1 << k, axis, reverse, jnp.int32(0))
        fp = _shift(pas, 1 << k, axis, reverse, jnp.int32(0))
        cand_d = fd + step
        cand_l = jnp.where(pas != 0, fl, 0)
        cd, cl = _minlex(cd, cl, cand_d, cand_l)
        step = fk + step
        pas = fp & pas

    carry_d = _shift(cd, 1, axis, reverse, _BIG_DIST)
    carry_l = _shift(cl, 1, axis, reverse, jnp.int32(0))

    cand_dist = carry_d + 1
    better = can_update & (carry_l > 0) & (
        (cand_dist < dist)
        | ((cand_dist == dist) & ((label == 0) | (carry_l < label)))
    )
    return (
        jnp.where(better, cand_dist, dist),
        jnp.where(better, carry_l, label),
    )


def flood_arrays(hmap, seeds, mask):
    """Both flood phases to their fixpoint over in-VMEM (H, W) arrays —
    the body of the whole-slice flood kernel."""
    seeds = jnp.where(mask, seeds, 0)
    is_seed = seeds > 0

    # true fixpoint loops: a capped fori_loop is NOT safe — banded
    # serpentine corridors turn at every band, needing Θ(H·W) rounds (one
    # directional segment resolves per round), far beyond any H+W bound

    # -- phase 1: altitude --------------------------------------------------
    def alt_cond(carry):
        _, changed = carry
        return changed

    def alt_round(carry):
        alt, _ = carry
        new = alt
        for axis in (0, 1):
            for rev in (False, True):
                new = _sweep_altitude(new, hmap, is_seed, mask, axis, rev)
        # reduce over int32, not i1 (Mosaic i1 vreg bitcast limitation)
        return new, jnp.max((new != alt).astype(jnp.int32)) > 0

    alt0 = jnp.where(is_seed, hmap, _BIG)
    alt, _ = lax.while_loop(alt_cond, alt_round, (alt0, jnp.bool_(True)))

    # -- phase 2: assignment ------------------------------------------------
    def asg_cond(carry):
        _, _, changed = carry
        return changed

    def asg_round(carry):
        dist, label, _ = carry
        d, l = dist, label
        for axis in (0, 1):
            for rev in (False, True):
                d, l = _sweep_assign(d, l, alt, hmap, is_seed, mask, axis, rev)
        changed = ((d != dist) | (l != label)).astype(jnp.int32)
        return d, l, jnp.max(changed) > 0

    dist0 = jnp.where(is_seed, 0, _BIG_DIST)
    _, label, _ = lax.while_loop(
        asg_cond, asg_round, (dist0, seeds, jnp.bool_(True))
    )
    return jnp.where(mask, label, 0)


def _flood_slice_kernel(h_ref, s_ref, m_ref, o_ref):
    """Whole per-slice flood: both phases iterated to their fixpoint in VMEM."""
    o_ref[0] = flood_arrays(h_ref[0], s_ref[0], m_ref[0] != 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flood_slices(hmap, seeds, mask, interpret: bool = False):
    """Flood every z-slice of ``hmap`` (N, H, W) independently from ``seeds``
    (int32, 0 = unlabeled), restricted to ``mask``.  One kernel instance per
    slice; returns int32 labels shaped like ``hmap``.

    Same fixpoint as ``seeded_watershed(..., per_slice=True)`` on a (N, H, W)
    volume (asserted in tests).  ``interpret=True`` runs the CPU interpreter
    (correctness testing without TPU hardware).
    """
    n, h, w = hmap.shape
    spec = lambda: pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))  # noqa: E731
    return pl.pallas_call(
        _flood_slice_kernel,
        grid=(n,),
        in_specs=[spec(), spec(), spec()],
        out_specs=spec(),
        out_shape=jax.ShapeDtypeStruct((n, h, w), jnp.int32),
        interpret=interpret,
    )(
        hmap.astype(jnp.float32),
        seeds.astype(jnp.int32),
        mask.astype(jnp.int32),
    )


def _flood_tile_alt_kernel(h_ref, s_ref, m_ref, o_ref):
    """Phase-1 (altitude) fixpoint of one in-VMEM tile — the ctt-cc
    hierarchy warm start: tile-local altitudes are min-max passes of real
    in-tile paths, a valid phase-1 over-approximation for the XLA global
    loops (ops.watershed._flood_scan_impl's ``warm``).  Phase 2 is NOT
    warm-started here on purpose: tile-local (hops, label) states against
    tile-local altitudes can undercut the global fixpoint (see the
    _flood_scan_impl docstring)."""
    hmap = h_ref[0]
    mask = m_ref[0] != 0
    is_seed = (s_ref[0] > 0) & mask

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        alt, _ = carry
        new = alt
        for axis in (0, 1):
            for rev in (False, True):
                new = _sweep_altitude(new, hmap, is_seed, mask, axis, rev)
        # reduce over int32, not i1 (Mosaic i1 vreg bitcast limitation)
        return new, jnp.max((new != alt).astype(jnp.int32)) > 0

    alt0 = jnp.where(is_seed, hmap, _BIG)
    alt, _ = lax.while_loop(cond, body, (alt0, jnp.bool_(True)))
    o_ref[0] = alt


@functools.partial(jax.jit, static_argnames=("tile_hw", "interpret"))
def flood_tiles_warm(hmap, seeds, mask, tile_hw, interpret: bool = False):
    """Tile-local flood-altitude fixpoints of a (N, H, W) volume: grid =
    (slices, tile rows, tile cols), each (th, tw) tile relaxed entirely in
    VMEM.  Returns the f32 warm altitude field (``_BIG`` outside mask) for
    ``ops.watershed`` to finish globally — the Pallas leg of the
    hierarchical flood."""
    n, h, w = hmap.shape
    th, tw = tile_hw
    spec = lambda: pl.BlockSpec((1, th, tw), lambda i, j, k: (i, j, k))  # noqa: E731
    return pl.pallas_call(
        _flood_tile_alt_kernel,
        grid=(n, h // th, w // tw),
        in_specs=[spec(), spec(), spec()],
        out_specs=spec(),
        out_shape=jax.ShapeDtypeStruct((n, h, w), jnp.float32),
        interpret=interpret,
    )(
        hmap.astype(jnp.float32),
        seeds.astype(jnp.int32),
        mask.astype(jnp.int32),
    )


def pallas_flood_tiled_available(shape, per_slice: bool, tile) -> bool:
    """True when the tiled Pallas warm start applies: opted in
    (CTT_FLOOD_MODE=pallas), 3d volume, TPU backend, and the flood tile's
    in-plane extent exactly tiles a lane-aligned slice and fits in VMEM
    (the whole-slice kernel's bound).  Valid for both 2d
    and 3d floods (in-tile paths are real paths either way); the whole-slice
    kernel is preferred when it applies (``pallas_flood_available``)."""
    from . import _backend

    if not _backend.use_pallas_flood():
        return False
    if len(shape) != 3 or len(tile) != 3:
        return False
    th, tw = int(tile[1]), int(tile[2])
    if th % 8 or tw % 128 or shape[1] % th or shape[2] % tw:
        return False
    if th * tw > _MAX_SLICE_ELEMS:
        return False
    return jax.default_backend() == "tpu"


def pallas_flood_available(shape, per_slice: bool) -> bool:
    """True when the Pallas flood applies: opted in (CTT_FLOOD_MODE=pallas or
    a ``force_flood_mode('pallas')`` scope), per-slice mode, 3d volume, TPU
    backend, lane-aligned slice shape whose flood state fits in VMEM.
    Larger slices take the tiled warm start or the XLA path.

    Evaluated at TRACE time (this runs inside jitted callers): a shape that
    was already compiled keeps its path until the jit caches are cleared —
    pin the mode before first use, or use ``_backend.force_flood_mode``,
    which owns the cache invalidation."""
    from . import _backend

    if not _backend.use_pallas_flood():
        return False
    if not per_slice or len(shape) != 3:
        return False
    if shape[1] % 8 or shape[2] % 128:
        return False
    if shape[1] * shape[2] > _MAX_SLICE_ELEMS:
        return False
    return jax.default_backend() == "tpu"
