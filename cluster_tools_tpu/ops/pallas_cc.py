"""Connected components with a per-slice Pallas TPU kernel.

The XLA CC (`ops.cc.connected_components_raw`) iterates (sweeps + pointer
jumping) as full-array programs under a `lax.while_loop`: every round trips
each state array through HBM, and the pointer-jump gathers are
latency-bound.  This path instead labels each z-slice entirely inside VMEM
(grid = slices, the layout of `ops.pallas_flood`): per slice, min-label
propagation runs to its fixpoint with log-depth directional sweeps — no
gathers anywhere in the kernel — so the HBM traffic is one mask read and one
label write per slice.  Slices are then fused along z by ONE device
pointer-jumping merge over the (z, z+1) face equivalences
(`ops.unionfind.merge_labels_device`), whose rounds are O(log n_slices),
not O(volume diameter).

Labels returned match `ops.cc.connected_components` exactly: components are
numbered 1..n in minimal-flat-index order (asserted in
tests/test_pallas_cc.py), so the two paths are drop-in interchangeable.

Activation mirrors the flood kernel: `CTT_CC_MODE=pallas` opts
`connectivity=1` 3d volumes with lane-aligned slices (H % 8 == 0,
W % 128 == 0, at most ``_MAX_SLICE_ELEMS`` per slice) into this path on the
TPU backend; everything else falls back to the XLA program.  Off by default;
tests/test_tpu_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .pallas_flood import _shift  # one shift/pad primitive for both kernels

_SENT = np.int32(np.iinfo(np.int32).max - 1)
_NEG = np.int32(-1)
# Largest slice the whole-slice kernel fits in VMEM on a TPU v5e, from AOT
# compiles: 512x512 compiles, 768x512 runs out of VMEM
_MAX_SLICE_ELEMS = 512 * 512


def _sweep_min(label, mask_i, axis, reverse):
    """One directional min-label sweep in log depth.

    Identical clamp-transfer composition to ops.cc._min_sweep (same
    (u, low) combine), expressed with reverse shifts instead of flips so no
    data reorientation is lowered.  ``low`` is −1 on conducting edges (the
    carry passes) and the sentinel on walls (the carry resets).

    ``mask_i`` is int32 0/1, not bool: Mosaic cannot concatenate/pad i1
    vregs (invalid bitcast_vreg i1->i32 on hardware), so the shifted mask
    must be full-width."""
    prev_m = _shift(mask_i, 1, axis, reverse, jnp.int32(0))
    conduct = (mask_i & prev_m) != 0
    mask = mask_i != 0

    u = jnp.where(mask, label, _SENT)
    l = jnp.where(conduct, _NEG, _SENT)

    n = label.shape[axis]
    for k in range(int(np.ceil(np.log2(max(n, 2))))):
        uf = _shift(u, 1 << k, axis, reverse, _SENT)
        lf = _shift(l, 1 << k, axis, reverse, _NEG)
        u = jnp.minimum(u, jnp.maximum(uf, l))
        l = jnp.maximum(lf, l)

    carry_in = _shift(u, 1, axis, reverse, _SENT)
    return jnp.where(conduct, jnp.minimum(label, carry_in), label)


def _cc_slice_kernel(m_ref, o_ref):
    """Label one slice's components with its minimal *volume* flat index."""
    mask_i = m_ref[0]
    mask = mask_i != 0
    h_dim, w_dim = mask.shape
    z = pl.program_id(0)
    row = lax.broadcasted_iota(jnp.int32, (h_dim, w_dim), 0)
    col = lax.broadcasted_iota(jnp.int32, (h_dim, w_dim), 1)
    flat = (z * h_dim + row) * w_dim + col
    # true fixpoint loop: a capped fori_loop is NOT safe here — banded
    # serpentine corridors need Θ(H·W) rounds, far beyond any H+W-style
    # bound (each round resolves one directional segment of the
    # min-label propagation path, and a corridor can turn at every band)
    lab = _cc_tile_fixpoint(mask_i, jnp.where(mask, flat, _SENT))
    o_ref[0] = jnp.where(mask, lab, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cc_slices(mask, interpret: bool = False):
    """Per-slice CC of a (N, H, W) bool volume: every foreground voxel gets
    the minimal volume-flat-index of its in-slice component; background −1."""
    n, h, w = mask.shape
    spec = lambda: pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))  # noqa: E731
    return pl.pallas_call(
        _cc_slice_kernel,
        grid=(n,),
        in_specs=[spec()],
        out_specs=spec(),
        out_shape=jax.ShapeDtypeStruct((n, h, w), jnp.int32),
        interpret=interpret,
    )(mask.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_connected_components(mask, interpret: bool = False):
    """3d connectivity-1 CC: Pallas per-slice labeling + one device
    pointer-jumping merge over the z-face equivalences.

    Returns ``(labels, n)`` with consecutive components 1..n in minimal-
    flat-index order — the same contract as ``ops.cc.connected_components``.
    """
    from .cc import merge_slice_labels

    mask = mask.astype(bool)
    sliced = cc_slices(mask, interpret=interpret)
    return merge_slice_labels(mask, sliced)


def _cc_tile_fixpoint(mask_i, label0):
    """Min-label fixpoint of one in-VMEM 2d block: directional log-depth
    sweeps iterated until stable (shared by the whole-slice and tiled
    kernels; see ``_cc_slice_kernel`` for why the loop must be a true
    fixpoint, not a capped fori_loop)."""

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        lab, _ = carry
        new = lab
        for axis in (0, 1):
            for rev in (False, True):
                new = _sweep_min(new, mask_i, axis, rev)
        # reduce over int32, not i1 (Mosaic i1 vreg bitcast limitation)
        return new, jnp.max((new != lab).astype(jnp.int32)) > 0

    lab, _ = lax.while_loop(cond, body, (label0, jnp.bool_(True)))
    return lab


@functools.partial(jax.jit, static_argnames=("tile_hw", "interpret"))
def cc_tiles(mask, tile_hw, interpret: bool = False):
    """Tile-local CC of a (N, H, W) bool volume: grid = (slices, tile rows,
    tile cols), each (th, tw) tile labeled entirely in VMEM with the minimal
    *volume* flat index of its in-tile component (background −1).  The
    coarse-to-fine analog of ``cc_slices`` for slices too large to hold
    whole in VMEM; fuse with ``ops.cc.merge_tiled_labels``."""
    n, h, w = mask.shape
    th, tw = tile_hw

    def kernel(m_ref, o_ref):
        mask_i = m_ref[0]
        msk = mask_i != 0
        z = pl.program_id(0)
        row = lax.broadcasted_iota(jnp.int32, (th, tw), 0) + pl.program_id(1) * th
        col = lax.broadcasted_iota(jnp.int32, (th, tw), 1) + pl.program_id(2) * tw
        flat = (z * h + row) * w + col
        lab = _cc_tile_fixpoint(mask_i, jnp.where(msk, flat, _SENT))
        o_ref[0] = jnp.where(msk, lab, _NEG)

    spec = lambda: pl.BlockSpec((1, th, tw), lambda i, j, k: (i, j, k))  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(n, h // th, w // tw),
        in_specs=[spec()],
        out_specs=spec(),
        out_shape=jax.ShapeDtypeStruct((n, h, w), jnp.int32),
        interpret=interpret,
    )(mask.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tile_hw", "interpret"))
def pallas_connected_components_tiled(mask, tile_hw, interpret: bool = False):
    """3d connectivity-1 CC via the tiled Pallas kernel + ONE compact
    value-table merge over every tile face (z faces included — tile depth is
    1, so the slice merge rides the same table).  Same ``(labels, n)``
    contract as ``pallas_connected_components``/``ops.cc.connected_components``.
    """
    from .cc import merge_tiled_labels

    mask = mask.astype(bool)
    tiled = cc_tiles(mask, tile_hw, interpret=interpret)
    return merge_tiled_labels(mask, tiled, (1,) + tuple(tile_hw))


def pallas_cc_tile(shape):
    """Tile shape for the tiled kernel: the largest lane-aligned divisors of
    (H, W) — W tile a multiple of 128 up to 512, H tile a multiple of 8 up
    to 256 — fitting the ~8-buffer VMEM budget; None when no aligned divisor
    exists."""
    _, h, w = shape
    budget = 12 * 1024 * 1024 // (4 * 8)  # i32 elements per tile
    tw = max(
        (t for t in range(128, min(w, 512) + 1, 128) if w % t == 0),
        default=None,
    )
    if tw is None:
        return None
    th = max(
        (
            t
            for t in range(8, min(h, 256) + 1, 8)
            if h % t == 0 and t * tw <= budget
        ),
        default=None,
    )
    if th is None:
        return None
    return (th, tw)


def pallas_cc_tiled_available(shape, connectivity: int, per_slice: bool) -> bool:
    """True when the TILED Pallas CC applies: the same opt-in and volume
    conditions as ``pallas_cc_available`` but without the whole-slice VMEM
    bound — slices of any size qualify as long as an aligned tile divisor
    exists.  The dispatch in ``ops.cc.connected_components`` prefers the
    whole-slice kernel when it fits."""
    from . import _backend

    if not _backend.use_pallas_cc():
        return False
    if per_slice or connectivity != 1 or len(shape) != 3:
        return False
    if shape[1] % 8 or shape[2] % 128:
        return False
    if pallas_cc_tile(shape) is None:
        return False
    return jax.default_backend() == "tpu"


def pallas_cc_available(shape, connectivity: int, per_slice: bool) -> bool:
    """True when the Pallas CC applies: opted in (CTT_CC_MODE=pallas or a
    ``force_cc_mode('pallas')`` scope), 3d connectivity-1 volume-wide
    labeling, TPU backend, lane-aligned slices.  Evaluated at TRACE time
    (compiled shapes keep their path until the jit caches clear)."""
    from . import _backend

    if not _backend.use_pallas_cc():
        return False
    if per_slice or connectivity != 1 or len(shape) != 3:
        return False
    if shape[1] % 8 or shape[2] % 128:
        return False
    # oversized slices take the tiled kernel or the XLA path instead of
    # failing Mosaic's VMEM check at compile time
    if shape[1] * shape[2] > _MAX_SLICE_ELEMS:
        return False
    return jax.default_backend() == "tpu"
