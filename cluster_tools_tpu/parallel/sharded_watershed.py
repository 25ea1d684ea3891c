"""The flagship DT-watershed as ONE collective program over the device mesh.

``ops.watershed.dt_watershed`` fuses the whole per-block pipeline for one
chip; this module is its sharded form for volumes that exceed a chip's HBM:
the volume z-shards over the mesh and every cross-shard dependency rides an
XLA collective inside the jit program (SURVEY.md §2.8/§2.9 — the "volume
larger than HBM = long context" mapping):

  * z line-scan of the EDT — directional distance relaxation across shard
    boundaries (``lax.ppermute`` plane exchange, ``psum`` convergence); the
    y/x min-plus parabola passes are plane-local, so with z as the sharded
    axis they need no communication at all;
  * seed smoothing and the 3x3x3 maxima window — ``halo_exchange`` with the
    gaussian's true radius, symmetric padding at the volume's outer faces
    (bit-matching the single-device ``filters.gaussian``);
  * seed-plateau CC — the sharded min-label machinery (full connectivity);
  * height-map normalization — global ``lax.pmin/pmax``;
  * the flood — the sharded two-phase relaxation of ``parallel.sharded``.

The size filter needs per-segment voxel counts over data-dependent ids; the
host computes counts from the flood output (one transfer that the writing
task pays anyway) and a second collective flood re-floods the survivors —
the same split the reference's ``size_filter`` re-flood implies.

Exactness: every stage reproduces the single-device numerics (same kernels,
same accumulation windows), and seed ids (plateau-root flat indices + 1) are
order-isomorphic to ``dt_seeds``' consecutive ids, so flood tie-breaking
agrees — ``sharded_dt_watershed`` yields the SAME PARTITION as
``dt_watershed(apply_dt_2d=False, apply_ws_2d=False)`` (tested on the
8-virtual-device mesh).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..obs import trace as obs_trace
from ..ops.dt import _BIG as _DT_BIG
from ..ops.dt import _parabola_pass
from ..ops.filters import _gauss_kernel
from .mesh import get_mesh
from .sharded import _neighbor_planes, halo_exchange


def _directional_z_distance(bg, axis_name, reverse):
    """Distance (in planes) to the nearest background plane at-or-before each
    voxel along z, across shard boundaries.

    Local part: cummax index arithmetic (exact within the shard).  Cross-
    shard: the incoming boundary distance grows linearly inside the shard
    (cand(z) = carry + z + 1), so one plane exchange updates every local
    plane at once; rounds iterate until the global fixpoint (information
    crosses one boundary per round, like the flood)."""
    z_local = bg.shape[0]
    b = jnp.flip(bg, 0) if reverse else bg
    iota = jnp.arange(z_local, dtype=jnp.float32)[:, None, None]
    last_bg = lax.cummax(jnp.where(b, iota, -_DT_BIG), axis=0)
    local = jnp.minimum(iota - last_bg, _DT_BIG)

    direction = -1 if reverse else +1

    def body(state):
        d, _ = state
        # the neighbor's far-plane distance, +1 for the boundary hop
        carry = _neighbor_planes(d[-1], axis_name, +1 * direction)
        n = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        edge = idx == (0 if direction > 0 else n - 1)
        carry = jnp.where(edge, jnp.full_like(carry, _DT_BIG), carry)
        cand = jnp.minimum(carry[None] + iota + 1.0, _DT_BIG)
        new = jnp.minimum(d, cand)
        changed = lax.psum(jnp.any(new != d).astype(jnp.int32), axis_name) > 0
        return new, changed

    local, _ = lax.while_loop(
        lambda st: st[1], body, (local, jnp.bool_(True))
    )
    return jnp.flip(local, 0) if reverse else local


def _sharded_edt(fg, pitch, axis_name):
    """Squared→exact Euclidean DT of a z-sharded foreground mask: cross-shard
    z line scan + plane-local min-plus parabola passes (ops.dt numerics)."""
    bg = ~fg
    fwd = _directional_z_distance(bg, axis_name, False)
    bwd = _directional_z_distance(bg, axis_name, True)
    g = (jnp.minimum(fwd, bwd) * pitch[0]) ** 2
    for axis in (1, 2):
        g = jnp.moveaxis(g, axis, -1)
        g = _parabola_pass(g, pitch[axis], 32)
        g = jnp.moveaxis(g, -1, axis)
    return jnp.sqrt(jnp.minimum(g, _DT_BIG)).astype(jnp.float32)


def _reflect_z(ext, radius, z_local, axis_name, total):
    """Replace out-of-volume halo planes with the volume's symmetric
    reflection (jnp.pad mode="symmetric": global position g < 0 mirrors
    plane -g-1, g >= total mirrors 2*total-g-1).  ``total`` is the REAL
    volume depth — when the z-extent was padded up to mesh divisibility this
    is smaller than n*z_local, and the pad slab itself mirrors real planes.
    With multi-hop halos a SHALLOW shard near an edge also has out-of-volume
    planes (not just shard 0 / n-1); one gather fixes all cases.

    Scope: mirror sources are provably in range for every tap feeding a REAL
    (g < total) output plane; taps feeding pad-slab outputs (internal
    z-padding, ``total < n*z_local``) may clip to a wrong plane — callers
    MUST mask pad-slab outputs out (the watershed stages do, via ``valid``).
    """
    idx = lax.axis_index(axis_name)
    z0 = idx * z_local
    g = z0 - radius + jnp.arange(ext.shape[0])
    src = jnp.where(g < 0, -g - 1, jnp.where(g >= total, 2 * total - g - 1, g))
    loc = jnp.clip(src - (z0 - radius), 0, ext.shape[0] - 1)
    return jnp.take(ext, loc, axis=0)


def _sharded_gaussian_z(x, sigma, axis_name, total):
    """Gaussian smoothing matching ``filters.gaussian`` on the unsharded
    volume of depth ``total``: y/x passes are plane-local; the z pass
    convolves a halo-extended shard (neighbor planes via ppermute, symmetric
    padding at the volume's outer faces — the same boundary rule
    ``_conv_along_axis`` applies)."""
    from ..ops.filters import _conv_along_axis

    x = x.astype(jnp.float32)
    kernel = jnp.asarray(_gauss_kernel(float(sigma), 0))
    radius = kernel.shape[0] // 2
    ext = halo_exchange(x, radius, axis_name)
    ext = _reflect_z(ext, radius, x.shape[0], axis_name, total)
    # z pass on the extended shard (halo consumed by the VALID conv)
    moved = jnp.moveaxis(ext, 0, -1)
    smoothed = _conv_along_axis_valid(moved, kernel)
    out = jnp.moveaxis(smoothed, -1, 0)
    # y/x passes, plane-local
    for axis in (1, 2):
        out = _conv_along_axis(out, kernel, axis)
    return out


def _conv_along_axis_valid(x, kernel):
    """1d conv along the last axis with NO padding (the caller supplied the
    halo), matching ``filters._conv_along_axis``'s accumulation."""
    batch_shape = x.shape[:-1]
    n = x.shape[-1]
    flat = x.reshape(-1, 1, n)
    out = lax.conv_general_dilated(
        flat, kernel[::-1].reshape(1, 1, -1),
        window_strides=(1,), padding="VALID",
    )
    return out.reshape(batch_shape + (out.shape[-1],))


def _local_maxima(smoothed, axis_name, total):
    """3x3x3 window maxima across shard boundaries: 1-plane halo exchange,
    then the same symmetric-edge reduce_window the single-device
    ``maximum_filter`` applies (1-deep symmetric pad == edge value at the
    real volume boundary ``total``)."""
    ext = halo_exchange(smoothed, 1, axis_name, fill=-np.inf)
    ext = _reflect_z(ext, 1, smoothed.shape[0], axis_name, total)
    pad_yx = [(0, 0), (1, 1), (1, 1)]
    padded = jnp.pad(ext, pad_yx, mode="symmetric")
    win = lax.reduce_window(
        padded, -jnp.inf, lax.max, (3, 3, 3), (1, 1, 1), "VALID"
    )
    return win == smoothed


@partial(
    jax.jit,
    static_argnames=(
        "threshold", "pitch", "sigma_seeds", "sigma_weights", "alpha",
        "invert_input", "axis_name", "mesh", "z_valid",
    ),
)
def _stage_a(
    x, threshold, pitch, sigma_seeds, sigma_weights, alpha, invert_input,
    axis_name, mesh, z_valid,
):
    """threshold → EDT → smoothed maxima → height map, one collective jit
    (module-level so one compilation serves every same-shape volume).

    ``z_valid`` (static) is the REAL volume depth: when z was padded up to
    mesh divisibility (with a foreground-side value, so the pad contributes
    no DT background), smoothing mirrors at the true boundary, maxima and
    the flood mask exclude the pad slab, and the normalization ignores it —
    the result matches the unpadded single-device kernel exactly."""

    def local_fn(x):
        z_local = x.shape[0]
        idx = lax.axis_index(axis_name)
        valid = (idx * z_local + jnp.arange(z_local) < z_valid)[:, None, None]
        if invert_input:
            x = 1.0 - x
        fg = x < threshold
        dt = _sharded_edt(fg, pitch, axis_name)
        smoothed = (
            _sharded_gaussian_z(dt, sigma_seeds, axis_name, z_valid)
            if sigma_seeds and sigma_seeds > 0 else dt
        )
        maxima = _local_maxima(smoothed, axis_name, z_valid) & (dt > 0) & valid
        # global normalize for the height map, over real voxels only
        gmin = lax.pmin(jnp.min(jnp.where(valid, dt, _DT_BIG)), axis_name)
        gmax = lax.pmax(jnp.max(jnp.where(valid, dt, -_DT_BIG)), axis_name)
        dtn = (dt - gmin) / jnp.maximum(gmax - gmin, 1e-6)
        hmap = alpha * x + (1.0 - alpha) * (1.0 - dtn)
        if sigma_weights and sigma_weights > 0:
            hmap = _sharded_gaussian_z(hmap, sigma_weights, axis_name, z_valid)
        return fg & valid, maxima, hmap

    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=P(axis_name),
        out_specs=(P(axis_name),) * 3, check_vma=False,
    )(x)


def _stage_input(input_, mesh, axis_name, invert_input, z_valid, who):
    """Shared placement contract of both collective watershed kernels:
    accept a pre-placed (padded) device array carrying the mesh sharding —
    validated float32 with a mesh-divisible z extent, ``z_valid``
    required — or a host array, padded on the foreground side of the
    threshold and placed via ``put_global``.  Returns ``(x_d, z_valid)``."""
    from .mesh import put_global

    n = mesh.shape[axis_name]
    pre_placed = isinstance(input_, jax.Array) and input_.sharding.is_equivalent_to(
        NamedSharding(mesh, P(axis_name)), input_.ndim
    )
    if pre_placed:
        if z_valid is None:
            raise ValueError(
                f"pass z_valid when handing {who} a pre-placed (possibly "
                "padded) device array"
            )
        if input_.dtype != jnp.float32 or input_.shape[0] % n:
            raise ValueError(
                "pre-placed input must be float32 with a mesh-divisible z "
                f"extent, got {input_.dtype} {input_.shape}"
            )
        return input_, int(z_valid)
    if z_valid is None:
        z_valid = int(input_.shape[0])
    pad = (-z_valid) % n
    arr = np.asarray(input_, dtype=np.float32)
    if pad:
        # foreground side of the threshold AFTER the kernel's inversion
        # (assumes 0 < threshold < 1, the reference's probability range)
        pad_val = 1.0 if invert_input else 0.0
        arr = np.pad(
            arr, ((0, pad), (0, 0), (0, 0)), constant_values=pad_val
        )
    return put_global(arr, mesh, axis_name, dtype=np.float32), int(z_valid)


@obs_trace.traced(kind="collective")
def sharded_dt_watershed_2d(
    input_,
    mesh=None,
    axis_name: str = "data",
    threshold: float = 0.25,
    sigma_seeds: float = 2.0,
    sigma_weights: float = 2.0,
    alpha: float = 0.8,
    size_filter: int = 25,
    invert_input: bool = False,
    z_valid: Optional[int] = None,
) -> Tuple[np.ndarray, int]:
    """Per-slice (2d DT + 2d flood) whole-volume watershed over the mesh —
    the collective form of the reference's CREMI default
    (``apply_dt_2d=True, apply_ws_2d=True``, watershed.py:286-344's 2d
    branch).

    z-slices are INDEPENDENT in this mode, so z-sharding makes the whole
    computation embarrassingly parallel: every shard runs the fused
    single-device kernel on its slab and NO collective is needed at all —
    the cheapest possible mapping onto the mesh (no cross-shard rounds, no
    boundary exchanges; contrast ``sharded_dt_watershed``'s 3d fixpoints).
    Slices are processed by the identical single-device kernel, so the
    PARTITION equals ``dt_watershed(x, apply_dt_2d=True,
    apply_ws_2d=True)`` exactly (tested).  Label values are slab-local
    (the kernel numbers seeds consecutively within its input) made
    globally unique by the shard's plane offset ``z0*Y*X`` — callers
    relabel consecutively anyway (both tasks do).

    Pad slabs (z not divisible by the mesh) are excluded via the kernel's
    ``valid`` mask, so they produce no labels.  Returns
    ``(labels int32 [host, z_valid], n_bound)`` where ``n_bound`` is the
    summed per-slab max id — the exact distinct count when
    ``size_filter=0`` and an upper bound otherwise (the filter removes ids
    without renumbering); production callers relabel consecutively anyway.
    """
    from ..ops.watershed import dt_watershed
    from .mesh import fetch_global

    mesh = mesh if mesh is not None else get_mesh(axis_name=axis_name)
    n = mesh.shape[axis_name]
    x_d, z_valid = _stage_input(
        input_, mesh, axis_name, invert_input, z_valid,
        "sharded_dt_watershed_2d",
    )
    zp, Y, X = x_d.shape
    if zp * Y * X >= np.iinfo(np.int32).max:
        raise ValueError(
            "volume exceeds the int32 flat-index label space "
            f"({zp}x{Y}x{X}); split it into ROIs"
        )
    h = zp // n

    def local_fn(x):
        idx = lax.axis_index(axis_name)
        z0 = idx * h
        plane = z0 + jnp.arange(h, dtype=jnp.int32)
        valid = jnp.broadcast_to(
            (plane < z_valid)[:, None, None], x.shape
        )
        lab, _ = dt_watershed(
            x, threshold=threshold, apply_dt_2d=True, apply_ws_2d=True,
            sigma_seeds=sigma_seeds, sigma_weights=sigma_weights,
            alpha=alpha, size_filter=size_filter,
            invert_input=invert_input, valid=valid,
        )
        off = z0 * jnp.int32(Y * X)
        # the kernel numbers its slab's seeds 1..k consecutively, so the
        # slab max bounds the slab's distinct count (exact when no size
        # filter removes ids) — summed on host below, no full-volume
        # unique pass for a value production callers discard
        return jnp.where(lab > 0, lab + off, 0), jnp.max(lab)[None]

    labels_d, n_per_shard = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False,
    )(x_d)
    labels = fetch_global(labels_d)[:z_valid]
    n_labels = int(np.asarray(n_per_shard).sum())
    return labels, n_labels


@obs_trace.traced(kind="collective")
def sharded_dt_watershed(
    input_,
    mesh=None,
    axis_name: str = "data",
    threshold: float = 0.25,
    pixel_pitch: Optional[Tuple[float, ...]] = None,
    sigma_seeds: float = 2.0,
    sigma_weights: float = 2.0,
    alpha: float = 0.8,
    size_filter: int = 25,
    invert_input: bool = False,
    z_valid: Optional[int] = None,
) -> Tuple[np.ndarray, int]:
    """DT-watershed of a whole z-sharded volume — the collective form of
    ``dt_watershed(apply_dt_2d=False, apply_ws_2d=False)`` (3d DT + 3d flood).

    Returns ``(labels int32 [host], n_seeds)``: labels carry seed-plateau
    root ids (+1); the partition equals the single-device kernel's (ids are
    order-isomorphic, so the min-label tie-break agrees — tested, including
    non-divisible z).  The size filter counts on host between two collective
    programs (see module docstring).  A z-extent not divisible by the mesh
    size is padded internally on the foreground side of the threshold — the
    pad contributes no DT background, mirrors at the TRUE boundary for
    smoothing, and is excluded from seeds/flood/counts, so the result still
    matches the unpadded single-device kernel.  Shards shallower than a
    gaussian radius are fine (multi-hop halos).

    ``input_`` may also be an already-placed (padded) device array carrying
    the mesh sharding — e.g. streamed by ``mesh.put_from_store(pad_to=n,
    pad_value=<foreground side>)`` — in which case ``z_valid`` must give
    the real (unpadded) z extent.
    """
    from .sharded import sharded_seeded_watershed

    mesh = mesh if mesh is not None else get_mesh(axis_name=axis_name)
    x_d, z_valid = _stage_input(
        input_, mesh, axis_name, invert_input, z_valid,
        "sharded_dt_watershed",
    )
    pitch = (1.0,) * 3 if pixel_pitch is None else tuple(
        float(p) for p in pixel_pitch
    )
    from .mesh import fetch_global

    fg_d, maxima_d, hmap_d = _stage_a(
        x_d, threshold, pitch, sigma_seeds, sigma_weights, alpha,
        invert_input, axis_name, mesh, z_valid,
    )

    # seed-plateau CC over the mesh (full connectivity, like dt_seeds)
    from .sharded import _sharded_cc

    roots, _, _ = _sharded_cc(maxima_d, 3, axis_name, mesh)
    seeds_d = jnp.where(roots >= 0, roots + 1, 0).astype(jnp.int32)

    labels = sharded_seeded_watershed(
        hmap_d, seeds_d, mask=fg_d, mesh=mesh, axis_name=axis_name
    )
    labels = fetch_global(labels)
    uniq, counts = np.unique(labels, return_counts=True)
    n_seeds = int((uniq > 0).sum())
    if size_filter > 0:
        # the pad slab holds no labels (flood mask excludes it), so these
        # counts are real-voxel counts
        too_small = uniq[(counts < size_filter) & (uniq > 0)]
        if too_small.size:
            kept = np.where(np.isin(labels, too_small), 0, labels)
            labels = fetch_global(
                sharded_seeded_watershed(
                    hmap_d, kept.astype(np.int32), mask=fg_d, mesh=mesh,
                    axis_name=axis_name,
                )
            )
    return labels[:z_valid], n_seeds
