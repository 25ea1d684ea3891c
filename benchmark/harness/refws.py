"""Plain reference of the block DT-watershed, in numpy, scipy and the C flood.

The semantics, step by step, for one block (2d mode: every z-slice alone):

1. The block is read clipped to the volume and padded up to the block shape
   by repeating its last plane, row and column; ``valid`` marks real voxels.
2. Foreground is ``x < threshold``.  Its exact Euclidean distance to the
   nearest background voxel of the slice is ``dt``.
3. Seeds: ``dt`` smoothed by a gaussian (truncated at 4 sigma, mirrored
   borders); local maxima of that in a 3x3 window, where ``dt > 0``;
   maxima that touch (8-neighbourhood) form one seed.  Seeds are numbered
   in raster order of their first voxel, slice by slice.
4. Height map: ``alpha * x + (1 - alpha) * (1 - dt_n)``, ``dt_n`` being
   ``dt`` min-max normalized per slice, then smoothed like ``dt``.
5. Seeded flood (``native.flood_slices``) inside ``foreground & valid``.
6. Segments of fewer than ``size_filter`` voxels are removed and the freed
   voxels flooded again from the voxels that kept their label.

``precision`` is ``"float64"`` for the reference and ``"bfloat16"`` for its
control: every floating value then passes through bfloat16.
"""

from __future__ import annotations

import numpy as np
from scipy import ndimage

from . import native

EIGHT = np.ones((3, 3), bool)


def _rounder(precision: str):
    if precision == "float64":
        return lambda a: np.asarray(a, np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def pad_block(x: np.ndarray, block_shape) -> tuple:
    """``(padded, valid)``: ``x`` padded up to ``block_shape`` by edge
    repetition, and the mask of its real voxels."""
    pad = [(0, b - s) for b, s in zip(block_shape, x.shape)]
    valid = np.pad(np.ones(x.shape, bool), pad)
    return np.pad(x, pad, mode="edge"), valid


def seeds_2d(dt: np.ndarray, sigma: float, rnd) -> np.ndarray:
    out = np.zeros(dt.shape, np.int32)
    n = 0
    for z in range(dt.shape[0]):
        sm = rnd(ndimage.gaussian_filter(dt[z], sigma, mode="reflect",
                                         truncate=4.0))
        peak = (ndimage.maximum_filter(sm, size=3, mode="reflect") == sm)
        lab, k = ndimage.label(peak & (dt[z] > 0), structure=EIGHT)
        out[z] = np.where(lab > 0, lab + n, 0)
        n += k
    return out


def dt_watershed(x: np.ndarray, valid: np.ndarray, params: dict,
                 precision: str = "float64") -> np.ndarray:
    """Labels (int32, 0 = background) of one padded block."""
    rnd = _rounder(precision)
    x = rnd(x.astype(np.float32))
    fg = x < float(params["threshold"])
    dt = np.empty(x.shape, np.float64)
    for z in range(x.shape[0]):
        if fg[z].all():
            dt[z] = 1e5  # no background in the slice
        else:
            dt[z] = ndimage.distance_transform_edt(fg[z])
    dt = rnd(dt)
    sigma_s = float(params["sigma_seeds"])
    sigma_w = float(params["sigma_weights"])
    alpha = float(params["alpha"])
    seeds = seeds_2d(dt, sigma_s, rnd)
    lo = dt.min(axis=(1, 2), keepdims=True)
    hi = dt.max(axis=(1, 2), keepdims=True)
    dtn = rnd((dt - lo) / np.maximum(hi - lo, 1e-6))
    hmap = rnd(alpha * x + (1.0 - alpha) * (1.0 - dtn))
    hmap = rnd(np.stack([
        ndimage.gaussian_filter(hmap[z], sigma_w, mode="reflect", truncate=4.0)
        for z in range(x.shape[0])
    ]))
    mask = fg & valid
    labels = native.flood_slices(hmap, np.where(mask, seeds, 0), mask)
    size_filter = int(params["size_filter"])
    if size_filter > 0:
        counts = np.bincount(labels.ravel())
        kept = np.where(counts[labels] < size_filter, 0, labels)
        labels = native.flood_slices(hmap, kept, mask)
    return labels


def block_labels(raw: np.ndarray, begin, end, block_shape, params: dict,
                 precision: str = "float64") -> np.ndarray:
    """Reference labels of the block ``raw[begin:end]``, cut back to it."""
    sl = tuple(slice(b, e) for b, e in zip(begin, end))
    x, valid = pad_block(raw[sl], block_shape)
    lab = dt_watershed(x, valid, params, precision)
    return lab[tuple(slice(0, e - b) for b, e in zip(begin, end))]
