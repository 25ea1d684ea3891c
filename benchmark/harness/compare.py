"""Partition comparisons between what the program wrote and a reference."""

from __future__ import annotations

import numpy as np


def _dense(a: np.ndarray) -> np.ndarray:
    """Ids of ``a`` mapped to 0..k-1 with 0 kept as 0 (background)."""
    flat = a.ravel()
    uniq, inv = np.unique(flat, return_inverse=True)
    if uniq[0] != 0:
        inv = inv + 1
    return inv.astype(np.int64)


def mismatch_share(got: np.ndarray, want: np.ndarray) -> float:
    """Share of voxels that would have to change segment to make the two
    partitions equal, the worse of the two directions.

    Background (id 0) is a segment of its own on both sides.  Ids need not
    agree; 0.0 means the same partition and the same background."""
    if got.shape != want.shape:
        raise ValueError(f"shapes differ: {got.shape} vs {want.shape}")
    n = got.size
    if n == 0:
        return 0.0
    a = _dense(got)
    b = _dense(want)
    nb = int(b.max()) + 1
    pairs, counts = np.unique(a * nb + b, return_counts=True)
    pa, pb = pairs // nb, pairs % nb
    best_a = np.zeros(int(a.max()) + 1, np.int64)
    np.maximum.at(best_a, pa, counts)
    best_b = np.zeros(nb, np.int64)
    np.maximum.at(best_b, pb, counts)
    return float(max(n - best_a.sum(), n - best_b.sum()) / n)
