"""The reference flood (``flood.c``), built with the system C compiler into
``benchmark/.build/`` under a name keyed by the source's hash, and loaded
with ctypes."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(HERE, "flood.c")
BUILD_DIR = os.path.join(os.path.dirname(HERE), ".build")

_LIB = None


def library_path() -> str:
    with open(SOURCE, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(BUILD_DIR, f"flood-{digest}.so")


def load() -> ctypes.CDLL:
    """The built library; compiles it first when this source has no build."""
    global _LIB
    if _LIB is not None:
        return _LIB
    path = library_path()
    if not os.path.exists(path):
        os.makedirs(BUILD_DIR, exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        subprocess.run(
            ["cc", "-O2", "-shared", "-fPIC", "-o", tmp, SOURCE],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, path)
    lib = ctypes.CDLL(path)
    lib.flood_slices.restype = ctypes.c_int
    lib.flood_slices.argtypes = [ctypes.c_void_p] * 4 + [ctypes.c_int64] * 3
    _LIB = lib
    return lib


def flood_slices(hmap: np.ndarray, seeds: np.ndarray,
                 mask: np.ndarray) -> np.ndarray:
    """Per-z-slice seeded flood of ``seeds`` over ``hmap`` inside ``mask``."""
    if hmap.ndim != 3 or seeds.shape != hmap.shape or mask.shape != hmap.shape:
        raise ValueError("flood_slices wants three arrays of one 3d shape")
    h = np.ascontiguousarray(hmap, dtype=np.float32)
    s = np.ascontiguousarray(seeds, dtype=np.int32)
    m = np.ascontiguousarray(mask, dtype=np.uint8)
    out = np.empty(h.shape, np.int32)
    rc = load().flood_slices(
        h.ctypes.data, s.ctypes.data, m.ctypes.data, out.ctypes.data,
        *h.shape,
    )
    if rc != 0:
        raise MemoryError("reference flood ran out of memory")
    return out
