"""ctypes bindings for the native C++ solver library.

Builds ``solvers.cpp`` with g++ on first use (no pybind11 in this
environment; plain C ABI + ctypes instead) into ``_lib/``, under a file
name keyed by the hash of the source: a checkout never loads a library
that was not built from its own ``solvers.cpp``.  ``available()`` reports
whether the native library could be built/loaded; callers fall back to the
pure-python implementations in ``ops.multicut`` / ``ops.mws``.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "solvers.cpp")
_LIB_DIR = os.path.join(_HERE, "_lib")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def lib_path(src: str = _SRC) -> str:
    """Where the library built from ``src`` lives: keyed by a hash of the
    source's content, so an edit selects a fresh build."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_LIB_DIR, f"libctt_solvers.{digest}.so")


def _build(out: str) -> bool:
    os.makedirs(_LIB_DIR, exist_ok=True)
    # concurrent builders (test workers, job processes) each compile to
    # their own temp name; the atomic rename publishes one of them
    tmp = f"{out}.tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.replace(tmp, out)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError, subprocess.TimeoutExpired) as e:
        stderr = getattr(e, "stderr", b"")
        print(f"[native] build failed ({e}); falling back to python solvers\n"
              f"{stderr.decode() if isinstance(stderr, bytes) else stderr}")
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        path = lib_path()
        if not os.path.exists(path) and not _build(path):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            print(f"[native] library failed to load ({e}); "
                  "falling back to python solvers")
            _build_failed = True
            return None
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.gaec_multicut.argtypes = [
            ctypes.c_int64, ctypes.c_int64, i64p, f64p, i64p,
        ]
        lib.agglomerative_clustering.argtypes = [
            ctypes.c_int64, ctypes.c_int64, i64p, f64p, ctypes.c_void_p,
            ctypes.c_double, i64p,
        ]
        lib.mutex_watershed.argtypes = [
            ctypes.c_int64, ctypes.c_int64, i64p, f64p, u8p, i64p,
        ]
        lib.lifted_gaec.argtypes = [
            ctypes.c_int64, ctypes.c_int64, i64p, f64p,
            ctypes.c_int64, i64p, f64p, i64p,
        ]
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.dt_watershed_cpu.argtypes = [
            f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_int64, i32p,
        ]
        lib.dt_watershed_cpu.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def gaec_multicut(n_nodes: int, uv: np.ndarray, costs: np.ndarray) -> np.ndarray:
    lib = _load()
    if lib is None:
        raise RuntimeError("native solver library unavailable")
    uv = np.ascontiguousarray(uv, dtype=np.int64)
    costs = np.ascontiguousarray(costs, dtype=np.float64)
    labels = np.empty(n_nodes, dtype=np.int64)
    lib.gaec_multicut(n_nodes, uv.shape[0], uv.reshape(-1), costs, labels)
    return labels


def agglomerative_clustering(
    n_nodes: int,
    uv: np.ndarray,
    weights: np.ndarray,
    threshold: float,
    sizes: Optional[np.ndarray] = None,
) -> np.ndarray:
    lib = _load()
    if lib is None:
        raise RuntimeError("native solver library unavailable")
    uv = np.ascontiguousarray(uv, dtype=np.int64)
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    labels = np.empty(n_nodes, dtype=np.int64)
    if sizes is None:
        sizes_ptr = None
    else:
        sizes = np.ascontiguousarray(sizes, dtype=np.float64)
        sizes_ptr = sizes.ctypes.data_as(ctypes.c_void_p)
    lib.agglomerative_clustering(
        n_nodes, uv.shape[0], uv.reshape(-1), weights, sizes_ptr,
        float(threshold), labels,
    )
    return labels


def lifted_gaec(
    n_nodes: int,
    uv: np.ndarray,
    costs: np.ndarray,
    lifted_uv: np.ndarray,
    lifted_costs: np.ndarray,
) -> np.ndarray:
    lib = _load()
    if lib is None:
        raise RuntimeError("native solver library unavailable")
    uv = np.ascontiguousarray(uv, dtype=np.int64)
    costs = np.ascontiguousarray(costs, dtype=np.float64)
    lifted_uv = np.ascontiguousarray(lifted_uv, dtype=np.int64)
    lifted_costs = np.ascontiguousarray(lifted_costs, dtype=np.float64)
    labels = np.empty(n_nodes, dtype=np.int64)
    lib.lifted_gaec(
        n_nodes, uv.shape[0], uv.reshape(-1), costs,
        lifted_uv.shape[0], lifted_uv.reshape(-1), lifted_costs, labels,
    )
    return labels


def mutex_watershed(
    n_nodes: int, uv: np.ndarray, weights: np.ndarray, attractive: np.ndarray
) -> np.ndarray:
    lib = _load()
    if lib is None:
        raise RuntimeError("native solver library unavailable")
    uv = np.ascontiguousarray(uv, dtype=np.int64)
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    attractive = np.ascontiguousarray(attractive, dtype=np.uint8)
    labels = np.empty(n_nodes, dtype=np.int64)
    lib.mutex_watershed(
        n_nodes, uv.shape[0], uv.reshape(-1), weights, attractive, labels
    )
    return labels


def dt_watershed_cpu(
    input_: np.ndarray,
    threshold: float = 0.25,
    sigma_seeds: float = 2.0,
    sigma_weights: float = 2.0,
    alpha: float = 0.8,
    size_filter: int = 25,
) -> "tuple[np.ndarray, int]":
    """Single-core C++ DT-watershed (per-slice 2d mode) — the honest host
    benchmark baseline for ops.watershed.dt_watershed (vigra moral
    equivalent, reference watershed/watershed.py:286-344)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native solver library unavailable")
    x = np.ascontiguousarray(input_, dtype=np.float32)
    if x.ndim != 3:
        raise ValueError("expected a 3d (z, y, x) volume")
    labels = np.zeros(x.shape, dtype=np.int32)
    n_seeds = lib.dt_watershed_cpu(
        x.reshape(-1), x.shape[0], x.shape[1], x.shape[2],
        float(threshold), float(sigma_seeds), float(sigma_weights),
        float(alpha), int(size_filter), labels.reshape(-1),
    )
    return labels, int(n_seeds)
