"""A small n5 reader and writer of the benchmark's own.

Writes uncompressed ("raw") float32 datasets, and reads back what the
program wrote: raw, gzip or blosc chunks (blosc through the system
``libblosc``).  n5 keeps dimensions in reversed (x, y, z) order and chunk
data big-endian, each chunk behind a header of mode, rank and its shape.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import itertools
import json
import os
import struct
import zlib

import numpy as np

_TYPES = {
    "float32": ">f4", "float64": ">f8", "uint8": ">u1", "uint16": ">u2",
    "uint32": ">u4", "uint64": ">u8", "int32": ">i4", "int64": ">i8",
}


def write(path: str, key: str, data: np.ndarray, chunks) -> None:
    """``data`` as dataset ``key`` of the n5 container ``path``, raw."""
    ds = os.path.join(path, key)
    os.makedirs(ds, exist_ok=True)
    with open(os.path.join(path, "attributes.json"), "w") as f:
        json.dump({"n5": "2.0.0"}, f)
    with open(os.path.join(ds, "attributes.json"), "w") as f:
        json.dump({
            "dimensions": list(data.shape)[::-1],
            "blockSize": list(chunks)[::-1],
            "dataType": str(data.dtype),
            "compression": {"type": "raw"},
        }, f)
    dtype = _TYPES[str(data.dtype)]
    grid = [range(0, s, c) for s, c in zip(data.shape, chunks)]
    for begin in itertools.product(*grid):
        sl = tuple(slice(b, min(b + c, s))
                   for b, c, s in zip(begin, chunks, data.shape))
        part = data[sl]
        idx = [b // c for b, c in zip(begin, chunks)][::-1]
        cdir = os.path.join(ds, *map(str, idx[:-1]))
        os.makedirs(cdir, exist_ok=True)
        header = struct.pack(">HH", 0, part.ndim) + struct.pack(
            f">{part.ndim}I", *part.shape[::-1])
        with open(os.path.join(cdir, str(idx[-1])), "wb") as f:
            f.write(header)
            f.write(np.ascontiguousarray(part, dtype=dtype).tobytes())


_BLOSC = None


def _blosc():
    global _BLOSC
    if _BLOSC is None:
        name = ctypes.util.find_library("blosc")
        if name is None:
            raise RuntimeError("a blosc chunk needs the system libblosc")
        lib = ctypes.CDLL(name)
        lib.blosc_decompress_ctx.restype = ctypes.c_int
        lib.blosc_decompress_ctx.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
        _BLOSC = lib
    return _BLOSC


def _decode(payload: bytes, nbytes: int, compression: dict) -> bytes:
    kind = compression.get("type", "raw")
    if kind == "raw":
        return payload
    if kind in ("gzip", "zlib"):
        return zlib.decompress(payload, 47)  # gzip or zlib header
    if kind == "blosc":
        out = ctypes.create_string_buffer(nbytes)
        n = _blosc().blosc_decompress_ctx(payload, out, nbytes, 1)
        if n != nbytes:
            raise ValueError(f"blosc chunk decoded to {n} of {nbytes} bytes")
        return out.raw
    raise ValueError(f"unsupported n5 compression {kind!r}")


def read(path: str, key: str, begin, end) -> np.ndarray:
    """The region ``[begin, end)`` of dataset ``key``; absent chunks read 0."""
    ds = os.path.join(path, key)
    with open(os.path.join(ds, "attributes.json")) as f:
        meta = json.load(f)
    chunks = meta["blockSize"][::-1]
    dtype = np.dtype(_TYPES[meta["dataType"]])
    comp = meta.get("compression", {"type": "raw"})
    out = np.zeros([e - b for b, e in zip(begin, end)], dtype.newbyteorder("="))
    grid = [range(b // c, (e - 1) // c + 1)
            for b, e, c in zip(begin, end, chunks)]
    for idx in itertools.product(*grid):
        fn = os.path.join(ds, *map(str, idx[::-1]))
        if not os.path.exists(fn):
            continue
        with open(fn, "rb") as f:
            raw = f.read()
        mode, ndim = struct.unpack(">HH", raw[:4])
        cshape = struct.unpack(f">{ndim}I", raw[4:4 + 4 * ndim])[::-1]
        off = 4 + 4 * ndim + (4 if mode == 1 else 0)
        n = int(np.prod(cshape))
        data = np.frombuffer(_decode(raw[off:], n * dtype.itemsize, comp),
                             dtype, count=n).reshape(cshape)
        c0 = [i * c for i, c in zip(idx, chunks)]
        src = tuple(slice(max(b, o) - o, min(e, o + s) - o)
                    for b, e, o, s in zip(begin, end, c0, cshape))
        dst = tuple(slice(max(b, o) - b, min(e, o + s) - b)
                    for b, e, o, s in zip(begin, end, c0, cshape))
        out[dst] = data[src]
    return out


def read_zarr(path: str, key: str) -> np.ndarray:
    """The whole zarr (v2) array ``key`` of the container ``path``."""
    ds = os.path.join(path, key)
    with open(os.path.join(ds, ".zarray")) as f:
        meta = json.load(f)
    dtype = np.dtype(meta["dtype"])
    shape, chunks = meta["shape"], meta["chunks"]
    comp = meta.get("compressor") or {"id": "raw"}
    comp = {"type": comp.get("id", "raw")}
    sep = meta.get("dimension_separator", ".")
    out = np.full(shape, meta.get("fill_value") or 0, dtype)
    grid = [range(0, max(1, -(-s // c))) for s, c in zip(shape, chunks)]
    for idx in itertools.product(*grid):
        fn = os.path.join(ds, sep.join(map(str, idx)))
        if not os.path.exists(fn):
            continue
        with open(fn, "rb") as f:
            payload = f.read()
        n = int(np.prod(chunks))
        data = np.frombuffer(_decode(payload, n * dtype.itemsize, comp),
                             dtype, count=n).reshape(chunks)
        c0 = [i * c for i, c in zip(idx, chunks)]
        sl = tuple(slice(o, min(o + c, s)) for o, c, s in zip(c0, chunks, shape))
        out[sl] = data[tuple(slice(0, e.stop - e.start) for e in sl)]
    return out
