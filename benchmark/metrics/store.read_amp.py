"""Bytes the chunked store read (``store.bytes_read``, a program counter,
over the window) per byte of the window's float32 input ROIs
(``utils/store.py``).  A count, not a time."""

from benchmark.harness import window


def read(ctx):
    got = ctx.counters.get("store.bytes_read")
    if not got:
        return None
    need = sum(4 * window.voxels((job["begin"], job["end"]))
               for job in ctx.jobs)
    return got / need
