"""The measured window: a closed loop of one client running jobs back to
back over a fixed sequence of ROIs."""

from __future__ import annotations

import time
from typing import Callable, List, Sequence


def roi_grid(volume_shape, block_shape, roi_blocks) -> List[tuple]:
    """Every ROI of ``roi_blocks`` blocks, as ``(begin, end)``, in z-major
    order, clipped to the volume."""
    step = [b * n for b, n in zip(block_shape, roi_blocks)]
    grid = [range(0, s, t) for s, t in zip(volume_shape, step)]
    return [
        (tuple(begin), tuple(min(b + t, s)
                             for b, t, s in zip(begin, step, volume_shape)))
        for begin in (
            (z, y, x) for z in grid[0] for y in grid[1] for x in grid[2])
    ]


def voxels(roi) -> int:
    n = 1
    for b, e in zip(*roi):
        n *= e - b
    return n


def blocks(roi, block_shape) -> int:
    """Blocks of ``block_shape`` that the ROI touches (its begin lies on
    the block grid)."""
    n = 1
    for b, e, s in zip(*roi, block_shape):
        n *= -(-(e - b) // s)
    return n


def run(sequence: Sequence, seconds: float, run_job: Callable,
        clock: Callable[[], float] = time.monotonic) -> List[dict]:
    """Run ``run_job(index, roi)`` over ``sequence`` until the first job
    that ends at or after ``seconds`` from the window's start.  Returns one
    record per job: ``index``, ``roi``, its start ``t0`` and end ``t1``,
    and ``ok``."""
    records: List[dict] = []
    t0 = None
    for index, roi in enumerate(sequence):
        start = clock()
        if t0 is None:
            t0 = start
        ok = run_job(index, roi)
        end = clock()
        records.append(
            {"index": index, "roi": roi, "t0": start, "t1": end,
             "ok": bool(ok)})
        if end - t0 >= seconds:
            return records
    raise RuntimeError(
        f"the ROI sequence ({len(sequence)} ROIs) ran out before "
        f"{seconds} s: a window may not use an ROI twice")


def window_seconds(records: Sequence[dict]) -> float:
    """From the first job's start to the last job's end."""
    return records[-1]["t1"] - records[0]["t0"]


def mvox_per_s(records: Sequence[dict]) -> float:
    """Input voxels of every job's ROI over the window's seconds, in
    millions per second."""
    return sum(voxels(r["roi"]) for r in records) / window_seconds(
        records) / 1e6
