"""Peak rates by ``device_kind`` (``benchmark/peaks.json``), and the least
bytes each measured device program must move, from its shapes alone."""

from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def block_dt_watershed_bytes(block_shape, halo) -> int:
    """One block of the DT-watershed: its halo'd float32 boundary map read
    once and its int32 labels written once."""
    outer = int(np.prod([b + 2 * h for b, h in zip(block_shape, halo)]))
    return 4 * outer + 4 * int(np.prod(block_shape))


def block_components_bytes(block_shape, halo) -> int:
    """One block of thresholded components: its float32 boundary map read
    once and its int32 labels written once."""
    return block_dt_watershed_bytes(block_shape, halo)
