"""The collective components program's share of its roofline: the least
time each chip's part of it could take (``shard_bytes`` over the chip's HBM
bandwidth) over the program's device time averaged over the chips.  The
program (``parallel/sharded.py`` ``_sharded_cc``) is matched by its module
name, and has to have run once per job on every chip of the trace."""

from benchmark.harness import xtrace

NAMES = ("jit__sharded_cc",)


def shard_bytes(roi_shape, n_shards: int) -> int:
    """Least bytes one chip moves for its z-shard of an ROI (the z extent
    padded to the mesh): its bool mask read once (1 B a voxel), its int32
    labels written once (4 B), and the face plane it exchanges with a
    neighbour (an int32 label and a bool mask a voxel of the plane)."""
    z = -(-int(roi_shape[0]) // n_shards)
    plane = int(roi_shape[1]) * int(roi_shape[2])
    return z * plane * (1 + 4) + plane * (4 + 1)


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    chips = len(ctx.trace.devices)
    n, ns = xtrace.program_time(ctx.trace, NAMES)
    if n != len(ctx.jobs) * chips or ns <= 0:
        raise ValueError(
            f"program {' or '.join(NAMES)} ran {n} times in the window for "
            f"{len(ctx.jobs)} jobs on {chips} chips; match it anew in its "
            "metric's file")
    least = sum(shard_bytes([e - b for b, e in zip(job["begin"], job["end"])],
                            chips) for job in ctx.jobs) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / chips / 1e9)
