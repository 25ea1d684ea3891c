"""The block DT-watershed program's share of its roofline: the least time
its executions could take (``harness.peaks.block_dt_watershed_bytes``
over the chip's HBM bandwidth; it does no matrix work) over their device
time in the trace.  The program is matched by its module name, and has to
have run once per block of the window's jobs (device batch 1)."""

from benchmark.harness import peaks, window, xtrace

NAMES = ("jit__lambda",)  # the fused kernel of tasks/watershed.py
TASK = "watershed"


def read(ctx):
    if ctx.trace is None:
        return None
    n = sum(window.blocks((job["begin"], job["end"]), ctx.block_shape)
            for job in ctx.jobs)
    ns = xtrace.once_per_block(ctx.trace, NAMES, n)
    halo = ctx.task_config(TASK).get("halo") or [0, 0, 0]
    least = n * peaks.block_dt_watershed_bytes(ctx.block_shape, halo) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / 1e9)
