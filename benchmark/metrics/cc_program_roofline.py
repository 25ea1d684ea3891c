"""The block components program's share of its roofline: the least time
its executions could take (``harness.peaks.block_components_bytes`` over
the chip's HBM bandwidth) over their device time in the trace.  The program
is matched by its module name, and has to have run once per block of the
window's jobs (device batch 1)."""

from benchmark.harness import peaks, window, xtrace

NAMES = ("jit__components_batch",)


def read(ctx):
    if ctx.trace is None:
        return None
    n = sum(window.blocks((job["begin"], job["end"]), ctx.block_shape)
            for job in ctx.jobs)
    ns = xtrace.once_per_block(ctx.trace, NAMES, n)
    least = n * peaks.block_components_bytes(ctx.block_shape, (0, 0, 0)) / \
        ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ns / 1e9)
