"""The block DT-watershed a job wrote, against ``harness.refws``, block by
block over the job's ROI.  Number: ``ws_mismatch_share``, the worst block's
share of voxels in another segment than the reference puts them
(``harness.compare.mismatch_share``)."""

from benchmark.harness import compare, n5, refws

NUMBER = "ws_mismatch_share"


def blocks(ctx, job):
    """``(begin, end)`` of every block of the job's ROI, z-major."""
    shape, bs = ctx.volume_shape, ctx.block_shape
    grid = [range(b // s, (e - 1) // s + 1)
            for b, e, s in zip(job["begin"], job["end"], bs)]
    for iz in grid[0]:
        for iy in grid[1]:
            for ix in grid[2]:
                begin = [i * s for i, s in zip((iz, iy, ix), bs)]
                end = [min(b + s, n) for b, s, n in zip(begin, bs, shape)]
                yield begin, end


def labels(ctx, job, task, precision):
    """The reference's labels of every block, each numbered from 1."""
    params = ctx.task_config(task)
    return [refws.block_labels(ctx.raw, b, e, ctx.block_shape, params,
                               precision)
            for b, e in blocks(ctx, job)]


def reference(ctx, job, entry, got):
    return labels(ctx, job, entry["task"], "float64")


def control(ctx, job, entry, precision="bfloat16"):
    """The reference in the program's place, every value in bfloat16."""
    return labels(ctx, job, entry["task"], precision)


def program(ctx, job, entry):
    key = entry["key"].format(job=job["index"])
    return [n5.read(ctx.output_path, key, b, e) for b, e in blocks(ctx, job)]


def numbers(got, want):
    return {NUMBER: max(compare.mismatch_share(g, w)
                        for g, w in zip(got, want))}
