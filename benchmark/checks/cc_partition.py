"""The thresholded components a job wrote over its ROI, against
``scipy.ndimage.label`` of ``raw > threshold`` (6-connectivity) on the same
ROI.  Number: ``cc_mismatch_share`` (``harness.compare.mismatch_share``);
the comparison is exact."""

import numpy as np
from scipy import ndimage

from benchmark.harness import compare, n5

NUMBER = "cc_mismatch_share"


def _roi(job):
    return tuple(slice(b, e) for b, e in zip(job["begin"], job["end"]))


def _labels(ctx, job, entry, precision):
    x = ctx.raw[_roi(job)]
    if precision == "bfloat16":
        import ml_dtypes

        x = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    elif precision != "float64":
        raise ValueError(f"unknown precision {precision!r}")
    threshold = float(ctx.task_config(entry["task"])["threshold"])
    labels, _ = ndimage.label(x > threshold)
    return labels


def reference(ctx, job, entry, got):
    return _labels(ctx, job, entry, "float64")


def control(ctx, job, entry, precision="bfloat16"):
    """The reference in the program's place, its input in bfloat16."""
    return _labels(ctx, job, entry, precision)


def program(ctx, job, entry):
    key = entry["key"].format(job=job["index"])
    return n5.read(ctx.output_path, key, job["begin"], job["end"])


def numbers(got, want):
    return {NUMBER: compare.mismatch_share(got, want)}
