"""Share of the staged executor's dispatch wall (``runtime/executor.py``)
in which its compute stage was not running: 1 - (seconds of the
``stage_compute`` spans) / (seconds of the ``dispatch`` spans of the same
tasks), over the window.  Only tasks that ran the three-stage pipeline
have compute-stage spans; one-block tasks have none."""

from benchmark.harness import spans


def read(ctx):
    compute, dispatch = {}, {}
    for job in ctx.jobs:
        for s in spans.inside(ctx.spans, job["t0"], job["t1"]):
            task = (s.get("attrs") or {}).get("task")
            if s["name"] == "stage_compute":
                compute[task] = compute.get(task, 0.0) + s["t1"] - s["t0"]
            elif s["name"] == "dispatch":
                dispatch[task] = dispatch.get(task, 0.0) + s["t1"] - s["t0"]
    wall = sum(dispatch.get(t, 0.0) for t in compute)
    if not compute or wall <= 0:
        return None
    return 100.0 * (1.0 - sum(compute.values()) / wall)
