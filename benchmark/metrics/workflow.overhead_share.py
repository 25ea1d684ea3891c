"""Share of the window's job wall outside every task span (program spans
of kind ``task``): the workflow and task-graph layer's own time
(``runtime/workflow.py``, ``runtime/task.py``)."""

from benchmark.harness import spans


def read(ctx):
    total = outside = 0.0
    for job in ctx.jobs:
        tasks = [(s["t0"], s["t1"]) for s in spans.inside(
            ctx.spans, job["t0"], job["t1"]) if s["kind"] == "task"]
        if not tasks:
            return None
        wall = job["t1"] - job["t0"]
        total += wall
        outside += wall - spans.union_seconds(tasks)
    return 100.0 * outside / total if total else None
