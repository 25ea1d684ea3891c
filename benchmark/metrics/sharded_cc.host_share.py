"""Share of the window's job wall that the collective components task
spent on the host around its program (``tasks/thresholded_components.py``
``ShardedComponentsTask``): inside its spans ``scc:ingest`` (store reads
and threshold), ``scc:relabel`` and ``scc:write``."""

from benchmark.harness import spans

HOST = ("scc:ingest", "scc:relabel", "scc:write")


def read(ctx):
    total = inside = 0.0
    for job in ctx.jobs:
        found = [(s["t0"], s["t1"]) for s in spans.inside(
            ctx.spans, job["t0"], job["t1"]) if s["name"] in HOST]
        total += job["t1"] - job["t0"]
        inside += spans.union_seconds(found)
    return 100.0 * inside / total if inside and total else None
