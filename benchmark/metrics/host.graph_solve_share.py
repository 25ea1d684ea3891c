"""Share of the window's job wall inside the host graph and solver tasks
(``tasks/graph.py``, ``tasks/multicut.py``, ``native/solvers.cpp``): the
program's task spans whose identifier starts with one of these."""

from benchmark.harness import spans

TASKS = ("initial_sub_graphs", "merge_sub_graphs", "map_edge_ids",
         "solve_subproblems", "reduce_problem", "solve_global")


def read(ctx):
    total = inside = 0.0
    for job in ctx.jobs:
        found = [(s["t0"], s["t1"]) for s in spans.inside(
            ctx.spans, job["t0"], job["t1"])
            if s["kind"] == "task" and s["name"].startswith(TASKS)]
        total += job["t1"] - job["t0"]
        inside += spans.union_seconds(found)
    return 100.0 * inside / total if inside and total else None
