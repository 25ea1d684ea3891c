"""Share of the window (first job's start to last job's end) that JAX
spent in backend compiles: the program counter ``jit.compile_s``
(``obs/metrics.py``, a load from the persistent cache included) over the
window's seconds."""


def read(ctx):
    got = ctx.counters.get("jit.compile_s")
    if got is None or not ctx.jobs:
        return None
    wall = ctx.jobs[-1]["t1"] - ctx.jobs[0]["t0"]
    return 100.0 * got / wall if wall > 0 else None
