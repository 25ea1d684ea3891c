"""Share of the traced window (first job's start to last job's end) in
which no operation ran on the device, averaged over the chips."""

from benchmark.harness import xtrace


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    span = xtrace.window_ns(ctx.trace)
    if span <= 0:
        return None
    return 100.0 * (1.0 - xtrace.busy_ns(ctx.trace) / span)
