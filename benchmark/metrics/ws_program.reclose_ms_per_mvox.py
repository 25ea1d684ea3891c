"""Device milliseconds per Mvox of the window's jobs that the block
DT-watershed program (``jit__lambda``) spent in its halo crop and CC
re-close (``ws.reclose``; only with a halo).  Read from the scope paths
of the traced operations (``harness.scopes``); silent for a program
without named scopes."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_mvox(ctx, scopes.WS_PROGRAM, "ws.", "ws.reclose")
