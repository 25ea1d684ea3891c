"""Device milliseconds per Mvox of the window's jobs that the block
components program (``jit__components_batch``) spent in its tile-face
merge (``cc.merge``).  Read from the scope paths of the traced
operations (``harness.scopes``); silent for a program without named
scopes."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.ms_per_mvox(ctx, scopes.CC_PROGRAM, "cc.", "cc.merge")
