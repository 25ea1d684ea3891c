"""Device milliseconds per Mvox of the window's jobs that the collective
components program (``jit__sharded_cc``) spent, summed over the chips,
resolving the face table and relabelling (``scc.resolve``:
``merge_value_table``, ``apply_value_roots``). Read from the scope paths
of the traced operations (``harness.scopes``); silent for a program
without named scopes."""

from benchmark.harness import scopes

NAMES = ("jit__sharded_cc",)


def read(ctx):
    return scopes.ms_per_mvox(ctx, NAMES, "scc.", "scc.resolve")
