"""Device milliseconds per Mvox of the window's jobs that the collective
components program (``jit__sharded_cc``) spent, summed over the chips,
labelling its shard to its local fixpoint (``scc.local``, the coarse
kernel's ``cc.tiles``/``cc.merge`` nested). Read from the scope paths of
the traced operations (``harness.scopes``); silent for a program without
named scopes."""

from benchmark.harness import scopes

NAMES = ("jit__sharded_cc",)


def read(ctx):
    return scopes.ms_per_mvox(ctx, NAMES, "scc.", "scc.local")
