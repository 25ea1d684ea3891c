"""Device milliseconds per Mvox of the window's jobs that the collective
components program (``jit__sharded_cc``) spent, summed over the chips,
exchanging the shard faces (``scc.exchange``: the ``ppermute`` of the
boundary planes and the ``all_gather`` of the face pairs). Read from the
scope paths of the traced operations (``harness.scopes``); silent for a
program without named scopes."""

from benchmark.harness import scopes

NAMES = ("jit__sharded_cc",)


def read(ctx):
    return scopes.ms_per_mvox(ctx, NAMES, "scc.", "scc.exchange")
