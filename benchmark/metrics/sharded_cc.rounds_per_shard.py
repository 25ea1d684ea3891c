"""Stage-1 fixpoint rounds per shard of the collective components program:
the program counters ``scc.local_rounds`` over ``scc.shards``
(``ShardedComponentsTask``; outputs of the program), in the window."""


def read(ctx):
    shards = ctx.counters.get("scc.shards")
    rounds = ctx.counters.get("scc.local_rounds")
    if not shards or rounds is None:
        return None
    return rounds / shards
