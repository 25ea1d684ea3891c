"""CC fixpoint rounds per block: the program counter ``cc.rounds`` (the
seed CC and any re-close of the block DT-watershed program, the CC of
the block components program; outputs of the programs) over
``blocks.computed``, in the window."""


def read(ctx):
    blocks = ctx.counters.get("blocks.computed")
    rounds = ctx.counters.get("cc.rounds")
    if not blocks or rounds is None:
        return None
    return rounds / blocks
