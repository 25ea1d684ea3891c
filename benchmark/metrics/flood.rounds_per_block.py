"""Global loop rounds of the floods per block: the program counter
``flood.rounds`` (altitude and assignment loops of the flood and of the
size filter's re-flood, outputs of the block DT-watershed program) over
``blocks.computed``, in the window."""


def read(ctx):
    blocks = ctx.counters.get("blocks.computed")
    rounds = ctx.counters.get("flood.rounds")
    if not blocks or rounds is None:
        return None
    return rounds / blocks
