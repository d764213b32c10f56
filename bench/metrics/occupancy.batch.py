"""Mean share of the decode slots the engine decoded per block, over the
window's decode blocks (the engine's own count, returned by `step`)."""


def read(ctx):
    occ = ctx.get("occupancy")
    return None if occ is None else 100.0 * occ
