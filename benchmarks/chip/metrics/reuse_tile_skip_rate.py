"""Percent of the reuse sites' input tiles skipped in the window: the
change of the program's cumulative sensor counters (`skipped_tiles`,
`computed_tiles`, summed over sites and layers) from window open to
close."""


def read(ctx):
    skipped = sum(s for s, _ in ctx.tiles.values())
    total = skipped + sum(c for _, c in ctx.tiles.values())
    if not total:
        return None
    return 100.0 * skipped / total
