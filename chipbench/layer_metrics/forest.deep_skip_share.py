"""Of the row tiles the deep histogram kernel's grids cover, the share it does
not stream: 1 - forest.deep_tiles_kept / forest.deep_tiles, the program's
counters.  Covered: every tree's tiles of the sorted deep layout, once a deep
level.  Kept: the tiles a tree's segments own (the fit's one fetch brings the
per-tree counts); the rest hold rows that weigh nothing in the tree and filler
no segment used, behind the segments, and the kernel does nothing for them.  A
program without the counters streams every tile it covers and reads 0; nothing
where no forest fit ran, or none had a deep phase (the counter stands at 0)."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    tiles = counters.get("forest.deep_tiles")
    if tiles is None:
        return 0.0 if counters.get("forest.fits", 0) else None
    return 100.0 * (1.0 - counters.get("forest.deep_tiles_kept", 0) / tiles) if tiles else None
