"""Of the rows the histogram kernels' grids cover, the share that is padding:
1 - forest.hist_rows_needed / forest.hist_rows.  Needed: a tree's real rows, once
a level.  Covered: the rows of every tree a dispatch takes, with the row tiles'
padding, the segments' filler and stray tiles, and the trees a clamped last
window takes again."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    rows = counters.get("forest.hist_rows", 0)
    return 100.0 * (1.0 - counters.get("forest.hist_rows_needed", 0) / rows) if rows else None
