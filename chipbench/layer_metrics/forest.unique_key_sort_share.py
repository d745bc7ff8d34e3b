"""Of the random-forest fits of the process that ran a deep phase's payload
sorts, the share whose sorts carried no stability operand: the program's
counters forest.unique_key_sort_fits over forest.sort_fits.  The sorts' combined
key is unique over a tree's positions, so a stable and an unstable sort have one
correct output, and the program counts a fit in both where its sorts carry the
key and the payloads alone (a choice static at dispatch).  A program without the
counters sorts stably, a third operand a sort, and reads 0; nothing where no
forest fit ran, or none had a deep phase (the counter stands at 0)."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("forest.sort_fits")
    if fits is None:
        return 0.0 if counters.get("forest.fits", 0) else None
    return 100.0 * counters.get("forest.unique_key_sort_fits", 0) / fits if fits else None
