"""Of the random-forest fits of the process, the share whose feature subsets
were copies of the rows they select: the program's counters
forest.gather_copy_fits over forest.fits.  The program counts a fit there when
it grows on the one-chip builder, whose gather reads the selected rows of the
binned table and nothing else (a choice static at dispatch); a program without
the counter, whose gather is a product over the whole table, reads 0."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("forest.fits", 0)
    return 100.0 * counters.get("forest.gather_copy_fits", 0) / fits if fits else None
