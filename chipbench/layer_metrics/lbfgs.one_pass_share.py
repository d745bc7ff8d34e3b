"""Of the L-BFGS fits of the process, the share whose objective read X once an
evaluation: the program's counters lbfgs.one_pass_fits over lbfgs.fits.  The
program counts a fit there when its data term came from the one-pass kernel (a
choice static at dispatch); a program without the counter reads 0."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("lbfgs.fits", 0)
    return 100.0 * counters.get("lbfgs.one_pass_fits", 0) / fits if fits else None
