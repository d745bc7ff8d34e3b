"""Evaluations of the objective a fit (the program's counters lbfgs.evals over
lbfgs.fits, over the process: the warm job and the window's fit the same rows).
One evaluation is two passes over X; the line search takes another number of
them on every seed's rows, which n_iter does not show."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("lbfgs.fits", 0)
    return counters.get("lbfgs.evals", 0) / fits if fits else None
