"""Evaluations the sweep's lanes shared, a job: the program's counter
tuning.sweep.scans (one trip of the batched line search evaluates every lane from
the same scan of X; NOT the sum over lanes that lbfgs.evals adds up) over the
sweeps the process ran (tuning.folds over the configuration's numFolds: the warm
job and the window's sweep the same rows).  Nothing where the program has no such
counter."""
from chipbench import program


def sweeps(ctx, counters):
    return counters.get("tuning.folds", 0) / ctx.config["validator"]["numFolds"]


def read(ctx):
    counters = program.counters()
    n = sweeps(ctx, counters)
    return counters["tuning.sweep.scans"] / n if n and "tuning.sweep.scans" in counters else None
