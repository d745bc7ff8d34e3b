"""Nodes of a fitted forest, internal and leaf (the program's counters forest.nodes
over forest.fits, over the process: the warm job and the window's fit the same
rows): the guard that a fit's work stayed what the configuration states.  A forest
whose trees stopped early has fewer, and did less work."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("forest.fits", 0)
    return counters.get("forest.nodes", 0) / fits if fits else None
