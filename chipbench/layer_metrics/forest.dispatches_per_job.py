"""Executables the builder launched a fit (the program's counters
forest.dispatches over forest.fits).  The blocking reads of the device a fit
(forest.host_syncs) and the distinct executables (forest.geometries) go on the
run's DETAIL line beside it."""
from chipbench import program


def read(ctx):
    counters = program.counters()
    fits = counters.get("forest.fits", 0)
    if not fits or "forest.dispatches" not in counters:
        return None
    if isinstance(ctx.detail, dict):
        ctx.detail["forest"] = {k: counters.get("forest." + k, 0) / fits for k in ("host_syncs", "geometries", "levels")}
    return counters["forest.dispatches"] / fits
