"""Device time, per job, of the coordinate-descent solve: the module the
configuration's `trace.cd_module` names.  A latency-bound loop: microseconds a
coordinate (over the program's counter cd.coordinates, per fit) and the Gram
rows' share of the HBM peak go on the run's DETAIL line."""
from chipbench import program
from chipbench.harness import load_reader
from chipbench.opcount import linreg


def read(ctx):
    seconds = load_reader("linreg.gram_ms_per_job").seconds(ctx, "cd_module")
    if seconds is None:
        return None
    counters = program.counters()
    fits, coordinates = counters.get("cd.fits", 0), counters.get("cd.coordinates", 0)
    if isinstance(ctx.detail, dict) and fits and coordinates:
        done = len(ctx.jobs) * coordinates / fits       # the warm job and the window's fit alike
        ctx.detail["cd.us_per_coordinate"] = 1e6 * seconds / done
        if ctx.peaks:
            least = linreg.cd_row_bytes(ctx.config["data"]["cols"], done) / ctx.peaks["hbm_bytes_per_s"]
            ctx.detail["cd.row_hbm_share"] = 100.0 * least / seconds
    return 1e3 * seconds / len(ctx.jobs)
