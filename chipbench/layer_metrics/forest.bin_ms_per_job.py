"""Device time, per job, of binning: the module the configuration's
`trace.bin_module` names (one pass over the table: 4.8 GB read, 1.2 GB written).
Its share of the HBM peak goes on the run's DETAIL line."""
from chipbench.opcount import forest
from chipbench.trace_reduce import module_seconds


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    pattern = ctx.config.get("trace", {}).get("bin_module")
    seconds = module_seconds(ctx.trace, pattern) if pattern else 0.0
    if seconds <= 0:
        return None
    d = ctx.config["data"]
    if isinstance(ctx.detail, dict) and ctx.peaks:
        least = len(ctx.jobs) * forest.bin_bytes(d["rows_per_chip"], d["cols"]) / ctx.peaks["hbm_bytes_per_s"]
        ctx.detail["forest.bin_hbm_share"] = 100.0 * least / seconds
    return 1e3 * seconds / len(ctx.jobs)
