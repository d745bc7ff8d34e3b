"""One read of X per L-BFGS iteration (opcount/lbfgs.py, a lower bound) at the
chip's HBM peak, over the device time of the solver's module in the trace."""
from chipbench.opcount import lbfgs
from chipbench.trace_reduce import module_seconds


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    seconds = module_seconds(ctx.trace, ctx.config["trace"]["solver_module"])
    if seconds <= 0:
        return None
    d = ctx.config["data"]
    done = sum(j["iters"] for j in ctx.jobs)
    least = lbfgs.bytes_read(d["rows_per_chip"], d["cols"], done) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
