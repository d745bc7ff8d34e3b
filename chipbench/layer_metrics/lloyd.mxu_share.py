"""Lloyd's distance products (opcount/lloyd.py, a lower bound) at the chip's bf16
peak, over the device time of the solver's module in the trace, per job."""
from chipbench.opcount import lloyd
from chipbench.trace_reduce import module_seconds


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    seconds = module_seconds(ctx.trace, ctx.config["trace"]["solver_module"])
    if seconds <= 0:
        return None
    d, p = ctx.config["data"], ctx.config["estimator"]["params"]
    done = sum(j["iters"] for j in ctx.jobs)
    least = lloyd.flops(d["rows_per_chip"], d["cols"], p["k"], done) / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least / seconds
