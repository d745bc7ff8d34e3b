"""Device time, per job, of the sweep's solve: the XLA module of the lane-batched
L-BFGS run (the configuration's `trace.sweep_module`) over the window's jobs.
Nothing where the trace has no such module (a program without the sweep, or a run
without a trace)."""
from chipbench.trace_reduce import module_seconds


def seconds(ctx):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    want = ctx.config.get("trace", {}).get("sweep_module")
    s = module_seconds(ctx.trace, want) if want else 0.0
    return s if s > 0 else None


def read(ctx):
    s = seconds(ctx)
    return None if s is None else 1e3 * s / len(ctx.jobs)
