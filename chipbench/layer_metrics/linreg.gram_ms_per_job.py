"""Device time, per job, of the sufficient-statistics pass (X'X, X'y and the
means in one scan over the table): the module the configuration's
`trace.gram_module` names."""
from chipbench.trace_reduce import module_seconds


def seconds(ctx, key: str):
    """Device time over the window of the module the configuration's trace block
    names under `key`; None without a trace, a job, the key or the module."""
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    pattern = ctx.config.get("trace", {}).get(key)
    s = module_seconds(ctx.trace, pattern) if pattern else 0.0
    return s if s > 0 else None


def read(ctx):
    s = seconds(ctx, "gram_module")
    return None if s is None else 1e3 * s / len(ctx.jobs)
