"""1 - (union of the device's operation intervals) / (traced window)."""
from chipbench.trace_reduce import idle_share


def read(ctx):
    return idle_share(ctx.trace) if ctx.trace is not None and ctx.trace["window_s"] > 0 else None
