"""Mean over the window's jobs of a job's wall time minus the time an operation
ran on the device inside it: host work of L1/L2 that the device waits for."""
from chipbench.trace_reduce import busy_inside


def read(ctx):
    if ctx.trace is None:
        return None
    jobs = ctx.trace["spans"].get("job", [])
    if not jobs:
        return None
    idle = [(e - s) - busy_inside(ctx.trace["busy_intervals"], s, e) for s, e in jobs]
    return 1e3 * sum(idle) / len(idle)
