"""Device time, per job, of the histogram kernels: self time of the window's
operations whose name starts with the configuration's `trace.hist_ops` (the
Pallas calls forest_hist_shallow and forest_hist_deep)."""


def seconds(ctx, key: str, by_op: bool = False):
    """Self time of the window's operations named by the configuration's trace
    block: by the start of an operation's name, or by its HLO opcode."""
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    want = ctx.config.get("trace", {}).get(key)
    if not want:
        return None
    hit = [sec for name, sec in ctx.trace["device_ops"] if (name.split(" ")[1:2] == [want] if by_op else name.startswith(want))]
    return sum(hit) if hit else None


def read(ctx):
    s = seconds(ctx, "hist_ops")
    return None if s is None else 1e3 * s / len(ctx.jobs)
