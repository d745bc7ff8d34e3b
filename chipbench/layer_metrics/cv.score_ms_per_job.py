"""Device time, per job, inside the program's scoring span (the configuration's
`trace.score_span`, tuning.sweep.score): the held-out statistics of the 24 models.
`busy_ms_inside` serves cv.refit_ms_per_job too.  Nothing where the trace has no
such span."""
from chipbench.trace_reduce import busy_inside


def busy_ms_inside(ctx, key):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    spans = ctx.trace["spans"].get(ctx.config.get("trace", {}).get(key, ""), [])
    if not spans:
        return None
    return 1e3 * sum(busy_inside(ctx.trace["busy_intervals"], s, e) for s, e in spans) / len(ctx.jobs)


def read(ctx):
    return busy_ms_inside(ctx, "score_span")
