"""Device time, per job, inside the best model's refit (the configuration's
`trace.refit_span`, tuning.refit): logreg_fit's path, on all rows."""
from chipbench.harness import load_reader


def read(ctx):
    return load_reader("cv.score_ms_per_job").busy_ms_inside(ctx, "refit_span")
