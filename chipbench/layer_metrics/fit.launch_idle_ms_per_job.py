"""Device idle inside srml.fit.init, .solve and .wait, per job: the initial state's
and the solver's dispatch, and whatever of the wait the device does not fill."""
from chipbench.step_spans import idle_ms_per_job

SPANS = ("srml.fit.init", "srml.fit.solve", "srml.fit.wait")


def read(ctx):
    return idle_ms_per_job(ctx.trace, SPANS)
