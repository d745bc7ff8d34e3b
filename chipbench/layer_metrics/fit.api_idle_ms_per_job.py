"""Device idle inside srml.prepare and srml.finish, per job: the public API's own
host work (cache rule, frame, validation; telemetry snapshot, model object)."""
from chipbench.step_spans import idle_ms_per_job

SPANS = ("srml.prepare", "srml.finish")


def read(ctx):
    return idle_ms_per_job(ctx.trace, SPANS)
