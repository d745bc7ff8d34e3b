"""Device idle inside srml.ingest, per job: building the fit's inputs, every
host-to-device copy among them."""
from chipbench.step_spans import idle_ms_per_job

SPANS = ("srml.ingest",)


def read(ctx):
    return idle_ms_per_job(ctx.trace, SPANS)
