"""Device idle inside srml.fit.fetch and srml.fit.pack, per job: the result's way
to the host and into the model's attribute dict."""
from chipbench.step_spans import idle_ms_per_job

SPANS = ("srml.fit.fetch", "srml.fit.pack")


def read(ctx):
    return idle_ms_per_job(ctx.trace, SPANS)
