"""Milliseconds of srml.ingest.verify a job, on the trace's clock: the view rule's
loop over every cell of every batch the job's ingest admitted on the cheap test,
run by the fit function's fetch between queueing the result's copies and blocking
on the solver.  It costs the job nothing while it is shorter than the solver
(srml.fit.wait stays above 0 and fit.result_idle_ms_per_job does not rise); the
milliseconds that ingest.extract_ms_per_job lost are here.  Nothing where the
trace has no such span: a program whose rule runs inside srml.ingest, a run
without a trace, a job that admitted nothing."""


def read(ctx):
    if ctx.trace is None:
        return None
    jobs = ctx.trace["spans"].get("job", [])
    spans = ctx.trace["spans"].get("srml.ingest.verify", [])
    if not jobs or not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(jobs)
