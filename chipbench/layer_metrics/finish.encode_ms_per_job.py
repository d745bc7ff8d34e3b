"""Milliseconds of srml.finish.encode a job, on the trace's clock: the executor's
launcher packing what it returns, a base64 copy of every model attribute
(parallel/runner.encode_attrs, inside srml.finish): 3001 floats here, every node of
every tree for a forest.  Nothing where the trace has no such span: a program from
before it, a driver-local fit (which encodes nothing), a run without a trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    jobs = ctx.trace["spans"].get("job", [])
    spans = ctx.trace["spans"].get("srml.finish.encode", [])
    if not jobs or not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / len(jobs)
