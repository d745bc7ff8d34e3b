"""Device time, per job, of the deep phase's payload sorts: self time of the
window's `sort` operations (the configuration's `trace.sort_ops` names the
opcode; a forest fit has no other sort inside the window but the bin edges'
quantile sort of a 2,778-row sample, which is counted with them)."""
from chipbench.harness import load_reader


def read(ctx):
    s = load_reader("forest.hist_ms_per_job").seconds(ctx, "sort_ops", by_op=True)
    return None if s is None else 1e3 * s / len(ctx.jobs)
