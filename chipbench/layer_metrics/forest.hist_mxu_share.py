"""The histogram kernels' one-hot products (opcount/forest.py: a lower bound,
which says what it counts and leaves out) at the chip's bf16 peak, over those
kernels' self time in the trace."""
from chipbench.harness import load_reader
from chipbench.opcount import forest


def read(ctx):
    seconds = load_reader("forest.hist_ms_per_job").seconds(ctx, "hist_ops")
    if not seconds or not ctx.peaks:
        return None
    d, p = ctx.config["data"], ctx.config["estimator"]["params"]
    features = max(1, int(d["cols"] ** 0.5))      # auto = sqrt for a classifier of several trees
    flops = forest.hist_flops(d["rows_per_chip"], p["numTrees"], features, p["maxBins"], d["classes"], p["maxDepth"])
    return 100.0 * len(ctx.jobs) * flops / ctx.peaks["bf16_flops_per_s"] / seconds
