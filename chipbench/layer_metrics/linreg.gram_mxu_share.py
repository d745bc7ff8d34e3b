"""The Gram product X'X counted once (opcount/linreg.py, which says what its
ceiling is at the precision the program computes it in) at the chip's bf16 peak,
over the device time of the sufficient-statistics module."""
from chipbench.harness import load_reader
from chipbench.opcount import linreg


def read(ctx):
    seconds = load_reader("linreg.gram_ms_per_job").seconds(ctx, "gram_module")
    if not seconds or not ctx.peaks:
        return None
    d = ctx.config["data"]
    flops = len(ctx.jobs) * linreg.gram_flops(d["rows_per_chip"], d["cols"])
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / seconds
