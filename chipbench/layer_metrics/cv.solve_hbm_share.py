"""The sweep's solve against its HBM roofline: ONE read of X a scan
(opcount/cv_logreg.py: the least any implementation needs, so a one-pass lane
kernel raises the share and does not make the count stale) at the chip's HBM
peak, over cv.solve_ms_per_job.  Autodiff over the lane einsum reads X twice a
scan: at most 50% today, and never above 100%."""
from chipbench.harness import load_reader
from chipbench.opcount import cv_logreg


def read(ctx):
    ms = load_reader("cv.solve_ms_per_job").read(ctx)
    scans = load_reader("cv.scans_per_job").read(ctx)
    if not ms or not scans or not ctx.peaks:
        return None
    d = ctx.config["data"]
    least = cv_logreg.solve_bytes(d["rows_per_chip"], d["cols"], scans) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (1e-3 * ms)
