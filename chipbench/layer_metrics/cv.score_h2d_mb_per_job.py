"""Megabytes of rows that went up to the device for scoring, a job: the program's
counter tuning.score.h2d_bytes over the sweeps the process ran.  0 on a
device-resident frame, and the table's gigabytes the day scoring goes back through
the host.  Nothing where the program does not count the rows it scores
(tuning.score.rows)."""
from chipbench import program
from chipbench.harness import load_reader


def read(ctx):
    counters = program.counters()
    n = load_reader("cv.scans_per_job").sweeps(ctx, counters)
    if not n or not counters.get("tuning.score.rows"):
        return None
    return counters.get("tuning.score.h2d_bytes", 0) / n / 1e6
