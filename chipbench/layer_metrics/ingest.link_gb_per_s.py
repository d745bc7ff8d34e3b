"""Gigabytes a second over the host link while it is fed: ingest.link_bytes over
ingest.link_fed_us of the program's landing journal (ingest.link_fed_share has what
the journal is), the bytes of the pieces a job's staging sent over the time at least
one of them was on its way.  The link's own rate, where ingest.h2d_gb_per_s is the
rate at which ingest as a whole delivers (extraction and waiting included).  No share
of a peak: peaks.json has no host-link entry.  Nothing at a program without the
journal, or one that journaled nothing."""
from chipbench import program


def read(ctx):
    c = program.counters()
    if not c.get("ingest.link_stagings") or not c.get("ingest.link_fed_us"):
        return None
    return 1e-3 * c.get("ingest.link_bytes", 0) / c["ingest.link_fed_us"]
