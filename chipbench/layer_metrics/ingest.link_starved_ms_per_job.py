"""Milliseconds of a job's staging, first enqueue to last landing, in which no copy
was on its way: ingest.link_starved_us over ingest.link_stagings of the program's
landing journal (ingest.link_fed_share has what the journal is; one journaled
staging a job, two where a job refits).  What a deeper or differently cut pipeline
could take from ingest; 0 where the link is the bound.  Nothing at a program without
the journal, or one that journaled nothing."""
from chipbench import program


def read(ctx):
    c = program.counters()
    if not c.get("ingest.link_stagings"):
        return None
    return 1e-3 * c.get("ingest.link_starved_us", 0) / c["ingest.link_stagings"]
