"""Of the time from a staging's first enqueue to its last landing, the share in which
at least one host-to-device copy was on its way: 100 * ingest.link_fed_us over
(ingest.link_fed_us + ingest.link_starved_us), the program's landing journal
(core.stage_dense_batches inside a fit job: a piece's `opened` where its copy is
enqueued, its `landed` stamped by the watcher thread that blocks on nothing else, the
union of the pieces' intervals taken once a staging).  What is missing from 100 a
better pipeline can take (nothing was in flight while the host extracted, placed or
woke up); what is not, only overlap with the solver or fewer bytes can.  The process's
counters are the jobs' alone (only a job journals: the warm job and the window's, one
regime; the check's own staging of the same batches is not journaled).

Beside it on the DETAIL line: link_depth, ingest.link_flight_us over
ingest.link_fed_us, the copies in flight while any was (near 2 where the pieces land
in pairs, near 1 where one at a time), and under link_per_staging the stagings and
the other five counters a staging.  Nothing at a program without the journal, or one
that journaled nothing."""
from chipbench import program


def read(ctx):
    c = program.counters()
    stagings, fed = c.get("ingest.link_stagings", 0), c.get("ingest.link_fed_us", 0)
    if not stagings or not fed:
        return None
    if isinstance(ctx.detail, dict):
        ctx.detail["link_depth"] = c.get("ingest.link_flight_us", 0) / fed
        per = {k: c.get("ingest.link_" + k, 0) / stagings for k in ("pieces", "bytes", "fed_us", "starved_us", "flight_us")}
        ctx.detail["link_per_staging"] = {"stagings": stagings, **per}
    return 100.0 * fed / (fed + c.get("ingest.link_starved_us", 0))
