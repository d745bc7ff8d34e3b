"""Of the feature batches the process's jobs took as ONE 2-D view of their buffer,
the share that went up on the cheap admission test, the view rule's proof of every
cell's address left to run while the solver does: the program's counter
ingest.deferred_batches (counted where a batch is admitted, beside
ingest.view_batches) over the view batches of the warm job and the window's jobs
(each job's own part, from the record its subject keeps).  Only a job defers, so the
jobs' batches are the base, and not the process's: the check's own staging of the
same batches once more asks the rule at once, as anyone outside a job does.  100
where every Arrow-made batch is admitted and proven under the solver; 0 for a
program without the counter whose jobs viewed a batch (the rule then ran inside
srml.ingest, the device waiting for it); less than 100 too where a proof failed and
a job staged again; nothing where no job viewed a batch."""
from chipbench import program


def read(ctx):
    jobs = [getattr(ctx, "warm_job", None)] + list(getattr(ctx, "jobs", None) or [])
    viewed = sum(j["ingest"]["counters"].get("ingest.view_batches", 0) for j in jobs if j and j.get("ingest"))
    return 100.0 * program.counters().get("ingest.deferred_batches", 0) / viewed if viewed else None
