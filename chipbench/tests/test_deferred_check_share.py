"""ingest.deferred_check_share and ingest.verify_ms_per_job (PR 48): their arithmetic
on made-up records, what they return where there is nothing to read (a program from
before the deferred proof reads 0 and nothing), their entries in BENCHMARK.json by
NAME, and logreg_exec_fit through the unchanged harness at a test's size: every batch
of every job admitted and proven under the solver, the check's own staging of the
same batches not counted against them."""
from types import SimpleNamespace

import pytest

from chipbench import harness, program
from chipbench.tests import test_logreg_exec_cell as cell

SHARE, VERIFY = "ingest.deferred_check_share", "ingest.verify_ms_per_job"
LAYER = "L1 public API and L2 ingest and dispatch"


def _ctx(jobs, warm=None, trace=None):
    return SimpleNamespace(jobs=jobs, warm_job=warm, detail={}, trace=trace)


@pytest.mark.parametrize(
    "counters,jobs,warm,share",
    [
        ({"ingest.deferred_batches": 240, "ingest.view_batches": 280}, [cell._job()] * 5, cell._job(), 100.0),
        ({"ingest.view_batches": 280}, [cell._job()] * 5, cell._job(), 0.0),          # the parent: the rule ran in srml.ingest
        ({"ingest.deferred_batches": 240}, [cell._job()] * 4 + [cell._job(view=79, stacked=1)], cell._job(), 100.0 * 240 / 279),
        ({"ingest.deferred_batches": 80}, [cell._job()] * 2, None, 100.0),
        ({}, [cell._job(view=0, stacked=40)], cell._job(view=0, stacked=40), None),
        ({"ingest.deferred_batches": 7}, [{"iters": 200}], {"iters": 200}, None),     # another cell's jobs
        ({}, [], None, None),
    ],
    ids=["every_batch", "no_counter", "a_job_refitted", "no_warm_record", "nothing_viewed", "no_records", "no_jobs"],
)
def test_deferred_check_share_is_the_counter_over_the_jobs_own_view_batches(monkeypatch, counters, jobs, warm, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    got = harness.load_reader(SHARE).read(_ctx(jobs, warm))
    assert got == (pytest.approx(share) if share is not None else None)
    assert harness.load_reader(SHARE).read(SimpleNamespace(detail={}, trace=None)) is None       # a window that never ran


def test_verify_ms_is_the_spans_length_a_job_and_nothing_without_the_span():
    read = harness.load_reader(VERIFY).read
    spans = {"job": [(0.0, 1.8), (1.8, 3.6)], "srml.ingest.verify": [(0.5, 1.2), (2.3, 3.02)], "srml.fit.wait": [(1.2, 1.79), (3.02, 3.59)]}
    assert read(_ctx([], trace={"spans": spans})) == pytest.approx(710.0)
    assert read(_ctx([], trace={"spans": {k: v for k, v in spans.items() if k != "srml.ingest.verify"}})) is None
    assert read(_ctx([], trace={"spans": {}})) is None and read(_ctx([])) is None


def test_the_two_entries_are_declared_for_the_executors_cell_alone():
    bench = harness.load_benchmark()
    for name, unit, better, source in ((SHARE, "%", "higher", "program_counter"), (VERIFY, "ms", "lower", "program_span")):
        entry, = (m for m in bench["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": LAYER,
                         "moves": "fit_throughput.lbfgs", "workloads": [cell.CELL]}
        for w in bench["workloads"]:
            assert (entry in harness.metrics_for(bench, w, "per_layer")) == (w["name"] == cell.CELL)
    names = [m["name"] for m in bench["per_layer"]]
    assert max(names.index(n) for n in cell.OWN) < names.index(SHARE) < names.index(VERIFY)


def test_a_traced_run_reads_every_batch_deferred_and_no_span_off_the_chip():
    before = program.counters()
    result = cell._run(True)
    moved = {k: v - before.get(k, 0) for k, v in program.counters().items()}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] and got[SHARE] == 100.0 and got["ingest.view_share"] == 100.0
    assert VERIFY not in got                    # the trace's clock: no device plane on the CPU, so no summary
    # the check staged the same 7 batches once more outside any job: viewed there, and not deferred
    jobs = result["attempted"] + 1
    assert moved["ingest.deferred_batches"] == 7 * jobs and moved["ingest.view_batches"] == 7 * (jobs + 1)
    assert not moved.get("ingest.refits") and not moved.get("ingest.refuted_batches")
