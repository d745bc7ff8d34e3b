"""ingest.link_fed_share, ingest.link_gb_per_s, ingest.link_starved_ms_per_job and
finish.encode_ms_per_job (PR 49): their entries in BENCHMARK.json by NAME, their
arithmetic on made-up counters and a made-up trace summary, what they return where
there is nothing to read (a program from before the landing journal or the encode's
span reads nothing, and raises nothing), and logreg_exec_fit through the unchanged
harness at a test's size: the journal is the jobs' alone, one staging a job, the
table's bytes each, the check's own staging of the same batches not in it."""
import os
from types import SimpleNamespace

import pytest

from chipbench import harness, program
from chipbench.tests import test_logreg_exec_cell as cell

FED, RATE, STARVED, ENCODE = "ingest.link_fed_share", "ingest.link_gb_per_s", "ingest.link_starved_ms_per_job", "finish.encode_ms_per_job"
COUNTED = (FED, RATE, STARVED)
LAYER = "L1 public API and L2 ingest and dispatch"
# seven jobs as the cell runs them: 40 pieces of 120 MB a staging, 430 ms from first enqueue to last landing
JOURNAL = {
    "ingest.link_stagings": 7, "ingest.link_pieces": 280, "ingest.link_bytes": 7 * 4_800_000_000,
    "ingest.link_fed_us": 7 * 420_000, "ingest.link_starved_us": 7 * 10_000, "ingest.link_flight_us": 7 * 780_000,
}


def _ctx(trace=None):
    return SimpleNamespace(jobs=[], warm_job=None, detail={}, trace=trace)


def _counters(monkeypatch, counters):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))


@pytest.mark.parametrize("name,unit,better,source", [
    (FED, "%", "higher", "program_counter"), (RATE, "GB/s", "higher", "program_counter"),
    (STARVED, "ms", "lower", "program_counter"), (ENCODE, "ms", "lower", "program_span"),
])
def test_entry_is_declared_by_name_for_the_executors_cell_alone(name, unit, better, source):
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": LAYER,
                     "moves": "fit_throughput.lbfgs", "workloads": [cell.CELL]}
    for w in bench["workloads"]:
        assert (entry in harness.metrics_for(bench, w, "per_layer")) == (w["name"] == cell.CELL)
    assert os.path.isfile(os.path.join(harness.ROOT, "chipbench", "layer_metrics", name + ".py"))


def test_the_four_were_appended_behind_the_entries_that_were_there():
    """By name: they follow PR 48's two, in the issue's order, and every entry that stood before stands once."""
    names = [m["name"] for m in harness.load_benchmark()["per_layer"]]
    assert len(set(names)) == len(names)
    places = [names.index(n) for n in ("ingest.deferred_check_share", "ingest.verify_ms_per_job", FED, RATE, STARVED, ENCODE)]
    assert places == sorted(places) and max(names.index(n) for n in cell.OWN) < places[0]
    # ingest.h2d_gb_per_s, which times the same copies from outside, stays until a benchmark issue retires it
    assert "ingest.h2d_gb_per_s" in names


def test_the_counter_readers_take_the_program_through_chipbench_program_alone():
    for name in COUNTED:
        with open(os.path.join(harness.ROOT, "chipbench", "layer_metrics", name + ".py")) as f:
            imports = [line.strip() for line in f if line.startswith(("import ", "from "))]
        assert imports == ["from chipbench import program"], name


def test_readers_reduce_the_journals_counters(monkeypatch):
    _counters(monkeypatch, JOURNAL)
    ctx = _ctx()
    assert harness.load_reader(FED).read(ctx) == pytest.approx(100.0 * 420 / 430)
    assert harness.load_reader(RATE).read(ctx) == pytest.approx(4.8 / 0.420)          # the link's own rate while fed
    assert harness.load_reader(STARVED).read(ctx) == pytest.approx(10.0)
    assert ctx.detail["link_depth"] == pytest.approx(780 / 420)
    assert ctx.detail["link_per_staging"] == {"stagings": 7, "pieces": 40.0, "bytes": 4.8e9, "fed_us": 420_000.0, "starved_us": 10_000.0, "flight_us": 780_000.0}
    # a job that refitted journaled two stagings: the per-job reading is per journaled staging
    _counters(monkeypatch, {**JOURNAL, "ingest.link_stagings": 8})
    assert harness.load_reader(STARVED).read(_ctx()) == pytest.approx(70.0 / 8)
    # a link that was never dry reads 100 and 0, not nothing
    _counters(monkeypatch, {k: v for k, v in JOURNAL.items() if k != "ingest.link_starved_us"})
    assert harness.load_reader(FED).read(_ctx()) == 100.0 and harness.load_reader(STARVED).read(_ctx()) == 0.0


@pytest.mark.parametrize("counters", [
    {},                                                                 # a program from before the journal
    {"ingest.h2d_bytes": 4_803_200_000, "ingest.view_batches": 280},    # its other counters are no journal
    {"ingest.link_stagings": 0},                                        # a journal that was never kept
], ids=["no_counters", "other_counters", "no_stagings"])
def test_the_counter_readers_return_nothing_without_a_journal(monkeypatch, counters):
    _counters(monkeypatch, counters)
    for name in COUNTED:
        ctx = SimpleNamespace(detail={}, trace=None)
        assert harness.load_reader(name).read(ctx) is None and ctx.detail == {}, name


def test_encode_ms_is_the_spans_length_a_job_and_nothing_without_the_span():
    read = harness.load_reader(ENCODE).read
    spans = {"job": [(0.0, 1.8), (1.8, 3.6)], "srml.finish": [(1.795, 1.8), (3.5952, 3.6)], "srml.finish.encode": [(1.7990, 1.7992), (3.5990, 3.5991)]}
    assert read(_ctx({"spans": spans})) == pytest.approx(0.15)
    assert read(_ctx({"spans": {k: v for k, v in spans.items() if k != "srml.finish.encode"}})) is None      # the parent; a driver-local fit
    assert read(_ctx({"spans": {}})) is None and read(_ctx()) is None


def test_a_traced_run_reports_the_three_counter_metrics_and_no_span_off_the_chip(capsys):
    before = program.counters()
    result = cell._run(True)
    moved = {k: v - before.get(k, 0) for k, v in program.counters().items() if k.startswith("ingest.link_")}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] and set(COUNTED) <= set(got)
    assert 0.0 < got[FED] <= 100.0 and got[RATE] > 0.0 and got[STARVED] >= 0.0
    assert ENCODE not in got                    # the trace's clock: no device plane on the CPU, so no summary
    # a staging a job (the warm one and the window's), the table's bytes each; the check staged the same 7
    # batches once more outside any job, and that staging is in ingest.h2d_bytes and not in the journal
    jobs = result["attempted"] + 1
    table = 424 * 24 * 4
    assert moved["ingest.link_stagings"] == jobs and moved["ingest.link_bytes"] == jobs * table
    assert moved["ingest.link_pieces"] >= 7 * jobs and moved["ingest.link_flight_us"] >= moved["ingest.link_fed_us"] > 0
    assert program.counters()["ingest.h2d_bytes"] - before.get("ingest.h2d_bytes", 0) >= (jobs + 1) * table
    out = capsys.readouterr().out
    detail = next(line for line in out.splitlines() if line.startswith("DETAIL "))
    assert '"link_depth": ' in detail and '"link_per_staging": {"stagings": ' in detail
