"""The six entries that read the program's compile account (setup.trace_lower_s,
setup.backend_s, setup.import_s, setup.executables, fit.retraces_per_job and its
.lbfgs name): declared by NAME (entries are appended, and no position holds), a
number each in a traced rehearsal, the arithmetic on journals written by hand, and
nothing, without raising, over a program that keeps no journal (the driver lays
these files over the parent)."""
from types import SimpleNamespace

import pytest

from chipbench import harness, program
from chipbench.clock import PhaseClock

from .conftest import small

LAYER = "L3 executable cache"
CELLS = ["kmeans_fit", "logreg_fit", "kmeans_fit_x4", "rf_clf_fit", "linreg_enet_fit", "rf_reg_fit"]
PLAIN = [c for c in CELLS if c != "logreg_fit"]
ENTRIES = {
    "setup.trace_lower_s": ("s", "program_span", "setup_s", CELLS),
    "setup.backend_s": ("s", "program_span", "setup_s", CELLS),
    "setup.import_s": ("s", "program_counter", "setup_s", CELLS),
    "setup.executables": ("count", "program_counter", "setup_s", CELLS),
    "fit.retraces_per_job": ("count", "program_counter", "fit_throughput", PLAIN),
    "fit.retraces_per_job.lbfgs": ("count", "program_counter", "fit_throughput.lbfgs", ["logreg_fit"]),
}
SETUP = ("setup.trace_lower_s", "setup.backend_s", "setup.import_s", "setup.executables")


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry_is_declared_by_name_with_its_cells(name):
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == name)
    unit, source, moves, cells = ENTRIES[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source, "layer": LAYER,
        "moves": moves, "workloads": cells,
    }
    for cell in bench["workloads"]:
        listed = entry in harness.metrics_for(bench, cell, "per_layer")
        assert listed == (cell["name"] in cells)


def _ctx(marks, jobs=2, detail=None):
    clock = PhaseClock()
    for name, t in marks.items():
        clock.mark(name, t)
    return SimpleNamespace(clock=clock, jobs=[{}] * jobs, detail=detail)


def _with_journal(monkeypatch, events, counters=None):
    monkeypatch.setattr(program, "profiling", SimpleNamespace(compile_events=lambda: list(events)))
    monkeypatch.setattr(program, "counters", lambda: dict(counters or {}))


MARKS = {"devices": 100.0, "window_start": 120.0, "window_end": 130.0}


def test_two_overlapping_workers_count_their_overlap_once(monkeypatch):
    _with_journal(monkeypatch, [
        ("lower", "a", 101.0, 104.0, "srml-precompile-0"),
        ("lower", "b", 103.0, 106.0, "srml-precompile-1"),      # 1 s of it beside a's
        ("backend", "a", 104.0, 108.0, "srml-precompile-0"),    # 2 s of it beside b's lowering
        ("backend", "b", 106.0, 107.0, "srml-precompile-1"),    # all of it beside a's load
    ])
    detail = {}
    ctx = _ctx(MARKS, detail=detail)
    assert harness.load_reader("setup.trace_lower_s").read(ctx) == pytest.approx(5.0)
    assert harness.load_reader("setup.backend_s").read(ctx) == pytest.approx(2.0)
    assert harness.load_reader("setup.executables").read(ctx) == 2
    assert detail["compile"]["wall_s"] == {"trace_lower": pytest.approx(5.0), "backend": pytest.approx(2.0)}
    assert detail["compile"]["thread_s"] == {"lower": pytest.approx(6.0), "backend": pytest.approx(5.0)}
    assert detail["compile"]["top"][0] == ["a", "backend", 1, pytest.approx(4.0)]


def test_what_lies_outside_set_up_is_cut_off_or_left_out(monkeypatch):
    _with_journal(monkeypatch, [
        ("trace", "f", 101.0, 105.0, "MainThread"),
        ("backend", "f", 118.0, 122.0, "MainThread"),           # runs into the window: cut there, not an executable of set-up
        ("trace", "g", 125.0, 125.5, "MainThread"),             # a window job traced again
        ("lower", "g", 125.5, 126.0, "MainThread"),
        ("trace", "ref", 131.0, 140.0, "MainThread"),           # the check's reference: neither set-up nor window
    ])
    detail = {}
    ctx = _ctx(MARKS, jobs=4, detail=detail)
    assert harness.load_reader("setup.trace_lower_s").read(ctx) == pytest.approx(4.0)
    assert harness.load_reader("setup.backend_s").read(ctx) == pytest.approx(2.0)
    assert harness.load_reader("setup.executables").read(ctx) == 0
    assert detail["compile"]["top"] == [["f", "trace", 1, pytest.approx(4.0)], ["f", "backend", 1, pytest.approx(2.0)]]
    assert detail["compile"]["thread_s"]["trace"] == pytest.approx(4.0)
    assert harness.load_reader("fit.retraces_per_job").read(ctx) == 0.5
    assert harness.load_reader("fit.retraces_per_job.lbfgs").read(ctx) == 0.5
    assert detail["retraced"] == {"trace g": [1, pytest.approx(0.5)], "lower g": [1, pytest.approx(0.5)]}


def test_import_seconds_follow_the_counter(monkeypatch):
    _with_journal(monkeypatch, [], {"import.us": 870_000})
    assert harness.load_reader("setup.import_s").read(_ctx(MARKS)) == pytest.approx(0.87)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_program_without_the_account_reads_nothing(monkeypatch, name):
    monkeypatch.setattr(program, "profiling", SimpleNamespace())
    monkeypatch.setattr(program, "counters", lambda: {"forest.fits": 3})
    assert harness.load_reader(name).read(_ctx(MARKS, detail={})) is None


@pytest.mark.parametrize("cell", ["kmeans_fit", "logreg_fit"])
def test_a_traced_rehearsal_reads_every_entry(cell, capsys):
    import jax

    jax.clear_caches()      # as a fresh process: an earlier rehearsal of the suite built these shapes
    bench = harness.load_benchmark()
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    result = harness.run_cell(bench, harness.find_cell(bench, cell), 2**31 + 34, 0.5, True, clock, rehearsal=small(cell))
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    retraces = "fit.retraces_per_job" + (".lbfgs" if cell == "logreg_fit" else "")
    assert set(SETUP) | {retraces} <= set(metrics)
    # a traced run reports no setup_s: that span of its phase clock is what it is
    setup = clock.span("devices", "window_start")
    assert 0 < metrics["setup.trace_lower_s"] and 0 <= metrics["setup.backend_s"]
    assert metrics["setup.trace_lower_s"] + metrics["setup.backend_s"] <= setup
    assert metrics["setup.import_s"] > 0
    assert metrics["setup.executables"] >= 1
    assert metrics[retraces] == 0, "a window job of a rehearsal built nothing again"
    detail = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("DETAIL "))
    assert '"compile"' in detail and '"wall_s"' in detail and '"top"' in detail
