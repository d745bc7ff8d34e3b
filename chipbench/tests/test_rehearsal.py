"""The harness's control flow at a tiny size on the CPU, through the internal
rehearsal hook; and the command line, which prints no result without a chip."""
import pytest

from chipbench import harness
from chipbench.clock import PHASES, PhaseClock

from .conftest import small

BENCH = harness.load_benchmark()


def _run(cell_name, trace, seed=2**31 + 77, chips=None, **kw):
    cell = dict(harness.find_cell(BENCH, cell_name))
    if chips:
        cell["chips"] = chips
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    return harness.run_cell(BENCH, cell, seed, 0.5, trace, clock, rehearsal=small(cell_name), **kw), clock


@pytest.mark.parametrize("cell", ["kmeans_fit", "logreg_fit", "kmeans_fit_x4"])
def test_end_to_end_run(cell, capsys):
    result, clock = _run(cell, trace=False)
    assert [n for n, _ in clock.marks] == list(PHASES)
    wanted = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, cell), "end_to_end")}
    assert set(result["metrics"]) == wanted and result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0 and result["device"]["platform"] == "cpu"
    out = capsys.readouterr().out
    assert "PHASES " in out and "COMPARED " in out


@pytest.mark.parametrize("cell,iters", [("kmeans_fit", "fit.iters_per_job"), ("logreg_fit", "fit.iters_per_job.lbfgs")])
def test_traced_run_reports_per_layer_metrics_only(cell, iters):
    result, _ = _run(cell, trace=True)
    listed = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, cell), "per_layer")}
    assert result["metrics"] and set(result["metrics"]) <= listed
    assert "setup.cache_misses" in result["metrics"]    # no trace needed to read it
    assert result["metrics"][iters]["value"] > 0        # under the name this configuration reports it as
    assert "lloyd.mxu_share" not in result["metrics"]   # nothing to read without a device plane


def test_command_line_prints_no_result_without_a_chip(capsys):
    rc = harness.main(["--workload", "kmeans_fit", "--seed", "5", "--seconds", "0.2", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0
    assert not any(line.startswith("{") and "correct" in line for line in out.splitlines())
