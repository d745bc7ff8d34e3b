"""linreg_enet_fit through the unchanged harness at a test's size (its sizes are
here, not in conftest.py), a timed path broken underneath, its per-layer readers'
arithmetic and what they return where there is nothing to read, and that the cell
came as new files and appended names only."""
from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench.clock import PhaseClock
from chipbench.opcount import linreg as opcount

BENCH = harness.load_benchmark()
CELL = "linreg_enet_fit"
SMALL = {"data": {"rows_per_chip": 4096, "cols": 64, "informative": 4}}
OWN = ["linreg.gram_ms_per_job", "linreg.gram_mxu_share", "cd.ms_per_job"]
SHARED = [
    "fit.host_ms_per_job", "fit.iters_per_job", "fit.device_idle_share", "fit.api_idle_ms_per_job", "fit.ingest_idle_ms_per_job",
    "fit.launch_idle_ms_per_job", "fit.result_idle_ms_per_job", "trace.device_lead_ms",
]


def _run(trace, seed=2**31 + 77):
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    return harness.run_cell(BENCH, dict(harness.find_cell(BENCH, CELL)), seed, 0.3, trace, clock, rehearsal=SMALL)


def test_end_to_end_run(capsys):
    result = _run(False)
    wanted = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, CELL), "end_to_end")}
    assert set(result["metrics"]) == wanted == {"fit_throughput", "setup_s"}
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert '"sweeps": [10]' in capsys.readouterr().out


def test_traced_run_reports_the_sweeps_and_nothing_of_the_device():
    result = _run(True)
    listed = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, CELL), "per_layer")}
    assert set(OWN + SHARED) <= listed and set(result["metrics"]) <= listed
    assert result["correct"] and result["metrics"]["fit.iters_per_job"]["value"] == 10
    assert "setup.cache_misses" in result["metrics"]
    assert not set(OWN) & set(result["metrics"])      # no device plane on the CPU


@pytest.mark.parametrize("broken,fails", [("stuck", "coef_gap"), ("early", "wrong_iters")])
def test_a_broken_timed_path_is_not_correct(broken, fails, monkeypatch, capsys):
    """The solver broken underneath the public fit: it hands back the zeros it
    started from, or stops a sweep early."""
    from spark_rapids_ml_tpu.models import linear_regression as program

    real = program.solve_elasticnet_cd

    def faulty(stats, alpha, l1_ratio, **kw):
        if broken == "early":
            return real(stats, alpha, l1_ratio, **dict(kw, max_iter=kw["max_iter"] - 1))
        coef, intercept, n_iter = real(stats, alpha, l1_ratio, **kw)
        return coef * 0.0, intercept, n_iter

    monkeypatch.setattr(program, "solve_elasticnet_cd", faulty)
    result = _run(False)
    out = capsys.readouterr().out
    failed = {line.split('"name": "')[1].split('"')[0] for line in out.splitlines() if line.startswith("COMPARED ") and '"ok": false' in line}
    assert result["correct"] is False and fails in failed, failed
    assert (result["failed"] == result["attempted"]) == (broken == "early")


def _ctx(modules=None, jobs=2):
    config = harness.load_json(harness.ROOT, "chipbench/configs/linreg-d3000-enet-iter10.json")
    trace = None if modules is None else {"device_ops": [], "modules": modules}
    return SimpleNamespace(trace=trace, jobs=[{}] * jobs, config=config, detail={}, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_trace_readers_find_their_modules_by_name(monkeypatch):
    from chipbench import program

    monkeypatch.setattr(program, "counters", lambda: {"cd.fits": 3, "cd.coordinates": 90000})
    ctx = _ctx({"jit_linreg_sufficient_stats(12)": 0.5, "jit_solve_elasticnet_cd(14)": 0.012, "jit_other(3)": 9.0})
    read = lambda name: harness.load_reader(name).read(ctx)
    assert read("linreg.gram_ms_per_job") == pytest.approx(250.0)
    assert read("linreg.gram_mxu_share") == pytest.approx(100 * 2 * 2.0 * 400000 * 3000**2 / 197e12 / 0.5)
    assert read("cd.ms_per_job") == pytest.approx(6.0)
    assert ctx.detail["cd.us_per_coordinate"] == pytest.approx(1e6 * 0.012 / 60000)
    assert ctx.detail["cd.row_hbm_share"] == pytest.approx(100 * 60000 * 12000 / 819e9 / 0.012)


@pytest.mark.parametrize("name", OWN)
def test_readers_return_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    """A program whose modules go by other names, or a run without a trace: no
    number, and no error."""
    from chipbench import program

    monkeypatch.setattr(program, "counters", lambda: {"precompile.compile": 3})
    for ctx in (_ctx(None), _ctx({"jit_other(1)": 1.0})):
        assert harness.load_reader(name).read(ctx) is None


def test_operation_count_of_the_cell():
    assert opcount.gram_flops(400000, 3000) == 2.0 * 400000 * 3000 * 3000 == 7.2e12
    assert 6 * opcount.gram_flops(400000, 3000) / 197e12 == pytest.approx(0.2193, rel=1e-3)      # six passes at the peak: seconds
    assert opcount.cd_row_bytes(3000, 30000) == 3000 * 4 * 30000


def test_the_cell_came_as_new_files_and_appended_names():
    """chipbench/tests/test_extend.py's rule, held for this cell: its entries are
    the last of their lists (the three per-layer metrics after
    forest.search_ms_per_job), and its name is appended to the lists it joins."""
    assert BENCH["configs"][-1]["name"] == "linreg-d3000-enet-iter10" and BENCH["configs"][-1]["reduced"] == ["rows"]
    assert BENCH["workloads"][-1] == {**BENCH["workloads"][-1], "name": CELL, "traffic": "fit-loop", "chips": 1}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-4:] == ["forest.search_ms_per_job"] + OWN
    assert all(m["workloads"] == [CELL] and m["moves"] == "fit_throughput" for m in BENCH["per_layer"][-3:])
    joined = [m for m in BENCH["end_to_end"] + BENCH["per_layer"][:-3] if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in joined) == sorted(["fit_throughput"] + SHARED)
    assert all(m["workloads"][-1] == CELL for m in joined)
