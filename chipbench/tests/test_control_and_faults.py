"""The comparison that decides `correct`, shown to fail: the control (the reference
in the lower precision, in the program's place) at a size a test can hold, and runs
whose timed path is broken underneath."""
import pytest

from chipbench import control, harness
from chipbench.clock import PhaseClock

from .conftest import small

BENCH = harness.load_benchmark()


def _limits(cell_name, spec):
    cell = harness.find_cell(BENCH, cell_name)
    files = harness.cell_files(BENCH, cell)
    pair = harness.load_json(harness.ROOT, files["config"])["family"] + "." + harness.load_json(harness.ROOT, files["traffic"])["driver"]
    return {**harness.load_json(harness.ROOT, f"chipbench/subjects/{pair}.json")["limits"], **spec.get("limits", {})}


@pytest.mark.parametrize("cell,lower,number", [
    ("kmeans_fit", "fp8", "fixed_point_gap_quartile"),
    ("logreg_fit", "fp8", "score_gap"),
])
def test_control_fails_the_cells_limit_and_a_sound_run_does_not(cell, lower, number):
    spec = small(cell)
    r = control.readings(BENCH, dict(harness.find_cell(BENCH, cell), chips=1), 2**31 + 9, 0.2, lower, spec)
    limit = _limits(cell, spec)[number]
    sound = {c["name"]: c["value"] for c in r["sound"]}
    ctrl = {c["name"]: c["value"] for c in r["control"]}
    assert sound[number] <= limit < ctrl[number], (sound[number], limit, ctrl[number])
    assert all(c["ok"] for c in r["sound"]), r["sound"]


def _run(cell_name):
    cell = dict(harness.find_cell(BENCH, cell_name), chips=1)
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    return harness.run_cell(BENCH, cell, 2**31 + 3, 0.3, False, clock, rehearsal=small(cell_name))


def _failed(capsys):
    out = capsys.readouterr().out
    return {line.split('"name": "')[1].split('"')[0] for line in out.splitlines() if line.startswith("COMPARED ") and '"ok": false' in line}


def test_fit_that_returns_its_state_unchanged_is_not_correct(monkeypatch, capsys):
    """The solver broken underneath: Lloyd hands back the centres it was given."""
    from spark_rapids_ml_tpu.models import kmeans as program

    def stuck(X, w, centres0, mesh, max_iter, tol, chunk):
        import jax.numpy as jnp

        return centres0, jnp.int32(max_iter), jnp.float32(0.0)

    monkeypatch.setattr(program, "lloyd_iterations", stuck)
    result = _run("kmeans_fit")
    assert result["correct"] is False
    assert "fixed_point_gap_quartile" in _failed(capsys)


@pytest.mark.parametrize("fault", ["left_behind", "nan"])
def test_fit_with_a_fault_in_a_part_of_the_centres_is_not_correct(fault, monkeypatch, capsys):
    """A fault in a part of the centres (a tile of them never updated, or not a
    number): the lower quartile cannot see it; the worst centre does."""
    from spark_rapids_ml_tpu.models import kmeans as program

    real = program.lloyd_iterations

    def partly(X, w, centres0, mesh, max_iter, tol, chunk):
        centres, n_iter, cost = real(X, w, centres0, mesh, max_iter, tol, chunk)
        return centres.at[-2:].set(centres0[-2:] if fault == "left_behind" else float("nan")), n_iter, cost

    monkeypatch.setattr(program, "lloyd_iterations", partly)
    result = _run("kmeans_fit")
    assert result["correct"] is False
    failed = _failed(capsys)
    assert "fixed_point_gap_worst" in failed, failed
    if fault == "left_behind":
        assert "fixed_point_gap_quartile" not in failed, failed


def test_fit_that_stops_short_counts_every_job_as_failed(monkeypatch):
    real = harness.load_part

    def short(kind, name, root=harness.ROOT):
        mod = real(kind, name, root)
        if kind == "subjects":
            job = mod.job

            def short_job(ctx):
                run = job(ctx)
                return lambda: dict(run(), iters=ctx.config["expected_iters"] - 1)

            mod.job = short_job
        return mod

    monkeypatch.setattr(harness, "load_part", short)
    result = _run("kmeans_fit")
    assert result["correct"] is False and result["failed"] == result["attempted"] > 0


def test_logreg_fit_on_a_part_of_the_rows_is_not_correct(monkeypatch, capsys):
    """A part of the batch left out: the solver sees the second half's weights as 0."""
    from spark_rapids_ml_tpu.models import logistic_regression as program

    real = program.logistic_fit_kernel

    def half(X, y_enc, w, *rest):
        return real(X, y_enc, w.at[w.shape[0] // 2:].set(0.0), *rest)

    monkeypatch.setattr(program, "logistic_fit_kernel", half)
    result = _run("logreg_fit")
    assert result["correct"] is False
    assert "score_gap" in _failed(capsys)
