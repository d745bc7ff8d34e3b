"""lbfgs.one_pass_share reads the program's counters: 0 for a program that has
no lbfgs.one_pass_fits (the parent of the PR that added it), 100 when every fit
counted there, nothing when no L-BFGS fit ran."""
import pytest

from chipbench import harness, program


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"lbfgs.fits": 5, "lbfgs.evals": 1040}, 0.0),
        ({"lbfgs.fits": 5, "lbfgs.one_pass_fits": 5}, 100.0),
        ({"lbfgs.fits": 4, "lbfgs.one_pass_fits": 1}, 25.0),
    ],
    ids=["no_fit", "no_counter", "every_fit", "one_of_four"],
)
def test_one_pass_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    assert harness.load_reader("lbfgs.one_pass_share").read(None) == share


def test_one_pass_share_is_declared_for_the_lbfgs_cell_alone():
    bench = harness.load_benchmark()
    entry = bench["per_layer"][-1]
    assert entry["name"] == "lbfgs.one_pass_share" and entry["workloads"] == ["logreg_fit"]
    cell = harness.find_cell(bench, "logreg_fit")
    assert entry in harness.metrics_for(bench, cell, "per_layer")
    assert entry not in harness.metrics_for(bench, harness.find_cell(bench, "kmeans_fit"), "per_layer")
