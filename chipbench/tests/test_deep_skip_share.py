"""forest.deep_skip_share reads the program's counters: 0 for a program that
has no forest.deep_tiles (the parent of the PR that added it: it streams every
tile it covers), the share of tiles not streamed where it has, nothing when no
forest fit ran or none had a deep phase.  Its entry in `per_layer` is found by
NAME: entries are appended, and no position holds."""
import pytest

from chipbench import harness, program

NAME = "forest.deep_skip_share"
CELLS = ["rf_clf_fit"]


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"linreg.fits": 3}, None),
        ({"forest.fits": 4, "forest.gather_copy_fits": 4}, 0.0),
        ({"forest.fits": 2, "forest.reg_fits": 2, "forest.deep_tiles": 0}, None),
        ({"forest.fits": 4, "forest.deep_tiles": 1000, "forest.deep_tiles_kept": 1000}, 0.0),
        ({"forest.fits": 4, "forest.deep_tiles": 273600, "forest.deep_tiles_kept": 168000}, 100.0 * (1 - 168000 / 273600)),
        ({"forest.fits": 4, "forest.deep_tiles": 912, "forest.deep_tiles_kept": 684}, 25.0),
    ],
    ids=["no_fit", "another_family", "no_counters", "no_deep_phase", "nothing_skipped", "a_bootstrapped_forest", "a_quarter"],
)
def test_deep_skip_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    assert harness.load_reader(NAME).read(None) == share


def test_deep_skip_share_is_declared_for_the_classifiers_cell_alone():
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "L5 kernels",
        "moves": "fit_throughput", "workloads": CELLS,
    }
    for cell in bench["workloads"]:
        listed = entry in harness.metrics_for(bench, harness.find_cell(bench, cell["name"]), "per_layer")
        assert listed == (cell["name"] in CELLS)


def test_deep_skip_share_was_appended_and_changed_no_entry_before_it():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAME)
    assert names[at - 2:at] == ["fit.retraces_per_job", "fit.retraces_per_job.lbfgs"]
    assert [m["workloads"] for m in bench["per_layer"][at - 2:at]] == [
        ["kmeans_fit", "kmeans_fit_x4", "rf_clf_fit", "linreg_enet_fit", "rf_reg_fit"], ["logreg_fit"],
    ]
