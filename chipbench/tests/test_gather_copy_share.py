"""forest.gather_copy_share reads the program's counters: 0 for a program that
has no forest.gather_copy_fits (the parent of the PR that added it), 100 when
every fit counted there, nothing when no forest fit ran.  Its entry in
`per_layer` is found by NAME: entries are appended, and no position holds."""
import pytest

from chipbench import harness, program

NAME = "forest.gather_copy_share"
CELLS = ["rf_clf_fit", "rf_reg_fit"]


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"linreg.fits": 3}, None),
        ({"forest.fits": 4, "forest.reg_fits": 4}, 0.0),
        ({"forest.fits": 4, "forest.gather_copy_fits": 4}, 100.0),
        ({"forest.fits": 4, "forest.gather_copy_fits": 3}, 75.0),
    ],
    ids=["no_fit", "another_family", "no_counter", "every_fit", "three_of_four"],
)
def test_gather_copy_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    assert harness.load_reader(NAME).read(None) == share


def test_gather_copy_share_is_declared_for_the_two_forest_cells():
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "L5 kernels",
        "moves": "fit_throughput", "workloads": CELLS,
    }
    for cell in bench["workloads"]:
        listed = entry in harness.metrics_for(bench, harness.find_cell(bench, cell["name"]), "per_layer")
        assert listed == (cell["name"] in CELLS)


def test_gather_copy_share_was_appended_and_changed_no_entry_before_it():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAME)
    assert names[at - 2:at] == ["forest_reg.hist_mxu_share", "forest_reg.gather_ms_per_job"]
    assert all(m["workloads"] == ["rf_reg_fit"] for m in bench["per_layer"][at - 2:at])
