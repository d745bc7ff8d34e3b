"""forest.unique_key_sort_share reads the program's counters: 0 for a program that
counts forest fits and has no forest.sort_fits (the parent of the PR that added
it: its sorts are stable, a third operand each), 100 where every fit that sorted
did so on the unique key alone, nothing when no forest fit ran or none had a deep
phase.  Its entry in `per_layer` is found by NAME: entries are appended, and no
position holds."""
import pytest

from chipbench import harness, program

NAME = "forest.unique_key_sort_share"
CELLS = ["rf_clf_fit", "rf_higgs_fit"]


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"linreg.fits": 3}, None),
        ({"forest.fits": 4, "forest.gather_copy_fits": 4, "forest.deep_tiles": 912}, 0.0),
        ({"forest.fits": 2, "forest.reg_fits": 2, "forest.sort_fits": 0, "forest.unique_key_sort_fits": 0}, None),
        ({"forest.fits": 4, "forest.sort_fits": 4, "forest.unique_key_sort_fits": 4, "forest.sort_operands": 72}, 100.0),
        ({"forest.fits": 4, "forest.sort_fits": 4}, 0.0),
        ({"forest.fits": 5, "forest.sort_fits": 4, "forest.unique_key_sort_fits": 3}, 75.0),
    ],
    ids=["no_fit", "another_family", "no_counters", "no_deep_phase", "every_fit", "stable_sorts_counted", "three_of_four"],
)
def test_unique_key_sort_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    assert harness.load_reader(NAME).read(None) == share


def test_unique_key_sort_share_is_declared_for_the_two_cells_that_sort():
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "L5 kernels",
        "moves": "fit_throughput", "workloads": CELLS,
    }
    for cell in bench["workloads"]:
        listed = entry in harness.metrics_for(bench, harness.find_cell(bench, cell["name"]), "per_layer")
        assert listed == (cell["name"] in CELLS)
    # the cells that sort are the cells forest.sort_ms_per_job reads
    sort_ms, = (m for m in bench["per_layer"] if m["name"] == "forest.sort_ms_per_job")
    assert sort_ms["workloads"] == CELLS and sort_ms["moves"] == entry["moves"]


def test_unique_key_sort_share_was_appended_after_the_entries_that_were_there():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NAME)
    assert names.count(NAME) == 1
    assert names[at - 2:at] == ["forest.subset_use_share", "forest.rowwork_ms_per_job"]
    assert all(m["workloads"] == ["rf_higgs_fit"] for m in bench["per_layer"][at - 2:at])
