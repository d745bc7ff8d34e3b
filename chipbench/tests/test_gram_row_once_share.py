"""linreg.gram_row_once_share reads the program's counters: 100 when the rows the
Gram scans sent through their products are the table's rows (every row once), 93.9
for 400,000 rows walked as 13 chunks of 32,768 (the clamped last chunk of the PR's
parent, by the shapes), nothing for a program that has no such counters (that
parent) or ran no dense LinearRegression fit.  Its entry in `per_layer` is found by
NAME and is the last one: entries are appended, and no other position holds."""
import pytest

from chipbench import harness, program

NAME = "linreg.gram_row_once_share"


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"linreg.fits": 38, "linreg.gram_triangle_fits": 38, "cd.fits": 38}, None),
        ({"linreg.fits": 66, "linreg.gram_rows": 66 * 400_000, "linreg.gram_rows_multiplied": 66 * 400_000}, 100.0),
        ({"linreg.fits": 62, "linreg.gram_rows": 62 * 400_000, "linreg.gram_rows_multiplied": 62 * 13 * 32768}, 93.9),
        ({"linreg.gram_rows": 100_000, "linreg.gram_rows_multiplied": 4 * 32768}, 76.3),
    ],
    ids=["no_fit", "no_counter", "every_row_once", "clamped_last_chunk", "clamped_on_a_quarter_shard"],
)
def test_gram_row_once_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    read = harness.load_reader(NAME).read(None)
    assert read == share if share in (None, 100.0) else read == pytest.approx(share, abs=0.05)


def test_gram_row_once_share_is_declared_last_and_for_the_linreg_cell_alone():
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "L5 kernels",
        "moves": "fit_throughput", "workloads": ["linreg_enet_fit"],
    }
    assert bench["per_layer"][-1] == entry
    cells = [c["name"] for c in bench["workloads"]]
    for cell in cells:
        listed = entry in harness.metrics_for(bench, harness.find_cell(bench, cell), "per_layer")
        assert listed == (cell == "linreg_enet_fit")


def test_the_entry_changed_nothing_that_stood_before_it():
    """PR 49's entries, by name and in their order, right before this one; the cell's
    other entries as they were declared."""
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-5:] == ["ingest.link_fed_share", "ingest.link_gb_per_s", "ingest.link_starved_ms_per_job", "finish.encode_ms_per_job", NAME]
    assert len(names) == len(set(names))
    own = [m["name"] for m in bench["per_layer"] if m.get("workloads") == ["linreg_enet_fit"]]
    assert own == ["linreg.gram_ms_per_job", "linreg.gram_mxu_share", "cd.ms_per_job", "linreg.gram_triangle_share", NAME]
