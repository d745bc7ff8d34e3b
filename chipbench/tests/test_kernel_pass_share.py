"""lloyd_tall.kernel_pass_share reads the program's counters: 0 for a program that
counted tall fits and has no lloyd.tall_kernel_fits (the parent of the PR that added
it: its update pass is XLA's two product fusions a block), 100 where every tall fit's
update passes went through the one-read Pallas kernel, nothing where no tall fit ran;
and the cell's traced run at a test's size reports 100 through the unchanged harness.
Its entry in `per_layer` is found by NAME: entries are appended, and no position holds."""
import pytest

from chipbench import harness, program
from chipbench.clock import PhaseClock

NAME = "lloyd_tall.kernel_pass_share"
CELL = "kmeans_tall_fit"


@pytest.mark.parametrize(
    "counters,share",
    [
        ({}, None),
        ({"lloyd.fits": 3}, None),
        ({"lloyd.tall_fits": 5, "lloyd.table_bytes": 5 * 3 * 10**9}, 0.0),
        ({"lloyd.tall_fits": 5, "lloyd.tall_kernel_fits": 5}, 100.0),
        ({"lloyd.tall_fits": 4, "lloyd.tall_kernel_fits": 1}, 25.0),
    ],
    ids=["no_fit", "no_tall_fit", "no_counter", "five_of_five", "a_quarter"],
)
def test_kernel_pass_share_follows_the_counters(monkeypatch, counters, share):
    monkeypatch.setattr(program, "counters", lambda: dict(counters))
    assert harness.load_reader(NAME).read(None) == share


def test_kernel_pass_share_is_declared_for_the_tall_cell_alone_behind_the_cells_three():
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "L5 kernels",
        "moves": "fit_throughput", "workloads": [CELL],
    }
    for cell in bench["workloads"]:
        listed = entry in harness.metrics_for(bench, harness.find_cell(bench, cell["name"]), "per_layer")
        assert listed == (cell["name"] == CELL)
    # appended behind the cell's three, which test_kmeans_tall_cell.py holds by name as they were
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == names.index("lloyd_tall.table_dense_share") + 1 and len(names) == len(set(names))


def test_the_cells_traced_run_reads_every_fit_through_the_kernel():
    """The cell at a test's size (20,000 rows a block: whole tiles and rows over), the
    kernel through the interpreter: every fit of the run counted both ways."""
    bench = harness.load_benchmark()
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    small = {"data": {"rows_per_chip": 60000}, "limits": {"fixed_point_gap_worst": 5e-3}}
    before = program.counters()
    result = harness.run_cell(bench, dict(harness.find_cell(bench, CELL)), 2**31 + 53, 0.3, True, clock, rehearsal=small)
    after = program.counters()
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert result["correct"] and result["failed"] == 0
    # the counters are the process's: this run's fits all took the kernel, and the reading is their ratio
    fits, kernel_fits = (after.get(c, 0) - before.get(c, 0) for c in ("lloyd.tall_fits", "lloyd.tall_kernel_fits"))
    assert fits == kernel_fits > 0
    assert got[NAME] == 100.0 * after["lloyd.tall_kernel_fits"] / after["lloyd.tall_fits"]
    assert got["fit.iters_per_job"] == 30.0
