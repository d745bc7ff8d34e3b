"""rf_clf_fit through the unchanged harness at a test's size (its sizes are here,
not in conftest.py), its control, and its per-layer readers' arithmetic and what
they return where there is nothing to read."""
from types import SimpleNamespace

import pytest

from chipbench import control, harness
from chipbench.clock import PhaseClock
from chipbench.opcount import forest as opcount

BENCH = harness.load_benchmark()
CELL = "rf_clf_fit"
SMALL = {
    "data": {"rows_per_chip": 2048, "cols": 32, "informative": 2, "redundant": 0},
    "params": {"numTrees": 4, "maxBins": 16, "maxDepth": 6},
    "check": {"trees": 4, "control_trees": 4, "nodes_shallow": 2, "nodes_deep": 64, "nodes_below": 64, "deep_from": 2, "duel_to": 5, "fresh_rows": 1000},
    # some 135 duels (816 in the cell), 2 of 32 columns informative, 2,048 rows of Poisson draws
    "limits": {"win_share_low": 0.05, "win_share_high": 0.25, "draw_mean_gap": 0.08, "draw_var_gap": 0.15, "draw_cross_corr": 0.08},
}
FOREST = [m["name"] for m in BENCH["per_layer"] if m["name"].startswith("forest.")]


def _run(trace):
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    return harness.run_cell(BENCH, dict(harness.find_cell(BENCH, CELL)), 2**31 + 77, 0.3, trace, clock, rehearsal=SMALL)


def test_end_to_end_run(capsys):
    result = _run(False)
    wanted = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, CELL), "end_to_end")}
    assert set(result["metrics"]) == wanted == {"fit_throughput", "setup_s"}
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert "COMPARED " in capsys.readouterr().out


def test_traced_run_reports_the_counters_and_nothing_of_the_device(capsys):
    result = _run(True)
    listed = {m["name"] for m in harness.metrics_for(BENCH, harness.find_cell(BENCH, CELL), "per_layer")}
    assert set(FOREST) <= listed and set(result["metrics"]) <= listed
    assert result["metrics"]["forest.nodes_per_job"]["value"] > 4      # more than four roots
    assert "setup.cache_misses" in result["metrics"]
    # no device plane on the CPU, and the mesh engine (the CPU's builder) keeps no dispatch record
    assert not {"forest.hist_ms_per_job", "forest.sort_ms_per_job", "forest.bin_ms_per_job", "forest.hist_mxu_share"} & set(result["metrics"])
    assert '"shallowest_tree": ' in capsys.readouterr().out


def test_control_fails_the_cells_limit_and_a_sound_run_does_not():
    r = control.readings(BENCH, dict(harness.find_cell(BENCH, CELL)), 2**31 + 9, 0.2, "bf16", SMALL)
    assert all(c["ok"] for c in r["sound"]), r["sound"]
    ctrl = {c["name"]: c for c in r["control"]}
    assert not ctrl["count_mismatch"]["ok"] and ctrl["count_mismatch"]["value"] > 0


def _ctx(device_ops=None, modules=None, jobs=2):
    config = harness.load_json(harness.ROOT, "chipbench/configs/rfclf-d3000-t50-depth13.json")
    trace = None if device_ops is None else {"device_ops": device_ops, "modules": modules or {}}
    return SimpleNamespace(trace=trace, jobs=[{}] * jobs, config=config, detail={}, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_trace_readers_find_their_operations_by_name():
    ops = [
        ["forest_hist_shallow.1 custom-call f32[64,128,128]", 1.0], ["forest_hist_deep.1 custom-call f32[258,64,64,128]", 0.5],
        ["forest_bin.1 custom-call s8[3072,401408]", 0.3], ["sort.8 sort s32[50,466944]", 0.8], ["fusion.3 fusion f32[50,466944]", 9.0],
    ]
    ctx = _ctx(ops, {"jit__bin_features_fm_pallas(7)": 0.4, "jit__deep_step(9)": 5.0})
    read = lambda name: harness.load_reader(name).read(ctx)
    assert read("forest.hist_ms_per_job") == pytest.approx(750.0)
    assert read("forest.sort_ms_per_job") == pytest.approx(400.0)
    assert read("forest.bin_ms_per_job") == pytest.approx(200.0)
    assert ctx.detail["forest.bin_hbm_share"] == pytest.approx(100 * 2 * 400000 * 3000 * 5 / 819e9 / 0.4)
    flops = opcount.hist_flops(400000, 50, 54, 128, 2, 13)
    assert read("forest.hist_mxu_share") == pytest.approx(100 * 2 * flops / 197e12 / 1.5)


@pytest.mark.parametrize("name", FOREST)
def test_readers_return_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    """A program from before the forest's counters and names (the parent commit),
    or a run without a trace: no number, and no error."""
    from chipbench import program

    monkeypatch.setattr(program, "counters", lambda: {"precompile.compile": 3})
    for ctx in (_ctx(None), _ctx([["fusion.1 fusion f32[8]", 1.0]], {"jit_other(1)": 1.0})):
        assert harness.load_reader(name).read(ctx) is None


def test_operation_count_of_the_cell():
    # levels 0-6: 2^l nodes x 2 classes (254 slots in all); levels 7-12: 2^(l-7) x 2 (126)
    assert opcount.shallow_levels(2) == 6 and opcount.shallow_levels(8) == 4
    assert opcount.hist_flops(400000, 50, 54, 128, 2, 13) == 2.0 * 400000 * 50 * 54 * 128 * 380
    assert opcount.bin_bytes(400000, 3000) == 400000 * 3000 * 5
