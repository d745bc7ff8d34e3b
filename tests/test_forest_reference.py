"""The forest family held to its plain reference (chipbench/references/forest.py)
at a test's size: with every feature searched the program's forest IS the
reference's, tree for tree and node for node, on the mesh engine and on the MXU
builder past its shallow levels; with sqrt subsets the benchmark's comparison
(chipbench/subjects/forest.fit_loop.py) passes, and fails the control and each
planted fault; a fit's step spans tile srml.fit and its counters add up.

CPU, seeded, small: <= 2,048 rows x 32 columns, <= 4 trees, depth <= 6, 16 bins.
The MXU builder's kernels run through the Pallas interpreter here."""
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu import RandomForestClassifier, profiling
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.models import random_forest as program

from chipbench import control, harness
from chipbench.references import forest as ref
from chipbench.references.common import seed_words

ROWS, COLS, BINS = 2048, 32, 16
STEPS = ["srml.fit.init", "srml.fit.solve", "srml.fit.wait", "srml.fit.fetch", "srml.fit.pack"]
BENCH = harness.load_benchmark()
# the benchmark's cell at a test's size (harness-internal rehearsal hook)
SMALL = {
    "data": {"rows_per_chip": ROWS, "cols": COLS, "informative": 2, "redundant": 0},
    "params": {"numTrees": 4, "maxBins": BINS, "maxDepth": 6},
    "check": {"trees": 4, "control_trees": 4, "nodes_shallow": 2, "nodes_deep": 64, "nodes_below": 64, "deep_from": 2, "duel_to": 5, "fresh_rows": 1000},
    # some 135 duels here (816 in the cell) and 2 of 32 columns informative: a sound fit
    # wins 0.64 of them, the block fault 0.38; 2,048 rows draw
    # Poisson counts less evenly than 400,000
    "limits": {"win_share_low": 0.05, "win_share_high": 0.25, "draw_mean_gap": 0.08, "draw_var_gap": 0.15, "draw_cross_corr": 0.08},
}


def _table(classes: int, seed: int = 11):
    mesh = harness.load_part("subjects", "forest.fit_loop").program.get_mesh(1)
    X, y = ref.table_fn(mesh, "data", ROWS, COLS, classes, 2, 0, 1.0)(seed_words(seed))
    return X, np.asarray(y)


def _mxu_everywhere(monkeypatch):
    """The one-chip TPU builder, through the public fit, on the CPU."""
    monkeypatch.setattr(program, "_mxu_eligible", lambda *a, **k: True)


@pytest.mark.parametrize("engine,classes,depth", [("mesh", 2, 5), ("mxu", 8, 6)])
def test_forest_with_every_feature_searched_is_the_references(engine, classes, depth, monkeypatch):
    """mxu: 8 classes leave the shallow phase 5 levels (2^4 nodes x 8 = 128 slots), so
    depth 6 splits level 5 in the segmented deep phase and totals level 6 there."""
    if engine == "mxu":
        _mxu_everywhere(monkeypatch)
    X, y = _table(classes)
    est = RandomForestClassifier(numTrees=3, maxDepth=depth, maxBins=BINS, featureSubsetStrategy="all", seed=5, num_workers=1)
    model = est.fit(DataFrame.from_device(X, y=y))
    edges = ref.quantile_edges(np.asarray(X)[ref.sample_rows(ROWS, COLS)], BINS)
    w = np.asarray(ref.bootstrap_weights(model.bootstrap_draw_))[:, :ROWS]
    want = ref.build_forest(ref.columns_fn(X, edges), edges, y, w, classes, depth, COLS, seed=0)
    np.testing.assert_array_equal(model.features_, want["features_"])
    np.testing.assert_array_equal(model.thresholds_, want["thresholds_"])
    np.testing.assert_array_equal(model.node_counts_, want["node_counts_"])
    np.testing.assert_allclose(model.leaf_values_ * model.node_counts_[..., None], want["leaf_values_"] * want["node_counts_"][..., None], atol=1e-3)
    held = want["node_counts_"] > 0
    np.testing.assert_allclose(model.impurities_[held], want["impurities_"][held], atol=1e-6)
    if engine == "mxu":
        # the deep kernel's tiles, counted: 3 trees x the sorted layout's tiles x one deep level
        # (level 5); a tree keeps at least a tile a segment and not its out-of-bag rows' tiles;
        # the per-tree counts came with the forest's one fetch
        moved = model.fit_telemetry().counters
        from spark_rapids_ml_tpu.ops import forest_hist, forest_mxu

        tiles = forest_mxu._deep_width(ROWS, 32) // forest_hist._ROW_TILE_DEEP
        assert moved["forest.deep_tiles"] == 3 * tiles
        assert 3 * 32 <= moved["forest.deep_tiles_kept"] < moved["forest.deep_tiles"]
        assert moved["forest.host_syncs"] <= 2               # the fetch; on the CPU the binning sample's too


def _readings(how):
    return control.readings(BENCH, dict(harness.find_cell(BENCH, "rf_clf_fit")), 2**31 + 5, 0.2, how, SMALL)


@pytest.mark.parametrize("how,number", [
    ("bf16", "count_mismatch"), ("fault_block", "win_share_low"), ("fault_swap", "count_mismatch"), ("fault_half", "split_gain_shortfall"),
    ("fault_wide", "win_share_high"), ("fault_edges", "threshold_gap"), ("fault_draw_shared", "draw_cross_corr"),
    ("fault_draw_clipped", "draw_mean_gap"), ("fault_draw_ones", "draw_var_gap"),
])
def test_comparison_passes_a_sound_fit_and_fails_the_control_and_each_planted_fault(how, number):
    r = _readings(how)
    assert all(c["ok"] for c in r["sound"]), [c for c in r["sound"] if not c["ok"]]
    failed = {c["name"] for c in r["control"] if not c["ok"]}
    assert number in failed, (how, r["control"])


@pytest.mark.parametrize("engine", ["mesh", "mxu"])
def test_step_spans_tile_the_fit_and_the_counters_add_up(engine, monkeypatch):
    if engine == "mxu":
        _mxu_everywhere(monkeypatch)
    X, y = _table(2)
    est = RandomForestClassifier(numTrees=2, maxDepth=4, maxBins=BINS, seed=3, num_workers=1)
    frame = DataFrame.from_device(X, y=y)
    est.fit(frame)                       # compiles; the tiling is of a warm fit
    me = threading.get_ident()
    for _attempt in range(3):            # a fit the scheduler cut into is taken again
        with profiling.collect_spans():
            model = est.fit(frame)
            mine = sorted((r for r in profiling.span_records() if r[3] == me), key=lambda r: r[1])
        fit = next(r for r in mine if r[0] == "srml.fit")
        steps = [r for r in mine if r[6] == fit[5]]
        order = [STEPS.index(r[0]) for r in steps]          # every child is a step span
        assert order == sorted(order) and {0, 1, 4} <= set(order)
        inner = [fit[1]] + [t for r in steps for t in (r[1], r[2])] + [fit[2]]
        assert all(a <= b for a, b in zip(inner, inner[1:]))
        bare = max(b - a for a, b in zip(inner[0::2], inner[1::2]))
        if bare < 1e-3:
            break
    assert bare < 1e-3
    moved = model.fit_telemetry().counters
    assert moved["forest.fits"] == 1
    assert moved["forest.nodes"] == 2 * int((model.features_ >= 0).sum()) + 2 == model.totalNumNodes
    if engine == "mxu":
        assert {2, 3} <= set(order)                          # wait and fetch: core.fetch_fit_result
        assert moved["forest.levels"] == 5 and moved["forest.dispatches"] >= moved["forest.geometries"] > 0
        assert moved["forest.hist_rows"] >= moved["forest.hist_rows_needed"] == 2 * 4 * ROWS
        assert moved["forest.host_syncs"] <= 2               # the fetch; on the CPU the binning sample's too
        assert "forest.deep_tiles" not in moved and "forest.deep_tiles_kept" not in moved   # depth 4: no deep phase
        levels = [r for r in mine if r[0] == "forest.level"]
        assert [r[7]["level"] for r in levels] == [0, 1, 2, 3, 4]
        assert sum(r[7]["dispatches"] for r in levels) == moved["forest.dispatches"]
