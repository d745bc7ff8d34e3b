"""The per-node feature subset of the one-chip forest builder (ops/forest_mxu.py's
second rule): on a table no wider than one feature block every scan runs over the
whole table and EVERY NODE searches its own subset, drawn by a counter-based rule
that numpy computes again from the four words a model keeps.  Held here to the
plain reference (chipbench/references/forest_pernode.py) node by node, through the
public fit past the shallow phase, for every kind; wider tables keep the shared
subsets, their draws and their compile keys; a forest whose trees' state cannot
fit the device is refused before any dispatch.

CPU, seeded, small: 2,048 rows, 3 trees, depth 8, 16 bins.  The MXU builder's
kernels run through the Pallas interpreter here."""
import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu import RandomForestClassifier, RandomForestRegressor, core, profiling
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.models import random_forest as program
from spark_rapids_ml_tpu.ops import forest_hist, forest_mxu

from chipbench import harness
from chipbench.references import forest_pernode as ref
from chipbench.references import forest_reg
from chipbench.references.common import seed_words

ROWS, BINS, TREES, DEPTH, F = 2048, 16, 3, 8, 5


def _mxu_everywhere(monkeypatch):
    """The one-chip TPU builder, through the public fit, on the CPU."""
    monkeypatch.setattr(program, "_mxu_eligible", lambda *a, **k: True)


def _table(cols: int, seed: int = 11):
    mesh = harness.load_part("subjects", "forest_pernode.fit_loop").program.get_mesh(1)
    X, y = ref.table_fn(mesh, "data", ROWS, cols, cols - 7, 7, 0.5, 0.53, 0.4)(seed_words(seed))
    return X, np.asarray(y)


@pytest.mark.parametrize("seed,trees,cols,width", [(0, 3, 28, 5), (5, 25, 28, 5), (2**31 - 1, 4, 32, 5), (77, 2, 8, 3), (123456789, 2, 32, 31)])
def test_the_rule_is_the_references_and_draws_exactly_the_width(seed, trees, cols, width):
    """The program's rule on the device and on the host, and the reference's own
    arithmetic (64-bit words masked to 32), give one mask; a node has exactly
    `width` columns; two nodes, and two trees' roots, rarely share a subset."""
    draw = np.array([forest_mxu.SUBSET_RULE, seed, width, cols], np.int64)
    t, n = np.arange(trees), np.arange(300)
    want = ref.subsets(draw, t, n)
    np.testing.assert_array_equal(forest_mxu.node_subsets(draw, t, n), want)
    on_device = forest_mxu.node_subset_mask(jnp.asarray(np.uint32(seed)), jnp.asarray(t), jnp.asarray(n), cols, width)
    np.testing.assert_array_equal(np.asarray(on_device), want)
    assert want.shape == (trees, 300, cols) and (want.sum(-1) == width).all()
    if cols == 28:      # 98,280 subsets of 5 of 28: 300 nodes of a tree collide once in two trees
        distinct = len({m.tobytes() for m in want[0]})
        assert distinct >= 297
        assert abs(want.mean() - width / cols) < 0.02 and np.abs(want.mean(axis=(0, 1)) - width / cols).max() < 0.08
    with pytest.raises(ValueError, match="rule 9"):
        forest_mxu.node_subsets(np.array([9, 0, 5, 28]), [0], [0])


def test_the_width_of_the_table_decides_the_rule():
    assert forest_mxu.per_node_subsets(28, 5) and forest_mxu.per_node_subsets(32, 5) and forest_mxu.per_node_subsets(2, 1)
    assert not forest_mxu.per_node_subsets(33, 5) and not forest_mxu.per_node_subsets(3000, 54)
    assert not forest_mxu.per_node_subsets(28, 28) and not forest_mxu.per_node_subsets(28, 40)      # every column: nothing to draw


@pytest.mark.parametrize("kind,cols", [("gini", 28), ("gini", 32), ("entropy", 28), ("regression", 28), ("regression", 32)])
def test_public_fit_gives_every_node_the_best_split_of_its_own_subset(kind, cols, monkeypatch, tmp_path):
    """Depth 8 with two slot rows a node: levels 0-6 are shallow scans, level 7
    splits in the segmented deep phase, level 8 is its leaf level.  Every internal
    node of every tree: its column lies in the subset the model's words give it, its
    (column, edge) is the exact search's best over that subset (a tie in float64
    may fall either way: the shortfall is held, the argmax counted), its counts are
    the reference's on the program's own routing.  And a saved model, loaded again,
    draws the same subsets."""
    _mxu_everywhere(monkeypatch)
    X, y = _table(cols)
    if kind == "regression":
        Xh = np.asarray(X)
        y = (2.0 * Xh[:, 0] + Xh[:, 3] - Xh[:, 9] + 0.1 * np.random.default_rng(0).standard_normal(ROWS)).astype(np.float32)
        est = RandomForestRegressor(numTrees=TREES, maxDepth=DEPTH, maxBins=BINS, featureSubsetStrategy=str(F), seed=5, num_workers=1)
    else:
        est = RandomForestClassifier(numTrees=TREES, maxDepth=DEPTH, maxBins=BINS, impurity=kind, featureSubsetStrategy=str(F), seed=5, num_workers=1)
    model = est.fit(DataFrame.from_device(X, y=y))
    draw = model.feature_subset_draw_
    np.testing.assert_array_equal(draw, [forest_mxu.SUBSET_RULE, 5, F, cols])

    edges = ref.quantile_edges(np.asarray(X)[ref.sample_rows(ROWS, cols)], BINS)
    table = ref.binned_table(X, edges)
    w = np.asarray(ref.bootstrap_weights(model.bootstrap_draw_))[:, :ROWS].astype(np.float64)
    paths = ref.paths_host(np.asarray(X), model.features_, model.thresholds_, DEPTH)
    classes = 0 if kind == "regression" else 2
    deep = 0
    for t in range(TREES):
        nodes = np.flatnonzero(model.features_[t] >= 0)
        assert nodes.max() >= 2**7 - 1          # the deep phase split a node
        deep += int((nodes >= 2**7 - 1).sum())
        if kind != "entropy":      # the reference's gains are gini's and the variance's
            r = ref.examine_splits(table, edges, paths[:, t], y, w[t], classes, draw, t, model.features_[t], model.thresholds_[t], nodes)
            assert r["inside"].all() and r["shortfall"].max() <= 1e-6 and r["threshold_gap"].max() == 0.0
            assert r["is_argmax"].mean() > 0.95
        if classes:
            exact = ref.node_counts(paths[:, t], y, w[t], 2, DEPTH)
            np.testing.assert_array_equal(model.node_counts_[t], exact.sum(-1))
            np.testing.assert_allclose(model.leaf_values_[t] * model.node_counts_[t][:, None], exact, atol=1e-3)
        else:
            exact = forest_reg.node_stats(paths[:, t], y.astype(np.float64), w[t], DEPTH)
            np.testing.assert_allclose(model.node_counts_[t], exact[:, 0], rtol=1e-6)
    assert deep > 20
    masks = ref.subsets(draw, np.arange(TREES), np.arange(2**DEPTH - 1))
    at_t, at_n = np.nonzero(model.features_[:, :2**DEPTH - 1] >= 0)
    assert masks[at_t, at_n, model.features_[at_t, at_n]].all()

    moved = model.fit_telemetry().counters
    searched = TREES * (2**DEPTH - 1)
    assert moved["forest.node_subsets"] == searched
    assert moved["forest.hist_feature_rows"] == searched * forest_hist._F_BLOCK and moved["forest.searched_features"] == searched * F
    n2 = forest_mxu._deep_width(ROWS, 128)
    assert moved["forest.tree_state_bytes"] == TREES * forest_mxu.tree_state_bytes(ROWS, cols, F, DEPTH, 2, kind) > TREES * 32 * n2
    assert moved["forest.gather_bytes"] == 32 * ROWS + 32 * ROWS      # the table once for the scans, once packed for the sort

    path = str(tmp_path / "forest")
    model.save(path)
    again = core.load(path)
    np.testing.assert_array_equal(again.feature_subset_draw_, draw)
    np.testing.assert_array_equal(forest_mxu.node_subsets(again.feature_subset_draw_, np.arange(TREES), np.arange(2**DEPTH - 1)), masks)
    np.testing.assert_array_equal(again.features_, model.features_)


class _Recorder(forest_mxu._Dispatcher):
    seen = []

    def call(self, key, fn, *args, **statics):
        _Recorder.seen.append((key, statics))
        return super().call(key, fn, *args, **statics)


def _grow(cols, width, trees, depth, rows, seed):
    """The builder itself on random bins: ((tree_buf, kept), plan)."""
    rng = np.random.default_rng(3)
    bins = jnp.asarray(rng.integers(0, BINS, (cols, rows)), jnp.int8)
    y = rng.integers(0, 2, rows)
    base = jnp.asarray(np.stack([y == 0, y == 1]).astype(np.float32))
    w = jnp.asarray(rng.poisson(1.0, (trees, rows)).astype(np.float32))
    return forest_mxu.grow_forest_mxu_device(
        forest_hist.tile_feature_rows(bins), base, w, None, max_depth=depth, n_bins=BINS, kind="gini", max_features=width,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=seed, y_vals=jnp.asarray(y, jnp.float32), interpret=True,
    )


@pytest.mark.parametrize("cols,width", [(33, 5), (200, 40)])
def test_wider_tables_keep_their_shared_subsets_and_their_compile_keys(cols, width, monkeypatch):
    """One column past a feature block, and a subset of two blocks (the 3000-column
    cells' geometry, f_pad 64): the subsets are the seed's generator's, in the order
    it was always asked (a (level, tree group) at a time, then a tree at a time);
    no step's key or statics names a per-node width; the model keeps no rule."""
    monkeypatch.setattr(forest_mxu, "_Dispatcher", _Recorder)
    _Recorder.seen = []
    before = profiling.counters("forest.")
    trees, depth, rows, seed = 3, 8, 2048, 7
    grown, plan = _grow(cols, width, trees, depth, rows, seed)
    assert plan.subset_draw is None and plan.max_features == width
    draws = np.random.default_rng(seed)
    groups = [(level, g0) for level in range(7) for g0 in range(0, trees, forest_mxu._even_chunk(trees, 128 // (2**level * 2)))]
    assert [(lv, g0) for lv, g0, _g1, _f in plan.shallow] == groups
    for _lv, _g0, _g1, feats in plan.shallow:
        np.testing.assert_array_equal(feats, draws.choice(cols, width, replace=False))
    np.testing.assert_array_equal(plan.deep_feats, np.stack([draws.choice(cols, width, replace=False) for _ in range(trees)]))
    f_pad = -(-width // 32) * 32
    names = {key[0] for key, _ in _Recorder.seen}
    # the payloads (ceil(width / 4) packed words a tree, w, y) share sorts of at most five: 4 in one, 12 in three of four
    sorts = {5: ["sort_part_i32x2_f32_f32_1d"], 40: ["sort_part_i32x4", "sort_part_i32x4", "sort_part_i32x2_f32_f32_1d"]}[width]
    assert [key[0] for key, _ in _Recorder.seen if key[0].startswith("sort_part")] == sorts
    assert {"gather_rows", "shallow_step", "deep_layout", "pack_all", "deep_state", "deep_step", "deep_leaf"} | set(sorts) == names
    for key, statics in _Recorder.seen:
        assert "subset" not in statics and not any(isinstance(k, tuple) for k in key)
        if key[0] == "shallow_step":
            assert key == ("shallow_step", trees, rows, 2 ** (depth + 1) - 1, 2, f_pad, statics["tpack"], statics["nodes"], 2, "gini", BINS, width, 1.0, 0.0, True)
        if key[0] == "deep_step":
            assert key[:5] == ("deep_step", trees, forest_mxu._deep_width(rows, 128), 2 ** (depth + 1) - 1, f_pad) and key[11] == width and len(key) == 15
        if key[0] == "pack_all":
            assert key == ("pack_all", cols, rows, trees, width, -(-width // 4), True)
    assert sum(key[0] == "gather_rows" for key, _ in _Recorder.seen) == len(groups)      # a gather a (level, group), as before
    moved = profiling.counter_deltas(before, "forest.")
    searched = trees * (2**depth - 1)
    assert "forest.node_subsets" not in moved and moved["forest.hist_feature_rows"] == searched * f_pad and moved["forest.searched_features"] == searched * width
    feature = forest_mxu.pack_forest((np.asarray(grown[0]), np.asarray(grown[1])), plan, np.zeros((cols, BINS - 1), np.float32))[0]
    assert (feature >= 0).sum() > 50 and feature.max() < cols


def test_a_per_node_fit_names_its_width_in_every_steps_key(monkeypatch):
    monkeypatch.setattr(forest_mxu, "_Dispatcher", _Recorder)
    _Recorder.seen = []
    trees, depth, rows, cols = 2, 8, 2048, 28
    _grown, plan = _grow(cols, F, trees, depth, rows, 2**31 + 9)
    np.testing.assert_array_equal(plan.subset_draw, [forest_mxu.SUBSET_RULE, 2**31 + 9, F, cols])
    assert plan.max_features == cols
    steps = [(key, statics) for key, statics in _Recorder.seen if key[0] in ("shallow_step", "deep_step")]
    assert steps and all(key[-1] == ("subset", F) and statics["subset"] == F and statics["F"] == cols for key, statics in steps)
    names = [key[0] for key, _ in _Recorder.seen]
    # the table's 7 packed words are the same for every tree, (n_pad,) payloads: with w and y, 9 in two sorts
    assert names.count("gather_rows") == 1 and not any(name.startswith("sort_part_i32x") for name in names)
    assert [name for name in names if name.startswith("sort_part")] == ["sort_part_i32_1dx4", "sort_part_i32_1dx3_f32_f32_1d"]
    assert next(key for key, _ in _Recorder.seen if key[0] == "pack_all")[3] == 1      # the table's words packed once for all trees


def _stub_free_bytes(monkeypatch, free):
    monkeypatch.setattr(program, "_device_free_bytes", lambda device: free)


def test_a_forest_whose_trees_cannot_fit_is_refused_before_any_dispatch(monkeypatch):
    """HIGGS's uncut 100 trees on one worker's 2,750,000 rows, on a chip of 16 GB:
    the ValueError names the bytes and the largest numTrees that fits, and nothing
    was binned or dispatched."""
    _mxu_everywhere(monkeypatch)
    _stub_free_bytes(monkeypatch, 15_500_000_000)
    rows = 2_750_000
    X = jnp.zeros((rows, 28), jnp.float32)
    y = (np.arange(rows) % 2).astype(np.float32)
    before = profiling.counters("forest.")
    est = RandomForestClassifier(numTrees=100, maxDepth=13, maxBins=128, seed=1, num_workers=1)
    with profiling.collect_spans():
        with pytest.raises(ValueError, match=r"numTrees=100 needs [\d,]+ bytes .* largest numTrees that fits is (\d+)") as caught:
            est.fit(DataFrame.from_device(X, y=y))
        spans = {r[0] for r in profiling.span_records()}
    assert "forest.bin" not in spans and "srml.fit.solve" not in spans
    moved = profiling.counter_deltas(before, "forest.")
    assert "forest.dispatches" not in moved and "forest.tree_state_bytes" not in moved
    n_pad = 2_750_464
    per_tree = forest_mxu.tree_state_bytes(n_pad, 28, 5, 13, 2, "gini")
    assert per_tree == 8 * n_pad + (n_pad + 128 * 512) * (4 * (7 + 2 + 2 + 1) + 32)      # 247 MB a tree
    fits = int(str(caught.value).rsplit(" ", 1)[1])
    assert fits == (15_500_000_000 - 28 * n_pad) // per_tree == 62 and f"{per_tree:,} bytes a tree" in str(caught.value)


@pytest.mark.parametrize("free,trees,raises", [(None, 10_000, False), (10**9, 2, False), (10**6, 2, True)])
def test_the_entry_check_counts_what_it_admits(free, trees, raises, monkeypatch):
    """No account of the device's memory (the CPU): no check.  A forest that fits
    counts its bound; one that does not raises."""
    from types import SimpleNamespace

    _stub_free_bytes(monkeypatch, free)
    inputs = SimpleNamespace(X=np.zeros((4096, 28), np.float32), n_cols=28, mesh=SimpleNamespace(devices=np.array([object()])))
    before = profiling.counter("forest.tree_state_bytes")
    if raises:
        with pytest.raises(ValueError, match="largest numTrees that fits is 0"):
            program._check_tree_state(inputs, trees, 5, 8, 2, "gini")
    else:
        program._check_tree_state(inputs, trees, 5, 8, 2, "gini")
        assert profiling.counter("forest.tree_state_bytes") - before == trees * forest_mxu.tree_state_bytes(4096, 28, 5, 8, 2, "gini")


@pytest.mark.parametrize("kind,depth,want", [("gini", 6, 8 * 4096), ("regression", 8, 8 * 4096 + (4096 + 65536) * (4 * (7 + 2 + 4 + 1 + 3) + 32))])
def test_tree_state_bytes_reads_the_geometry(kind, depth, want):
    assert forest_mxu.tree_state_bytes(4096, 28, 5, depth, 2, kind) == want
