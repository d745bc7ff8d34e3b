"""The regression forest held to its plain reference (chipbench/references/forest_reg.py)
at a test's size, on CONTINUOUS labels: the public fit on the mesh engine and on the
one-chip MXU builder (its kernels through the Pallas interpreter), at depths that end
in the shallow phase and one that enters the deep phase; the kernels' regression
histograms and the node totals against float64 on labels where one bfloat16 pass
fails by a hundred times the limit; and a classifier's forest, unchanged to the bit.

CPU, seeded, small: 2,048 rows x 24 columns, <= 3 trees, depth <= 7, 16 bins."""
import hashlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import RandomForestClassifier, RandomForestRegressor
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.models import random_forest as program
from spark_rapids_ml_tpu.ops import forest_hist, forest_mxu

from chipbench import harness
from chipbench.references import forest as shared
from chipbench.references import forest_reg as ref
from chipbench.references.common import seed_words

ROWS, COLS, BINS, INFORMATIVE = 2048, 24, 16, 4
DATA = {"rows_per_chip": ROWS, "cols": COLS, "informative": INFORMATIVE, "coef_scale": 100.0, "noise": 1.0, "bias": 0.0}
SUBJECT = harness.load_part("subjects", "forest_reg.fit_loop")


def _table(seed: int = 11):
    X, y = ref.table_fn(SUBJECT.program.get_mesh(1), "data", ROWS, COLS, INFORMATIVE, 100.0, 1.0, 0.0)(seed_words(seed))
    return X, np.asarray(y)


def _mxu_everywhere(monkeypatch):
    """The one-chip TPU builder, through the public fit, on the CPU."""
    monkeypatch.setattr(program, "_mxu_eligible", lambda *a, **k: True)


@pytest.mark.parametrize("engine,depth", [("mesh", 4), ("mxu", 3), ("mxu", 6), ("mxu", 7)])
def test_regression_forest_is_held_to_the_reference(engine, depth, monkeypatch):
    """Two stat rows leave the shallow phase levels 0 to 6, so depth 3 and depth 6 end
    inside it (depth 6 totals its leaves at the last shallow level) and depth 7
    splits level 6 there, sorts, and totals level 7 in the segmented deep phase.
    Every feature searched: node counts to the unit, means and variances within
    float32's rounding, the stored edge the best of its column, the implied gain
    the reference's, and no informative column left unsearched."""
    if engine == "mxu":
        _mxu_everywhere(monkeypatch)
    X, y = _table()
    assert np.abs(y - np.round(y)).mean() > 0.1, "the labels are continuous"
    params = {"numTrees": 3, "maxDepth": depth, "maxBins": BINS, "featureSubsetStrategy": "all"}
    model = RandomForestRegressor(seed=5, num_workers=1, **params).fit(DataFrame.from_device(X, y=y))
    ctx = SimpleNamespace(
        config={"data": DATA, "estimator": {"params": params}}, check={"trees": 3, "tie_margin": 1e-6, "fresh_rows": 1000},
        X=X, rows=ROWS, seed=5, words=seed_words(11), reference_precision="highest",
    )
    edges = shared.quantile_edges(np.asarray(X)[shared.sample_rows(ROWS, COLS)], BINS)
    job = {a: getattr(model, a) for a in SUBJECT.ARRAYS}
    read = SUBJECT._examine(
        ctx, dict(job, bootstrap_draw_=model.bootstrap_draw_), ref.binned_table(X, edges), edges, y.astype(np.float64), np.random.default_rng(0), None
    )
    assert read["count_mismatch"] == 0
    assert read["mean_gap"] < 2e-6 and read["variance_gap"] < 1e-5, read
    assert read["threshold_gap"] < 1e-5 and read["split_gain_shortfall"] < 1e-5 and read["implied_gain_gap"] < 1e-4, read
    assert read["detail"]["found_out"] == 0 and read["detail"]["nodes_searched"] > 2 ** (depth - 1)
    assert read["r2_loss"] < 0.6, read


def _float64_histogram(sub, node_rel, w, y, nodes, bins):
    """(F, trees, nodes, 2, bins) float64 sums of (w, w y)."""
    out = np.zeros((sub.shape[0], w.shape[0], nodes, 2, bins))
    for f in range(sub.shape[0]):
        for t in range(w.shape[0]):
            at = node_rel[t] < nodes
            flat = node_rel[t][at] * bins + sub[f][at]
            for s, v in enumerate((w[t], w[t] * y)):
                out[f, t, :, s] = np.bincount(flat, weights=v[at], minlength=nodes * bins).reshape(nodes, bins)
    return out


def _kernel_rows(seed=3, n=4096, t_pack=2, nodes=4):
    rng = np.random.default_rng(seed)
    sub = rng.integers(0, BINS, (forest_hist._F_BLOCK, n)).astype(np.int8)
    node_rel = rng.integers(0, nodes + 1, (t_pack, n)).astype(np.int32)      # some rows at no node
    w = rng.poisson(1.0, (t_pack, n)).astype(np.float32)
    y = (25.0 + 180.0 * rng.standard_normal(n)).astype(np.float32)
    return sub, node_rel, w, y


@pytest.mark.parametrize("kernel", ["shallow", "deep"])
def test_regression_histograms_are_float32_sums_and_one_piece_is_not(kernel):
    """The label's three pieces through the kernels' bfloat16 operands against
    float64: within 1e-6 of the largest entry.  The same rows as ONE product (a
    classifier's path: the label rounded to bfloat16) miss by more than 1e-4."""
    t_pack, nodes = (2, 4) if kernel == "shallow" else (1, 4)
    sub, node_rel, w, y = _kernel_rows(n=4096, t_pack=t_pack, nodes=nodes)
    exact = _float64_histogram(sub, node_rel, w.astype(np.float64), y.astype(np.float64), nodes, BINS)
    base = jnp.asarray(np.stack([np.ones_like(y), y]))

    def run(kind):
        products = forest_mxu._hist_products(kind)
        rows = forest_mxu._stats_rows(base, jnp.asarray(w), t_pack, 2, kind)
        if kernel == "shallow":
            H = forest_hist.node_histograms(
                jnp.asarray(sub), jnp.asarray(node_rel), rows, t_pack=t_pack, nodes=nodes, s_dim=2, n_bins=BINS,
                products=products, interpret=True,
            )
        else:       # one tree, every tile in segment 0 of 1 (none stray)
            tiles = sub.shape[1] // forest_hist._ROW_TILE_DEEP
            H = forest_hist.node_histograms_segmented(
                jnp.asarray(sub)[None], jnp.asarray(node_rel)[:, None, :], rows[None], jnp.zeros((tiles,), jnp.int32),
                jnp.zeros((), jnp.int32), t_chunk=1, n_segs=1, nodes=nodes, s_dim=2, n_bins=BINS,
                f_pad=forest_hist._F_BLOCK, products=products, interpret=True,
            )
            H = H[:, 0] if products > 1 else H[0]
        if kind == "regression":
            H = forest_hist.fold_label_products(H, t_pack * nodes * 2)
        return np.asarray(H)[:, : t_pack * nodes * 2].reshape(sub.shape[0], t_pack, nodes, 2, BINS)

    pieces, one = run("regression"), run("gini")
    scale = np.abs(exact[..., 1, :]).max()
    for got in (pieces, one):
        np.testing.assert_array_equal(got[..., 0, :], exact[..., 0, :])      # weights: whole numbers, exact either way
    assert np.abs(pieces[..., 1, :] - exact[..., 1, :]).max() / scale < 1e-6
    assert np.abs(one[..., 1, :] - exact[..., 1, :]).max() / scale > 1e-4


def test_a_label_is_cut_into_three_exact_pieces():
    rng = np.random.default_rng(1)
    wy = (rng.standard_normal(4096) * 10.0 ** rng.integers(-3, 6, 4096)).astype(np.float32)
    rows = np.asarray(forest_hist.label_stat_rows(jnp.ones(4096, jnp.float32), jnp.asarray(wy), axis=0))
    in_bf16 = lambda a: np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))
    for piece in rows[1:]:
        np.testing.assert_array_equal(in_bf16(piece), piece)
    np.testing.assert_array_equal((rows[1].astype(np.float64) + rows[2] + rows[3]).astype(np.float32), wy)
    assert forest_hist.LABEL_PIECES == rows.shape[0] - 1 == 3


def _dot_precisions(fn, *args):
    """The precision of every dot_general in fn's jaxpr, nested calls included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(eqn.params["precision"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("totals", ["node", "segment"])
def test_node_totals_are_float32_sums_and_state_their_precision(totals):
    """(w, w y, w y^2) a node against float64, within 1e-6 of each statistic's
    largest; the same sums with the rows held in bfloat16 (what this backend's
    default precision makes of a float32 product) miss by more than 1e-4; and
    every product of the program's says Precision.HIGHEST, which a CPU cannot
    show by its result."""
    nodes = 4
    _, node_rel, w, y = _kernel_rows(n=2048, t_pack=2, nodes=nodes)
    stats3 = np.stack([w, w * y[None], w * (y * y)[None]], axis=1)                           # (t, 3, n)
    on = (node_rel[:, None, :] == np.arange(nodes)[None, :, None]).astype(np.float64)
    exact = np.einsum("tcn,tsn->tcs", on, stats3.astype(np.float64))
    if totals == "node":
        fn = lambda st: forest_mxu._node_totals(jnp.asarray(node_rel), st, nodes)
    else:       # two tiles a tree, each its own segment
        tile = node_rel.shape[1] // 2
        seg_oh = jnp.broadcast_to(jnp.eye(2, dtype=jnp.float32)[None], (2, 2, 2))
        rl = jnp.asarray(node_rel).reshape(2, 2, tile)
        fn = lambda st: forest_mxu._seg_totals(rl, st.reshape(2, 3, 2, tile), seg_oh, nodes).sum(axis=1)
    scale = np.abs(exact).max(axis=(0, 1))
    got = np.asarray(fn(jnp.asarray(stats3)))
    rounded = np.asarray(fn(jnp.asarray(stats3).astype(jnp.bfloat16).astype(jnp.float32)))
    assert (np.abs(got - exact) / scale).max() < 1e-6
    assert (np.abs(rounded - exact) / scale).max() > 1e-4
    precisions = _dot_precisions(fn, jnp.asarray(stats3))
    highest = jax.lax.Precision.HIGHEST
    assert precisions and all(p in (highest, (highest, highest)) for p in precisions), precisions


@pytest.mark.parametrize("depth,classes,digest", [
    (4, 2, "e7acfff54b20e6d3d02478393cf72bb9e423ea72380657bb5bf0ae5209a30e6f"),
    (5, 3, "96b7361fa90e18200eaa37adc1828498e7b4286043c53f4148b553510a30ef00"),
    (6, 8, "fdab43009f87ebc5c17677dc98bb434d370a1e845dbf2dd3b7cbe2b96fcc9ef0"),
])
def test_a_classifiers_forest_is_unchanged_to_the_bit(depth, classes, digest, monkeypatch):
    """The MXU builder's classifier path (one product a feature) through the public
    fit: the digests are those of the commit before the regressor's pieces, at a
    depth that ends in the shallow phase (two classes, depth 4: its leaves' counts
    are _node_totals'), one level into the deep phase (3 classes, depth 5) and,
    with 8 classes, two."""
    _mxu_everywhere(monkeypatch)
    rng = np.random.default_rng(5)
    X = rng.standard_normal((2048, 24)).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.standard_normal(2048)) > 0).astype(np.float32) + (X[:, 2] > 1)
    labels = {2: (y > 0).astype(np.float32), 3: y, 8: np.floor(np.abs(X[:, 3]) * 3) % 8}[classes]
    model = RandomForestClassifier(numTrees=3, maxDepth=depth, maxBins=16, seed=9, num_workers=1).fit(DataFrame.from_numpy(X, y=labels))
    arrays = ("features_", "thresholds_", "leaf_values_", "node_counts_", "impurities_")
    got = hashlib.sha256(b"".join(np.ascontiguousarray(getattr(model, a)).tobytes() for a in arrays)).hexdigest()
    assert got == digest


def test_regression_fit_counts_its_pieces_and_its_subsets(monkeypatch):
    _mxu_everywhere(monkeypatch)
    X, y = _table()
    model = RandomForestRegressor(numTrees=2, maxDepth=3, maxBins=BINS, seed=3, num_workers=1).fit(DataFrame.from_device(X, y=y))
    moved = model.fit_telemetry().counters
    assert moved["forest.fits"] == moved["forest.reg_fits"] == 1
    assert moved["forest.label_pieces"] == 3 and moved["forest.subset_features"] == COLS // 3
    # three split levels, one tree group a level: three gathers of a 32-row block over the padded rows
    assert moved["forest.gather_bytes"] == 3 * 32 * 2048
    assert moved["forest.dispatches"] == 3 * 2 + 1


@pytest.mark.parametrize("route,estimator", [
    ("one_chip", RandomForestRegressor), ("one_chip", RandomForestClassifier), ("mesh_engine", RandomForestRegressor),
], ids=["regressor", "classifier", "mesh_engine"])
def test_a_fit_on_the_one_chip_builder_counts_its_subsets_as_row_copies(route, estimator, monkeypatch):
    """forest.gather_copy_fits beside forest.fits, process-wide and in the fit's
    telemetry: one a fit whose subsets gather_rows_matmul copied out of the tiled
    table, none for a fit on the mesh engine, which gathers no subset."""
    from spark_rapids_ml_tpu import profiling

    if route == "one_chip":
        _mxu_everywhere(monkeypatch)
    X, y = _table()
    labels = y if estimator is RandomForestRegressor else (y > jnp.median(y)).astype(jnp.float32)
    before = profiling.counters("forest.")
    model = estimator(numTrees=2, maxDepth=3, maxBins=BINS, seed=3, num_workers=1).fit(DataFrame.from_device(X, y=labels))
    copies = int(route == "one_chip")
    moved = model.fit_telemetry().counters
    assert moved["forest.fits"] == 1 and moved.get("forest.gather_copy_fits", 0) == copies
    process = profiling.counter_deltas(before, "forest.")
    assert process["forest.fits"] == 1 and process.get("forest.gather_copy_fits", 0) == copies
