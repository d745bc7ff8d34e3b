#
# MXU forest-histogram path tests (ops/forest_hist.py + ops/forest_mxu.py).
# The pallas kernel runs in interpret mode on the CPU test mesh; with
# SRML_TPU_TESTS=1 on a chip the same tests compile it through Mosaic.
#
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.forest import (
    bin_features,
    compute_bin_edges,
    forest_predict_kernel,
    grow_forest,
)
from spark_rapids_ml_tpu.ops.forest_hist import (
    _F_BLOCK,
    _ROW_TILE,
    gather_rows_matmul,
    node_histograms,
    tile_feature_rows,
    node_histograms_reference,
)
from spark_rapids_ml_tpu.ops.forest_mxu import (
    grow_forest_mxu,
    mxu_depth_supported,
)

# On a real TPU (SRML_TPU_TESTS=1) run the compiled Mosaic kernels; on the
# CPU mesh interpret — the tests/test_pallas.py switch.
KERNEL_INTERPRET = jax.devices()[0].platform != "tpu"


@pytest.mark.parametrize(
    "D,F,n_pad",
    [
        (23, 7, 2 * _ROW_TILE),       # D no multiple of 32, F < 32
        (70, 54, 3 * _ROW_TILE),      # an odd number of row tiles: half a 4096-row tile is padding
        (3000, 1000, _ROW_TILE),      # a regressor's third of the columns, 32 whole blocks
        (40, 40, 2 * _ROW_TILE),      # F >= D: the table in its own order, padded
        (70, 33, 35 * _ROW_TILE),     # two feature blocks, two row blocks, the last of each partial
    ],
    ids=["d23_f7", "odd_tiles", "f1000_of_3000", "every_feature", "partial_blocks"],
)
def test_gather_rows_matmul_exact(D, F, n_pad):
    """The subset is bins[feats] to the byte, its padding rows zero (the name
    is the benchmark's: since PR 33 the function copies rows, no product)."""
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 128, (D, n_pad)).astype(np.int8)
    feats = (
        np.arange(D, dtype=np.int32) if F >= D
        else rng.choice(D, F, replace=False).astype(np.int32)
    )
    f_pad = -(-F // _F_BLOCK) * _F_BLOCK
    rows = tile_feature_rows(jnp.asarray(bins))
    assert rows.shape[0] == D and rows.shape[2:] == (32, 128)
    sub = np.asarray(
        gather_rows_matmul(
            rows, jnp.asarray(feats), f_pad=f_pad, n_pad=n_pad,
            interpret=KERNEL_INTERPRET,
        )
    )
    assert sub.shape == (f_pad, n_pad) and sub.dtype == np.int8
    np.testing.assert_array_equal(sub[:F], bins[feats])
    np.testing.assert_array_equal(sub[F:], 0)


def test_pack_all_is_every_trees_subset_packed():
    """The deep phase's T subsets ride one gather: word p of tree t holds the
    bins of its features 4p .. 4p + 3, a byte each, and nothing of the rows
    past its subset."""
    from spark_rapids_ml_tpu.ops.forest_mxu import _pack_all

    rng = np.random.default_rng(3)
    D, F, T, n_pad = 19, 6, 3, 3 * _ROW_TILE
    bins = rng.integers(0, 128, (D, n_pad)).astype(np.int8)
    feats = np.stack([rng.choice(D, F, replace=False) for _ in range(T)]).astype(np.int32)
    packed = _pack_all(
        tile_feature_rows(jnp.asarray(bins)), jnp.asarray(feats),
        n_pad=n_pad, P=2, interpret=KERNEL_INTERPRET,
    )
    assert isinstance(packed, tuple) and len(packed) == 2           # an array a word
    packed = np.stack([np.asarray(word) for word in packed], axis=1)
    assert packed.shape == (T, 2, n_pad) and packed.dtype == np.int32
    want = np.zeros((T, 8, n_pad), np.int64)
    for t in range(T):
        want[t, :F] = bins[feats[t]]
    words = sum(want[:, k::4] << (8 * k) for k in range(4))
    np.testing.assert_array_equal(packed, words.astype(np.int32))


def test_node_histograms_matches_oracle():
    rng = np.random.default_rng(1)
    N = 2 * _ROW_TILE
    T, nodes, S, B = 3, 4, 2, 16
    sub = rng.integers(0, B, (_F_BLOCK, N)).astype(np.int8)
    node_rel = rng.integers(0, nodes + 2, (T, N)).astype(np.int32)
    stats = rng.random((T * S, N)).astype(np.float32)
    H = np.asarray(
        node_histograms(
            jnp.asarray(sub), jnp.asarray(node_rel), jnp.asarray(stats),
            t_pack=T, nodes=nodes, s_dim=S, n_bins=B, interpret=KERNEL_INTERPRET,
        )
    )
    Href = node_histograms_reference(sub, node_rel, stats, T, nodes, S, B)
    # bf16 operands: ~2^-8 relative on sums of thousands of terms
    np.testing.assert_allclose(H, Href, rtol=2e-2, atol=1e-3)


def test_depth_support():
    # shallow phase: 2^l * S <= 128; deep bucketed phase doubles the depth
    # budget (+1): S=2 -> 13, S=3 -> 11, S=8 (8-class) -> 9
    assert mxu_depth_supported(13, 2)
    assert not mxu_depth_supported(14, 2)
    assert mxu_depth_supported(11, 3)
    assert not mxu_depth_supported(12, 3)
    assert mxu_depth_supported(9, 8)
    assert not mxu_depth_supported(10, 8)


@pytest.mark.parametrize(
    "kind,tiles",
    [
        pytest.param("regression", 2, marks=pytest.mark.slow),
        ("gini", 1),
        # cross-row-tile accumulation is a distinct failure mode: keep an
        # equivalence (not just quality) check spanning two tiles, slow-
        # tagged since the single-tile default already gates the rest
        pytest.param("gini", 2, marks=pytest.mark.slow),
    ],
)
def test_mxu_builder_matches_scatter_builder(kind, tiles):
    """No bootstrap + all features: both builders are deterministic on the
    same binned data and must grow IDENTICAL trees."""
    rng = np.random.default_rng(2)
    N, D, B, T, depth = tiles * _ROW_TILE, 8, 8, 2, 4
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = (X @ rng.standard_normal(D) + 0.2 * rng.standard_normal(N)).astype(
        np.float32
    )
    y_cls = (y > 0).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)

    if kind == "regression":
        base = np.stack([np.ones(N, np.float32), y])
        stats3 = np.stack([np.ones(N, np.float32), y, y * y])
        st_old = jnp.stack(
            [jnp.ones(N), jnp.asarray(y), jnp.asarray(y) ** 2], axis=1
        )
    else:
        base = np.stack([(y_cls == 0), (y_cls == 1)]).astype(np.float32)
        stats3 = None
        st_old = jnp.asarray(base.T)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees),
        None if stats3 is None else jnp.asarray(stats3),
        edges, max_depth=depth, n_bins=B, kind=kind, max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=7,
        interpret=KERNEL_INTERPRET,
    )
    stats_t = jnp.broadcast_to(st_old[None], (T, N, st_old.shape[1]))
    f2, t2, v2, ns2, imp2 = grow_forest(
        jnp.asarray(Xb), stats_t, edges, max_depth=depth, n_bins=B,
        kind=kind, max_features=D, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=7,
    )
    # bf16 histogram rounding can flip near-tie splits on small samples (and
    # one flipped ancestor rewrites its whole subtree), so demand
    # near-identical structure plus matching predictions rather than exact
    # node-for-node equality — a 4096-row development check matched 100%
    f2_h = np.asarray(f2)
    assert (f == f2_h).mean() > 0.9, (f == f2_h).mean()
    # a flipped near-tie reroutes whole subtrees, so rows near the boundary
    # legitimately get different leaves; model QUALITY must agree
    p1 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    p2 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f2), jnp.asarray(t2),
            jnp.asarray(v2), max_depth=depth,
        )
    )
    if kind == "regression":
        e1 = ((p1[:, 0] - y) ** 2).mean() / y.var()
        e2 = ((p2[:, 0] - y) ** 2).mean() / y.var()
    else:
        e1 = (p1.argmax(1) != y_cls).mean()
        e2 = (p2.argmax(1) != y_cls).mean()
    assert abs(e1 - e2) < 0.02, (e1, e2)


@pytest.mark.slow
def test_mxu_builder_feature_subsets_and_bootstrap_quality():
    """With max_features < D and Poisson bootstrap the forests can't be
    compared structurally; check learning quality instead."""
    rng = np.random.default_rng(3)
    N, D, B, T, depth = 2 * _ROW_TILE, 12, 32, 8, 5
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = (2.0 * X[:, 0] - X[:, 3]).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.random.default_rng(4).poisson(
        1.0, (T, N)
    ).astype(np.float32)
    base = np.stack([np.ones(N, np.float32), y])
    stats3 = np.stack([np.ones(N, np.float32), y, y * y])
    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees),
        jnp.asarray(stats3), edges, max_depth=depth, n_bins=B,
        kind="regression", max_features=6, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=11, interpret=KERNEL_INTERPRET,
    )
    pred = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )[:, 0]
    r2 = 1.0 - ((pred - y) ** 2).mean() / y.var()
    assert r2 > 0.75, r2


@pytest.mark.slow
def test_mxu_deep_phase_smoke_fast():
    """Classification deep-phase gate: 4 classes shrink the slot budget
    (l_s=4), so depth 6 already exercises the bucket sort, the class
    layout and the clamped chunk windows.  Slow-tagged: the REGRESSION
    smoke below stays in default CI (the round-4 advisor's requirement)
    and covers the identical deep machinery; this one rides --runslow
    with the depth-9+ equivalence sweeps."""
    rng = np.random.default_rng(11)
    N, D, B, T, depth, C = _ROW_TILE, 8, 8, 2, 6, 4
    X = rng.standard_normal((N, D)).astype(np.float32)
    logits = X @ rng.standard_normal((D, C))
    y = logits.argmax(1).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([(y == c) for c in range(C)]).astype(np.float32)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees), None,
        edges, max_depth=depth, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=3,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET,
    )
    stats_t = jnp.broadcast_to(jnp.asarray(base.T)[None], (T, N, C))
    f2, t2, v2, _, _ = grow_forest(
        jnp.asarray(Xb), stats_t, edges, max_depth=depth, n_bins=B,
        kind="gini", max_features=D, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=3,
    )
    f2_h = np.asarray(f2)
    # shallow levels must agree exactly; deep levels tolerate bf16 tie flips
    shallow = slice(0, 2**5 - 1)
    assert (f[:, shallow] == f2_h[:, shallow]).mean() > 0.97
    assert (f == f2_h).mean() > 0.85, (f == f2_h).mean()
    p1 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    p2 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f2), jnp.asarray(t2),
            jnp.asarray(v2), max_depth=depth,
        )
    )
    a1 = (p1.argmax(1) == y).mean()
    a2 = (p2.argmax(1) == y).mean()
    assert abs(a1 - a2) < 0.03, (a1, a2)


def test_mxu_deep_phase_smoke_fast_regression():
    """Regression-kind deep-phase gate for default CI (round-3 advice): the
    stats3 plumbing (tot3 rows, base=stat_rows[:2]) through the fused
    shallow/deep steps previously ran only behind --runslow, so a
    regression-kind breakage would merge green.  S=2 stat rows -> l_s=6,
    so depth 7 crosses into the bucketed deep phase."""
    rng = np.random.default_rng(12)
    # B=4 halves the interpreter-mode histogram width — this is the
    # single biggest default-CI cost; the deep machinery it gates is
    # bin-count-invariant
    N, D, B, T, depth = _ROW_TILE, 8, 4, 2, 7
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = (
        X @ rng.standard_normal(D) + 0.1 * rng.standard_normal(N)
    ).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([np.ones(N, np.float32), y])
    stats3 = np.stack([np.ones(N, np.float32), y, y * y])

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees),
        jnp.asarray(stats3), edges, max_depth=depth, n_bins=B,
        kind="regression", max_features=D, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=3, y_vals=jnp.asarray(y),
        interpret=KERNEL_INTERPRET,
    )
    st_old = jnp.stack(
        [jnp.ones(N), jnp.asarray(y), jnp.asarray(y) ** 2], axis=1
    )
    stats_t = jnp.broadcast_to(st_old[None], (T, N, 3))
    f2, t2, v2, _, _ = grow_forest(
        jnp.asarray(Xb), stats_t, edges, max_depth=depth, n_bins=B,
        kind="regression", max_features=D, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=3,
    )
    f2_h = np.asarray(f2)
    # shallow levels must agree exactly; deep levels tolerate bf16 tie flips
    shallow = slice(0, 2**5 - 1)
    assert (f[:, shallow] == f2_h[:, shallow]).mean() > 0.97
    assert (f == f2_h).mean() > 0.85, (f == f2_h).mean()
    p1 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )[:, 0]
    p2 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f2), jnp.asarray(t2),
            jnp.asarray(v2), max_depth=depth,
        )
    )[:, 0]
    e1 = ((p1 - y) ** 2).mean() / y.var()
    e2 = ((p2 - y) ** 2).mean() / y.var()
    assert abs(e1 - e2) < 0.03, (e1, e2)


@pytest.mark.slow
def test_mxu_deep_phase_matches_scatter_builder():
    """Depth past the slot budget triggers the bucket-sort deep phase;
    tree structure and quality must track the scatter builder."""
    rng = np.random.default_rng(5)
    N, D, B, T, depth = 2 * _ROW_TILE, 10, 16, 2, 9  # l_s=6 -> deep at 7+
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = (
        X @ rng.standard_normal(D) + 0.3 * rng.standard_normal(N) > 0
    ).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([(y == 0), (y == 1)]).astype(np.float32)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees), None,
        edges, max_depth=depth, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=7,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET,
    )
    st_old = jnp.asarray(base.T)
    stats_t = jnp.broadcast_to(st_old[None], (T, N, 2))
    f2, t2, v2, ns2, imp2 = grow_forest(
        jnp.asarray(Xb), stats_t, edges, max_depth=depth, n_bins=B,
        kind="gini", max_features=D, min_samples_leaf=1.0,
        min_impurity_decrease=0.0, seed=7,
    )
    f2_h = np.asarray(f2)
    # shallow levels must agree exactly; deep levels tolerate bf16 tie flips
    shallow = slice(0, 2**5 - 1)
    assert (f[:, shallow] == f2_h[:, shallow]).mean() > 0.97
    assert (f == f2_h).mean() > 0.85, (f == f2_h).mean()
    p1 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    p2 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f2), jnp.asarray(t2),
            jnp.asarray(v2), max_depth=depth,
        )
    )
    a1 = (p1.argmax(1) == y).mean()
    a2 = (p2.argmax(1) == y).mean()
    assert abs(a1 - a2) < 0.02, (a1, a2)


@pytest.mark.slow
def test_mxu_deep_phase_skewed_trees():
    """Heavily skewed label distribution concentrates rows in few deep
    buckets — the size-class layout must stay data-proportional and match
    the scatter builder's quality (the round-1 equal-cap layout bailed out
    on this shape)."""
    rng = np.random.default_rng(5)
    N, D, B, T, depth = 2 * _ROW_TILE, 10, 16, 2, 9
    X = rng.standard_normal((N, D)).astype(np.float32)
    # skew: 95% of rows in one tight blob -> one bucket holds most rows
    blob = rng.random(N) < 0.95
    X[blob] *= 0.05
    y = (
        X @ rng.standard_normal(D) + 0.1 * rng.standard_normal(N) > 0
    ).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([(y == 0), (y == 1)]).astype(np.float32)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees), None,
        edges, max_depth=depth, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=7,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET,
    )
    p1 = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    acc = (p1.argmax(1) == y).mean()
    # the 0.05-scale blob leaves a thin margin vs the 0.1 label noise, so
    # ~0.88-0.92 train accuracy is what any builder reaches here
    assert acc > 0.85, acc
    assert np.isfinite(np.asarray(imp)).all()


@pytest.mark.slow
def test_mxu_deep_phase_three_classes():
    """s_dim=3: deep slots are 3 per node — non-power-of-two slot packing
    through the size-class deep phase (and the generic stat axis of the
    bucketed node totals)."""
    rng = np.random.default_rng(9)
    N, D, B, T, depth = 2 * _ROW_TILE, 8, 16, 2, 7  # l_s=5 for s_dim=3
    X = rng.standard_normal((N, D)).astype(np.float32)
    logits = X @ rng.standard_normal((D, 3))
    y = logits.argmax(1).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([(y == c) for c in range(3)]).astype(np.float32)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees), None,
        edges, max_depth=depth, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=7,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET,
    )
    p = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    acc = (p.argmax(1) == y).mean()
    assert acc > 0.85, acc
    assert np.isfinite(np.asarray(imp)).all()


@pytest.mark.slow
def test_mxu_deep_phase_mostly_dead_rows():
    """60% of rows sit in a pure node that leafs at a shallow level, so
    thousands of DEAD rows reach the deep phase — the sorted-layout width
    must account for them (they occupy columns past every bucket), not just
    live + filler rows."""
    rng = np.random.default_rng(13)
    N, D, B, T, depth = 2 * _ROW_TILE, 6, 16, 2, 9
    X = rng.standard_normal((N, D)).astype(np.float32)
    dead = rng.random(N) < 0.6
    X[dead] = 5.0  # one identical (pure) blob far from the rest
    y = np.where(
        dead, 1.0, (X @ rng.standard_normal(D) > 0).astype(np.float64)
    ).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = np.asarray(bin_features(jnp.asarray(X), jnp.asarray(edges)))
    bins_fm = Xb.T.astype(np.int8)
    w_trees = np.ones((T, N), np.float32)
    base = np.stack([(y == 0), (y == 1)]).astype(np.float32)

    f, t, v, ns, imp = grow_forest_mxu(
        jnp.asarray(bins_fm), jnp.asarray(base), jnp.asarray(w_trees), None,
        edges, max_depth=depth, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=3,
        y_vals=jnp.asarray(y), interpret=KERNEL_INTERPRET,
    )
    p = np.asarray(
        forest_predict_kernel(
            jnp.asarray(X), jnp.asarray(f), jnp.asarray(t), jnp.asarray(v),
            max_depth=depth,
        )
    )
    # the pure blob must be perfectly classified; the rest reasonably
    assert (p.argmax(1)[dead] == 1.0).all()
    assert (p.argmax(1) == y).mean() > 0.9


# ---------------------------------------------------------------------------
# The split search's prefix sums: one triangular product (ops/forest_mxu.py,
# _prefix_sums), exact on whole numbers below 2^24.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_bins", [32, 128])
def test_prefix_sums_are_exact_for_whole_numbers_below_2_24(n_bins):
    """On the rows the chip's smoke holds the product to (chip_smoke.py,
    kernel_forest): one bin holding 2^24 - 1, compositions of it, and parts
    whose bfloat16 roundings add up to an odd number above 2^24."""
    from chip_smoke import TOP, hard_histograms
    from spark_rapids_ml_tpu.ops.forest_mxu import _prefix_sums

    x = hard_histograms(np.random.default_rng(n_bins), 3 * 2 * 4 * 8, n_bins)
    want = np.cumsum(x.astype(np.float64), axis=-1)
    assert want.max() == TOP and (want[:, -1] == TOP).sum() >= x.shape[0] // 2
    assert (want[:, 0] == TOP).any()
    hist = jnp.asarray(x.astype(np.float32).reshape(3, 2, 4, 8, n_bins))
    got = np.asarray(jax.jit(_prefix_sums)(hist))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(
        got.astype(np.float64).reshape(want.shape), want
    )


def _seeded_histogram(rng, kind, F_pad, tpack, nodes, B, n):
    """A slot-packed histogram (F_pad, slots, B) of n weighted rows a tree,
    as the kernels build it, with more slots than the search uses, and the
    regression kind's per-node totals.  Node 1 of tree 0 is pure (constant
    target), the last node of the last tree is empty."""
    s_dim = 2
    used = tpack * nodes * s_dim
    H = np.zeros((F_pad, used + 6, B), np.float64)
    tot = np.zeros((tpack, nodes, 3), np.float64)
    for t in range(tpack):
        bins = rng.integers(0, B, (F_pad, n))
        node = rng.integers(0, nodes - (t == tpack - 1), n)
        w = rng.poisson(1.0, n).astype(np.float64)
        signal = bins[2] + 0.5 * bins[5] + rng.normal(0.0, B / 8, n)
        if kind == "regression":
            y = np.round(signal / B * 16) / 4      # quarters: sums stay exact
            if t == 0:
                y[node == 1] = 1.25
            stats = np.stack([w, w * y])
            np.add.at(tot[t], node, np.stack([w, w * y, w * y * y], axis=1))
        else:
            y = (signal > 0.75 * B).astype(np.int64)
            if t == 0:
                y[node == 1] = 1
            stats = np.stack([w * (y == 0), w * (y == 1)])
        for f in range(F_pad):
            for s in range(s_dim):
                np.add.at(H[f], ((t * nodes + node) * s_dim + s, bins[f]), stats[s])
    H[:, used:, :] = 7.0      # slots the search must not read
    return H.astype(np.float32), tot.astype(np.float32)


def _plain_split_search(H, tot, feat_valid, tpack, nodes, kind, msl, mid):
    """The split search, node by node, in float64 numpy: sequential prefix
    sums, gains as ops/forest_mxu states them, ties to the first (feature,
    bin).  Returns _split_from_hist's six outputs and each node's margin
    between its best and its second-best gain."""
    F_pad, _, B = H.shape
    s_dim, eps = 2, 1e-12
    h = H[:, : tpack * nodes * s_dim].astype(np.float64)
    h = h.reshape(F_pad, tpack, nodes, s_dim, B)
    bf = np.zeros((tpack, nodes), np.int32)
    bb = np.zeros((tpack, nodes), np.int32)
    ok = np.zeros((tpack, nodes), bool)
    p_w = np.zeros((tpack, nodes))
    p_imp = np.zeros((tpack, nodes))
    p_val = np.zeros((tpack, nodes, 1 if kind == "regression" else s_dim))
    margin = np.full((tpack, nodes), np.inf)

    def entropy(c, w):      # weight x entropy of class counts c (..., S, ...) on axis 1
        p = c / np.maximum(w, eps)[:, None]
        return -(p * np.log2(np.maximum(p, eps))).sum(axis=1) * w

    def gini(c, w):
        return (c * (w[:, None] - c)).sum(axis=1) / np.maximum(w, eps)

    for t in range(tpack):
        for c in range(nodes):
            left = np.cumsum(h[:, t, c], axis=-1)      # (F_pad, S, B)
            right = left[..., -1:] - left
            if kind == "regression":
                w, wy, wy2 = tot[t, c].astype(np.float64)
                l_w, r_w = left[:, 0], right[:, 0]
                gain = (
                    left[:, 1] ** 2 / np.maximum(l_w, eps)
                    + right[:, 1] ** 2 / np.maximum(r_w, eps)
                    - wy * wy / max(w, eps)
                )
                imp = max(wy2 / max(w, eps) - (wy / max(w, eps)) ** 2, 0.0)
                val = [wy / max(w, eps)]
            else:
                l_w, r_w = left.sum(axis=1), right.sum(axis=1)
                cls = h[0, t, c].sum(axis=-1)
                w = cls.sum()
                wimp = entropy if kind == "entropy" else gini
                p_wimp = wimp(cls[None, :, None], np.array([[w]]))[0, 0]
                gain = p_wimp - (wimp(left, l_w) + wimp(right, r_w))
                imp = p_wimp / max(w, eps)
                val = cls / max(w, eps)
            gain = np.where((l_w >= msl) & (r_w >= msl), gain, -np.inf)
            gain[:, -1] = -np.inf
            gain[~feat_valid] = -np.inf
            flat = gain.reshape(-1)
            best = int(np.argmax(flat))
            bf[t, c], bb[t, c] = divmod(best, B)
            finite = np.sort(flat[np.isfinite(flat)])
            if finite.size > 1:
                margin[t, c] = finite[-1] - finite[-2]
            ok[t, c] = (
                np.isfinite(flat[best]) and imp > 0
                and flat[best] > max(mid * w, 1e-6 * imp * w + 1e-30)
                and w >= 2 * msl
            )
            p_w[t, c], p_imp[t, c], p_val[t, c] = w, imp, val
    return (bf, bb, ok, p_w, p_imp, p_val), margin


@pytest.mark.parametrize("kind", ["gini", "entropy", "regression"])
def test_split_from_hist_matches_a_plain_search(kind):
    from spark_rapids_ml_tpu.ops.forest_mxu import _split_from_hist

    F_pad, tpack, nodes, B, msl, mid = 8, 3, 4, 32, 2.0, 1e-4
    rng = np.random.default_rng(29)
    H, tot = _seeded_histogram(rng, kind, F_pad, tpack, nodes, B, 600)
    feat_valid = np.arange(F_pad) < 6
    want, margin = _plain_split_search(
        H, tot, feat_valid, tpack, nodes, kind, msl, mid
    )
    got = _split_from_hist(
        jnp.asarray(H), jnp.asarray(tot) if kind == "regression" else None,
        jnp.asarray(feat_valid), tpack, nodes, 2, kind, msl, mid,
    )
    bf, bb, ok, p_w, p_imp, p_val = (np.asarray(a) for a in got)
    # where the seeded data decide a split by more than rounding can move
    sure = want[2] & (margin > 1e-3)
    assert sure.sum() >= 8
    assert not want[2][0, 1] and want[4][0, 1] == 0.0, "the pure node stays a leaf"
    assert want[3][-1, -1] == 0 and not want[2][-1, -1], "the empty node too"
    np.testing.assert_array_equal(ok, want[2])
    np.testing.assert_array_equal(bf[sure], want[0][sure])
    np.testing.assert_array_equal(bb[sure], want[1][sure])
    if kind == "regression":
        np.testing.assert_allclose(p_w, want[3], rtol=1e-6)
    else:      # whole numbers: to the bit, and every split is decided
        np.testing.assert_array_equal(p_w.astype(np.float64), want[3])
        np.testing.assert_array_equal(sure, want[2])
        assert (bf.max(), bb.max()) < (6, B - 1)
    np.testing.assert_allclose(p_imp, want[4], rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(p_val, want[5], rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("kind", ["gini", "entropy", "regression"])
def test_split_search_lowers_to_a_product_not_a_scan(kind):
    """XLA:TPU lowers jnp.cumsum to reduce-window, 18 passes' worth of HBM
    traffic where a prefix sum is one (PERF.md, PR 29): the search takes its
    sums as a dot_general, and a scan must not come back unseen on a CPU run."""
    from spark_rapids_ml_tpu.ops.forest_mxu import _split_from_hist

    tot = jnp.zeros((2, 4, 3), jnp.float32) if kind == "regression" else None
    text = str(
        jax.make_jaxpr(
            lambda h, t, v: _split_from_hist(h, t, v, 2, 4, 2, kind, 1.0, 0.0)
        )(jnp.zeros((8, 16, 32), jnp.float32), tot, jnp.ones((8,), bool))
    )
    assert "dot_general" in text
    assert "cumsum" not in text and "reduce_window" not in text
