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
    node_histograms_reference,
)
from spark_rapids_ml_tpu.ops.forest_mxu import (
    grow_forest_mxu,
    mxu_depth_supported,
)

# On a real TPU (SRML_TPU_TESTS=1) run the compiled Mosaic kernels; on the
# CPU mesh interpret — the tests/test_pallas.py switch.
KERNEL_INTERPRET = jax.devices()[0].platform != "tpu"


def test_gather_rows_matmul_exact():
    rng = np.random.default_rng(0)
    N, D, F = 2 * _ROW_TILE, 23, 7
    bins = rng.integers(0, 128, (D, N)).astype(np.int8)
    feats = rng.choice(D, F, replace=False).astype(np.int32)
    sub = np.asarray(
        gather_rows_matmul(
            jnp.asarray(bins), jnp.asarray(feats), f_pad=_F_BLOCK,
            chunk=_ROW_TILE,
        )
    )
    np.testing.assert_array_equal(sub[:F], bins[feats])
    np.testing.assert_array_equal(sub[F:], 0)


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
