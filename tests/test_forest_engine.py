# Device-resident random-forest engine contracts (ops/forest.grow_forest
# rework): mesh-shape parity of the fitted forest (the CI 8-device gate),
# the scan-batched dispatch/transfer collapse (forest.* counters), the
# sharded+psum MXU histogram rule against the numpy oracle, reference
# equivalence against the per-tree grow_tree builder, AOT warm staging, and
# zero-recompile repeat fits.
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import (
    RandomForestClassifier,
    RandomForestRegressor,
    profiling,
)
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops.forest import (
    bin_features,
    compute_bin_edges,
    grow_forest,
    grow_tree,
    warm_forest_kernels,
)
from spark_rapids_ml_tpu.parallel.mesh import get_mesh

# On a real TPU (SRML_TPU_TESTS=1) run the compiled Mosaic kernels; on the
# CPU mesh interpret — the tests/test_pallas.py switch.
KERNEL_INTERPRET = jax.devices()[0].platform != "tpu"


def _cls_df(n=512, d=10, k=3, seed=1):
    from sklearn.datasets import make_classification

    X, y = make_classification(
        n_samples=n, n_features=d, n_informative=min(6, d - 2), n_classes=k,
        random_state=seed,
    )
    return (
        DataFrame.from_numpy(
            X.astype(np.float64), y=y.astype(np.float64), num_partitions=2
        ),
        X,
        y,
    )


def _int_reg_df(n=512, d=8, seed=0):
    """Regression fixture with SMALL-INTEGER targets: every histogram stat
    (w, w*y, w*y^2) is an exact small integer in f32, so per-shard partial
    sums + psum equal the single-device sums BITWISE regardless of
    reduction order — the documented exactness basis of the parity gate."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float64)
    y = rng.integers(0, 8, size=n).astype(np.float64)
    return DataFrame.from_numpy(X, y=y, num_partitions=2), X, y


def test_mesh_parity_classifier():
    """The acceptance gate: a fixed seed must produce the IDENTICAL forest
    (features, thresholds, leaf values) on a 1-device and an 8-device mesh.
    Exactness argument: n = 512 rows divide every mesh size, so the padded
    row count — and with it every Poisson bootstrap draw and feature-subset
    draw — is mesh-independent; one-hot class stats times integer bootstrap
    weights are exact in f32, so the psum-combined shard histograms match
    the single-device histograms bitwise and every gain/argmax agrees."""
    df, X, y = _cls_df()
    kw = dict(numTrees=6, maxDepth=5, maxBins=16, seed=5)
    m1 = RandomForestClassifier(**kw, num_workers=1).fit(df)
    m8 = RandomForestClassifier(**kw, num_workers=None).fit(df)
    np.testing.assert_array_equal(m1.features_, m8.features_)
    np.testing.assert_array_equal(m1.thresholds_, m8.thresholds_)
    np.testing.assert_array_equal(m1.leaf_values_, m8.leaf_values_)
    np.testing.assert_array_equal(m1.node_counts_, m8.node_counts_)
    np.testing.assert_array_equal(m1.bootstrap_draw_, m8.bootstrap_draw_)
    # and the forest learned something on either mesh.  A sanity bound with a
    # margin, not a quality gate (that is the equality above): 6 trees of
    # depth 5 on 512 rows of 3 classes read 0.8457 since jax 0.9.0 moved the
    # bootstrap stream (0.86 before); chance is 0.33
    acc = (
        m8.transform(df).toPandas()["prediction"].to_numpy() == y
    ).mean()
    assert acc > 0.75, acc


def test_mesh_parity_regressor_integer_targets():
    df, X, y = _int_reg_df()
    kw = dict(numTrees=4, maxDepth=5, maxBins=16, seed=2)
    m1 = RandomForestRegressor(**kw, num_workers=1).fit(df)
    m8 = RandomForestRegressor(**kw, num_workers=None).fit(df)
    np.testing.assert_array_equal(m1.features_, m8.features_)
    np.testing.assert_array_equal(m1.thresholds_, m8.thresholds_)
    np.testing.assert_array_equal(m1.leaf_values_, m8.leaf_values_)


def _grow_fixture(n=1024, d=6, B=16, T=3, seed=4):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) > 0).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = bin_features(jnp.asarray(X), jnp.asarray(edges))
    stats = np.stack([1.0 - y, y], axis=1).astype(np.float32)
    stats_t = jnp.broadcast_to(jnp.asarray(stats)[None], (T, n, 2))
    return Xb, stats_t, edges


def test_dispatch_and_transfer_counters(monkeypatch):
    """The scan-batching acceptance bound: ceil(levels / LEVEL_BLOCK)
    engine dispatches, ONE early-stop flag sync per block, and ONE
    device_get for the whole forest."""
    monkeypatch.setenv("SRML_FOREST_LEVEL_BLOCK", "2")
    Xb, stats_t, edges = _grow_fixture()
    kw = dict(
        max_depth=5, n_bins=16, kind="gini", max_features=6,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=3,
    )
    c0 = profiling.counters("forest")
    grow_forest(Xb, stats_t, edges, **kw)
    d = profiling.counter_deltas(c0, "forest")
    assert d.get("forest.levels.dispatches", 0) == 3  # ceil(6 / 2)
    assert d.get("forest.level_syncs", 0) == 3
    assert d.get("forest.d2h_transfers", 0) == 1


def test_early_stop_skips_dead_level_blocks(monkeypatch):
    """Constant features leaf every tree at the root: the on-device
    any-split mask must stop the block loop after the FIRST dispatch
    instead of running all ceil(levels/block) blocks."""
    monkeypatch.setenv("SRML_FOREST_LEVEL_BLOCK", "2")
    n, T = 256, 2
    Xb = jnp.zeros((n, 4), jnp.int8)
    y = np.zeros(n, np.float32)
    y[::2] = 1.0
    stats = np.stack([1.0 - y, y], axis=1).astype(np.float32)
    stats_t = jnp.broadcast_to(jnp.asarray(stats)[None], (T, n, 2))
    edges = np.zeros((4, 7), np.float32)
    c0 = profiling.counters("forest")
    f, t, v, ns, imp = grow_forest(
        Xb, stats_t, edges, max_depth=5, n_bins=8, kind="gini",
        max_features=4, min_samples_leaf=1.0, min_impurity_decrease=0.0,
        seed=0,
    )
    d = profiling.counter_deltas(c0, "forest")
    assert d.get("forest.levels.dispatches", 0) == 1
    assert (f == -1).all()  # pure roots: no splits anywhere
    np.testing.assert_allclose(ns[:, 0], n)


def test_engine_matches_reference_grow_tree():
    """No bootstrap + all features: the engine and the kept per-tree
    reference builder (grow_tree) are deterministic on the same binned
    data and must grow IDENTICAL trees — on the 1-device mesh by identical
    ops, and on the full mesh because integer class stats make the
    psum-combined histograms bitwise equal to the single-pass sums."""
    Xb, stats_t, edges = _grow_fixture(T=2)
    kw = dict(
        max_depth=5, n_bins=16, kind="gini", max_features=6,
        min_samples_leaf=1.0, min_impurity_decrease=0.0,
    )
    ref = grow_tree(Xb, stats_t[0], edges, seed=11, **kw)
    for mesh in (get_mesh(1), get_mesh()):
        f, t, v, ns, imp = grow_forest(
            Xb, stats_t, edges, seed=11, mesh=mesh, **kw
        )
        for tree in range(2):
            np.testing.assert_array_equal(f[tree], np.asarray(ref.feature))
            np.testing.assert_allclose(t[tree], np.asarray(ref.threshold))
            np.testing.assert_allclose(
                v[tree], np.asarray(ref.leaf_value), atol=1e-6
            )
            np.testing.assert_allclose(
                ns[tree], np.asarray(ref.n_samples), atol=1e-4
            )


def test_sharded_histogram_rule_matches_oracle():
    """forest_hist.node_histograms_sharded (per-shard pallas pass + one
    psum) must reproduce the plain-numpy oracle on the 8-device mesh —
    the interpret-mode gate for the MXU path's sharding rule."""
    from spark_rapids_ml_tpu.ops.forest_hist import (
        _F_BLOCK,
        _ROW_TILE,
        node_histograms_reference,
        node_histograms_sharded,
    )

    mesh = get_mesh()
    n_dev = mesh.devices.size
    rng = np.random.default_rng(6)
    N = n_dev * _ROW_TILE
    T, nodes, S, B = 2, 4, 2, 16
    sub = rng.integers(0, B, (_F_BLOCK, N)).astype(np.int8)
    node_rel = rng.integers(0, nodes + 2, (T, N)).astype(np.int32)
    stats = rng.integers(0, 4, (T * S, N)).astype(np.float32)
    H = np.asarray(
        node_histograms_sharded(
            jnp.asarray(sub), jnp.asarray(node_rel), jnp.asarray(stats),
            mesh=mesh, t_pack=T, nodes=nodes, s_dim=S, n_bins=B,
            interpret=KERNEL_INTERPRET,
        )
    )
    Href = node_histograms_reference(sub, node_rel, stats, T, nodes, S, B)
    # integer-valued stats: the bf16 one-hot matmuls and the psum are exact
    np.testing.assert_allclose(H, Href, rtol=2e-2, atol=1e-3)


def test_warm_forest_kernels_covers_the_fit():
    """warm_forest_kernels must enumerate the exact executables the engine
    dispatches: after warming (and draining the compile pool) a first-ever
    grow_forest at that geometry performs ZERO new compilations and never
    falls back to plain jit."""
    from spark_rapids_ml_tpu.ops.precompile import global_precompiler

    Xb, stats_t, edges = _grow_fixture(n=768, d=5, B=8, T=2, seed=9)
    mesh = get_mesh()
    kw = dict(
        max_depth=4, n_bins=8, kind="gini", max_features=5,
        min_samples_leaf=1.0, min_impurity_decrease=0.0,
    )
    keys = warm_forest_kernels(768, 5, 2, 2, mesh=mesh, dtype=np.float32, **kw)
    assert keys
    global_precompiler().wait(keys)
    c0 = profiling.counters("precompile")
    grow_forest(Xb, stats_t, edges, seed=1, mesh=mesh, **kw)
    d = profiling.counter_deltas(c0, "precompile")
    assert d.get("precompile.compile", 0) == 0, d
    assert d.get("precompile.fallback", 0) == 0, d
    assert d.get("precompile.aot_hit", 0) >= len(keys) - 1  # early stop may skip blocks


def test_repeat_fit_zero_new_compiles():
    """The acceptance smoke mirroring test_umap_engine: a second same-shape
    RandomForest fit performs ZERO new compilations — every engine kernel
    lands on a cached AOT executable — and grows the identical forest."""
    df, X, y = _cls_df(n=256, d=6, seed=3)
    est = RandomForestClassifier(numTrees=4, maxDepth=4, maxBins=8, seed=7)
    m1 = est.fit(df)
    c0 = profiling.counters("precompile")
    m2 = est.fit(df)
    d = profiling.counter_deltas(c0, "precompile")
    assert d.get("precompile.compile", 0) == 0, d
    assert d.get("precompile.fallback", 0) == 0, d
    assert d.get("precompile.aot_hit", 0) > 0, d
    np.testing.assert_array_equal(m1.features_, m2.features_)
    np.testing.assert_array_equal(m1.leaf_values_, m2.leaf_values_)


def test_repeat_transform_zero_new_compiles():
    """Prediction rides the same executable cache (power-of-two row
    buckets): a repeat transform at the same partition shape compiles
    nothing new."""
    df, X, y = _cls_df(n=256, d=6, seed=3)
    model = RandomForestClassifier(numTrees=4, maxDepth=4, maxBins=8, seed=7).fit(df)
    p1 = model.transform(df).toPandas()["prediction"].to_numpy()
    c0 = profiling.counters("precompile")
    p2 = model.transform(df).toPandas()["prediction"].to_numpy()
    d = profiling.counter_deltas(c0, "precompile")
    assert d.get("precompile.compile", 0) == 0, d
    np.testing.assert_array_equal(p1, p2)


def test_engine_min_samples_and_depth_gates():
    """The engine must honor min_samples_leaf and the depth cap exactly as
    the split gate documents: split nodes carry >= 2*min samples and the
    bottom level never splits."""
    Xb, stats_t, edges = _grow_fixture(n=512, T=2, seed=12)
    f, t, v, ns, imp = grow_forest(
        Xb, stats_t, edges, max_depth=3, n_bins=16, kind="gini",
        max_features=6, min_samples_leaf=40.0, min_impurity_decrease=0.0,
        seed=5, mesh=get_mesh(),
    )
    split = f >= 0
    assert ns[split].min() >= 2 * 40.0
    assert not split[:, 7:].any()  # nodes at the depth cap are leaves
