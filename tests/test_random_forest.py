# RandomForest classifier/regressor quality vs sklearn + persistence +
# evaluate (strategy modeled on the reference's test_random_forest.py).
import numpy as np
import pytest

from spark_rapids_ml_tpu import (
    RandomForestClassificationModel,
    RandomForestClassifier,
    RandomForestRegressionModel,
    RandomForestRegressor,
)
from spark_rapids_ml_tpu.core import load
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.evaluation import (
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)


def _cls_data(n=500, d=8, k=3, seed=0):
    from sklearn.datasets import make_classification

    X, y = make_classification(
        n_samples=n, n_features=d, n_informative=5, n_classes=k, random_state=seed
    )
    return X.astype(np.float64), y.astype(np.float64)


def _reg_data(n=500, d=8, seed=0):
    from sklearn.datasets import make_regression

    X, y = make_regression(n_samples=n, n_features=d, n_informative=5, noise=5.0, random_state=seed)
    return X.astype(np.float64), y.astype(np.float64)


def test_default_params():
    rf = RandomForestClassifier()
    assert rf.tpu_params["n_estimators"] == 20   # spark numTrees default
    assert rf.tpu_params["n_bins"] == 32         # spark maxBins default
    assert rf.tpu_params["max_depth"] == 5       # spark maxDepth default
    assert rf.tpu_params["split_criterion"] == "gini"
    rf = RandomForestRegressor(numTrees=7, maxBins=16, maxDepth=4)
    assert rf.tpu_params["n_estimators"] == 7
    assert rf.tpu_params["split_criterion"] == "variance"


def test_param_mapping_and_unsupported():
    rf = RandomForestClassifier(featureSubsetStrategy="onethird")
    assert rf.tpu_params["max_features"] == pytest.approx(1 / 3)
    rf = RandomForestClassifier(featureSubsetStrategy="0.5")
    assert rf.tpu_params["max_features"] == 0.5
    with pytest.raises(ValueError):
        RandomForestClassifier(weightCol="w")
    with pytest.raises(ValueError):
        RandomForestClassifier(impurity="nope")
    # silently-ignored params accepted
    rf = RandomForestClassifier(minInfoGain=0.1, subsamplingRate=0.5)
    assert "minInfoGain" not in rf.tpu_params


@pytest.mark.slow
def test_classifier_accuracy():
    X, y = _cls_data()
    df = DataFrame.from_numpy(X, y=y, num_partitions=4)
    model = RandomForestClassifier(numTrees=30, maxDepth=8, seed=7).fit(df)
    out = model.transform(df).toPandas()
    acc = (out["prediction"].to_numpy() == y).mean()
    assert acc > 0.9, acc
    probs = np.stack(out["probability"].to_numpy())
    assert probs.shape == (len(y), 3)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    raw = np.stack(out["rawPrediction"].to_numpy())
    assert raw.shape == (len(y), 3)
    assert model.numClasses == 3
    assert model.getNumTrees == 30


@pytest.mark.slow
def test_classifier_vs_sklearn_holdout():
    from sklearn.ensemble import RandomForestClassifier as SkRF
    from sklearn.model_selection import train_test_split

    X, y = _cls_data(n=800)
    Xtr, Xte, ytr, yte = train_test_split(X, y, random_state=0)
    df = DataFrame.from_numpy(Xtr, y=ytr, num_partitions=4)
    model = RandomForestClassifier(numTrees=40, maxDepth=8, seed=3).fit(df)
    ours = (
        model.transform(DataFrame.from_numpy(Xte)).toPandas()["prediction"].to_numpy()
    )
    sk = SkRF(n_estimators=40, max_depth=8, random_state=3).fit(Xtr, ytr)
    acc_ours = (ours == yte).mean()
    acc_sk = (sk.predict(Xte) == yte).mean()
    assert acc_ours >= acc_sk - 0.05, (acc_ours, acc_sk)


def test_regressor_quality():
    from sklearn.ensemble import RandomForestRegressor as SkRF
    from sklearn.metrics import r2_score

    X, y = _reg_data()
    df = DataFrame.from_numpy(X, y=y, num_partitions=4)
    # 10 trees depth 6 keep the quality claim while shrinking the default
    # CI cost of this test (was 30 x depth-8, ~23 s)
    model = RandomForestRegressor(numTrees=10, maxDepth=6, seed=5).fit(df)
    preds = model.transform(df).toPandas()["prediction"].to_numpy()
    r2 = r2_score(y, preds)
    sk = SkRF(n_estimators=10, max_depth=6, random_state=5).fit(X, y)
    r2_sk = r2_score(y, sk.predict(X))
    assert r2 > 0.8, r2
    assert r2 >= r2_sk - 0.15, (r2, r2_sk)


@pytest.mark.slow
def test_binary_classification():
    X, y = _cls_data(k=2)
    df = DataFrame.from_numpy(X, y=y, num_partitions=3)
    model = RandomForestClassifier(numTrees=20, maxDepth=6, seed=1).fit(df)
    out = model.transform(df).toPandas()
    assert (out["prediction"].to_numpy() == y).mean() > 0.9
    assert model.predict(X[0]) in (0.0, 1.0)
    assert model.predictProbability(X[0]).shape == (2,)


def test_no_bootstrap_deterministic_with_all_features():
    X, y = _reg_data(n=200)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    kw = dict(numTrees=3, maxDepth=5, bootstrap=False, featureSubsetStrategy="all", seed=1)
    m1 = RandomForestRegressor(**kw).fit(df)
    # without bootstrap and with all features every tree is identical
    assert np.array_equal(m1.features_[0], m1.features_[1])
    np.testing.assert_allclose(m1.leaf_values_[0], m1.leaf_values_[2])


def test_min_instances_per_node():
    X, y = _cls_data(n=300, k=2)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    model = RandomForestClassifier(numTrees=5, maxDepth=8, minInstancesPerNode=50, seed=2).fit(df)
    # every split node must have had >= 2*min instances to split at all
    split_counts = model.node_counts_[model.features_ >= 0]
    assert split_counts.min() >= 2 * 50
    # children of any split satisfy the constraint: check leaves reached by data
    leaf_counts = model.node_counts_[(model.features_ < 0) & (model.node_counts_ > 0)]
    assert leaf_counts.min() >= 50


@pytest.mark.slow
def test_transform_evaluate():
    X, y = _cls_data(n=300)
    df = DataFrame.from_numpy(X, y=y, num_partitions=3)
    model = RandomForestClassifier(numTrees=10, maxDepth=6, seed=4).fit(df)
    ev = MulticlassClassificationEvaluator(metricName="accuracy")
    scores = model._transformEvaluate(df, ev)
    direct = ev.evaluate(model.transform(df))
    assert abs(scores[0] - direct) < 1e-9

    Xr, yr = _reg_data(n=300)
    dfr = DataFrame.from_numpy(Xr, y=yr, num_partitions=3)
    rmodel = RandomForestRegressor(numTrees=10, maxDepth=6, seed=4).fit(dfr)
    evr = RegressionEvaluator(metricName="rmse")
    scores = rmodel._transformEvaluate(dfr, evr)
    direct = evr.evaluate(rmodel.transform(dfr))
    assert abs(scores[0] - direct) < 1e-9


@pytest.mark.slow
def test_persistence(tmp_path):
    X, y = _cls_data(n=200)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    model = RandomForestClassifier(numTrees=8, maxDepth=5, seed=9).fit(df)
    model.save(str(tmp_path / "rf"))
    loaded = load(str(tmp_path / "rf"))
    assert isinstance(loaded, RandomForestClassificationModel)
    p1 = model.transform(df).toPandas()["prediction"]
    p2 = loaded.transform(df).toPandas()["prediction"]
    assert (p1 == p2).all()

    Xr, yr = _reg_data(n=150)
    dfr = DataFrame.from_numpy(Xr, y=yr, num_partitions=2)
    rmodel = RandomForestRegressor(numTrees=5, maxDepth=4, seed=9).fit(dfr)
    rmodel.save(str(tmp_path / "rfr"))
    rloaded = load(str(tmp_path / "rfr"))
    assert isinstance(rloaded, RandomForestRegressionModel)
    np.testing.assert_allclose(
        rloaded.transform(dfr).toPandas()["prediction"],
        rmodel.transform(dfr).toPandas()["prediction"],
    )


def test_trees_to_dicts():
    X, y = _reg_data(n=150)
    model = RandomForestRegressor(numTrees=2, maxDepth=3, seed=0).fit(
        DataFrame.from_numpy(X, y=y)
    )
    dicts = model.trees_to_dicts()
    assert len(dicts) == 2
    root = dicts[0]
    assert "split_feature" in root and "yes" in root and "no" in root


def test_max_depth_limit():
    X, y = _reg_data(n=100)
    with pytest.raises(ValueError, match="maxDepth"):
        RandomForestRegressor(maxDepth=20).fit(DataFrame.from_numpy(X, y=y))


@pytest.mark.slow
def test_fit_multiple():
    X, y = _cls_data(n=250)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    est = RandomForestClassifier(maxDepth=5, seed=11)
    pmaps = [
        {RandomForestClassifier.numTrees: 5},
        {RandomForestClassifier.numTrees: 10},
    ]
    models = [m for _, m in est.fitMultiple(df, pmaps)]
    assert models[0].getNumTrees == 5
    assert models[1].getNumTrees == 10


@pytest.mark.slow
def test_wide_level_kernel_matches_node_chunked():
    # the deep-level one-pass kernel (level_split_kernel_wide) must grow the
    # same tree as the node-chunked kernel; force it by shrinking node_batch
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.forest import (
        bin_features,
        compute_bin_edges,
        grow_tree,
    )

    rng = np.random.default_rng(5)
    N, D, B = 2000, 12, 32
    X = rng.normal(size=(N, D)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 3] > 0).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = bin_features(jnp.asarray(X), jnp.asarray(edges))
    stats = jnp.asarray(
        np.stack([1.0 - y, y], axis=1).astype(np.float32)
    )
    kw = dict(
        max_depth=6, n_bins=B, kind="gini", max_features=D,
        min_samples_leaf=1.0, min_impurity_decrease=0.0, seed=3,
    )
    t_chunked = grow_tree(Xb, stats, edges, node_batch=256, **kw)
    t_wide = grow_tree(Xb, stats, edges, node_batch=1, **kw)  # all levels >1 wide
    np.testing.assert_array_equal(
        np.asarray(t_chunked.feature), np.asarray(t_wide.feature)
    )
    np.testing.assert_allclose(
        np.asarray(t_chunked.threshold), np.asarray(t_wide.threshold)
    )
    np.testing.assert_allclose(
        np.asarray(t_chunked.leaf_value), np.asarray(t_wide.leaf_value), atol=1e-6
    )


def test_wide_level_kernel_feature_subset_and_chunking():
    # wide path with max_features < D and feat_batch smaller than D (uneven)
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.forest import (
        bin_features,
        compute_bin_edges,
        level_split_kernel_wide,
    )

    rng = np.random.default_rng(7)
    N, D, B, n_nodes = 500, 10, 16, 4
    X = rng.normal(size=(N, D)).astype(np.float32)
    edges = compute_bin_edges(X, B)
    Xb = bin_features(jnp.asarray(X), jnp.asarray(edges))
    yb = rng.integers(0, 2, N)
    stats = jnp.asarray(np.stack([1.0 - yb, yb], axis=1).astype(np.float32))
    rel = jnp.asarray(rng.integers(0, n_nodes, N).astype(np.int32))
    out = level_split_kernel_wide(
        Xb, stats, rel, jax.random.PRNGKey(0),
        n_nodes=n_nodes, n_bins=B, feat_batch=3, kind="gini",
        max_features=4, min_samples_leaf=1.0, min_impurity_decrease=0.0,
    )
    bf, bb, ok, cnt, imp, val = [np.asarray(o) for o in out]
    assert bf.shape == (n_nodes,) and np.all((bf >= 0) & (bf < D))
    assert np.all((bb >= 0) & (bb < B))
    np.testing.assert_allclose(cnt.sum(), N)


def test_mxu_route_wiring_feature_major(monkeypatch):
    """The MXU route is TPU-gated, so a broken symbol/shape in its wiring
    would merge green on the CPU suite (round-4 regression: the lazily
    bound feature-major binner raised NameError only on hardware).  Force
    the route (its kernels run through the Pallas interpreter off the
    chip) and verify _grow_mxu_device receives the int8 feature-major bins,
    a feature a slice of whole (32, 128) tiles (the subset gather's layout:
    tile_feature_rows of the (D, n_pad) table), and its result flows into
    the model."""
    import numpy as np

    import spark_rapids_ml_tpu.models.random_forest as rfm
    from spark_rapids_ml_tpu import RandomForestRegressor
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops.forest_hist import _ROW_TILE

    rng = np.random.default_rng(4)
    X = rng.standard_normal((300, 7)).astype(np.float32)
    y = (X @ np.ones(7, np.float32)).astype(np.float32)
    seen = {}

    monkeypatch.setattr(
        rfm, "_mxu_eligible", lambda *a, **kw: True
    )
    real = rfm._grow_mxu_device

    def _spy(inputs, bins_fm, *a, **kw):
        seen["shape"] = tuple(bins_fm.shape)
        seen["dtype"] = str(bins_fm.dtype)
        return real(inputs, bins_fm, *a, **kw)

    monkeypatch.setattr(rfm, "_grow_mxu_device", _spy)
    # one device, as the route's gate asks: the builder drives a single chip
    model = RandomForestRegressor(
        numTrees=3, maxDepth=3, maxBins=8, num_workers=1
    ).fit(DataFrame.from_numpy(X, y))
    n_pad = -(-X.shape[0] // _ROW_TILE) * _ROW_TILE
    assert n_pad == _ROW_TILE     # half a 4096-row tile: the other half is padding
    assert seen["shape"] == (7, 1, 32, 128) and seen["dtype"] == "int8"
    assert model.getNumTrees == 3 and (model.features_[:, 0] >= 0).all()
    assert model.node_counts_[:, 0].max() <= 2 * 300    # padding rows weigh nothing
    pred = model.transform(DataFrame.from_numpy(X)).toPandas()["prediction"]
    assert np.corrcoef(pred.to_numpy(), y)[0, 1] > 0.4   # 3 trees of depth 3: reads 0.62


def test_device_bin_edges_match_host():
    """compute_bin_edges_device (chunked device sort + f32 interpolation)
    must reproduce the host float64 quantile edges up to f32 interpolation
    error — including a ragged column count that exercises the 256-column
    chunk padding."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.forest import (
        compute_bin_edges,
        compute_bin_edges_device,
    )

    rng = np.random.default_rng(17)
    for S, D, B in [(2778, 300, 128), (513, 700, 32), (100, 5, 16)]:
        # offset-heavy features stress the f32 interpolation the most
        X = (rng.normal(size=(S, D)) * rng.gamma(1.0, 5.0, size=D)[None]
             + rng.normal(size=D)[None] * 100).astype(np.float32)
        host = compute_bin_edges(X, B)
        dev = compute_bin_edges_device(jnp.asarray(X), B)
        assert dev.shape == host.shape == (D, B - 1)
        np.testing.assert_allclose(dev, host, rtol=2e-5, atol=2e-4)
