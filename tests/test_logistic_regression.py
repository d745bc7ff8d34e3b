# LogisticRegression correctness vs sklearn (binary/multinomial, L2/L1/EN) +
# single-pass fitMultiple + transform-evaluate (strategy modeled on the
# reference's test_logistic_regression.py).
import numpy as np
import pytest

from spark_rapids_ml_tpu import LogisticRegression, LogisticRegressionModel
from spark_rapids_ml_tpu.core import load
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator


def _cls_data(n=500, d=8, k=2, seed=0, sep=2.0):
    rng = np.random.default_rng(seed)
    centers = sep * rng.normal(size=(k, d))
    y = rng.integers(0, k, size=n)
    X = centers[y] + rng.normal(size=(n, d))
    return X.astype(np.float64), y.astype(np.float64)


def _df(X, y, parts=4):
    return DataFrame.from_numpy(X, y=y, num_partitions=parts)


def test_default_params():
    lr = LogisticRegression()
    assert lr.tpu_params["penalty"] == "none"  # regParam default 0
    assert lr.tpu_params["C"] == 0.0
    lr = LogisticRegression(regParam=0.5)
    assert lr.tpu_params["penalty"] == "l2"
    assert lr.tpu_params["C"] == 2.0
    lr = LogisticRegression(regParam=0.5, elasticNetParam=1.0)
    assert lr.tpu_params["penalty"] == "l1"
    lr = LogisticRegression(regParam=0.5, elasticNetParam=0.4)
    assert lr.tpu_params["penalty"] == "elasticnet"
    assert lr.tpu_params["l1_ratio"] == 0.4


def test_unsupported_params():
    with pytest.raises(ValueError):
        LogisticRegression(threshold=0.7)
    with pytest.raises(ValueError):
        LogisticRegression(weightCol="w")
    # ignored params accepted
    lr = LogisticRegression(standardization=False, family="binomial")
    assert "standardization" not in lr.tpu_params


def test_binary_l2_matches_sklearn():
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = _cls_data()
    reg = 0.1
    model = LogisticRegression(regParam=reg, maxIter=500, tol=1e-10).fit(_df(X, y))
    # spark objective: (1/n)sum logloss + reg*||w||^2/2 == sklearn C=1/(reg*n)
    sk = SkLR(C=1.0 / (reg * len(y)), max_iter=5000, tol=1e-12).fit(X, y)
    np.testing.assert_allclose(model.coefficients, sk.coef_[0], atol=2e-2)
    assert abs(model.intercept - sk.intercept_[0]) < 2e-2
    assert model.numClasses == 2
    assert model.coef_.shape == (1, 8)


def test_binary_transform_accuracy():
    X, y = _cls_data(n=400, sep=3.0)
    df = _df(X, y)
    model = LogisticRegression(regParam=0.01, maxIter=200).fit(df)
    out = model.transform(df).toPandas()
    acc = (out["prediction"].to_numpy() == y).mean()
    assert acc > 0.95
    probs = np.stack(out["probability"].to_numpy())
    assert probs.shape == (400, 2)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    raw = np.stack(out["rawPrediction"].to_numpy())
    assert raw.shape == (400, 2)
    np.testing.assert_allclose(raw[:, 0], -raw[:, 1], atol=1e-6)


def test_multinomial_matches_sklearn():
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = _cls_data(n=600, d=6, k=4)
    reg = 0.05
    model = LogisticRegression(regParam=reg, maxIter=500, tol=1e-10).fit(_df(X, y))
    assert model.numClasses == 4
    assert model.coefficientMatrix.shape == (4, 6)
    sk = SkLR(C=1.0 / (reg * len(y)), max_iter=5000, tol=1e-12).fit(X, y)
    df = _df(X, y)
    ours = model.transform(df).toPandas()["prediction"].to_numpy()
    theirs = sk.predict(X)
    assert (ours == theirs).mean() > 0.98


def test_l1_sparsity():
    X, y = _cls_data(n=400, d=20)
    # only first 3 features informative
    X[:, 3:] = np.random.default_rng(1).normal(size=(400, 17))
    model = LogisticRegression(regParam=0.1, elasticNetParam=1.0, maxIter=500).fit(
        _df(X, y)
    )
    coef = np.asarray(model.coefficients)
    # OWL-QN must produce exact zeros on noise features
    assert (coef == 0.0).sum() >= 10
    # signal features survive (center draw can leave one near-zero)
    assert (np.abs(coef[:3]) > 0).sum() >= 2


def test_noncontiguous_labels():
    X, y = _cls_data(n=300, k=2)
    y = np.where(y == 0, 3.0, 7.0)
    df = _df(X, y)
    model = LogisticRegression(maxIter=100).fit(df)
    np.testing.assert_array_equal(model.classes_, [3.0, 7.0])
    preds = model.transform(df).toPandas()["prediction"].unique()
    assert set(preds) <= {3.0, 7.0}


def test_fit_multiple_single_pass():
    X, y = _cls_data()
    df = _df(X, y)
    est = LogisticRegression(maxIter=200)
    pmaps = [
        {LogisticRegression.regParam: 0.01},
        {LogisticRegression.regParam: 1.0},
    ]
    models = [m for _, m in est.fitMultiple(df, pmaps)]
    assert len(models) == 2
    for pm, m in zip(pmaps, models):
        solo = est.copy(pm).fit(df)
        np.testing.assert_allclose(
            np.asarray(m.coefficients), np.asarray(solo.coefficients), atol=1e-4
        )
    # heavier regularization shrinks coefficients
    assert np.linalg.norm(models[1].coefficients) < np.linalg.norm(models[0].coefficients)


def test_combine_and_transform_evaluate():
    X, y = _cls_data(n=400)
    df = _df(X, y)
    est = LogisticRegression(maxIter=200)
    m0 = est.copy({LogisticRegression.regParam: 0.001}).fit(df)
    m1 = est.copy({LogisticRegression.regParam: 100.0}).fit(df)
    combined = LogisticRegressionModel._combine([m0, m1])
    for metric in ("accuracy", "f1", "logLoss"):
        ev = MulticlassClassificationEvaluator(metricName=metric)
        scores = combined._transformEvaluate(df, ev)
        assert len(scores) == 2
        direct = ev.evaluate(m0.transform(df))
        assert abs(scores[0] - direct) < 1e-9, metric
    # near-unregularized beats heavily-regularized on train accuracy
    ev = MulticlassClassificationEvaluator(metricName="accuracy")
    s = combined._transformEvaluate(df, ev)
    assert s[0] >= s[1]


def test_persistence(tmp_path):
    X, y = _cls_data(n=200)
    df = _df(X, y)
    model = LogisticRegression(regParam=0.1).fit(df)
    model.save(str(tmp_path / "m"))
    loaded = load(str(tmp_path / "m"))
    assert isinstance(loaded, LogisticRegressionModel)
    np.testing.assert_allclose(loaded.coef_, model.coef_)
    np.testing.assert_array_equal(loaded.classes_, model.classes_)
    p1 = model.transform(df).toPandas()["prediction"]
    p2 = loaded.transform(df).toPandas()["prediction"]
    assert (p1 == p2).all()


def test_predict_single():
    X, y = _cls_data(n=200, sep=4.0)
    model = LogisticRegression(maxIter=100).fit(_df(X, y))
    pred = model.predict(X[0])
    assert pred in (0.0, 1.0)
    probs = model.predictProbability(X[0])
    assert probs.shape == (2,)
    np.testing.assert_allclose(probs.sum(), 1.0, atol=1e-5)


def test_float64_warns_and_ignores():
    X, y = _cls_data(n=100)
    lr = LogisticRegression(float32_inputs=False)
    assert lr._float32_inputs is True
    model = lr.fit(_df(X, y))
    assert model.dtype == "float32"


# -- the one-pass data term (ops/logistic_pass.py) through the public fit -----
# Off the chip the rule `one_pass_objective` is False; a test steers it (the
# program has no option for it) and the kernel then runs interpreted.


def _force_one_pass(monkeypatch, on=True):
    from spark_rapids_ml_tpu.ops import logistic, logistic_pass

    monkeypatch.setattr(logistic, "pallas_enabled", lambda: on)
    # a test's table is one XLA would keep in VMEM: the rule leaves those alone
    monkeypatch.setattr(logistic_pass, "_RESIDENT_BYTES", 0)
    # the jitted entry keeps what it traced: the rule is read at trace time
    logistic.logistic_fit_kernel.clear_cache()


@pytest.mark.parametrize("num_workers", [1, 8], ids=["one_device", "mesh8"])
def test_one_pass_fit_matches_autodiff_fit_and_sklearn(monkeypatch, num_workers):
    import jax
    from sklearn.linear_model import LogisticRegression as SkLR

    from spark_rapids_ml_tpu.ops import logistic

    X, y = _cls_data(n=2300, d=8, seed=3, sep=0.7)   # 287-288 rows a device of 8
    reg = 0.05
    est = LogisticRegression(
        regParam=reg, maxIter=200, tol=1e-10, num_workers=num_workers
    )

    def fit():
        model = est.fit(_df(X, y))
        return np.append(model.coefficients, model.intercept), model.fit_telemetry().counters

    _force_one_pass(monkeypatch)
    one_pass, counted = fit()
    assert counted["lbfgs.fits"] == 1 and counted["lbfgs.one_pass_fits"] == 1

    # the objective L-BFGS had before: autodiff of the data loss
    _force_one_pass(monkeypatch, on=False)

    def autodiff(X, y01, w, d, fit_intercept, mesh):
        return jax.value_and_grad(
            lambda t: logistic._binary_data_loss(t, X, y01, w, d, fit_intercept)
        )

    monkeypatch.setattr(logistic, "_binary_value_and_grad", autodiff)
    before, counted = fit()
    assert counted["lbfgs.fits"] == 1 and "lbfgs.one_pass_fits" not in counted
    monkeypatch.undo()
    logistic.logistic_fit_kernel.clear_cache()
    plain, _ = fit()                                  # the closed form in plain jnp

    np.testing.assert_allclose(one_pass, before, atol=2e-4)
    np.testing.assert_allclose(plain, before, atol=2e-4)
    sk = SkLR(C=1.0 / (reg * len(y)), max_iter=5000, tol=1e-12).fit(X, y)
    np.testing.assert_allclose(one_pass[:-1], sk.coef_[0], atol=2e-3)
    assert abs(one_pass[-1] - sk.intercept_[0]) < 2e-3


@pytest.mark.parametrize(
    "case,takes_it",
    [("dense_binary", True), ("multinomial", False), ("sparse_binary", False),
     ("dense_binary_disabled", False), ("dense_binary_that_fits_vmem", False)],
)
def test_one_pass_fits_counts_exactly_the_fits_that_took_it(monkeypatch, case, takes_it):
    from spark_rapids_ml_tpu.ops import logistic, logistic_pass

    calls = []
    real = logistic_pass.one_pass_sums
    monkeypatch.setattr(
        logistic, "one_pass_sums", lambda *a, **k: calls.append(1) or real(*a, **k)
    )
    _force_one_pass(monkeypatch, on=case != "dense_binary_disabled")
    if case == "dense_binary_that_fits_vmem":
        monkeypatch.setattr(logistic_pass, "_RESIDENT_BYTES", 112 << 20)
    extra = {}
    if case == "multinomial":
        X, y = _cls_data(n=600, k=3, seed=5)
    elif case == "sparse_binary":
        scipy_sparse = pytest.importorskip("scipy.sparse")
        X, y = _cls_data(n=600, seed=6)
        X = scipy_sparse.csr_matrix(np.where(np.abs(X) > 1.0, X, 0.0))
        extra = {"float32_inputs": False}
    else:
        X, y = _cls_data(n=600, seed=7)
    model = LogisticRegression(regParam=0.1, maxIter=30, num_workers=1, **extra).fit(
        DataFrame.from_numpy(X, y=y, num_partitions=2)
    )
    counted = model.fit_telemetry().counters
    assert counted["lbfgs.fits"] == 1
    assert counted.get("lbfgs.one_pass_fits", 0) == int(takes_it)
    assert bool(calls) == takes_it
    monkeypatch.undo()
    logistic.logistic_fit_kernel.clear_cache()
