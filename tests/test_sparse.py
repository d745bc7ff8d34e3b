# Sparse (CSR -> ELL) ingest + GLM kernels: conversion exactness, sufficient-
# stats parity with the dense pass, end-to-end LogisticRegression /
# LinearRegression fits on CSR DataFrames vs sklearn, transform parity, and
# the densify-with-warning fallback for estimators without a sparse path
# (strategy mirrors the reference's sparse logreg tests,
# test_logistic_regression.py sparse vector cases).
import numpy as np
import pytest

scipy_sparse = pytest.importorskip("scipy.sparse")

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import (
    KMeans,
    LinearRegression,
    LogisticRegression,
)
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops.sparse import (
    EllMatrix,
    ell_device_from_scipy,
    ell_from_csr,
    ell_matmat,
    ell_matvec,
    ell_sufficient_stats,
)


def _random_csr(n=300, d=40, density=0.08, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    X = scipy_sparse.random(
        n, d, density=density, format="csr", random_state=rng, dtype=dtype
    )
    # ensure at least one row is empty and one is the max-nnz row
    X[0] = 0
    X.eliminate_zeros()
    return X.tocsr()


def test_ell_from_csr_roundtrip():
    X = _random_csr()
    idx, val = ell_from_csr(X.indptr, X.indices, X.data, X.shape[1], np.float64)
    dense = np.zeros(X.shape)
    np.add.at(dense, (np.arange(X.shape[0])[:, None], idx), val)
    np.testing.assert_array_equal(dense, X.toarray())


def test_ell_matvec_matmat():
    with jax.enable_x64(True):  # the fit path's f64 scope (core._maybe_x64)
        X = _random_csr(seed=1)
        ell = ell_device_from_scipy(X, np.float64)
        b = np.random.default_rng(2).normal(size=X.shape[1])
        np.testing.assert_allclose(
            np.asarray(ell_matvec(ell, jnp.asarray(b))), X @ b, rtol=1e-12
        )
        B = np.random.default_rng(3).normal(size=(X.shape[1], 5))
        np.testing.assert_allclose(
            np.asarray(ell_matmat(ell, jnp.asarray(B))), X @ B, rtol=1e-12
        )


@pytest.mark.parametrize("use_mesh", [False, True])
def test_ell_sufficient_stats_parity(use_mesh):
    import jax

    from spark_rapids_ml_tpu.ops.glm import linreg_sufficient_stats
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_rows

    with jax.enable_x64(True):  # the fit path's f64 scope (core._maybe_x64)
        X = _random_csr(n=256, seed=4)
        rng = np.random.default_rng(5)
        y = rng.normal(size=256)
        w = np.ones(256)
        mesh = get_mesh() if use_mesh else None
        ell = ell_device_from_scipy(X, np.float64, mesh=mesh)
        if use_mesh:
            y_s, _ = shard_rows(y, mesh)
            w_s, _ = shard_rows(w, mesh)
            stats = ell_sufficient_stats(
                ell, jnp.asarray(y_s), jnp.asarray(w_s), mesh=mesh, chunk=37
            )
        else:
            stats = ell_sufficient_stats(
                ell, jnp.asarray(y), jnp.asarray(w), mesh=None, chunk=37
            )
        ref = linreg_sufficient_stats(
            jnp.asarray(X.toarray()), jnp.asarray(y), jnp.asarray(w), mesh=None
        )
        # one batched fetch, then compare on host (graftlint R1: a per-field
        # np.asarray in the loop pays a device round-trip each)
        for got, want in zip(jax.device_get(tuple(stats)), jax.device_get(tuple(ref))):
            np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def _sparse_cls_data(n=2000, d=60, density=0.08, classes=2, seed=7):
    rng = np.random.default_rng(seed)
    X = scipy_sparse.random(
        n, d, density=density, format="csr", random_state=rng, dtype=np.float64
    )
    W = rng.normal(size=(d, classes))
    logits = X @ W
    y = np.argmax(logits + 0.3 * rng.normal(size=logits.shape), axis=1).astype(
        np.float64
    )
    return X.tocsr(), y


def test_logistic_sparse_binary_matches_sklearn():
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = _sparse_cls_data()
    df = DataFrame.from_numpy(X, y=y, num_partitions=4)
    model = LogisticRegression(
        regParam=0.01, maxIter=300, tol=1e-9, standardization=False,
        float32_inputs=False,
    ).fit(df)
    sk = SkLR(C=1.0 / (0.01 * X.shape[0]), max_iter=5000, tol=1e-10).fit(X, y)
    np.testing.assert_allclose(
        np.asarray(model.coefficients), sk.coef_.ravel(), atol=2e-3
    )
    # accuracy parity on the training set
    pred = model.transform(df).toPandas()["prediction"].to_numpy()
    assert (pred == y).mean() >= (sk.predict(X) == y).mean() - 0.01


def test_logistic_sparse_multinomial_matches_sklearn():
    from sklearn.linear_model import LogisticRegression as SkLR

    X, y = _sparse_cls_data(classes=3, seed=8)
    df = DataFrame.from_numpy(X, y=y, num_partitions=3)
    model = LogisticRegression(
        regParam=0.02, maxIter=300, tol=1e-9, standardization=False,
        float32_inputs=False,
    ).fit(df)
    sk = SkLR(C=1.0 / (0.02 * X.shape[0]), max_iter=5000, tol=1e-10).fit(X, y)
    ours = (model.transform(df).toPandas()["prediction"].to_numpy() == y).mean()
    theirs = (sk.predict(X) == y).mean()
    assert ours >= theirs - 0.01


def test_linreg_sparse_matches_sklearn():
    from sklearn.linear_model import LinearRegression as SkLR, Ridge

    rng = np.random.default_rng(9)
    X = _random_csr(n=1500, d=50, density=0.1, seed=9)
    coef = rng.normal(size=50)
    y = X @ coef + 1.7 + 0.05 * rng.normal(size=1500)
    df = DataFrame.from_numpy(X, y=y, num_partitions=4)

    model = LinearRegression(regParam=0.0, float32_inputs=False).fit(df)
    sk = SkLR().fit(X, y)
    np.testing.assert_allclose(model.coefficients, sk.coef_, atol=1e-6)
    assert abs(model.intercept - sk.intercept_) < 1e-6

    # Ridge (Spark alpha*n scaling; standardization off for direct compare)
    model_r = LinearRegression(
        regParam=0.1, standardization=False, float32_inputs=False
    ).fit(df)
    sk_r = Ridge(alpha=0.1 * X.shape[0]).fit(X, y)
    np.testing.assert_allclose(model_r.coefficients, sk_r.coef_, atol=1e-5)

    # sparse transform parity with the dense transform
    preds = model.transform(df).toPandas()["prediction"].to_numpy()
    df_dense = DataFrame.from_numpy(X.toarray(), y=y, num_partitions=4)
    preds_dense = model.transform(df_dense).toPandas()["prediction"].to_numpy()
    np.testing.assert_allclose(preds, preds_dense, atol=1e-5)


def test_sparse_fit_never_densifies(monkeypatch):
    """The GLM fit path must not call toarray() on the CSR input."""
    X, y = _sparse_cls_data(n=400, d=30)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    import scipy.sparse as sp

    calls = []
    orig = sp.csr_matrix.toarray

    def spy(self, *a, **k):
        calls.append(self.shape)
        return orig(self, *a, **k)

    monkeypatch.setattr(sp.csr_matrix, "toarray", spy)
    LogisticRegression(maxIter=20, float32_inputs=False).fit(df)
    assert calls == []


def test_sparse_fallback_densifies(monkeypatch):
    """Estimators without a sparse path densify partition-by-partition and
    still fit correctly (the package logger doesn't propagate, so the
    densification is asserted via a toarray spy)."""
    X = _random_csr(n=200, d=12, density=0.2, seed=11)
    df = DataFrame.from_numpy(X, num_partitions=2)
    import scipy.sparse as sp

    calls = []
    orig = sp.csr_matrix.toarray

    def spy(self, *a, **k):
        calls.append(self.shape)
        return orig(self, *a, **k)

    monkeypatch.setattr(sp.csr_matrix, "toarray", spy)
    model = KMeans(k=3, seed=1).fit(df)
    assert calls, "KMeans (no sparse path) should densify CSR partitions"
    assert model.cluster_centers_.shape == (3, 12)


def test_sparse_float32_default_dtype():
    X, y = _sparse_cls_data(n=300, d=20)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    model = LogisticRegression(maxIter=30).fit(df)  # float32_inputs default
    assert model.dtype == "float32"
