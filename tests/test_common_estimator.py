# Framework proof with a fake algorithm — the analog of the reference's
# test_common_estimator.py (CumlDummy/SparkRapidsMLDummy,
# /root/reference/python/tests/test_common_estimator.py:46-310): exercises the
# param translation layer, fit/transform dispatch, PartitionDescriptor
# visibility inside the fit function, persistence, and num_workers handling —
# with no real algorithm.
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.core import (
    FitInputs,
    _TpuEstimator,
    _TpuModel,
    load,
)
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.params import Param, Params, TypeConverters, _dummy, HasFeaturesCol, HasFeaturesCols


class _DummyParams(HasFeaturesCol, HasFeaturesCols):
    alpha = Param(_dummy(), "alpha", "alpha param", TypeConverters.toFloat)
    beta = Param(_dummy(), "beta", "ignored param", TypeConverters.toInt)
    gamma = Param(_dummy(), "gamma", "unsupported param", TypeConverters.toString)

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._setDefault(alpha=1.0, beta=2, gamma="three")


class TpuDummy(_DummyParams, _TpuEstimator):
    """Fake estimator: solver params are {alpha_: float, k: int}; spark param
    `beta` is silently ignored, `gamma` is unsupported (raises on set)."""

    @classmethod
    def _param_mapping(cls):
        return {"alpha": "alpha_", "beta": "", "gamma": None}

    @classmethod
    def _get_tpu_params_default(cls):
        return {"alpha_": 1.0, "k": 4}

    def __init__(self, **kwargs):
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)
        self.fit_calls = []

    def _get_tpu_fit_func(self, dataset, extra_params=None):
        n_expected = dataset.count()
        pdesc_rows = [len(p) for p in dataset.partitions]

        def _fit(inputs: FitInputs, params):
            # PartitionDescriptor carries original partition layout
            assert inputs.pdesc.m == n_expected
            assert [s for _, s in inputs.pdesc.parts_rank_size] == pdesc_rows
            assert inputs.X.shape[0] >= inputs.n_rows
            assert inputs.X.shape[1] == inputs.n_cols
            # weighted row count equals true row count (padding masked)
            assert float(np.sum(np.asarray(inputs.weight))) == pytest.approx(inputs.n_rows)
            mean = np.asarray(
                (inputs.X * inputs.weight[:, None]).sum(axis=0)
            ) / inputs.n_rows
            return {
                "mean": np.asarray(mean, dtype=np.float64),
                "n_cols": inputs.n_cols,
                "alpha_used": params["alpha_"],
            }

        return _fit

    def _create_model(self, result):
        return TpuDummyModel(**result)


class TpuDummyModel(_DummyParams, _TpuModel):
    @classmethod
    def _param_mapping(cls):
        return {"alpha": "alpha_", "beta": "", "gamma": None}

    @classmethod
    def _get_tpu_params_default(cls):
        return {"alpha_": 1.0, "k": 4}

    def __init__(self, mean, n_cols, alpha_used):
        super().__init__(mean=np.asarray(mean), n_cols=int(n_cols), alpha_used=float(alpha_used))
        self.mean = np.asarray(mean)
        self.n_cols = int(n_cols)
        self.alpha_used = float(alpha_used)

    def _out_columns(self):
        return ["centered_norm"]

    def _get_tpu_transform_func(self, dataset):
        mean = self.mean

        def _transform(features: np.ndarray):
            return {"centered_norm": np.linalg.norm(features - mean, axis=1)}

        return _transform


def _make_df(layout, n_parts=3):
    X = np.arange(24, dtype=np.float64).reshape(8, 3)
    return X, DataFrame.from_numpy(X, feature_layout=layout, num_partitions=n_parts)


def test_param_mapping_and_defaults():
    est = TpuDummy()
    assert est.tpu_params == {"alpha_": 1.0, "k": 4}
    est = TpuDummy(alpha=2.5)
    assert est.getOrDefault("alpha") == 2.5
    assert est.tpu_params["alpha_"] == 2.5
    # solver-name route reflects back into the Spark param
    est = TpuDummy(alpha_=3.5)
    assert est.getOrDefault("alpha") == 3.5
    # solver-only param
    est = TpuDummy(k=9)
    assert est.tpu_params["k"] == 9
    # ignored param: settable, not propagated
    est = TpuDummy(beta=7)
    assert est.getOrDefault("beta") == 7
    assert "beta" not in est.tpu_params and "" not in est.tpu_params


def test_unsupported_param_raises():
    with pytest.raises(ValueError, match="not supported"):
        TpuDummy(gamma="x")
    with pytest.raises(ValueError, match="Unsupported param"):
        TpuDummy(nonexistent=1)


@pytest.mark.parametrize("layout", ["array", "vector", "multi_cols"])
def test_fit_transform_layouts(layout):
    X, df = _make_df(layout)
    est = TpuDummy()
    if layout == "multi_cols":
        est.setFeaturesCol([c for c in df.columns])
    model = est.fit(df)
    np.testing.assert_allclose(model.mean, X.mean(axis=0), rtol=1e-6)
    out = model.transform(df)
    assert "centered_norm" in out.columns
    got = np.asarray(out.toPandas()["centered_norm"].to_numpy(), dtype=np.float64)
    np.testing.assert_allclose(
        got, np.linalg.norm(X - X.mean(axis=0), axis=1), rtol=1e-5
    )


def test_float32_inputs_flag():
    X, df = _make_df("array")
    est = TpuDummy(float32_inputs=False)
    assert est._float32_inputs is False
    model = est.fit(df)
    np.testing.assert_allclose(model.mean, X.mean(axis=0), rtol=1e-12)


def test_num_workers(n_devices):
    est = TpuDummy()
    assert est.num_workers == n_devices
    est = TpuDummy(num_workers=2)
    assert est.num_workers == 2
    _, df = _make_df("array")
    model = est.fit(df)
    assert model is not None


def test_empty_dataset_raises():
    df = DataFrame.from_pandas(pd.DataFrame({"features": []}))
    with pytest.raises(RuntimeError, match="empty"):
        TpuDummy().fit(df)


def test_estimator_persistence(tmp_path):
    est = TpuDummy(alpha=4.0, k=11, num_workers=3, float32_inputs=False)
    path = str(tmp_path / "dummy_est")
    est.save(path)
    loaded = load(path)
    assert isinstance(loaded, TpuDummy)
    assert loaded.getOrDefault("alpha") == 4.0
    assert loaded.tpu_params["alpha_"] == 4.0
    assert loaded.tpu_params["k"] == 11
    assert loaded.num_workers == 3
    assert loaded._float32_inputs is False


def test_model_persistence(tmp_path):
    X, df = _make_df("array")
    model = TpuDummy(alpha=2.0).fit(df)
    path = str(tmp_path / "dummy_model")
    model.save(path)
    loaded = load(path)
    assert isinstance(loaded, TpuDummyModel)
    np.testing.assert_allclose(loaded.mean, model.mean)
    assert loaded.n_cols == 3
    assert loaded.alpha_used == 2.0
    out = loaded.transform(df)
    assert "centered_norm" in out.columns


def test_copy_semantics():
    est = TpuDummy(alpha=2.0)
    est2 = est.copy({TpuDummy.alpha: 5.0})
    assert est.getOrDefault("alpha") == 2.0
    assert est2.getOrDefault("alpha") == 5.0


def test_fit_with_params_list():
    _, df = _make_df("array")
    est = TpuDummy()
    models = est.fit(df, [{TpuDummy.alpha: 1.5}, {TpuDummy.alpha: 2.5}])
    assert len(models) == 2
    assert models[0].getOrDefault("alpha") == 1.5
    assert models[1].getOrDefault("alpha") == 2.5


def test_sparse_feature_cells():
    # pyspark SparseVector/DenseVector cells and scipy CSR rows densify at
    # ingest (the reference accepts Vectors.sparse inputs,
    # classification.py:418,435)
    import scipy.sparse as sp

    from spark_rapids_ml_tpu.utils import stack_feature_cells

    # duck-typed stand-ins for pyspark.ml.linalg vectors (pyspark itself is
    # not installed in the test image; ingest keys on toArray/indices/values)
    class FakeSparseVector:
        def __init__(self, size, indices, values):
            self.size, self.indices = size, np.asarray(indices)
            self.values = np.asarray(values, dtype=np.float64)

        def __len__(self):
            return self.size

        def toArray(self):
            out = np.zeros(self.size)
            out[self.indices] = self.values
            return out

    class FakeDenseVector:
        def __init__(self, values):
            self.values = np.asarray(values, dtype=np.float64)

        def __len__(self):
            return len(self.values)

        def toArray(self):
            return self.values

    dense = np.array([[1.0, 0.0, 2.0], [0.0, 3.0, 0.0]])
    cells_ps = [FakeSparseVector(3, [0, 2], [1.0, 2.0]), FakeDenseVector([0.0, 3.0, 0.0])]
    np.testing.assert_allclose(stack_feature_cells(cells_ps, np.float32), dense)
    csr = sp.csr_matrix(dense)
    cells_sp = [csr[i] for i in range(2)]
    np.testing.assert_allclose(stack_feature_cells(cells_sp, np.float32), dense)

    # end-to-end: fit from a DataFrame whose feature cells are SparseVectors
    rng = np.random.default_rng(0)
    Xd = rng.normal(size=(40, 5))
    Xd[rng.random(Xd.shape) < 0.6] = 0.0
    cells = [
        FakeSparseVector(5, np.nonzero(r)[0], r[np.nonzero(r)[0]]) for r in Xd
    ]
    pdf = pd.DataFrame({"features": cells})
    df = DataFrame([pdf])
    model = TpuDummy().fit(df)
    np.testing.assert_allclose(model.mean, Xd.mean(axis=0), atol=1e-5)


def test_from_numpy_scipy_sparse():
    import scipy.sparse as sp

    rng = np.random.default_rng(1)
    Xd = rng.normal(size=(30, 4))
    Xd[rng.random(Xd.shape) < 0.7] = 0.0
    df = DataFrame.from_numpy(sp.csr_matrix(Xd), num_partitions=2)
    model = TpuDummy().fit(df)
    np.testing.assert_allclose(model.mean, Xd.mean(axis=0), atol=1e-5)


def test_low_precision_features_keep_float32_labels():
    """A half/bfloat16 FEATURE dtype must never round labels: integer
    values above the half-precision mantissa (e.g. 2049 in f16, 257 in
    bf16) have to survive ingest exactly on all three paths — host
    partitions, from_device frames, and the multicontroller global build
    (parallel/runner.DistributedFitSession).  weightCol is unsupported by
    every estimator (reference parity), so only the default ones-mask
    weight dtype is assertable."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import LinearRegression
    from spark_rapids_ml_tpu.parallel.context import LocalControlPlane
    from spark_rapids_ml_tpu.parallel.runner import DistributedFitSession

    n, d = 32, 4
    rng = np.random.default_rng(0)
    X16 = rng.standard_normal((n, d)).astype(np.float16)
    labels = (2048 + np.arange(n)).astype(np.float64)  # 2049 rounds in f16

    est = LinearRegression(float32_inputs=False)
    pdf = pd.DataFrame({"features": list(X16), "label": labels})
    df = DataFrame.from_pandas(pdf, 2)
    feats, labs, _ws, dtype = est._pre_process_data(df)
    assert np.dtype(dtype) == np.float16  # features keep their precision
    y = np.concatenate(labs)
    assert y.dtype == np.float32
    np.testing.assert_array_equal(y, labels)  # no rounding

    inputs = est._build_fit_inputs(df)
    np.testing.assert_array_equal(
        np.asarray(inputs.y)[: inputs.n_rows], labels
    )
    assert np.asarray(inputs.weight).dtype == np.float32

    # the multicontroller global build (rank 0 of 1 over the local mesh)
    sess = DistributedFitSession(0, 1, LocalControlPlane())
    inputs_mc = sess.build_fit_inputs(est, df)
    np.testing.assert_array_equal(
        np.asarray(inputs_mc.y)[: inputs_mc.n_rows], labels
    )
    assert np.asarray(inputs_mc.weight).dtype == np.float32

    # from_device with a bf16 feature array
    Xd = jax.device_put(rng.standard_normal((n, d)).astype(np.float32)).astype(
        jnp.bfloat16
    )
    labels_b = (256 + np.arange(n)).astype(np.float64)  # 257 rounds in bf16
    dfd = DataFrame.from_device(Xd, y=labels_b)
    inputs2 = est._build_fit_inputs(dfd)
    np.testing.assert_array_equal(
        np.asarray(inputs2.y)[: inputs2.n_rows], labels_b
    )


def test_params_are_found_once_a_class_and_by_name():
    """Params.params walks the MRO once a class (a fit resolves a hundred
    params by name); the list is sorted by name, a caller's own copy, and a
    subclass's further params are its own."""
    from spark_rapids_ml_tpu import params as params_mod

    class _More(_DummyParams):
        delta = Param(_dummy(), "delta", "a subclass's own", TypeConverters.toInt)

    base, more = _DummyParams(), _More()
    names = [p.name for p in base.params]
    assert names == sorted(names) and {"alpha", "beta", "gamma", "featuresCol", "featuresCols"} <= set(names)
    assert [p.name for p in more.params] == sorted(names + ["delta"])
    assert not base.hasParam("delta") and more.hasParam("delta")
    assert base.getParam("alpha") is _DummyParams.alpha is more.getParam("alpha")
    with pytest.raises(AttributeError, match="no param 'delta'"):
        base.getParam("delta")
    base.params.clear()      # the caller's copy, not the class's index
    assert [p.name for p in base.params] == names
    index = params_mod._PARAMS_BY_CLASS[_DummyParams]
    assert _DummyParams().getParam("beta") is index["beta"] and params_mod._PARAMS_BY_CLASS[_DummyParams] is index
