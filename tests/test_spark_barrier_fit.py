#
# fit(pyspark_df) must train through the Spark barrier path — NOT collect to
# the driver (VERDICT round 1, item 1).  pyspark is not installable on this
# image (no network), so the pyspark surfaces run_barrier_fit
# actually touches (repartition/mapInPandas/rdd.barrier/collect,
# BarrierTaskContext) are mocked faithfully in-process with ONE barrier task;
# the real multi-process jax.distributed execution underneath is covered by
# test_multicontroller.py with OS-process workers.
#
import sys
import types

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import KMeans, LinearRegression
from spark_rapids_ml_tpu.dataframe import DataFrame


class _FakeBarrierTaskContext:
    _current = None

    def __init__(self, rank: int):
        self._rank = rank

    @classmethod
    def get(cls):
        return cls._current

    def partitionId(self):
        return self._rank

    def allGather(self, message):
        return [message]

    def barrier(self):
        return None


class _FakeRdd:
    def __init__(self, partitions, udf=None):
        self._partitions = partitions
        self._udf = udf

    def getNumPartitions(self):
        return len(self._partitions)

    def barrier(self):
        return self

    def mapPartitions(self, f):
        return self

    def withResources(self, profile):
        return self

    def collect(self):
        rows = []
        for rank, part in enumerate(self._partitions):
            _FakeBarrierTaskContext._current = _FakeBarrierTaskContext(rank)
            try:
                for out in self._udf(iter([part])):
                    for _, r in out.iterrows():
                        rows.append({"model_attributes": r["model_attributes"]})
            finally:
                _FakeBarrierTaskContext._current = None
        return rows


class _FakeConf:
    def __init__(self, conf=None):
        self._conf = {"spark.master": "local[1]", **(conf or {})}

    def get(self, key, default=None):
        return self._conf.get(key, default)


class _FakeSparkSession:
    version = "3.5.0"

    def __init__(self, conf=None):
        self.sparkContext = types.SimpleNamespace(
            getConf=lambda: _FakeConf(conf)
        )


class _FakeSparkDataFrame:
    """Just enough of pyspark.sql.DataFrame for run_barrier_fit; the class
    advertises the pyspark module path so core._is_pyspark_dataframe routes
    it to the barrier dispatcher."""

    def __init__(self, partitions, udf=None, conf=None):
        self._partitions = partitions
        self._udf = udf
        self._conf = conf
        self.sparkSession = _FakeSparkSession(conf)

    def repartition(self, n):
        if n == len(self._partitions):
            return self
        whole = pd.concat(self._partitions, ignore_index=True)
        idx = np.array_split(np.arange(len(whole)), n)
        return _FakeSparkDataFrame(
            [whole.iloc[ix].reset_index(drop=True) for ix in idx],
            conf=self._conf,
        )

    def sample(self, fraction=None, seed=None, withReplacement=None):
        rng = np.random.default_rng(seed)
        return _FakeSparkDataFrame(
            [
                p[rng.random(len(p)) < fraction].reset_index(drop=True)
                for p in self._partitions
            ],
            conf=self._conf,
        )

    def mapInPandas(self, udf, schema=None):
        return _FakeSparkDataFrame(self._partitions, udf=udf, conf=self._conf)

    @property
    def rdd(self):
        return _FakeRdd(self._partitions, self._udf)

    @property
    def columns(self):
        return list(self._partitions[0].columns)


_FakeSparkDataFrame.__module__ = "pyspark.sql.dataframe"


@pytest.fixture()
def fake_pyspark(monkeypatch):
    mod = types.ModuleType("pyspark")
    mod.BarrierTaskContext = _FakeBarrierTaskContext
    monkeypatch.setitem(sys.modules, "pyspark", mod)
    monkeypatch.delenv("SRML_SPARK_COLLECT", raising=False)


def _data():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((600, 6)).astype(np.float32)
    X[:300] += 4.0
    y = (X @ rng.standard_normal(6).astype(np.float32)).astype(np.float32)
    return X, y


def _fake_sdf(X, y=None):
    pdf = pd.DataFrame({"features": list(X)})
    if y is not None:
        pdf["label"] = y
    return _FakeSparkDataFrame([pdf])


def test_kmeans_fit_routes_through_barrier(fake_pyspark):
    X, _ = _data()
    model = KMeans(k=2, maxIter=15, seed=5).fit(_fake_sdf(X))
    baseline = KMeans(k=2, maxIter=15, seed=5).fit(DataFrame.from_numpy(X))
    np.testing.assert_allclose(
        np.asarray(model.cluster_centers_),
        np.asarray(baseline.cluster_centers_),
        rtol=1e-5, atol=1e-5,
    )
    # and the returned model is a full working model
    preds = model.transform(DataFrame.from_numpy(X)).toPandas()["prediction"]
    assert set(np.unique(preds)) == {0, 1}


def test_linreg_fit_multiple_single_pass_over_barrier(fake_pyspark):
    X, y = _data()
    est = LinearRegression(maxIter=50)
    pm = [
        {est.getParam("regParam"): 0.0},
        {est.getParam("regParam"): 0.5},
    ]
    models = est.fit(_fake_sdf(X, y), pm)
    assert len(models) == 2
    facade = DataFrame.from_numpy(X, y)
    for m, p in zip(models, pm):
        b = LinearRegression(maxIter=50, regParam=list(p.values())[0]).fit(facade)
        np.testing.assert_allclose(
            np.asarray(m.coef_), np.asarray(b.coef_), rtol=1e-4, atol=1e-5
        )
    # the two regularizations genuinely differ
    assert not np.allclose(
        np.asarray(models[0].coef_), np.asarray(models[1].coef_), rtol=1e-3
    )


def test_barrier_fit_surfaces_merged_telemetry(fake_pyspark):
    """The executor-side fit's TelemetrySnapshot must ride the model-
    attribute wire and surface on the DRIVER-side model — the live-Spark
    half of the srml-scope acceptance gate (the local half lives in
    test_profiling.test_local_fit_attaches_telemetry)."""
    from spark_rapids_ml_tpu.core import TELEMETRY_ATTR

    X, _ = _data()
    model = KMeans(k=2, maxIter=5, seed=5).fit(_fake_sdf(X))
    t = model.fit_telemetry()
    assert t is not None, "barrier fit lost its telemetry snapshot"
    # the executor's step spans (the fit job's, core.FitJob) are what must
    # cross the wire — the driver thread never ran the fit
    assert t.phases["srml.fit"]["count"] == 1
    assert t.phases["srml.fit"]["total_s"] > 0.0
    assert "srml.ingest" in t.phases
    assert t.meta["ranks"] == [0]
    # driver-side phase view is rebuilt from the snapshot
    est = KMeans(k=2, maxIter=5, seed=5)
    est.fit(_fake_sdf(X))
    assert est._last_fit_phase_times.get("srml.fit", 0.0) > 0.0
    # and the wire key never leaks into model attributes
    assert TELEMETRY_ATTR not in model._get_model_attributes()


def test_missing_input_column_fails_on_driver(fake_pyspark):
    """A wrong featuresCol must raise BEFORE any barrier stage launches —
    not as an executor traceback."""
    X, _ = _data()
    est = KMeans(k=2, maxIter=5).setFeaturesCol("nope")
    with pytest.raises(ValueError, match="nope"):
        est.fit(_fake_sdf(X))


def test_num_workers_inference_order(fake_pyspark):
    from spark_rapids_ml_tpu.spark.adapter import (
        NUM_WORKERS_CONF,
        infer_spark_num_workers,
    )

    class _Spark:
        def __init__(self, conf):
            self.sparkContext = types.SimpleNamespace(
                getConf=lambda: types.SimpleNamespace(get=conf.get)
            )

    est = KMeans(k=2)
    # estimator num_workers means mesh DEVICE count everywhere else, so the
    # barrier task count deliberately ignores it — even when set
    est._num_workers = 3
    assert infer_spark_num_workers(est, _Spark({NUM_WORKERS_CONF: "5"})) == 5
    est._num_workers = None
    # our own conf beats executor instances
    assert infer_spark_num_workers(
        est, _Spark({NUM_WORKERS_CONF: "5", "spark.executor.instances": "7"})
    ) == 5
    # then executor instances
    assert infer_spark_num_workers(
        est, _Spark({"spark.executor.instances": "7"})
    ) == 7
    # fallback: single worker (NOT the partition or device count)
    assert infer_spark_num_workers(est, _Spark({})) == 1


def test_umap_cluster_fit_degrades_to_single_task(fake_pyspark):
    """UMAP on a >1-worker cluster must NOT raise: the adapter runs a 1-task
    barrier stage (the reference samples + coalesces to one worker,
    umap.py:831-850) and inference stays distributed."""
    from spark_rapids_ml_tpu import UMAP
    from spark_rapids_ml_tpu.spark.adapter import NUM_WORKERS_CONF

    rng = np.random.default_rng(6)
    X = rng.standard_normal((256, 6)).astype(np.float32)
    parts = [
        pd.DataFrame({"features": list(X[ix])}).reset_index(drop=True)
        for ix in np.array_split(np.arange(len(X)), 4)
    ]
    sdf = _FakeSparkDataFrame(parts, conf={NUM_WORKERS_CONF: "4"})
    model = UMAP(n_neighbors=5, n_epochs=30, random_state=4).fit(sdf)
    emb = np.asarray(model.embedding_)
    assert emb.shape == (256, 2) and np.isfinite(emb).all()


def test_umap_cluster_fit_samples_with_spark(fake_pyspark):
    """sample_fraction < 1 on the cluster path samples the DISTRIBUTED frame
    before the 1-task stage — only the sampled rows reach the fit."""
    from spark_rapids_ml_tpu import UMAP
    from spark_rapids_ml_tpu.spark.adapter import NUM_WORKERS_CONF

    rng = np.random.default_rng(8)
    X = rng.standard_normal((400, 5)).astype(np.float32)
    parts = [
        pd.DataFrame({"features": list(X[ix])}).reset_index(drop=True)
        for ix in np.array_split(np.arange(len(X)), 4)
    ]
    sdf = _FakeSparkDataFrame(parts, conf={NUM_WORKERS_CONF: "4"})
    est = UMAP(n_neighbors=5, n_epochs=30, random_state=7, sample_fraction=0.5)
    model = est.fit(sdf)
    n_fit = model.raw_data_.shape[0]
    assert 120 <= n_fit <= 280  # ~half the rows, sampled Spark-side
    # the estimator the user holds is untouched by the internal copy
    assert est.getSampleFraction() == 0.5


def test_collect_override_falls_back_to_driver_local(fake_pyspark, monkeypatch):
    """SRML_SPARK_COLLECT=1 keeps the old driver-collect path for single
    TPU-VM notebooks; the mock lacks toPandas so routing there must fail
    loudly (proving the switch flips the path, not just the default)."""
    monkeypatch.setenv("SRML_SPARK_COLLECT", "1")
    X, _ = _data()
    with pytest.raises((AttributeError, TypeError)):
        KMeans(k=2, maxIter=5).fit(_fake_sdf(X))
