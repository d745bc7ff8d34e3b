# CrossValidator single-pass multi-model CV tests (strategy modeled on the
# reference's test_tuning.py / per-algo test_crossvalidator tests), plus the
# srml-sweep batched-engine gates: batched-vs-sequential EXACT equality on
# 1/2/8-device meshes, the one-staged-dataset transfer contract, and the
# zero-new-compiles repeat-sweep contract (docs/tuning_engine.md).
import numpy as np
import pytest

from spark_rapids_ml_tpu import LinearRegression, LogisticRegression, profiling
from spark_rapids_ml_tpu.core import clear_fit_cache
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.evaluation import (
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.tuning import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
)


def _int_reg_df(n=300, d=6, seed=0, num_partitions=4):
    """Integer-valued float32 regression data: every sum in the
    sufficient-statistics pass is exactly representable, so summation ORDER
    is irrelevant and the masked-fold batched route can be gated BITWISE
    against the restaged sequential route (float addition is associative on
    exact integers; see docs/tuning_engine.md §equality contract)."""
    rng = np.random.default_rng(seed)
    X = rng.integers(-3, 4, size=(n, d)).astype(np.float32)
    c = rng.integers(-2, 3, size=d).astype(np.float32)
    y = (X @ c + rng.integers(-2, 3, size=n)).astype(np.float32)
    return DataFrame.from_numpy(X, y=y, num_partitions=num_partitions)


def _int_cls_df(n=300, d=6, seed=1, num_partitions=3):
    """Integer-valued, margin-separated binary data: integer scores X@c are
    either 0 or at least 1 in magnitude, and the 0-score rows are dropped,
    so every row carries a true margin >= 1 — the last-bit solver-path
    differences the batched L-BFGS is allowed cannot flip a prediction,
    which is what makes the ACCURACY equality gate exact."""
    rng = np.random.default_rng(seed)
    X = rng.integers(-3, 4, size=(int(n * 1.5), d)).astype(np.float32)
    c = rng.integers(-2, 3, size=d).astype(np.float32)
    X = X[X @ c != 0][:n]
    assert len(X) == n
    y = (X @ c > 0).astype(np.float32)
    return DataFrame.from_numpy(X, y=y, num_partitions=num_partitions)


def _run_cv(df, est, grid, eva, batched, monkeypatch, **cv_kwargs):
    monkeypatch.setenv("SRML_SWEEP_BATCH", "1" if batched else "0")
    clear_fit_cache()
    cv = CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=eva, **cv_kwargs
    )
    c0 = profiling.counters("ingest.")
    model = cv.fit(df)
    return model, profiling.counter_deltas(c0, "ingest.")


def _reg_df(n=300, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = X @ rng.normal(size=d) + 0.5 * rng.normal(size=n)
    return DataFrame.from_numpy(X, y=y, num_partitions=4), X, y


def test_param_grid_builder():
    grid = (
        ParamGridBuilder()
        .addGrid(LinearRegression.regParam, [0.0, 0.1])
        .addGrid(LinearRegression.elasticNetParam, [0.0, 0.5, 1.0])
        .build()
    )
    assert len(grid) == 6
    assert all(LinearRegression.regParam in pm for pm in grid)


def test_cv_regression_single_pass():
    df, X, y = _reg_df()
    est = LinearRegression(standardization=False)
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.0, 10.0]).build()
    eva = RegressionEvaluator(metricName="rmse")
    assert est._supportsTransformEvaluate(eva)
    cv = CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=eva, numFolds=3, seed=5
    )
    cv_model = cv.fit(df)
    assert len(cv_model.avgMetrics) == 2
    # regParam=0 must beat absurd regParam=10 on rmse
    assert cv_model.avgMetrics[0] < cv_model.avgMetrics[1]
    assert cv_model.bestModel.getOrDefault("regParam") == 0.0
    out = cv_model.transform(df)
    assert "prediction" in out.columns


def test_cv_classification_single_pass():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(300, 5))
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    df = DataFrame.from_numpy(X, y=y, num_partitions=3)
    est = LogisticRegression(maxIter=100)
    grid = ParamGridBuilder().addGrid(LogisticRegression.regParam, [0.01, 50.0]).build()
    eva = MulticlassClassificationEvaluator(metricName="accuracy")
    cv = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=eva, numFolds=3)
    cv_model = cv.fit(df)
    assert cv_model.avgMetrics[0] > cv_model.avgMetrics[1]
    assert cv_model.bestModel.getOrDefault("regParam") == 0.01


def test_cv_parallel_folds_match_serial():
    df, _, _ = _reg_df()
    est = LinearRegression(standardization=False)
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.0, 1.0]).build()
    eva = RegressionEvaluator()
    m1 = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=eva, seed=3).fit(df)
    m2 = CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=eva, seed=3, parallelism=3
    ).fit(df)
    np.testing.assert_allclose(m1.avgMetrics, m2.avgMetrics, rtol=1e-6)


def test_cv_collect_sub_models():
    df, _, _ = _reg_df(n=200)
    est = LinearRegression(standardization=False)
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.0, 0.5]).build()
    cv = CrossValidator(
        estimator=est,
        estimatorParamMaps=grid,
        evaluator=RegressionEvaluator(),
        numFolds=2,
        collectSubModels=True,
    )
    cv_model = cv.fit(df)
    assert cv_model.subModels is not None
    assert len(cv_model.subModels) == 2
    assert len(cv_model.subModels[0]) == 2


def test_cv_model_persistence(tmp_path):
    df, _, _ = _reg_df(n=150)
    est = LinearRegression()
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.0, 0.1]).build()
    cv = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=RegressionEvaluator())
    cv_model = cv.fit(df)
    path = str(tmp_path / "cv")
    cv_model.save(path)
    loaded = CrossValidatorModel.load(path)
    np.testing.assert_allclose(loaded.avgMetrics, cv_model.avgMetrics)
    p1 = cv_model.transform(df).toPandas()["prediction"]
    p2 = loaded.transform(df).toPandas()["prediction"]
    np.testing.assert_allclose(p1, p2, atol=1e-7)


@pytest.mark.slow
def test_cv_random_forest_classifier_single_pass():
    from spark_rapids_ml_tpu import RandomForestClassifier

    rng = np.random.default_rng(4)
    X = rng.normal(size=(240, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    df = DataFrame.from_numpy(X, y=y, num_partitions=3)
    est = RandomForestClassifier(numTrees=5, seed=9)
    assert est._supportsTransformEvaluate(
        MulticlassClassificationEvaluator(metricName="accuracy")
    )
    # grid varies BOTH tree count and depth: _combine must concatenate
    # differing dense layouts
    grid = (
        ParamGridBuilder()
        .addGrid(RandomForestClassifier.maxDepth, [1, 6])
        .build()
    )
    eva = MulticlassClassificationEvaluator(metricName="accuracy")
    cv = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=eva, numFolds=3)
    cv_model = cv.fit(df)
    assert len(cv_model.avgMetrics) == 2
    # depth-6 forest must beat decision stumps on this 2-feature interaction
    assert cv_model.avgMetrics[1] > cv_model.avgMetrics[0]
    assert cv_model.bestModel.getOrDefault("maxDepth") == 6


@pytest.mark.slow
def test_cv_random_forest_regressor_single_pass():
    from spark_rapids_ml_tpu import RandomForestRegressor

    df, X, y = _reg_df(n=240)
    est = RandomForestRegressor(numTrees=5, seed=9)
    eva = RegressionEvaluator(metricName="rmse")
    assert est._supportsTransformEvaluate(eva)
    grid = ParamGridBuilder().addGrid(RandomForestRegressor.maxDepth, [1, 7]).build()
    cv = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=eva, numFolds=3)
    cv_model = cv.fit(df)
    assert cv_model.avgMetrics[1] < cv_model.avgMetrics[0]  # rmse: deeper wins
    assert cv_model.bestModel.getOrDefault("maxDepth") == 7


# -- srml-sweep: batched one-dispatch CV gates -------------------------------


@pytest.mark.parametrize("num_workers", [1, 2, 8])
def test_batched_sweep_exact_equality_linreg(num_workers, monkeypatch):
    """Acceptance: the batched CV route produces EXACTLY the sequential
    route's avgMetrics/stdMetrics/best_index and sub-model coefficients —
    bitwise, not allclose — on a mixed closed-form + coordinate-descent
    grid, on 1/2/8-device meshes."""
    df = _int_reg_df()
    grid = (
        ParamGridBuilder()
        .addGrid(LinearRegression.regParam, [0.0, 0.1])
        .addGrid(LinearRegression.elasticNetParam, [0.0, 0.5])
        .build()
    )

    def run(batched):
        est = LinearRegression(standardization=False, num_workers=num_workers)
        return _run_cv(
            df, est, grid, RegressionEvaluator(metricName="rmse"),
            batched, monkeypatch, numFolds=3, seed=5, collectSubModels=True,
        )

    m_seq, _ = run(False)
    m_bat, d_bat = run(True)
    # EXACT equality: compare raw float64 payloads, no tolerance
    assert m_bat.avgMetrics == m_seq.avgMetrics
    assert m_bat.stdMetrics == m_seq.stdMetrics
    assert (
        m_bat.bestModel.getOrDefault("regParam")
        == m_seq.bestModel.getOrDefault("regParam")
    )
    for f in range(3):
        for i in range(len(grid)):
            s, b = m_seq.subModels[f][i], m_bat.subModels[f][i]
            np.testing.assert_array_equal(
                np.asarray(s.coef_), np.asarray(b.coef_)
            )
            assert float(s.intercept_) == float(b.intercept_)
    np.testing.assert_array_equal(
        np.asarray(m_seq.bestModel.coef_), np.asarray(m_bat.bestModel.coef_)
    )
    # transfer contract: the whole batched CV staged the dataset ONCE (the
    # sweep); the best-model refit rode the device-input cache
    assert d_bat.get("ingest.staged", 0) == 1, d_bat


@pytest.mark.parametrize("num_workers", [1, 2, 8])
def test_batched_sweep_exact_equality_logreg(num_workers, monkeypatch):
    """Logreg sweep gate: EXACT avgMetrics (accuracy is a ratio of integer
    counts, and the margin-separated data forbids prediction flips) and
    best_index vs the sequential path on 1/2/8-device meshes; coefficients
    agree to the documented L-BFGS trajectory tolerance (the fused lane
    contraction reduces across a different geometry than the solo fit —
    docs/tuning_engine.md §equality contract)."""
    df = _int_cls_df()
    grid = (
        ParamGridBuilder()
        .addGrid(LogisticRegression.regParam, [0.01, 1.0])
        .addGrid(LogisticRegression.elasticNetParam, [0.0, 0.5])
        .build()
    )

    def run(batched):
        est = LogisticRegression(maxIter=200, num_workers=num_workers)
        return _run_cv(
            df, est, grid,
            MulticlassClassificationEvaluator(metricName="accuracy"),
            batched, monkeypatch, numFolds=3, seed=7, collectSubModels=True,
        )

    m_seq, _ = run(False)
    m_bat, d_bat = run(True)
    assert m_bat.avgMetrics == m_seq.avgMetrics
    assert m_bat.stdMetrics == m_seq.stdMetrics
    assert int(np.argmax(m_bat.avgMetrics)) == int(np.argmax(m_seq.avgMetrics))
    for f in range(3):
        for i in range(len(grid)):
            np.testing.assert_allclose(
                np.asarray(m_bat.subModels[f][i].coef_),
                np.asarray(m_seq.subModels[f][i].coef_),
                atol=5e-3,
            )
    assert d_bat.get("ingest.staged", 0) == 1, d_bat


def test_batched_sweep_repeat_is_deterministic(monkeypatch):
    """Two identical batched sweeps produce bitwise-identical sub-model
    coefficients and metrics (no set-order / thread-order nondeterminism
    anywhere in the engine)."""
    df = _int_cls_df(n=240, seed=4)
    grid = ParamGridBuilder().addGrid(
        LogisticRegression.regParam, [0.01, 0.5, 2.0]
    ).build()

    def run():
        est = LogisticRegression(maxIter=100)
        return _run_cv(
            df, est, grid,
            MulticlassClassificationEvaluator(metricName="accuracy"),
            True, monkeypatch, numFolds=2, seed=3, collectSubModels=True,
        )[0]

    m1, m2 = run(), run()
    assert m1.avgMetrics == m2.avgMetrics
    for f in range(2):
        for i in range(len(grid)):
            np.testing.assert_array_equal(
                np.asarray(m1.subModels[f][i].coef_),
                np.asarray(m2.subModels[f][i].coef_),
            )


def test_batched_sweep_zero_new_compiles_on_repeat(monkeypatch):
    """Acceptance: a repeat sweep at the same shapes — even with DIFFERENT
    grid values (the reg/l1 lanes are traced, not baked) — performs ZERO
    new kernel compilations: precompile.compile/fallback frozen, aot_hit
    moving (the candidate-bucket AOT cache key contract)."""
    df = _int_reg_df(n=256, seed=9)
    eva = RegressionEvaluator()

    def run(alphas):
        est = LinearRegression(standardization=False)
        grid = ParamGridBuilder().addGrid(
            LinearRegression.regParam, alphas
        ).build()
        return _run_cv(
            df, est, grid, eva, True, monkeypatch, numFolds=3, seed=2
        )

    run([0.0, 0.1, 1.0])  # cold: compiles the sweep kernels
    before = profiling.counters("precompile.")
    run([0.0, 0.5, 2.0])  # same shapes, same 3->4 candidate bucket
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    assert delta.get("precompile.aot_hit", 0) >= 2, delta  # stats + solve


@pytest.mark.parametrize("algo", ["linreg", "logreg"])
def test_batched_sweep_at_ci_smoke_size(algo, monkeypatch):
    """What the CI smoke of the removed harness asserted of a batched sweep,
    at its size (20,000 x 64, 3 folds, a grid of 8; PR 28), on both solver
    families: every candidate goes through the sweep, its solve span is
    recorded, and a repeat sweep at the same shapes compiles nothing.  (Its
    third assertion, batched faster than sequential, was a timing on the CPU
    backend and says nothing of the chip: no cell measures a sweep yet.)"""
    rng = np.random.default_rng(42)
    X = rng.standard_normal((20_000, 64)).astype(np.float32)
    coef = rng.standard_normal(64).astype(np.float32)
    if algo == "linreg":
        y = (X @ coef + 0.1 * rng.standard_normal(20_000)).astype(np.float32)
        est_cls, est_kwargs = LinearRegression, {"standardization": False}
        eva = RegressionEvaluator(metricName="rmse")
    else:
        y = (X @ coef > 0).astype(np.float32)
        est_cls, est_kwargs = LogisticRegression, {"maxIter": 100}
        eva = MulticlassClassificationEvaluator(metricName="accuracy")
    df = DataFrame.from_numpy(X, y=y, num_partitions=4)
    grid = ParamGridBuilder().addGrid(
        est_cls.regParam, np.geomspace(1e-3, 1.0, 8).tolist()
    ).build()

    def sweep():
        model, _ = _run_cv(
            df, est_cls(**est_kwargs), grid, eva, True, monkeypatch,
            numFolds=3, seed=7, collectSubModels=True,
        )
        return model

    c0 = profiling.counters("tuning.")
    sweep()  # cold: compiles the sweep kernels
    before = profiling.counters("precompile.")
    model = sweep()
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    phases = model.subModels[0][0].fit_telemetry().phases
    assert phases["tuning.sweep.solve"]["total_s"] > 0, phases.keys()
    tuned = profiling.counter_deltas(c0, "tuning.")
    assert tuned.get("tuning.candidates", 0) >= 2 * len(grid), tuned


def test_batched_sweep_single_candidate_grid(monkeypatch):
    """m=1 must still route through the batched engine (tuning.candidates
    moves) and equal the sequential path exactly."""
    df = _int_reg_df(n=200, seed=11)
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.1]).build()
    eva = RegressionEvaluator()

    def run(batched):
        c0 = profiling.counter("tuning.candidates")
        est = LinearRegression(standardization=False)
        model, _ = _run_cv(
            df, est, grid, eva, batched, monkeypatch, numFolds=3, seed=6
        )
        return model, profiling.counter("tuning.candidates") - c0

    m_seq, routed_seq = run(False)
    m_bat, routed_bat = run(True)
    assert routed_seq == 0 and routed_bat == 1
    assert m_bat.avgMetrics == m_seq.avgMetrics
    np.testing.assert_array_equal(
        np.asarray(m_seq.bestModel.coef_), np.asarray(m_bat.bestModel.coef_)
    )


def test_batched_sweep_many_small_folds_edge(monkeypatch):
    """numFolds greater than the rows-per-fold count (24 rows, 8 folds —
    3-row validation folds, near-rank-deficient trains): the masked-fold
    formulation must still match the restaged sequential path exactly."""
    df = _int_reg_df(n=24, d=4, seed=13, num_partitions=2)
    grid = ParamGridBuilder().addGrid(
        LinearRegression.regParam, [0.0, 1.0]
    ).build()

    def run(batched):
        est = LinearRegression(standardization=False)
        return _run_cv(
            df, est, grid, RegressionEvaluator(), batched, monkeypatch,
            numFolds=8, seed=1,
        )[0]

    m_seq, m_bat = run(False), run(True)
    assert m_bat.avgMetrics == m_seq.avgMetrics
    assert m_bat.stdMetrics == m_seq.stdMetrics


def test_batched_sweep_kill_switch_and_fallbacks(monkeypatch):
    """SRML_SWEEP_BATCH=0 forces the legacy loop; a grid over a
    non-lane-batchable param (fitIntercept) falls back to it on its own;
    sparse CSR input keeps the legacy loop (documented non-goal)."""
    import scipy.sparse as sp

    df = _int_reg_df(n=120, seed=8)
    eva = RegressionEvaluator()

    def candidates_delta(df_, grid, batched):
        c0 = profiling.counter("tuning.candidates")
        est = LinearRegression(standardization=False)
        _run_cv(df_, est, grid, eva, batched, monkeypatch, numFolds=2, seed=4)
        return profiling.counter("tuning.candidates") - c0

    plain = ParamGridBuilder().addGrid(
        LinearRegression.regParam, [0.0, 0.1]
    ).build()
    assert candidates_delta(df, plain, batched=False) == 0  # kill switch
    mixed = (
        ParamGridBuilder()
        .addGrid(LinearRegression.regParam, [0.0, 0.1])
        .addGrid(LinearRegression.fitIntercept, [True, False])
        .build()
    )
    assert candidates_delta(df, mixed, batched=True) == 0  # non-lane param
    # sparse CSR frames: the batched hook must decline (masked-fold ELL
    # stats are a documented non-goal; CV over sparse frames keeps whatever
    # the legacy route does with them)
    rng = np.random.default_rng(0)
    Xs = sp.random(150, 8, density=0.3, random_state=1, dtype=np.float32).tocsr()
    ys = np.asarray(Xs @ rng.standard_normal(8), dtype=np.float32)
    sparse_df = DataFrame.from_numpy(Xs, ys, num_partitions=2)
    assert not LinearRegression()._supportsBatchedSweep(sparse_df, plain, eva)
    assert LinearRegression()._supportsBatchedSweep(df, plain, eva)


def test_batched_sweep_telemetry_spans_and_counters(monkeypatch):
    """The sweep emits the documented tuning.sweep.{stats,solve,score}
    spans and tuning.candidates/tuning.folds counters, and the sub-models
    carry the sweep's mergeable telemetry snapshot."""
    df = _int_reg_df(n=160, seed=14)
    grid = ParamGridBuilder().addGrid(
        LinearRegression.regParam, [0.0, 0.1, 1.0]
    ).build()
    c0 = profiling.counters("tuning.")
    est = LinearRegression(standardization=False)
    model, _ = _run_cv(
        df, est, grid, RegressionEvaluator(), True, monkeypatch,
        numFolds=3, seed=5, collectSubModels=True,
    )
    delta = profiling.counter_deltas(c0, "tuning.")
    assert delta.get("tuning.candidates", 0) == 3, delta
    assert delta.get("tuning.folds", 0) == 3, delta
    snap = model.subModels[0][0].fit_telemetry()
    assert snap is not None
    phases = snap.phases
    for name in ("tuning.sweep", "tuning.sweep.stats", "tuning.sweep.solve",
                 "tuning.sweep.score"):
        assert name in phases and phases[name]["count"] >= 1, phases.keys()
    assert snap.counters.get("tuning.candidates") == 3


def test_cv_copy_carries_bookkeeping():
    """CrossValidator.copy must carry (not alias) the estimator/evaluator/
    param-map bookkeeping CrossValidatorModel relies on — the old override
    was a dead pass-through."""
    est = LinearRegression()
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [0.0, 0.1]).build()
    eva = RegressionEvaluator()
    cv = CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=eva, numFolds=4
    )
    cp = cv.copy()
    assert cp.getNumFolds() == 4
    assert cp.getEstimator() is not None and cp.getEstimator() is not est
    assert cp.getEvaluator() is not None and cp.getEvaluator() is not eva
    assert cp.getEstimatorParamMaps() == grid
    assert cp.getEstimatorParamMaps() is not cv.getEstimatorParamMaps()
    # the copy still fits end to end
    df = _int_reg_df(n=120, seed=2)
    model = cp.fit(df)
    assert len(model.avgMetrics) == 2


def test_rf_combined_multi_model_matches_per_model_eval():
    from spark_rapids_ml_tpu import RandomForestClassifier

    rng = np.random.default_rng(6)
    X = rng.normal(size=(200, 4))
    y = (X[:, 0] > 0).astype(np.float64)
    train = DataFrame.from_numpy(X, y=y, num_partitions=2)
    est = RandomForestClassifier(numTrees=4, seed=3)
    pm = [
        {est.getParam("maxDepth"): 2},
        {est.getParam("maxDepth"): 5},
    ]
    models = [m for _, m in est.fitMultiple(train, pm)]
    combined = models[0]._combine(models)
    assert combined._num_models == 2
    eva = MulticlassClassificationEvaluator(metricName="accuracy")
    single = [eva.evaluate(m.transform(train)) for m in models]
    fused = combined._transformEvaluate(train, eva)
    np.testing.assert_allclose(fused, single, atol=1e-12)
    # combined models refuse plain transform (ambiguous tree averaging)
    with pytest.raises(AssertionError):
        combined.transform(train)
