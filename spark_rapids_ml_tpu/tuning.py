#
# Model selection: ParamGridBuilder, CrossValidator, CrossValidatorModel.
#
# Capability parity with the reference's accelerated CrossValidator
# (/root/reference/python/src/spark_rapids_ml/tuning.py:33-177): when the
# estimator supports it, each fold is ONE pass — fitMultiple trains every
# param map over a single data load, the models are _combine'd, and one
# transform+evaluate pass scores them all (the reference's
# single-pass design, tuning.py:108-121); otherwise it degrades to the
# classic per-model loop (the pyspark CrossValidator fallback,
# tuning.py:96-99).  Folds run on a thread pool bounded by `parallelism`.
#
# Beyond the reference: estimators whose solvers batch over a candidate
# lane axis (the GLMs — _supportsBatchedSweep) route the WHOLE sweep
# through the srml-sweep engine instead of the fold loop: folds become
# weight masks over one staged dataset (zero per-fold re-staging) and all
# m x k fits run as a handful of compiled dispatches through the AOT
# executable cache; scoring then rides the same fold frames and mergeable
# metric buffers the sequential path uses, so the two routes are gated
# equal (docs/tuning_engine.md).  SRML_SWEEP_BATCH=0 forces the legacy
# loop; live pyspark datasets keep it too (their folds live on the
# cluster).
#
# A device-resident frame (DataFrame.from_device) has no host rows to split,
# collect or upload: its sweep takes the batched route whatever the variable
# says, and each model is scored on its fold's rows where the staged table
# lies, under the fold ids its training mask came from (the estimator's
# _fitBatchedSweep, given the evaluator, returns the metric partials).
#

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import threading
from multiprocessing.pool import ThreadPool
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# serializes parallel CV fold fits on the cpu backend (see one_fold)
_CPU_FOLD_LOCK = threading.Lock()

from .core import _TpuEstimator, _TpuModel, fit_job, load as _load_any
from .dataframe import DataFrame, as_dataframe
from .params import Param, Params, TypeConverters, _dummy
from .utils import get_logger


def _materialize_sweep_models(
    est: _TpuEstimator,
    fold_results: List[List[Dict[str, Any]]],
    paramMaps: List[Dict[Param, Any]],
) -> List[List[_TpuModel]]:
    """Per-(fold, candidate) model-attribute dicts -> models, through the
    SAME core._materialize_model bookkeeping _fit_internal applies on the
    sequential path — so a batched sub-model is indistinguishable from its
    sequential twin by construction."""
    return [
        [
            est._materialize_model(dict(attrs), paramMaps[i])
            for i, attrs in enumerate(results)
        ]
        for results in fold_results
    ]


class ParamGridBuilder:
    """pyspark.ml.tuning.ParamGridBuilder-compatible grid builder."""

    def __init__(self) -> None:
        self._param_grid: Dict[Param, List[Any]] = {}

    def addGrid(self, param: Param, values: List[Any]) -> "ParamGridBuilder":
        if not isinstance(param, Param):
            raise TypeError("param must be an instance of Param")
        self._param_grid[param] = list(values)
        return self

    def baseOn(self, *args: Any) -> "ParamGridBuilder":
        if isinstance(args[0], dict):
            for param, value in args[0].items():
                self.addGrid(param, [value])
        else:
            for param, value in args:
                self.addGrid(param, [value])
        return self

    def build(self) -> List[Dict[Param, Any]]:
        keys = list(self._param_grid.keys())
        grids = [self._param_grid[k] for k in keys]
        return [dict(zip(keys, combo)) for combo in itertools.product(*grids)]


class _ValidatorParams(Params):
    numFolds = Param(_dummy(), "numFolds", "number of folds for cross validation (>= 2)", TypeConverters.toInt)
    parallelism = Param(_dummy(), "parallelism", "number of threads to run parallel folds", TypeConverters.toInt)
    collectSubModels = Param(_dummy(), "collectSubModels", "whether to collect sub models during fitting", TypeConverters.toBoolean)
    seed = Param(_dummy(), "seed", "random seed for fold assignment", TypeConverters.toInt)

    def __init__(self) -> None:
        super().__init__()
        self._setDefault(numFolds=3, parallelism=1, collectSubModels=False, seed=0)
        self._estimator: Optional[_TpuEstimator] = None
        self._evaluator: Any = None
        self._estimatorParamMaps: List[Dict[Param, Any]] = []

    def getEstimator(self) -> Optional[_TpuEstimator]:
        return self._estimator

    def setEstimator(self, value: _TpuEstimator):
        self._estimator = value
        return self

    def getEvaluator(self) -> Any:
        return self._evaluator

    def setEvaluator(self, value: Any):
        self._evaluator = value
        return self

    def getEstimatorParamMaps(self) -> List[Dict[Param, Any]]:
        return self._estimatorParamMaps

    def setEstimatorParamMaps(self, value: List[Dict[Param, Any]]):
        self._estimatorParamMaps = list(value)
        return self

    def getNumFolds(self) -> int:
        return self.getOrDefault("numFolds")

    def setNumFolds(self, value: int):
        self.set(self.getParam("numFolds"), value)
        return self

    def getParallelism(self) -> int:
        return self.getOrDefault("parallelism")

    def setParallelism(self, value: int):
        self.set(self.getParam("parallelism"), value)
        return self

    def getCollectSubModels(self) -> bool:
        return self.getOrDefault("collectSubModels")

    def setSeed(self, value: int):
        self.set(self.getParam("seed"), value)
        return self


class CrossValidator(_ValidatorParams):
    """K-fold cross validation with single-pass multi-model fit + evaluate
    per fold when the estimator supports it."""

    def __init__(
        self,
        estimator: Optional[_TpuEstimator] = None,
        estimatorParamMaps: Optional[List[Dict[Param, Any]]] = None,
        evaluator: Any = None,
        numFolds: int = 3,
        seed: int = 0,
        parallelism: int = 1,
        collectSubModels: bool = False,
    ) -> None:
        super().__init__()
        if estimator is not None:
            self.setEstimator(estimator)
        if estimatorParamMaps is not None:
            self.setEstimatorParamMaps(estimatorParamMaps)
        if evaluator is not None:
            self.setEvaluator(evaluator)
        self.setNumFolds(numFolds)
        self.setSeed(seed)
        self.setParallelism(parallelism)
        self.set(self.getParam("collectSubModels"), collectSubModels)
        self.logger = get_logger(type(self))

    def _kFold(self, df: DataFrame) -> List[Tuple[DataFrame, DataFrame]]:
        n = self.getNumFolds()
        folds = df.randomSplit([1.0] * n, seed=self.getOrDefault("seed"))
        pairs = []
        for i in range(n):
            train_parts = [p for j, f in enumerate(folds) if j != i for p in f.partitions]
            pairs.append((DataFrame(train_parts), folds[i]))
        return pairs

    def _kFold_spark(self, sdf: Any) -> List[Tuple[Any, Any]]:
        """Fold a LIVE pyspark DataFrame with Spark itself (randomSplit +
        union) so the dataset is never collected to the driver — each fold's
        train/valid frames stay distributed and ride the estimator's barrier
        fit and the executor-side transform-evaluate (the reference folds
        with Spark the same way, tuning.py:91-148)."""
        n = self.getNumFolds()
        folds = sdf.randomSplit([1.0] * n, seed=self.getOrDefault("seed"))
        pairs = []
        for i in range(n):
            train = None
            for j, f in enumerate(folds):
                if j == i:
                    continue
                train = f if train is None else train.union(f)
            # cache both frames: the fit and the transform-evaluate each
            # action the fold, and uncached randomSplit branches would
            # re-scan the full source lineage per action (pyspark's own CV
            # caches folds the same way); fit() unpersists after the run
            pairs.append((train.cache(), folds[i].cache()))
        return pairs

    def fit(self, dataset: Any) -> "CrossValidatorModel":
        from .core import _use_executor_path

        if _use_executor_path(dataset):
            # cluster CV: folds, fits, and scoring all stay on the executors
            folds = self._kFold_spark(dataset)

            def _release_fold(train: Any, valid: Any) -> None:
                train.unpersist()
                valid.unpersist()

            try:
                # per-fold release: holding every cached train frame until
                # the end would pin ~(numFolds-1)x the dataset in executor
                # storage at once (pyspark's CV unpersists per fold too)
                return self._fit(dataset, folds, fold_cleanup=_release_fold)
            finally:
                for train, valid in folds:  # safety for error paths
                    train.unpersist()
                    valid.unpersist()
        df = as_dataframe(dataset)
        return self._fit(df)

    def _fit(
        self,
        dataset: Any,
        datasets: Optional[List[Tuple[Any, Any]]] = None,
        fold_cleanup: Optional[Any] = None,
    ) -> "CrossValidatorModel":
        est = self.getEstimator()
        eva = self.getEvaluator()
        epm = self.getEstimatorParamMaps()
        assert est is not None and eva is not None and epm, (
            "estimator, evaluator and estimatorParamMaps must be set"
        )
        num_models = len(epm)
        n_folds = self.getNumFolds()
        collect_sub = self.getCollectSubModels()
        single_pass = isinstance(est, _TpuEstimator) and est._supportsTransformEvaluate(eva)
        # a from_device frame's rows are on the device and nowhere else
        on_device = getattr(dataset, "_device_features", None) is not None
        batched = (
            datasets is None  # facade path: folds are ours to formulate
            and single_pass
            and est._supportsBatchedSweep(dataset, epm, eva)
        )
        if on_device and not batched:
            raise ValueError(
                "CrossValidator on a DataFrame.from_device frame scores the folds "
                "where the table lies, which takes an estimator, a grid and an "
                "evaluator the batched sweep carries (docs/tuning_engine.md: "
                "LogisticRegression on a dense table, regParam / elasticNetParam, "
                "MulticlassClassificationEvaluator); "
                f"{type(est).__name__} with this grid and {type(eva).__name__} "
                "needs a host frame"
            )
        if batched and (on_device or os.environ.get("SRML_SWEEP_BATCH", "1") != "0"):
            return self._fit_batched(dataset, est, eva, epm, on_device)
        metrics_all: List[List[float]] = [[0.0] * num_models for _ in range(n_folds)]
        sub_models: Optional[List[List[_TpuModel]]] = (
            [[None] * num_models for _ in range(n_folds)] if collect_sub else None  # type: ignore[list-item]
        )
        if datasets is None:
            datasets = self._kFold(dataset)

        def one_fold(fold: int):
            train, valid = datasets[fold]
            try:
                # On the cpu backend (virtual test mesh) fold fits are
                # SERIALIZED: XLA:CPU's cross_module rendezvous deadlocks
                # when two multi-device programs from different threads
                # interleave enqueue order on shared devices, so concurrent
                # fold fits over one mesh wedge the suite.  Accelerator
                # backends keep true thread parallelism.  Safe to hold
                # across the whole fold: single-controller fits never touch
                # a control plane, so no cross-thread rendezvous exists.
                import jax

                guard = (
                    _CPU_FOLD_LOCK
                    if jax.default_backend() == "cpu"
                    else contextlib.nullcontext()
                )
                with guard:
                    if single_pass:
                        models = [m for _, m in est.fitMultiple(train, epm)]
                        combined = models[0]._combine(models)
                        metrics = combined._transformEvaluate(valid, eva)
                    else:
                        models = [m for _, m in est.fitMultiple(train, epm)]
                        metrics = [
                            eva.evaluate(m.transform(valid)) for m in models
                        ]
            finally:
                if fold_cleanup is not None:
                    fold_cleanup(train, valid)
            return fold, metrics, models if collect_sub else None

        pool = ThreadPool(processes=min(self.getParallelism(), max(1, n_folds)))
        try:
            for fold, metrics, models in pool.imap_unordered(one_fold, range(n_folds)):
                metrics_all[fold] = metrics
                if collect_sub and models is not None:
                    sub_models[fold] = models  # type: ignore[index]
        finally:
            pool.close()
            pool.join()
        return self._finish(dataset, est, eva, epm, metrics_all, sub_models)

    def _fit_batched(
        self,
        df: DataFrame,
        est: _TpuEstimator,
        eva: Any,
        epm: List[Dict[Param, Any]],
        on_device: bool = False,
    ) -> "CrossValidatorModel":
        """srml-sweep route: one staged dataset, masked folds, lane-batched
        candidate solves — no per-fold thread pool, so the CPU-backend fold
        lock never serializes this path.  Scoring reuses the sequential
        path's fold frames and mergeable metric machinery per (fold,
        candidate), which is what the equality gates lean on; `on_device`
        (a from_device frame) has the estimator reduce the same metric
        partials on the device instead, under the staged fold ids.

        The sweep is a fit job like any other (core.FitJob): its step spans
        tile the sweep as they tile a fit, the estimator's _fitBatchedSweep
        opening the fit function's (init, solve, wait, fetch, pack)."""
        from . import profiling

        n_folds = self.getNumFolds()
        num_models = len(epm)
        seed = self.getOrDefault("seed")
        with fit_job(est, kind="sweep") as job:
            with job.prepare():
                pass  # the estimator resolves its grid beside its solver
            with profiling.span(
                "tuning.sweep",
                estimator=type(est).__name__,
                candidates=num_models,
                folds=n_folds,
            ):
                profiling.incr_counter("tuning.candidates", num_models)
                profiling.incr_counter("tuning.folds", n_folds)
                with job.staged(functools.partial(est._build_fit_inputs, df)) as inputs:
                    fold_results, held_out = est._fitBatchedSweep(
                        inputs, epm, n_folds, seed, eva if on_device else None
                    )
                with profiling.span("srml.fit.pack"):
                    fold_models = _materialize_sweep_models(est, fold_results, epm)
                if held_out is not None:
                    metrics_all = [[m.evaluate(eva) for m in ms] for ms in held_out]
                else:
                    with profiling.span("tuning.sweep.score"):
                        metrics_all = []
                        for fold, (_train, valid) in enumerate(self._kFold(df)):
                            combined = fold_models[fold][0]._combine(
                                fold_models[fold]
                            )
                            metrics_all.append(
                                combined._transformEvaluate(valid, eva)
                            )
                        # every row went through pandas into a fold's frame and
                        # up to the device again, at the models' width and dtype
                        one = fold_results[0][0]
                        row_bytes = one["n_cols"] * np.dtype(one["dtype"]).itemsize
                        rows = df.count()
                        profiling.incr_counter("tuning.score.rows", rows)
                        profiling.incr_counter(
                            "tuning.score.h2d_bytes", rows * row_bytes
                        )
            with job.finish():
                self._last_fit_phase_times = job.phase_times
                for models in fold_models:
                    for m in models:
                        m._fit_telemetry = job.snapshot
        self.logger.info(
            "batched sweep: %d candidates x %d folds over one staged dataset",
            num_models,
            n_folds,
        )
        sub_models = fold_models if self.getCollectSubModels() else None
        return self._finish(df, est, eva, epm, metrics_all, sub_models)

    def _finish(
        self,
        dataset: Any,
        est: _TpuEstimator,
        eva: Any,
        epm: List[Dict[Param, Any]],
        metrics_all: List[List[float]],
        sub_models: Optional[List[List[_TpuModel]]],
    ) -> "CrossValidatorModel":
        """Shared tail of both CV routes: average/std the per-fold metrics,
        pick the winner, refit it on the full dataset."""
        avg = np.mean(np.asarray(metrics_all), axis=0)
        std = np.std(np.asarray(metrics_all), axis=0)
        best_index = int(np.argmax(avg) if eva.isLargerBetter() else np.argmin(avg))
        self.logger.info(
            "CV avg metrics: %s; best param map index: %d", avg.tolist(), best_index
        )
        from . import profiling

        with profiling.span("tuning.refit", index=best_index):
            best_model = est.fit(dataset, epm[best_index])
        cv_model = CrossValidatorModel(
            bestModel=best_model,
            avgMetrics=avg.tolist(),
            subModels=sub_models,
            stdMetrics=std.tolist(),
        )
        cv_model._estimator = est
        cv_model._evaluator = eva
        cv_model._estimatorParamMaps = epm
        self._copyValues(cv_model)
        return cv_model

    def copy(self, extra: Optional[Dict] = None) -> "CrossValidator":
        """Copy with pyspark CrossValidator.copy semantics: the estimator
        and evaluator are themselves copied (so tuning a copy never mutates
        the original's components) and the param-map list is duplicated —
        the bookkeeping the previous pass-through override silently skipped
        (it aliased all three onto the copy)."""
        that = super().copy(extra)
        if self._estimator is not None:
            that._estimator = self._estimator.copy()
        if self._evaluator is not None and hasattr(self._evaluator, "copy"):
            that._evaluator = self._evaluator.copy()
        that._estimatorParamMaps = [dict(pm) for pm in self._estimatorParamMaps]
        return that


class CrossValidatorModel(_ValidatorParams):
    def __init__(
        self,
        bestModel: _TpuModel,
        avgMetrics: Optional[List[float]] = None,
        subModels: Optional[List[List[_TpuModel]]] = None,
        stdMetrics: Optional[List[float]] = None,
    ) -> None:
        super().__init__()
        self.bestModel = bestModel
        self.avgMetrics = avgMetrics or []
        self.stdMetrics = stdMetrics or []
        self.subModels = subModels

    def transform(self, dataset: Any) -> DataFrame:
        return self.bestModel.transform(dataset)

    def write(self) -> "_CrossValidatorModelWriter":
        return _CrossValidatorModelWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_CrossValidatorModelReader":
        return _CrossValidatorModelReader()

    @classmethod
    def load(cls, path: str) -> "CrossValidatorModel":
        return cls.read().load(path)


class _CrossValidatorModelWriter:
    def __init__(self, instance: CrossValidatorModel):
        self.instance = instance

    def overwrite(self) -> "_CrossValidatorModelWriter":
        return self

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        meta = {
            "class": "spark_rapids_ml_tpu.tuning.CrossValidatorModel",
            "avgMetrics": self.instance.avgMetrics,
            "stdMetrics": self.instance.stdMetrics,
            "numFolds": self.instance.getNumFolds(),
        }
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=2)
        self.instance.bestModel.save(os.path.join(path, "bestModel"))


class _CrossValidatorModelReader:
    def load(self, path: str) -> CrossValidatorModel:
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
        best = _load_any(os.path.join(path, "bestModel"))
        model = CrossValidatorModel(
            bestModel=best,  # type: ignore[arg-type]
            avgMetrics=meta.get("avgMetrics"),
            stdMetrics=meta.get("stdMetrics"),
        )
        model.setNumFolds(meta.get("numFolds", 3))
        return model
