#
# LinearRegression estimator/model (OLS, Ridge, Lasso/ElasticNet).
#
# Capability parity with the reference's LinearRegression/
# LinearRegressionModel (/root/reference/python/src/spark_rapids_ml/
# regression.py:173-777): same Spark param mapping (:174-187), same value
# mapping for loss/solver (:189-205), same solver defaults (:207-221), same
# solver choice by (regParam, elasticNetParam) incl. the Spark-parity ridge
# alpha scaling (:499-556), single-pass fitMultiple (:588-605), model combine
# (:743-766) and single-pass transform-evaluate with RegressionMetrics
# (:85-168, :768-776).  The solver is sufficient-statistics + replicated
# solve/CD (ops/glm.py) instead of cuML MG classes — the data is read once
# for ALL param maps.
#

from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiling
from ..core import (
    FitInputs,
    _TpuEstimatorSupervised,
    _TpuModelWithPredictionCol,
    fetch_fit_result,
)
from ..dataframe import DataFrame, as_dataframe
from ..metrics.regression import RegressionMetrics, _SummarizerBuffer
from ..params import (
    HasElasticNetParam,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasVerbose,
    HasWeightCol,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from ..ops.glm import (
    linear_predict_kernel,
    linreg_sufficient_stats,
    multi_linear_predict_kernel,
    solve_elasticnet_cd,
    solve_linear,
    sweep_linreg_fold_stats,
    sweep_solve_elasticnet_cd,
    sweep_solve_linear,
)
from ..ops.linalg import gram_panels, scan_rows
from ..utils import get_logger


class _RegressionModelEvaluationMixIn:
    """Single-pass transform+evaluate shared by LinearRegressionModel and
    RandomForestRegressionModel (reference regression.py:85-168)."""

    def _partition_metrics(
        self, part: Any, evaluator: Any, num_models: int, predict_all=None
    ) -> List[RegressionMetrics]:
        """One partition's per-model mergeable metric partials — shared by
        the local evaluate loop and the Spark executor UDF.  Callers looping
        over partitions pass a hoisted predict_all so the model arrays are
        device-staged once per evaluate, not once per partition."""
        from ..core import extract_partition_features

        input_col, input_cols = self._get_input_columns()
        dtype = self._transform_dtype(self._model_attributes.get("dtype"))
        feats = extract_partition_features(part, input_col, input_cols, dtype)
        labels = part[self.getOrDefault("labelCol")].to_numpy()
        if predict_all is None:
            predict_all = self._get_eval_predict_func()
        preds = predict_all(feats)  # (num_models, n)
        return [
            RegressionMetrics.from_arrays(labels, preds[i])
            for i in range(num_models)
        ]

    def _transform_evaluate(
        self, dataset: Any, evaluator: Any, num_models: int
    ) -> List[float]:
        from ..core import _use_executor_path
        from ..evaluation import RegressionEvaluator

        if not isinstance(evaluator, RegressionEvaluator):
            raise NotImplementedError(f"{evaluator} is unsupported yet.")
        if _use_executor_path(dataset):
            from ..spark.adapter import executor_transform_evaluate

            return executor_transform_evaluate(
                self, dataset, evaluator, num_models
            )
        df = as_dataframe(dataset)
        label_col = self.getOrDefault("labelCol")
        if label_col not in df.columns:
            raise RuntimeError("Label column is not existing.")
        predict_all = self._get_eval_predict_func()
        metrics: List[Optional[RegressionMetrics]] = [None] * num_models
        for part in df.partitions:
            if len(part) == 0:
                continue
            for i, m in enumerate(
                self._partition_metrics(part, evaluator, num_models, predict_all)
            ):
                metrics[i] = m if metrics[i] is None else metrics[i].merge(m)
        return [m.evaluate(evaluator) for m in metrics]  # type: ignore[union-attr]


def _host_intercept(
    coef64: np.ndarray, x_mean, y_mean, fit_intercept: bool
) -> float:
    """intercept = y_mean - x_mean . coef, derived on HOST in float64 from
    the replicated means.  Kept out of the solver kernels deliberately: the
    same 6-element f32 dot compiles with different fusion (fma) context in
    the solo-fit and lane-batched sweep programs and drifts a ulp, which is
    exactly the drift the sweep's batched == sequential exact-equality gate
    exists to forbid.  The host form is identical on both routes by
    construction (and slightly more precise)."""
    if not fit_intercept:
        return 0.0
    return float(
        np.asarray(y_mean, dtype=np.float64)
        - np.asarray(x_mean, dtype=np.float64) @ coef64
    )


@functools.lru_cache(maxsize=256)
def _device_scalar(value: float, dtype: str) -> jax.Array:
    """A hyperparameter as a scalar that already lies on the device, made once
    a value.  A Python float handed to a jitted solver goes up as one small
    host-to-device copy a call: three a fit for coordinate descent, 0.65 ms
    of the host's time in a fit whose whole host share is 3 ms (PERF.md,
    PR 30)."""
    return jnp.asarray(value, dtype)


@functools.cache
def _stats_chunk() -> int:
    """The chunk _fit's statistics pass walks by: linreg_sufficient_stats'
    own default (_fit passes none), read from the function and not restated."""
    return inspect.signature(linreg_sufficient_stats).parameters["chunk"].default


def _count_fit(sweeps: Optional[int], inputs: FitInputs, dense: bool) -> None:
    """Add one fit to the process-wide counters, which a fit's telemetry
    snapshot then carries as its own deltas: linreg.fits for every solve;
    for a dense fit what its Gram scan was handed, static at dispatch:
    linreg.gram_triangle_fits if the table is wide enough that the scan
    computes one triangle (ops/linalg.gram_panels), linreg.gram_rows (the
    staged table's rows, over all shards) and linreg.gram_rows_multiplied
    (the rows the scan's blocks hold, by the plan the scan itself walks by:
    ops/linalg.scan_rows; a fit of several param maps shares one scan and
    counts it once a map, as linreg.fits counts the maps); for a
    coordinate-descent solve also cd.fits, cd.sweeps and cd.coordinates
    (sweeps x columns: the solver's dependent steps)."""
    n_cols = int(inputs.n_cols)
    profiling.incr_counter("linreg.fits", 1)
    if dense:
        if gram_panels(n_cols) > 1:
            profiling.incr_counter("linreg.gram_triangle_fits", 1)
        rows = multiplied = int(inputs.X.shape[0])
        if inputs.mesh is not None:  # no mesh: one contraction over the table, no scan
            shards, chunk = inputs.mesh.devices.size, _stats_chunk()
            n_full, tail = scan_rows(rows // shards, chunk)
            multiplied = shards * (n_full * chunk + tail)
        profiling.incr_counter("linreg.gram_rows", rows)
        profiling.incr_counter("linreg.gram_rows_multiplied", multiplied)
    if sweeps is not None:
        profiling.incr_counter("cd.fits", 1)
        profiling.incr_counter("cd.sweeps", sweeps)
        profiling.incr_counter("cd.coordinates", sweeps * n_cols)


class LinearRegressionClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "aggregationDepth": "",
            "elasticNetParam": "l1_ratio",
            "epsilon": "",
            "fitIntercept": "fit_intercept",
            "loss": "loss",
            "maxBlockSizeInMB": "",
            "maxIter": "max_iter",
            "regParam": "alpha",
            "solver": "solver",
            "standardization": "normalize",
            "tol": "tol",
            "weightCol": None,
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "loss": lambda x: {
                "squaredError": "squared_loss",
                "squared_loss": "squared_loss",
            }.get(x),
            "solver": lambda x: {
                "auto": "eig",
                "normal": "eig",
                "eig": "eig",
            }.get(x),
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "algorithm": "eig",
            "fit_intercept": True,
            "normalize": False,
            "verbose": False,
            "alpha": 0.0001,
            "solver": "eig",
            "loss": "squared_loss",
            "l1_ratio": 0.15,
            "max_iter": 1000,
            "tol": 0.001,
            "shuffle": True,
        }


class _LinearRegressionParams(
    LinearRegressionClass,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasWeightCol,
    HasVerbose,
):
    # CSR input fits/transforms without densification via the ELL kernels
    # (ops/sparse.py: chunk-densified MXU Gram pass)
    _supports_sparse_input = True

    loss = Param(_dummy(), "loss", "the loss function to be optimized (squaredError)", TypeConverters.toString)
    solver = Param(_dummy(), "solver", "the solver algorithm (auto|normal|eig)", TypeConverters.toString)
    aggregationDepth = Param(_dummy(), "aggregationDepth", "suggested depth for treeAggregate", TypeConverters.toInt)
    epsilon = Param(_dummy(), "epsilon", "shape parameter of huber loss (unsupported loss)", TypeConverters.toFloat)
    maxBlockSizeInMB = Param(_dummy(), "maxBlockSizeInMB", "maximum memory in MB for stacking input data", TypeConverters.toFloat)

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(
            maxIter=100,
            regParam=0.0,
            elasticNetParam=0.0,
            tol=1e-6,
            loss="squaredError",
            solver="auto",
            standardization=True,
            aggregationDepth=2,
            epsilon=1.35,
            maxBlockSizeInMB=0.0,
        )

    def setMaxIter(self, value: int):
        return self._set_params(maxIter=value)

    def setRegParam(self, value: float):
        return self._set_params(regParam=value)

    def setElasticNetParam(self, value: float):
        return self._set_params(elasticNetParam=value)

    def setStandardization(self, value: bool):
        return self._set_params(standardization=value)

    def setTol(self, value: float):
        return self._set_params(tol=value)

    def setFitIntercept(self, value: bool):
        return self._set_params(fitIntercept=value)

    def setLossFunction(self, value: str):
        return self._set_params(loss=value)


class LinearRegression(_LinearRegressionParams, _TpuEstimatorSupervised):
    """Distributed linear regression on a TPU mesh.

    One fused pass computes the normal-equation statistics; OLS/Ridge solve
    closed-form, Lasso/ElasticNet run covariance-update coordinate descent —
    all param maps of a fitMultiple share the single data pass (the TPU
    formulation of the reference's single-load multi-fit,
    regression.py:588-605)."""

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return True

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        from ..evaluation import RegressionEvaluator

        return isinstance(evaluator, RegressionEvaluator)

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        logger = get_logger(type(self))

        def _solve(stats, params: Dict[str, Any], mesh):
            """Queue one param map's solve on the statistics replicated on
            `mesh`: (coef, sweeps), both still on the device; sweeps is None
            for the closed form."""
            alpha = float(params["alpha"])
            l1_ratio = float(params["l1_ratio"])
            fit_intercept = bool(params["fit_intercept"])
            normalize = bool(params["normalize"])
            dtype = str(stats.G.dtype)
            if alpha == 0.0 or l1_ratio == 0.0:
                # OLS ("eig") or Ridge with Spark-parity alpha*n scaling —
                # scaling handled inside solve_linear (reg = alpha * wsum)
                coef, _ = solve_linear(
                    stats,
                    _device_scalar(alpha, dtype),
                    fit_intercept=fit_intercept,
                    normalize=normalize,
                )
                return coef, None
            coef, _, n_iter = solve_elasticnet_cd(
                stats,
                _device_scalar(alpha, dtype),
                _device_scalar(l1_ratio, dtype),
                fit_intercept=fit_intercept,
                normalize=normalize,
                max_iter=int(params["max_iter"]),
                tol=_device_scalar(float(params["tol"]), dtype),
                mesh=mesh,
            )
            return coef, n_iter

        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            # the step spans below tile srml.fit (core.FitJob.run)
            with profiling.span("srml.fit.init"):
                assert inputs.y is not None
                from ..ops.sparse import EllMatrix, ell_sufficient_stats

                maps = [dict(params, **ov) for ov in extra_params or [{}]]
            with profiling.span("srml.fit.solve"):
                sparse = isinstance(inputs.X, EllMatrix)
                if sparse:
                    # CSR ingest: chunk-densify + MXU Gram pass, never the
                    # whole matrix (ops/sparse.py); downstream solves are
                    # unchanged — the sufficient statistics are dense either way
                    stats = ell_sufficient_stats(
                        inputs.X, inputs.y, inputs.weight, mesh=inputs.mesh
                    )
                else:
                    stats = linreg_sufficient_stats(
                        inputs.X, inputs.y, inputs.weight, mesh=inputs.mesh
                    )
                # one pass over the data, then every map's solve queued
                # behind it before the one wait
                solved = [_solve(stats, p, inputs.mesh) for p in maps]
            # ONE batched device fetch for all maps (each np.asarray / int()
            # alone costs a host round-trip)
            solved_h, xm_h, ym_h = fetch_fit_result(
                (solved, stats.x_mean, stats.y_mean)
            )
            with profiling.span("srml.fit.pack"):
                # the device buffers go inside a step, not with the frame
                # after the last one
                del solved, stats
                results = []
                for p, (coef_h, n_iter_h) in zip(maps, solved_h):
                    coef64 = np.asarray(coef_h, dtype=np.float64)
                    sweeps = None if n_iter_h is None else int(n_iter_h)
                    _count_fit(sweeps, inputs, dense=not sparse)
                    if sweeps is not None:
                        logger.info("CD sweeps: %d", sweeps)
                    results.append(
                        {
                            "coef_": coef64,
                            "intercept_": _host_intercept(
                                coef64, xm_h, ym_h, bool(p["fit_intercept"])
                            ),
                            "n_cols": inputs.n_cols,
                            "dtype": str(inputs.dtype),
                            "num_iters": sweeps,
                        }
                    )
                return results if extra_params else results[0]

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "LinearRegressionModel":
        return LinearRegressionModel(**result)

    def streaming(self):
        """Streaming incremental-fit engine over this configured estimator:
        mergeable Gram-moment accumulation finalized through the SAME
        solve kernels as the batch fit (streamed == batch bitwise on the
        exact-arithmetic data families) — partial_fit/merge/finalize
        (srml-stream, docs/streaming.md)."""
        from ..stream.engines import StreamingLinearRegression

        return StreamingLinearRegression(self)

    # -- batched hyperparameter sweep (srml-sweep) -------------------------
    def _supportsBatchedSweep(self, df, paramMaps, evaluator) -> bool:
        if not paramMaps or not self._supportsTransformEvaluate(evaluator):
            return False
        try:
            overrides = [self._paramMap_to_tpu_overrides(pm) for pm in paramMaps]
        except ValueError:
            # unsupported value: let the legacy loop raise its own error
            return False
        if any(set(ov) - {"alpha", "l1_ratio"} for ov in overrides):
            return False  # only the regularizer axes batch as lanes
        if getattr(df, "_device_features", None) is not None:
            # the masked statistics pass has no scoring where the table lies
            # yet, and a from_device frame has no host rows to score
            return False
        return not self._sweep_sparse_input(df)

    def _fitBatchedSweep(self, inputs, paramMaps, n_folds, seed, evaluator=None):
        """All n_folds x len(paramMaps) linreg fits as a fused masked-fold
        stats pass + one stacked-lane solve dispatch per solver family over
        the ONE staged dataset (ops/glm.py sweep kernels; exact-equality
        contract in docs/tuning_engine.md)."""
        from ..ops import sweep as sweep_ops

        params = dict(self._tpu_params)
        cand = []
        for pm in paramMaps:
            p = dict(params)
            p.update(self._paramMap_to_tpu_overrides(pm))
            cand.append((float(p["alpha"]), float(p["l1_ratio"])))
        fit_intercept = bool(params["fit_intercept"])
        normalize = bool(params["normalize"])
        statics = {"fit_intercept": fit_intercept, "normalize": normalize}
        # same solver choice per candidate as _single_fit: OLS/Ridge closed
        # form when the L1 term vanishes, covariance-update CD otherwise
        closed = [i for i, (a, l1r) in enumerate(cand) if a == 0.0 or l1r == 0.0]
        cd = [i for i in range(len(cand)) if i not in closed]
        assert inputs.y is not None
        mesh = inputs.mesh
        fid = sweep_ops.stage_fold_ids(
            inputs.n_rows, inputs.X.shape[0], n_folds, seed, mesh
        )
        # warm the solve kernels at sweep entry: their lowerings are
        # known from shapes alone (stacked stats are mesh-replicated),
        # so they compile on the pool WHILE the stats pass runs
        compute_dt = np.dtype(inputs.dtype)
        if compute_dt in (np.dtype(np.float32), np.dtype(np.float64)):
            d = inputs.n_cols
            aval = lambda shape: sweep_ops.replicated_aval(  # noqa: E731
                shape, compute_dt, mesh
            )
            from ..ops.glm import LinregStats

            stats_avals = LinregStats(
                wsum=aval((n_folds,)),
                x_mean=aval((n_folds, d)),
                y_mean=aval((n_folds,)),
                G=aval((n_folds, d, d)),
                c=aval((n_folds, d)),
                y2=aval((n_folds,)),
            )
            entries = []
            if closed:
                mb = sweep_ops.candidate_bucket(len(closed))
                entries.append(
                    (
                        "sweep.linreg.solve",
                        sweep_solve_linear,
                        (stats_avals, aval((mb,))),
                        dict(statics),
                    )
                )
            if cd:
                mb = sweep_ops.candidate_bucket(len(cd))
                entries.append(
                    (
                        "sweep.linreg.cd",
                        sweep_solve_elasticnet_cd,
                        (stats_avals, aval((mb,)), aval((mb,)), aval(())),
                        dict(statics, max_iter=int(params["max_iter"])),
                    )
                )
            sweep_ops.warm(entries, mesh=mesh)
        with profiling.span("tuning.sweep.stats", folds=n_folds, rows=inputs.n_rows):
            stats = sweep_ops.dispatch(
                "sweep.linreg.stats",
                sweep_linreg_fold_stats,
                inputs.X,
                inputs.y,
                inputs.weight,
                fid,
                mesh=mesh,
                k=n_folds,
            )
        results: List[List[Dict[str, Any]]] = [
            [None] * len(cand) for _ in range(n_folds)  # type: ignore[list-item]
        ]
        xm_h, ym_h = jax.device_get((stats.x_mean, stats.y_mean))

        def _collect(idxs, coef_h, n_iter_h=None):
            for j, i in enumerate(idxs):
                for f in range(n_folds):
                    coef64 = np.asarray(coef_h[f, j], dtype=np.float64)
                    results[f][i] = {
                        "coef_": coef64,
                        # same host float64 derivation as _single_fit
                        # (see _host_intercept): bit-equal across the
                        # batched and sequential routes
                        "intercept_": _host_intercept(
                            coef64, xm_h[f], ym_h[f], fit_intercept
                        ),
                        "n_cols": inputs.n_cols,
                        "dtype": str(inputs.dtype),
                    }
            if n_iter_h is not None:
                get_logger(type(self)).info(
                    "sweep CD sweeps (fold x candidate): %s",
                    np.asarray(n_iter_h)[:, : len(idxs)].tolist(),
                )

        with profiling.span("tuning.sweep.solve", candidates=len(cand), folds=n_folds):
            if closed:
                _, (alphas,) = sweep_ops.pack_lane_subset(cand, closed)
                coef, _ = sweep_ops.dispatch(
                    "sweep.linreg.solve",
                    sweep_solve_linear,
                    stats,
                    alphas,
                    mesh=mesh,
                    **statics,
                )
                _collect(closed, jax.device_get(coef))
            if cd:
                _, (alphas, l1s) = sweep_ops.pack_lane_subset(cand, cd, fields=(0, 1))
                tol = jax.numpy.asarray(np.float64(float(params["tol"])))
                coef, _, n_iter = sweep_ops.dispatch(
                    "sweep.linreg.cd",
                    sweep_solve_elasticnet_cd,
                    stats,
                    alphas,
                    l1s,
                    tol,
                    mesh=mesh,
                    max_iter=int(params["max_iter"]),
                    **statics,
                )
                coef_h, n_iter_h = jax.device_get((coef, n_iter))
                _collect(cd, coef_h, n_iter_h)
        return results, None


class LinearRegressionModel(
    _LinearRegressionParams, _RegressionModelEvaluationMixIn, _TpuModelWithPredictionCol
):
    def __init__(
        self,
        coef_: Union[np.ndarray, List],
        intercept_: Union[float, List[float]],
        n_cols: int,
        dtype: str,
        num_iters: Union[None, int, List[Optional[int]]] = None,
    ) -> None:
        super().__init__(
            coef_=np.asarray(coef_),
            intercept_=intercept_,
            n_cols=int(n_cols),
            dtype=str(dtype),
            num_iters=num_iters,
        )
        self.coef_ = np.asarray(coef_)
        self.intercept_ = intercept_
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        # coordinate-descent sweeps the fit ran (Spark's
        # summary.totalIterations); None for the closed form
        self.num_iters = num_iters

    @property
    def _num_models(self) -> int:
        return len(self.intercept_) if isinstance(self.intercept_, (list, np.ndarray)) and self.coef_.ndim == 2 else 1

    @property
    def coefficients(self) -> np.ndarray:
        assert self._num_models == 1
        return self.coef_

    @property
    def intercept(self) -> float:
        assert self._num_models == 1
        return float(self.intercept_)

    @property
    def scale(self) -> float:
        """huber loss unsupported: constant 1.0 for API compatibility
        (reference regression.py:693-697)."""
        return 1.0

    @property
    def hasSummary(self) -> bool:
        return False

    def predict(self, value: np.ndarray) -> float:
        np_dtype = self._transform_dtype(self.dtype)
        x = np.asarray(value, dtype=np_dtype)
        return float(
            linear_predict_kernel(
                jax.numpy.asarray(x[None, :]),
                jax.numpy.asarray(self.coef_.astype(np_dtype)),
                jax.numpy.asarray(np_dtype.type(self.intercept_)),
            )[0]
        )

    def cpu(self):
        from ..spark.interop import to_spark_linear_model

        return to_spark_linear_model(self)

    def _get_tpu_transform_func(self, dataset: DataFrame):
        assert self._num_models == 1, "transform() on a combined multi-model is unsupported; use _transformEvaluate"
        np_dtype = self._transform_dtype(self.dtype)
        coef = jax.device_put(np.asarray(self.coef_, dtype=np_dtype))
        intercept = jax.numpy.asarray(np_dtype.type(self.intercept_))
        pred_col = self.getOrDefault("predictionCol")

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            if hasattr(features, "tocsr"):  # CSR partition -> device ELL
                from ..ops.sparse import ell_device_from_scipy

                Xd = ell_device_from_scipy(features, np_dtype)
            else:
                Xd = jax.device_put(np.asarray(features, dtype=np_dtype))
            preds = linear_predict_kernel(Xd, coef, intercept)
            return {pred_col: np.asarray(preds, dtype=np.float64)}

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): the dense Xw + b prediction as
        one bucket-padded kernel through the AOT executable cache (serving
        requests arrive as dense rows; sparse bulk scoring stays on the
        batch transform path)."""
        assert self._num_models == 1, "combined multi-models are not servable"
        from ..serving.entry import kernel_entry

        np_dtype = self._transform_dtype(self.dtype)
        coef = jax.device_put(np.asarray(self.coef_, dtype=np_dtype))
        intercept = jax.numpy.asarray(np_dtype.type(self.intercept_))
        pred_col = self.getOrDefault("predictionCol")
        return kernel_entry(
            "serve.linreg",
            linear_predict_kernel,  # module-level @jax.jit
            (coef, intercept),
            {},
            lambda preds: {pred_col: np.asarray(preds, dtype=np.float64)},
            dtype=np_dtype,
            n_cols=self.n_cols,
            out_cols=[pred_col],
            mesh=mesh,
        )

    def _lane_entry(self, mesh: Any = None):
        """Multiplexed serving hook (serving/multiplex): this model's
        (coef, intercept) as ONE lane of a lane-stacked GLM predict — K
        same-shape variants share one lane_linear_predict_kernel dispatch
        per micro-batch, bitwise-equal per tenant to the dedicated entry
        above on integer-exact data."""
        assert self._num_models == 1, "combined multi-models are not servable"
        from ..ops.glm import lane_linear_predict_kernel
        from ..serving.multiplex import LaneEntry

        np_dtype = self._transform_dtype(self.dtype)
        coef = np.ascontiguousarray(np.asarray(self.coef_, dtype=np_dtype))
        intercept = np.asarray(np_dtype.type(self.intercept_))
        pred_col = self.getOrDefault("predictionCol")
        return LaneEntry(
            name="lanes.linreg",
            n_cols=self.n_cols,
            dtype=np_dtype,
            out_cols=[pred_col],
            leaves=(coef, intercept),
            kernel=lane_linear_predict_kernel,
            statics={},
            postprocess=lambda preds: {pred_col: np.asarray(preds, dtype=np.float64)},
        )

    def _get_eval_predict_func(self) -> Callable[[np.ndarray], np.ndarray]:
        np_dtype = self._transform_dtype(self.dtype)
        coefs = np.atleast_2d(np.asarray(self.coef_, dtype=np_dtype))
        intercepts = np.atleast_1d(np.asarray(self.intercept_, dtype=np_dtype))

        def _predict_all(feats: np.ndarray) -> np.ndarray:
            return np.asarray(
                multi_linear_predict_kernel(
                    jax.device_put(np.asarray(feats, dtype=np_dtype)),
                    jax.numpy.asarray(coefs),
                    jax.numpy.asarray(intercepts),
                ),
                dtype=np.float64,
            )

        return _predict_all

    @classmethod
    def _combine(cls, models: List["LinearRegressionModel"]) -> "LinearRegressionModel":
        assert models and all(isinstance(m, cls) for m in models)
        first = models[0]
        combined = cls(
            coef_=np.stack([np.asarray(m.coef_) for m in models]),
            intercept_=[float(m.intercept_) for m in models],
            n_cols=first.n_cols,
            dtype=first.dtype,
            num_iters=[m.num_iters for m in models],
        )
        first._copyValues(combined)
        combined._tpu_params.update(first._tpu_params)
        combined._float32_inputs = first._float32_inputs
        return combined

    def _transformEvaluate(self, dataset: Any, evaluator: Any, params=None) -> List[float]:
        return self._transform_evaluate(dataset, evaluator, self._num_models)
