#
# LogisticRegression estimator/model (binary sigmoid + multinomial softmax,
# L2 / L1 / ElasticNet via L-BFGS / OWL-QN).
#
# Capability parity with the reference's LogisticRegression/
# LogisticRegressionModel (/root/reference/python/src/spark_rapids_ml/
# classification.py:646-1388): same param mapping incl. C = 1/regParam
# (:648-672), same penalty derivation from (regParam, elasticNetParam)
# (:687-710), solver defaults (:674-683) with lbfgs memory 10 and
# non-normalized penalty semantics (:955-961), same model attributes
# (coef_, intercept_, classes_, n_cols, dtype, num_iters), sigmoid/softmax
# probability and argmax/threshold label transforms (:1236-1262), intercept
# sparse-compression rule (:1206-1218), model combine (:1330-1360) and
# single-pass transform-evaluate over MulticlassMetrics.
#

from __future__ import annotations

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
from ..metrics.multiclass import MulticlassMetrics
from ..params import (
    HasElasticNetParam,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
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
from ..ops.logistic import (
    ell_pass_objective,
    logistic_decision_kernel,
    logistic_fit_kernel,
    one_pass_objective,
    scores_to_labels,
    scores_to_probs,
    sweep_logistic_fit_kernel,
    sweep_logistic_score_kernel,
    sweep_on_line,
)
from ..ops.softmax_ell_pass import slot_steps
from ..utils import get_logger


def _count_lbfgs(n_iter: Any, n_evals: Any, one_pass: bool = False) -> None:
    """Add fetched L-BFGS counts (scalars, or one entry a lane) to the
    process-wide counters lbfgs.fits / lbfgs.iters / lbfgs.evals, which a
    fit's telemetry snapshot then carries as its own deltas.  one_pass: the
    fits read X once an evaluation (ops/logistic.one_pass_objective), and
    count in lbfgs.one_pass_fits too."""
    profiling.incr_counter("lbfgs.fits", int(np.size(n_iter)))
    if one_pass:
        profiling.incr_counter("lbfgs.one_pass_fits", int(np.size(n_iter)))
    profiling.incr_counter("lbfgs.iters", int(np.sum(n_iter)))
    profiling.incr_counter("lbfgs.evals", int(np.sum(n_evals)))


def _count_ell_pass(inputs: FitInputs, classes: int) -> None:
    """One fit whose data term came from the Pallas call softmax_ell_pass
    (ops/logistic.ell_pass_objective, static at dispatch): lbfgs.ell_pass_fits
    beside lbfgs.fits; ell.piece_stack_fits beside it, since the kernel makes
    the bfloat16 pieces of its products' operands itself and latches each
    piece of a block's dense tile once a product (every fit that takes the
    kernel: it has no other form); and what one evaluation of it walks:
    ell.slots (rows x width), ell.nnz (the real nonzeros, where the table's builder stated
    them), ell.table_bytes (idx, val and the packed labels and weights, as
    stored) and ell.classes; of the kernel's lane tiles of 128 rows,
    ell.slot_steps (lane tiles x width) and ell.slot_steps_kept (the slots its
    build runs of them: up to the last slot holding a value in any row of the
    lane tile's block, rounded up to the kernel's step).  The two are the
    table's: counted on the device with the frame's first such fit
    (ops/softmax_ell_pass.slot_steps), kept with its inputs."""
    X = inputs.X
    rows = X.shape[0]
    profiling.incr_counter("lbfgs.ell_pass_fits")
    profiling.incr_counter("ell.piece_stack_fits")
    profiling.incr_counter("ell.slots", rows * X.width)
    if X.nnz is not None:
        profiling.incr_counter("ell.nnz", X.nnz)
    profiling.incr_counter(
        "ell.table_bytes", int(X.idx.nbytes) + int(X.val.nbytes) + 2 * 4 * rows
    )
    profiling.incr_counter("ell.classes", classes)
    if inputs.ell_slot_steps is None:
        kept, covered = jax.device_get(slot_steps(X, inputs.mesh))
        inputs.ell_slot_steps = int(kept), int(covered)
    profiling.incr_counter("ell.slot_steps_kept", inputs.ell_slot_steps[0])
    profiling.incr_counter("ell.slot_steps", inputs.ell_slot_steps[1])


class _ClassificationModelEvaluationMixIn:
    """Single-pass transform+evaluate via MulticlassMetrics, shared by
    LogisticRegressionModel and RandomForestClassificationModel (reference
    classification.py:180-295)."""

    def _partition_metrics(
        self, part: Any, evaluator: Any, num_models: int, predict_all=None
    ) -> List[MulticlassMetrics]:
        """One partition's per-model mergeable metric partials — shared by
        the local evaluate loop and the Spark executor UDF.  Callers looping
        over partitions pass a hoisted predict_all so the model arrays are
        device-staged once per evaluate, not once per partition."""
        from ..core import extract_partition_features

        needs_probs = evaluator.getMetricName() == "logLoss"
        eps = evaluator.getEps()
        input_col, input_cols = self._get_input_columns()
        dtype = self._transform_dtype(self._model_attributes.get("dtype"))
        feats = extract_partition_features(part, input_col, input_cols, dtype)
        labels = part[self.getOrDefault("labelCol")].to_numpy()
        if predict_all is None:
            predict_all = self._get_eval_predict_func()
        preds_all, probs_all = predict_all(feats)  # (M, n), (M, n, C)
        return [
            MulticlassMetrics.from_arrays(
                labels,
                preds_all[i],
                probs=probs_all[i] if needs_probs else None,
                eps=eps,
            )
            for i in range(num_models)
        ]

    def _transform_evaluate(
        self, dataset: Any, evaluator: Any, num_models: int
    ) -> List[float]:
        from ..core import _use_executor_path
        from ..evaluation import MulticlassClassificationEvaluator

        if not isinstance(evaluator, MulticlassClassificationEvaluator):
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
        metrics: List[Optional[MulticlassMetrics]] = [None] * num_models
        for part in df.partitions:
            if len(part) == 0:
                continue
            for i, m in enumerate(
                self._partition_metrics(part, evaluator, num_models, predict_all)
            ):
                metrics[i] = m if metrics[i] is None else metrics[i].merge(m)
        return [m.evaluate(evaluator) for m in metrics]  # type: ignore[union-attr]


class LogisticRegressionClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "maxIter": "max_iter",
            "regParam": "C",
            "elasticNetParam": "l1_ratio",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            "threshold": None,
            "thresholds": None,
            "standardization": "",
            "weightCol": None,
            "aggregationDepth": None,
            "family": "",
            "maxBlockSizeInMB": None,
        }

    @classmethod
    def _param_value_mapping(cls):
        # spark regParam -> C = 1/regParam (0 stays 0), classification.py:668-672
        return {"C": lambda x: 1 / x if x > 0.0 else (0.0 if x == 0.0 else None)}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "fit_intercept": True,
            "verbose": False,
            "C": 1.0,
            "penalty": "l2",
            "l1_ratio": None,
            "max_iter": 1000,
            "tol": 0.0001,
        }

    @staticmethod
    def _reg_params_value_mapping(reg_param: float, elasticnet_param: float):
        """(regParam, elasticNetParam) -> (penalty, C, l1_ratio), parity with
        classification.py:687-710."""
        if reg_param == 0.0:
            return "none", 0.0, elasticnet_param
        if elasticnet_param == 0.0:
            return "l2", 1.0 / reg_param, elasticnet_param
        if elasticnet_param == 1.0:
            return "l1", 1.0 / reg_param, elasticnet_param
        return "elasticnet", 1.0 / reg_param, elasticnet_param


class _LogisticRegressionParams(
    LogisticRegressionClass,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasWeightCol,
    HasVerbose,
):
    family = Param(_dummy(), "family", "the name of family (auto|binomial|multinomial); detected automatically", TypeConverters.toString)
    threshold = Param(_dummy(), "threshold", "binary classification threshold", TypeConverters.toFloat)

    # CSR input fits/transforms without densification via the ELL kernels
    # (ops/sparse.py; reference sparse qn, classification.py:1206-1218)
    _supports_sparse_input = True

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(
            maxIter=100,
            regParam=0.0,
            elasticNetParam=0.0,
            tol=1e-6,
            standardization=True,
            family="auto",
        )

    def setMaxIter(self, value: int):
        return self._set_params(maxIter=value)

    def setRegParam(self, value: float):
        return self._set_params(regParam=value)

    def setElasticNetParam(self, value: float):
        return self._set_params(elasticNetParam=value)

    def setTol(self, value: float):
        return self._set_params(tol=value)

    def setFitIntercept(self, value: bool):
        return self._set_params(fitIntercept=value)

    def setProbabilityCol(self, value: str):
        return self._set_params(probabilityCol=value)

    def setRawPredictionCol(self, value: str):
        return self._set_params(rawPredictionCol=value)


class LogisticRegression(_LogisticRegressionParams, _TpuEstimatorSupervised):
    """Distributed logistic regression on a TPU mesh via fully-jitted
    L-BFGS/OWL-QN with psum'd loss/grad (ops/lbfgs.py, ops/logistic.py)."""

    # class discovery runs per-rank on local shards + control-plane union
    # (core.discover_label_classes) and the encode is a jitted kernel over
    # the row-sharded labels (ops/labels.py), so the whole fit is safe on a
    # multi-process mesh — distributed-capability parity with the
    # reference's LogisticRegressionMG (classification.py:915-1001)
    _supports_multicontroller_fit = True

    def __init__(self, **kwargs: Any) -> None:
        if not kwargs.get("float32_inputs", True):
            get_logger(type(self)).warning(
                "This estimator does not support double precision inputs. "
                "Setting float32_inputs to False will be ignored."
            )
            kwargs.pop("float32_inputs")
        super().__init__()
        self._initialize_tpu_params()
        self._set_tpu_reg_params()
        self._set_params(**kwargs)
        self._set_tpu_reg_params()

    def _set_tpu_reg_params(self) -> None:
        penalty, C, l1_ratio = self._reg_params_value_mapping(
            self.getOrDefault("regParam"), self.getOrDefault("elasticNetParam")
        )
        self._tpu_params["penalty"] = penalty
        self._tpu_params["C"] = C
        self._tpu_params["l1_ratio"] = l1_ratio

    def _set_params(self, **kwargs: Any):
        out = super()._set_params(**kwargs)
        if hasattr(self, "_tpu_params") and (
            "regParam" in kwargs or "elasticNetParam" in kwargs
        ):
            self._set_tpu_reg_params()
        return out

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return True

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        from ..evaluation import MulticlassClassificationEvaluator

        return isinstance(evaluator, MulticlassClassificationEvaluator)

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        logger = get_logger(type(self))

        def _single_fit(
            inputs: FitInputs, params: Dict[str, Any], classes: np.ndarray, y_enc
        ) -> Dict[str, Any]:
            C = float(params["C"])
            l1_ratio = float(params.get("l1_ratio") or 0.0)
            reg = 1.0 / C if C > 0 else 0.0
            num_classes = len(classes)
            k = 1 if num_classes == 2 else num_classes
            use_owlqn = reg > 0 and l1_ratio > 0
            with profiling.span("srml.fit.solve"):
                solved = logistic_fit_kernel(
                    inputs.X,
                    y_enc,
                    inputs.weight,
                    k,
                    reg,
                    l1_ratio,
                    bool(params["fit_intercept"]),
                    int(params["max_iter"]),
                    float(params["tol"]),
                    use_owlqn,
                    inputs.mesh,
                )
            # one batched device fetch (each scalar coercion alone costs a
            # host round-trip)
            W_h, b_h, n_iter_h, conv_h, n_evals_h = fetch_fit_result(solved)
            with profiling.span("srml.fit.pack"):
                # the device buffers go inside a step, not with the frame
                # after the last one
                del solved
                _count_lbfgs(
                    n_iter_h, n_evals_h, one_pass_objective(inputs.X, k, inputs.mesh)
                )
                if ell_pass_objective(inputs.X, k):
                    _count_ell_pass(inputs, k)
                logger.info(
                    "L-BFGS iters: %d evaluations: %d converged: %s",
                    int(n_iter_h), int(n_evals_h), bool(conv_h),
                )
                return {
                    "coef_": np.asarray(W_h, dtype=np.float64),
                    "intercept_": np.asarray(b_h, dtype=np.float64),
                    "classes_": np.asarray(classes, dtype=np.float64),
                    "n_cols": inputs.n_cols,
                    "dtype": str(inputs.dtype),
                    "num_iters": int(n_iter_h),
                }

        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            from ..core import discover_label_classes
            from ..ops.labels import encode_labels_kernel

            # the step spans here and in _single_fit tile srml.fit
            # (core.FitJob.run)
            with profiling.span("srml.fit.init"):
                assert inputs.y is not None
                classes = discover_label_classes(inputs)
                if len(classes) < 2:
                    raise RuntimeError(
                        "LogisticRegression requires at least two distinct labels"
                    )
                # encode labels as class indices on device, preserving the
                # row sharding (padded rows clamp into range; masked by w)
                y_enc = encode_labels_kernel(
                    inputs.y, jnp.asarray(classes.astype(inputs.y.dtype))
                )
            if extra_params:
                results = []
                for override in extra_params:
                    p = dict(params)
                    p.update(override)
                    if "C" in override or "l1_ratio" in override:
                        # re-derive penalty kind for parity bookkeeping
                        reg = 1 / p["C"] if p["C"] else 0.0
                        p["penalty"], _, _ = self._reg_params_value_mapping(
                            reg, p.get("l1_ratio") or 0.0
                        )
                    results.append(_single_fit(inputs, p, classes, y_enc))
                return results
            return _single_fit(inputs, params, classes, y_enc)

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "LogisticRegressionModel":
        return LogisticRegressionModel(**result)

    def streaming(self, classes=None):
        """Streaming incremental-fit engine over this configured estimator:
        warm-started per-chunk L-BFGS with count-weighted coefficient
        averaging — partial_fit/merge/finalize (srml-stream,
        docs/streaming.md).  Pass classes= when early chunks may not cover
        the full label set."""
        from ..stream.engines import StreamingLogisticRegression

        return StreamingLogisticRegression(self, classes=classes)

    # -- batched hyperparameter sweep (srml-sweep) -------------------------
    def _supportsBatchedSweep(self, df, paramMaps, evaluator) -> bool:
        if not paramMaps or not self._supportsTransformEvaluate(evaluator):
            return False
        try:
            overrides = [self._paramMap_to_tpu_overrides(pm) for pm in paramMaps]
        except ValueError:
            return False
        if any(set(ov) - {"C", "l1_ratio"} for ov in overrides):
            return False  # only the regularizer axes batch as lanes
        return not self._sweep_sparse_input(df)

    def _fitBatchedSweep(self, inputs, paramMaps, n_folds, seed, evaluator=None):
        """All n_folds x len(paramMaps) logreg fits as ONE lane-batched
        L-BFGS/OWL-QN run per penalty family over the ONE staged dataset —
        folds as fold-id weight masks, candidates as traced reg/l1 lanes
        with per-lane convergence masks (ops/logistic.py,
        ops/lbfgs.minimize_lbfgs_batched).  With `evaluator` (a
        device-resident frame) every model is scored on its fold's rows where
        the table lies, from the solve's own device arrays
        (ops/logistic.sweep_logistic_score_kernel): no row, no label, no
        fold id and no coefficient goes up for it.

        The step spans of a fit function tile a sweep too (srml.fit.init,
        .solve, .wait, .fetch, .pack), inside tuning.sweep.solve and
        tuning.sweep.score where they belong to one of the two."""
        from ..core import discover_label_classes
        from ..ops import sweep as sweep_ops
        from ..ops.labels import encode_labels_kernel

        params = dict(self._tpu_params)
        cand = []
        for pm in paramMaps:
            p = dict(params)
            p.update(self._paramMap_to_tpu_overrides(pm))
            C = float(p["C"])
            l1_ratio = float(p.get("l1_ratio") or 0.0)
            reg = 1.0 / C if C > 0 else 0.0
            cand.append((reg, l1_ratio, reg > 0 and l1_ratio > 0))
        fit_intercept = bool(params["fit_intercept"])
        max_iter = int(params["max_iter"])
        with profiling.span("srml.fit.init"):
            assert inputs.y is not None
            classes = discover_label_classes(inputs)
            if len(classes) < 2:
                raise RuntimeError(
                    "LogisticRegression requires at least two distinct labels"
                )
            num_classes = len(classes)
            kcls = 1 if num_classes == 2 else num_classes
            mesh = inputs.mesh
            fid = sweep_ops.stage_fold_ids(
                inputs.n_rows, inputs.X.shape[0], n_folds, seed, mesh
            )
            y_enc = encode_labels_kernel(
                inputs.y, jnp.asarray(classes.astype(inputs.y.dtype))
            )
            # one lane-batched run per penalty family (OWL-QN is a
            # structurally different optimizer, so it cannot share lanes
            # with the smooth-penalty group) — mirrors _single_fit's
            # per-candidate use_owlqn choice
            tol = jnp.asarray(np.float64(float(params["tol"])))
            families = []
            for owlqn in (False, True):
                idxs = [i for i, c in enumerate(cand) if c[2] == owlqn]
                if not idxs:
                    continue
                _, (regs, l1s) = sweep_ops.pack_lane_subset(
                    cand, idxs, fields=(0, 1)
                )
                families.append((owlqn, idxs, regs, l1s))
            # warm BOTH penalty families' sweep kernels at entry (concrete
            # args — the staged arrays themselves — so the derived keys and
            # captured shardings are exactly the dispatch's): with a mixed
            # grid the OWL-QN executable compiles on the pool WHILE the
            # smooth family's sweep runs instead of serializing behind it
            sweep_ops.warm(
                [
                    (
                        "sweep.logreg.fit",
                        sweep_logistic_fit_kernel,
                        (inputs.X, y_enc, inputs.weight, fid, regs, l1s, tol),
                        dict(
                            k_folds=n_folds,
                            kcls=kcls,
                            fit_intercept=fit_intercept,
                            max_iter=max_iter,
                            use_owlqn=owlqn,
                        ),
                    )
                    for owlqn, _idxs, regs, l1s in families
                ],
                mesh=mesh,
            )
        results: List[List[Dict[str, Any]]] = [
            [None] * len(cand) for _ in range(n_folds)  # type: ignore[list-item]
        ]
        held_out: Optional[List[List[MulticlassMetrics]]] = None
        if evaluator is not None:
            held_out = [[None] * len(cand) for _ in range(n_folds)]  # type: ignore[list-item]
            needs_probs = evaluator.getMetricName() == "logLoss"
            eps = jnp.asarray(evaluator.getEps(), inputs.X.dtype)
        logger = get_logger(type(self))

        def sweep_family(owlqn, idxs, regs, l1s) -> None:
            """One penalty family's lanes: the solve, its ONE batched fetch,
            the scoring where the table lies, and the (fold, candidate)
            results it fills in."""
            with profiling.span(
                "tuning.sweep.solve",
                candidates=len(idxs),
                folds=n_folds,
                owlqn=owlqn,
            ):
                with profiling.span("srml.fit.solve"):
                    solved = sweep_ops.dispatch(
                        "sweep.logreg.fit",
                        sweep_logistic_fit_kernel,
                        inputs.X,
                        y_enc,
                        inputs.weight,
                        fid,
                        regs,
                        l1s,
                        tol,
                        mesh=mesh,
                        k_folds=n_folds,
                        kcls=kcls,
                        fit_intercept=fit_intercept,
                        max_iter=max_iter,
                        use_owlqn=owlqn,
                    )
                W_h, b_h, n_iter_h, conv_h, n_evals_h, n_scans_h = fetch_fit_result(
                    solved
                )
            if held_out is not None:
                with profiling.span("tuning.sweep.score"):
                    with profiling.span("srml.fit.solve"):
                        scored = sweep_ops.dispatch(
                            "sweep.logreg.score",
                            sweep_logistic_score_kernel,
                            inputs.X,
                            y_enc,
                            fid,
                            solved[0],
                            solved[1],
                            eps,
                            mesh=mesh,
                            num_classes=num_classes,
                        )
                    conf_h, loss_h = fetch_fit_result(scored)
                    del scored
            with profiling.span("srml.fit.pack"):
                del solved
                # the lanes of the candidate bucket beyond the grid are not fits
                _count_lbfgs(
                    n_iter_h[:, : len(idxs)], n_evals_h[:, : len(idxs)]
                )
                profiling.incr_counter("tuning.sweep.scans", int(n_scans_h))
                profiling.incr_counter("tuning.sweep.solves")
                if sweep_on_line(owlqn):
                    profiling.incr_counter("tuning.sweep.line_solves")
                logger.info(
                    "sweep L-BFGS iters (fold x candidate): %s converged: %s",
                    n_iter_h[:, : len(idxs)].tolist(),
                    conv_h[:, : len(idxs)].tolist(),
                )
                for j, i in enumerate(idxs):
                    for f in range(n_folds):
                        results[f][i] = {
                            "coef_": np.asarray(W_h[f, j], dtype=np.float64),
                            "intercept_": np.asarray(
                                b_h[f, j], dtype=np.float64
                            ),
                            "classes_": np.asarray(classes, dtype=np.float64),
                            "n_cols": inputs.n_cols,
                            "dtype": str(inputs.dtype),
                            "num_iters": int(n_iter_h[f, j]),
                        }
                        if held_out is not None:
                            held_out[f][i] = MulticlassMetrics.from_confusion(
                                classes,
                                conf_h[f, j],
                                float(loss_h[f, j]) if needs_probs else None,
                            )

        # at most two families, each a distinct compiled sweep whose results
        # ship home together
        for family in families:
            sweep_family(*family)
        if held_out is not None:
            # every row is held out of exactly one fold, and scored there
            profiling.incr_counter("tuning.score.rows", inputs.n_rows)
        return results, held_out


class LogisticRegressionModel(
    _LogisticRegressionParams,
    _ClassificationModelEvaluationMixIn,
    _TpuModelWithPredictionCol,
):
    def __init__(
        self,
        coef_: np.ndarray,
        intercept_: np.ndarray,
        classes_: np.ndarray,
        n_cols: int,
        dtype: str,
        num_iters: Union[int, List[int]] = 0,
    ) -> None:
        super().__init__(
            coef_=np.asarray(coef_),
            intercept_=np.asarray(intercept_),
            classes_=np.asarray(classes_),
            n_cols=int(n_cols),
            dtype=str(dtype),
            num_iters=num_iters,
        )
        self.coef_ = np.asarray(coef_)
        self.intercept_ = np.asarray(intercept_)
        self.classes_ = np.asarray(classes_)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        self.num_iters = num_iters
        self._num_classes = len(self.classes_)

    @property
    def _num_models(self) -> int:
        return self.coef_.shape[0] if self.coef_.ndim == 3 else 1

    @property
    def numClasses(self) -> int:
        return self._num_classes

    @property
    def coefficients(self) -> np.ndarray:
        assert self._num_models == 1
        if self.coef_.shape[0] == 1:
            return self.coef_[0]
        raise AttributeError(
            "Multinomial models contain a matrix of coefficients, use coefficientMatrix instead."
        )

    @property
    def intercept(self) -> float:
        assert self._num_models == 1
        if len(self.intercept_) == 1:
            return float(self.intercept_[0])
        raise AttributeError(
            "Multinomial models contain a vector of intercepts, use interceptVector instead."
        )

    @property
    def coefficientMatrix(self) -> np.ndarray:
        assert self._num_models == 1
        return self.coef_

    @property
    def interceptVector(self) -> Any:
        """Dense or sparse intercepts, following Spark's compression rule
        (1.5*(nnz+1) < size -> sparse; classification.py:1206-1218).  Returns
        a pyspark Vector when pyspark is available, else a numpy array."""
        assert self._num_models == 1
        intercepts = self.intercept_
        try:
            from pyspark.ml.linalg import Vectors

            nnz = int(np.count_nonzero(intercepts))
            if 1.5 * (nnz + 1.0) < len(intercepts):
                data = {i: float(v) for i, v in enumerate(intercepts) if v != 0}
                return Vectors.sparse(len(intercepts), data)
            return Vectors.dense(list(intercepts))
        except ImportError:
            return intercepts

    def predict(self, value: np.ndarray) -> float:
        np_dtype = self._transform_dtype(self.dtype)
        scores = np.asarray(
            logistic_decision_kernel(
                jnp.asarray(np.asarray(value, np_dtype)[None, :]),
                jnp.asarray(self.coef_.astype(np_dtype)),
                jnp.asarray(self.intercept_.astype(np_dtype)),
            )
        )
        idx = int(
            np.asarray(scores_to_labels(jnp.asarray(scores), self._num_classes))[0]
        )
        return float(self.classes_[idx])

    def predictProbability(self, value: np.ndarray) -> np.ndarray:
        np_dtype = self._transform_dtype(self.dtype)
        scores = logistic_decision_kernel(
            jnp.asarray(np.asarray(value, np_dtype)[None, :]),
            jnp.asarray(self.coef_.astype(np_dtype)),
            jnp.asarray(self.intercept_.astype(np_dtype)),
        )
        return np.asarray(scores_to_probs(scores, self._num_classes))[0]

    def _out_columns(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _get_tpu_transform_func(self, dataset: DataFrame):
        assert self._num_models == 1
        np_dtype = self._transform_dtype(self.dtype)
        W = jax.device_put(self.coef_.astype(np_dtype))
        b = jax.device_put(self.intercept_.astype(np_dtype))
        classes = self.classes_
        num_classes = self._num_classes
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            if hasattr(features, "tocsr"):  # CSR partition -> device ELL
                from ..ops.sparse import ell_device_from_scipy

                Xd = ell_device_from_scipy(features, np_dtype)
            else:
                Xd = jax.device_put(np.asarray(features, np_dtype))
            scores = logistic_decision_kernel(Xd, W, b)
            probs = np.asarray(scores_to_probs(scores, num_classes), np.float64)
            idx = np.asarray(
                scores_to_labels(scores, num_classes), np.int64
            )
            raw = np.asarray(scores, np.float64)
            if num_classes == 2 and raw.shape[1] == 1:
                raw = np.concatenate([-raw, raw], axis=1)
            return {
                pred_col: classes[idx].astype(np.float64),
                prob_col: probs,
                raw_col: raw,
            }

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): decision scores, probabilities
        and label indices fused into ONE bucket-padded kernel through the
        AOT executable cache — the same ops the batch transform composes,
        kept on device so a served batch is one dispatch, not three."""
        assert self._num_models == 1, "combined multi-models are not servable"
        from ..serving.entry import kernel_entry

        np_dtype = self._transform_dtype(self.dtype)
        W = jax.device_put(self.coef_.astype(np_dtype))
        b = jax.device_put(self.intercept_.astype(np_dtype))
        classes = self.classes_
        num_classes = self._num_classes
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")

        def _serve_kernel(X: jax.Array, W: jax.Array, b: jax.Array):
            scores = logistic_decision_kernel(X, W, b)
            return (
                scores,
                scores_to_probs(scores, num_classes),
                scores_to_labels(scores, num_classes),
            )

        def _post(out) -> Dict[str, Any]:
            scores, probs, labels = out
            raw = np.asarray(scores, np.float64)
            if num_classes == 2 and raw.shape[1] == 1:
                raw = np.concatenate([-raw, raw], axis=1)
            idx = np.asarray(labels, np.int64)
            return {
                pred_col: classes[idx].astype(np.float64),
                prob_col: np.asarray(probs, np.float64),
                raw_col: raw,
            }

        return kernel_entry(
            "serve.logreg",
            jax.jit(_serve_kernel),
            (W, b),
            {},
            _post,
            dtype=np_dtype,
            n_cols=self.n_cols,
            out_cols=[pred_col, prob_col, raw_col],
            info={"num_classes": num_classes},
            mesh=mesh,
        )

    def _lane_entry(self, mesh: Any = None):
        """Multiplexed serving hook (serving/multiplex): (W, b) as ONE lane
        of the lane-stacked fused decision/probability/label kernel.  The
        class labels ride `meta`: variants sharing a lane buffer must agree
        on them, because the shared postprocess maps label indices through
        variant 0's classes_."""
        assert self._num_models == 1, "combined multi-models are not servable"
        from ..ops.logistic import lane_logistic_predict_kernel
        from ..serving.multiplex import LaneEntry

        np_dtype = self._transform_dtype(self.dtype)
        W = np.ascontiguousarray(self.coef_.astype(np_dtype))
        b = np.ascontiguousarray(self.intercept_.astype(np_dtype))
        classes = self.classes_
        num_classes = self._num_classes
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")

        def _post(out) -> Dict[str, Any]:
            scores, probs, labels = out
            raw = np.asarray(scores, np.float64)
            if num_classes == 2 and raw.shape[1] == 1:
                raw = np.concatenate([-raw, raw], axis=1)
            idx = np.asarray(labels, np.int64)
            return {
                pred_col: classes[idx].astype(np.float64),
                prob_col: np.asarray(probs, np.float64),
                raw_col: raw,
            }

        return LaneEntry(
            name="lanes.logreg",
            n_cols=self.n_cols,
            dtype=np_dtype,
            out_cols=[pred_col, prob_col, raw_col],
            leaves=(W, b),
            kernel=lane_logistic_predict_kernel,
            statics={"num_classes": num_classes},
            postprocess=_post,
            meta=(str(np.asarray(classes).dtype), np.asarray(classes).tobytes()),
            info={"num_classes": num_classes},
        )

    def _get_eval_predict_func(self) -> Callable[[np.ndarray], tuple]:
        np_dtype = self._transform_dtype(self.dtype)
        coefs = jnp.asarray(
            (self.coef_ if self.coef_.ndim == 3 else self.coef_[None]).astype(np_dtype)
        )  # (M, k, D)
        intercepts = jnp.asarray(
            (
                self.intercept_ if self.intercept_.ndim == 2 else self.intercept_[None]
            ).astype(np_dtype)
        )  # (M, k)
        classes = self.classes_
        num_classes = self._num_classes

        def _predict_all(feats: np.ndarray):
            # one transfer + one batched matmul for all M models; HIGHEST
            # keeps scores bit-comparable with the single-model decision
            # kernel (ops/logistic.py logistic_decision_kernel), which the
            # single-pass CV scoring path is asserted against
            Xd = jax.device_put(np.asarray(feats, np_dtype))
            scores = (
                jnp.einsum(
                    "nd,mkd->mnk",
                    Xd,
                    coefs,
                    precision=jax.lax.Precision.HIGHEST,
                )
                + intercepts[:, None, :]
            )
            probs = np.stack(
                [
                    np.asarray(scores_to_probs(scores[m], num_classes), np.float64)
                    for m in range(scores.shape[0])
                ]
            )
            idx = np.stack(
                [
                    np.asarray(scores_to_labels(scores[m], num_classes), np.int64)
                    for m in range(scores.shape[0])
                ]
            )
            return classes[idx].astype(np.float64), probs

        return _predict_all

    def cpu(self):
        """pyspark.ml LogisticRegressionModel (parity hook for
        classification.py:1124-1146)."""
        from ..spark.interop import to_spark_logistic_model

        return to_spark_logistic_model(self)

    @classmethod
    def _combine(cls, models: List["LogisticRegressionModel"]) -> "LogisticRegressionModel":
        assert models and all(isinstance(m, cls) for m in models)
        first = models[0]
        combined = cls(
            coef_=np.stack([m.coef_ for m in models]),
            intercept_=np.stack([m.intercept_ for m in models]),
            classes_=first.classes_,
            n_cols=first.n_cols,
            dtype=first.dtype,
            num_iters=[int(np.ravel(m.num_iters)[0]) for m in models],
        )
        first._copyValues(combined)
        combined._tpu_params.update(first._tpu_params)
        combined._float32_inputs = first._float32_inputs
        return combined

    def _transformEvaluate(self, dataset: Any, evaluator: Any, params=None) -> List[float]:
        return self._transform_evaluate(dataset, evaluator, self._num_models)
