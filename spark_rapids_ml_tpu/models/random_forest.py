#
# RandomForest classifier/regressor estimators and models.
#
# Capability parity with the reference's shared tree machinery
# (/root/reference/python/src/spark_rapids_ml/tree.py:66-607) and its
# Spark-facing subclasses (classification.py:297-643, regression.py:780-1057):
# same Spark param mapping (tree.py:68-86), same max_features value mapping
# (tree.py:88-110), same solver defaults (tree.py:112-128), int32 label cast
# for classification (classification.py:483-496), probability/rawPrediction
# columns, model combine and single-pass transform-evaluate.
#
# The builder is redesigned TPU-first (ops/forest.py): every tree trains on
# the FULL row-sharded dataset with Poisson bootstrap weights (a statistical
# improvement over the reference's per-worker data shards, tree.py:256-267 —
# there each worker only sees 1/num_workers of the rows).  The forest is
# stored as dense arrays (feature/threshold/leaf-value per node) instead of
# treelite bytes; `trees_to_dicts` exports the portable nested-dict format
# that plays the role of the reference's treelite JSON (utils.py:385-447
# translate_trees interop).
#

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..core import (
    FitInputs,
    _TpuEstimatorSupervised,
    _TpuModelWithPredictionCol,
    fetch_fit_result,
)
from ..dataframe import DataFrame
from .linear_regression import _RegressionModelEvaluationMixIn
from .logistic_regression import _ClassificationModelEvaluationMixIn
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasSeed,
    HasVerbose,
    HasWeightCol,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from .. import profiling
from ..ops.forest import (
    bin_features,
    bin_features_feature_major,
    bootstrap_chunks,
    bootstrap_counts,
    bootstrap_weights,
    _bin_edges_device_kernel,
    compute_bin_edges,
    forest_predict_cached,
    grow_forest,
    warm_forest_kernels,
)
from ..utils import get_logger

_MAX_SUPPORTED_DEPTH = 16  # dense tree layout: 2^(d+1)-1 node slots

# binning subsample cap (compute_bin_edges subsamples to 100k anyway; this
# bound also caps the device->host transfer that feeds it)
_BINNING_SAMPLE_ROWS = 16_384
# cap the sample FETCH, not just its row count: the sample crosses the
# host link inside every estimator fit, while edge quality needs only ~100
# samples per bin
_BINNING_SAMPLE_BYTES = 32 << 20


def _binning_quota(X, n_shards_global: int) -> int:
    """Rows each shard may contribute to the binning sample: the byte/row
    budget divided over the GLOBAL shard count, so a 2-process x 4-device
    fit samples exactly like a 1-process x 8-device fit over the same
    global row layout (identical edges either way).  The floor sits on
    the TOTAL, not per shard — a per-shard floor times a big mesh would
    overshoot the byte cap this sample exists to enforce."""
    row_bytes = max(1, X.shape[1] * X.dtype.itemsize)
    budget = max(
        2048, min(_BINNING_SAMPLE_ROWS, _BINNING_SAMPLE_BYTES // row_bytes)
    )
    return max(1, budget // max(1, n_shards_global))


def _binning_rows(shard_weight, quota: int) -> np.ndarray:
    """One shard's sampled row indices: valid (weight > 0) rows, ceil-
    strided down to the quota.  Ceil stride spans the FULL row range — a
    floor stride would truncate to a leading prefix, badly biasing edges
    on label/time-sorted data.  The ONE row-selection policy shared by
    the host-gather and device-edges paths."""
    wv = np.asarray(shard_weight)
    idx = np.flatnonzero(wv > 0)
    if idx.size > quota:
        step = -(-idx.size // quota)
        idx = idx[::step]
    return idx


def _binning_sample(inputs: FitInputs) -> np.ndarray:
    """Bounded strided row sample of the device-resident features for
    quantile binning: per-shard strided gathers of valid rows (at most
    min(_BINNING_SAMPLE_ROWS, _BINNING_SAMPLE_BYTES worth) across the whole
    job), gathered across ranks through the control plane so every rank
    computes IDENTICAL bin edges — the per-rank-sample + gather the
    reference's byte-capped binning would do under its barrier allGather.
    Never round-trips the full dataset to the host and never touches a
    non-addressable shard, so it is safe in multi-process fits."""
    from ..core import _aligned_shard_objs

    X, w = inputs.X, inputs.weight
    shard_pairs = list(_aligned_shard_objs(X, w))
    quota = _binning_quota(
        X, max(1, inputs.nranks) * max(1, len(shard_pairs))
    )
    # On TPU the sample crosses the host link: fetch it bf16 — half the
    # bytes.  Quantile edges from a ~2.8k-row sample carry
    # sampling error orders of magnitude above bf16 rounding OF THE
    # RESIDUALS: each feature is centered on device before the cast and
    # restored after the fetch, so offset-dominated features (a year
    # column in [2020, 2026], sensor readings 1000 +/- 0.5) keep their
    # full bin resolution — raw bf16 would collapse them to 1-2 codes.
    # The rounded edges are used consistently for training AND prediction
    # thresholds (no train/serve skew).
    halve = (
        jax.default_backend() == "tpu"
        and np.dtype(inputs.dtype) == np.float32
    )
    parts = []
    for sx, sw in shard_pairs:
        idx = _binning_rows(sw.data, quota)
        if idx.size:
            sub = sx.data[jnp.asarray(idx)]
            if halve:
                mu = jnp.mean(sub, axis=0)
                # graftlint: disable=R1 (loop is over device shards; one fetch per shard IS the batch unit)
                sub_h, mu_h = jax.device_get(
                    ((sub - mu[None, :]).astype(jnp.bfloat16), mu)
                )
                parts.append(
                    sub_h.astype(X.dtype) + np.asarray(mu_h, X.dtype)[None, :]
                )
            else:
                # graftlint: disable=R1 (per-shard fetch: the shard is the batch unit)
                parts.append(np.asarray(sub).astype(X.dtype, copy=False))
    local = (
        np.concatenate(parts)
        if parts
        else np.zeros((0, X.shape[1]), dtype=X.dtype)
    )
    if inputs.nranks > 1 and inputs.control_plane is not None:
        from ..parallel.runner import allgather_ndarray

        # the gathered total stays ~budget rows (the per-shard quota divides
        # by nranks), so each rank posts ~budget/nranks rows worth of
        # message — bounded by _BINNING_SAMPLE_BYTES across the whole job
        local = np.concatenate(
            allgather_ndarray(inputs.control_plane, inputs.rank, local)
        ).astype(X.dtype, copy=False)
    return local


def _binning_sample_device(inputs: FitInputs):
    """Single-rank TPU path: the strided binning sample STAYS ON DEVICE
    (same row selection as _binning_sample) so the quantile edges can be
    computed there (ops/forest._bin_edges_device_kernel) and only the
    (D, B-1) edge matrix crosses the host link.  Returns None when the
    fit is multi-rank/multi-shard or non-f32 — those keep the host
    gather path."""
    from ..core import _aligned_shard_objs

    if jax.default_backend() != "tpu" or inputs.nranks > 1:
        return None
    X, w = inputs.X, inputs.weight
    if np.dtype(inputs.dtype) != np.float32:
        return None
    shard_pairs = list(_aligned_shard_objs(X, w))
    if len(shard_pairs) != 1:
        return None
    sx, _ = shard_pairs[0]
    # the valid rows from the ingest's HOST weights (none given: the frame's
    # n_rows rows): reading the device's mask back would block every fit
    host_w = (
        inputs.host_w if inputs.host_w is not None
        else np.ones(inputs.n_rows, np.float32)
    )
    idx = _binning_rows(host_w, _binning_quota(X, 1))
    if idx.size == 0:
        return None
    return sx.data[jnp.asarray(idx)]


@partial(jax.jit, static_argnames=("n_trees", "bootstrap"))
def _per_tree_stats(stats, weight, key, n_trees, bootstrap):
    """(T, N, S) per-tree bootstrap-weighted stats.  Jitted so the Poisson
    draw is generated SHARDED alongside the row-sharded weight (an eager
    jax.random.poisson would materialize the full (T, N) matrix replicated
    on every device — and is not expressible at all on a multi-process
    mesh)."""
    if bootstrap:
        bw = bootstrap_counts(key, n_trees, weight.shape[0]).astype(weight.dtype)
        w_t = weight[None, :] * bw
    else:
        w_t = jnp.broadcast_to(weight[None, :], (n_trees, weight.shape[0]))
    return stats[None, :, :] * w_t[:, :, None]


def _str_or_numerical(value: str) -> Union[str, float, int]:
    """'0.3' -> 0.3, '5' -> 5, else the string (reference utils helper
    used by the max_features mapping)."""
    try:
        return int(value)
    except (TypeError, ValueError):
        try:
            return float(value)
        except (TypeError, ValueError):
            return value


def _mxu_eligible(inputs, n_bins, max_features, max_depth, s_split) -> bool:
    """Whether the MXU histogram builder (ops/forest_mxu) serves this fit;
    False -> the mesh-parallel engine (ops/forest.grow_forest).  TPU
    scatter sustains ~10M updates/s, the MXU path ~36 TF-equivalent.  The
    histogram KERNEL has a mesh sharding rule
    (forest_hist.node_histograms_sharded), but the full builder still
    drives a single chip end-to-end (unsharded deep-phase payload sort), so
    multi-device fits run the sharded scan-batched engine — no longer the
    old host-driven per-level loop."""
    from ..ops import forest_mxu

    return (
        jax.default_backend() == "tpu"
        and inputs.mesh.devices.size == 1
        and n_bins <= 128
        and max_features <= 1024
        and forest_mxu.mxu_depth_supported(max_depth, s_split)
    )


def _grow_mxu_device(
    inputs,
    bins_rows,      # (D, tiles, 32, 128) int8: bin_features_feature_major's
                    # (D, n_pad) table, a feature a slice (tile_feature_rows)
    stats,
    n_trees,
    bootstrap,
    seed,
    is_classification,
    *,
    max_depth,
    n_bins,
    kind,
    max_features,
    min_samples_leaf,
    min_impurity_decrease,
):
    """Grow on the MXU histogram builder: every dispatch of the growth, and
    nothing read back.  Returns ((tree_buf, kept), plan) for
    core.fetch_fit_result and forest_mxu.pack_forest.  Caller has already
    checked _mxu_eligible and binned feature-major — the row-major int bin matrix this path used
    to re-lay-out was a redundant 1.2-4.8 GB resident copy that tipped the
    depth-13 benchmark fit over HBM."""
    from ..ops import forest_mxu

    n_pad = _fm_rows(inputs)

    @partial(jax.jit, static_argnames=("n_pad",))
    def _layout(stats, weight, n_pad):
        pad = n_pad - stats.shape[0]
        st = jnp.pad(stats, ((0, pad), (0, 0))).T  # (S_in, n_pad)
        w = jnp.pad(weight, (0, pad))
        return st, w

    st_fm, w_pad = _layout(stats, inputs.weight, n_pad)
    if is_classification:
        base_stats, stats3 = st_fm, None
        # class index per row (deep phase rebuilds one-hot stats post-sort)
        y_vals = jnp.argmax(st_fm, axis=0).astype(jnp.float32)
    else:
        # stats rows are (1, y, y^2)*mask; split search needs only (w, wy)
        base_stats, stats3 = st_fm[:2], st_fm
        y_vals = st_fm[1]
    if bootstrap:
        w_trees = w_pad[None, :] * bootstrap_weights(
            _bootstrap_draw(seed, n_trees, n_pad, 0)
        )
    else:
        w_trees = jnp.broadcast_to(w_pad[None, :], (n_trees, n_pad))
    return forest_mxu.grow_forest_mxu_device(
        bins_rows, base_stats, w_trees, stats3,
        max_depth=max_depth, n_bins=n_bins, kind=kind,
        max_features=int(max_features),
        min_samples_leaf=min_samples_leaf,
        min_impurity_decrease=min_impurity_decrease,
        seed=seed, y_vals=y_vals, n_rows=inputs.n_rows,
        # the kernels run through the interpreter anywhere but on the chip
        interpret=jax.default_backend() != "tpu",
    )


def _fm_rows(inputs) -> int:
    """Rows of the MXU builder's feature-major arrays: the frame's, up to
    whole histogram row tiles."""
    from ..ops.forest_hist import _ROW_TILE

    return -(-inputs.X.shape[0] // _ROW_TILE) * _ROW_TILE


def _bootstrap_draw(seed: int, n_trees: int, n_rows: int, tree_chunk: int) -> np.ndarray:
    """A model's bootstrap_draw_: what ops/forest.bootstrap_weights needs to
    draw the fit's (tree, row) bootstrap counts again."""
    return np.array([seed, n_trees, n_rows, tree_chunk], np.int64)


def _draw_attr(draw) -> Dict[str, np.ndarray]:
    """The model attribute a bootstrapped fit keeps (32 bytes: what
    ops/forest.bootstrap_weights draws the (tree, row) counts again from);
    nothing for a fit without bootstrap, or a model built from arrays."""
    return {} if draw is None else {"bootstrap_draw_": np.asarray(draw, np.int64)}


class _RandomForestClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {
            "maxBins": "n_bins",
            "maxDepth": "max_depth",
            "numTrees": "n_estimators",
            "impurity": "split_criterion",
            "featureSubsetStrategy": "max_features",
            "bootstrap": "bootstrap",
            "seed": "random_state",
            "minInstancesPerNode": "min_samples_leaf",
            "minInfoGain": "",
            "maxMemoryInMB": "",
            "cacheNodeIds": "",
            "checkpointInterval": "",
            "subsamplingRate": "",
            "minWeightFractionPerNode": "",
            "weightCol": None,
            "leafCol": None,
        }

    @classmethod
    def _param_value_mapping(cls):
        def _subset_mapping(v):
            maybe = _str_or_numerical(v) if isinstance(v, str) else v
            if isinstance(maybe, (int, float)) and not isinstance(maybe, bool):
                return maybe
            return {
                "onethird": 1 / 3.0,
                "all": 1.0,
                "auto": "auto",
                "sqrt": "sqrt",
                "log2": "log2",
            }.get(maybe)

        return {"max_features": _subset_mapping}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_estimators": 100,
            "max_depth": 16,
            "max_features": "auto",
            "n_bins": 128,
            "bootstrap": True,
            "verbose": False,
            "min_samples_leaf": 1,
            "min_samples_split": 2,
            "max_samples": 1.0,
            "max_leaves": -1,
            "min_impurity_decrease": 0.0,
            "random_state": None,
            "max_batch_size": 4096,
        }


class _RandomForestParams(
    _RandomForestClass,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasSeed,
    HasWeightCol,
    HasVerbose,
):
    numTrees = Param(_dummy(), "numTrees", "number of trees to train (>= 1)", TypeConverters.toInt)
    maxDepth = Param(_dummy(), "maxDepth", "maximum depth of the tree (>= 0, <= 16)", TypeConverters.toInt)
    maxBins = Param(_dummy(), "maxBins", "max number of bins for discretizing continuous features", TypeConverters.toInt)
    impurity = Param(_dummy(), "impurity", "criterion used for information gain calculation", TypeConverters.toString)
    featureSubsetStrategy = Param(_dummy(), "featureSubsetStrategy", "number of features to consider per split (auto|all|onethird|sqrt|log2|n|fraction)", TypeConverters.toString)
    bootstrap = Param(_dummy(), "bootstrap", "whether bootstrap samples are used", TypeConverters.toBoolean)
    minInstancesPerNode = Param(_dummy(), "minInstancesPerNode", "minimum number of instances each child must have after split", TypeConverters.toInt)
    minInfoGain = Param(_dummy(), "minInfoGain", "minimum information gain for a split (ignored)", TypeConverters.toFloat)
    subsamplingRate = Param(_dummy(), "subsamplingRate", "fraction of data used per tree (ignored)", TypeConverters.toFloat)
    maxMemoryInMB = Param(_dummy(), "maxMemoryInMB", "max memory for histogram aggregation (ignored)", TypeConverters.toInt)
    cacheNodeIds = Param(_dummy(), "cacheNodeIds", "ignored", TypeConverters.toBoolean)
    checkpointInterval = Param(_dummy(), "checkpointInterval", "ignored", TypeConverters.toInt)
    minWeightFractionPerNode = Param(_dummy(), "minWeightFractionPerNode", "ignored", TypeConverters.toFloat)

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(
            numTrees=20,
            maxDepth=5,
            maxBins=32,
            featureSubsetStrategy="auto",
            bootstrap=True,
            minInstancesPerNode=1,
            minInfoGain=0.0,
            subsamplingRate=1.0,
            maxMemoryInMB=256,
            cacheNodeIds=False,
            checkpointInterval=10,
            minWeightFractionPerNode=0.0,
        )

    def setNumTrees(self, value: int):
        return self._set_params(numTrees=value)

    def setMaxDepth(self, value: int):
        return self._set_params(maxDepth=value)

    def setMaxBins(self, value: int):
        return self._set_params(maxBins=value)

    def setImpurity(self, value: str):
        return self._set_params(impurity=value)

    def setFeatureSubsetStrategy(self, value: str):
        return self._set_params(featureSubsetStrategy=value)

    def setSeed(self, value: int):
        return self._set_params(seed=value)

    def getNumTrees(self) -> int:
        return self.getOrDefault("numTrees")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")

    def getMaxBins(self) -> int:
        return self.getOrDefault("maxBins")


def _resolve_max_features(value: Any, n_cols: int, is_classification: bool, n_trees: int) -> int:
    """Spark featureSubsetStrategy semantics: auto = all when numTrees == 1,
    else sqrt (classification) / onethird (regression)."""
    if value == "auto" or value is None:
        if n_trees == 1:
            return n_cols
        return (
            max(1, int(math.sqrt(n_cols)))
            if is_classification
            else max(1, int(n_cols / 3.0))
        )
    if value == "sqrt":
        return max(1, int(math.sqrt(n_cols)))
    if value == "log2":
        return max(1, int(math.log2(n_cols)))
    if isinstance(value, float):
        return max(1, min(n_cols, int(value * n_cols)))
    return max(1, min(n_cols, int(value)))


class _RandomForestEstimator(_RandomForestParams, _TpuEstimatorSupervised):
    _is_classification = False

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    # binning samples per-rank local shards + control-plane gather
    # (_binning_sample) and label stats encode on device (ops/labels.py +
    # jax.nn.one_hot over the sharded labels), so the whole fit runs on a
    # multi-process mesh — and unlike the reference's per-worker tree
    # subsets over per-worker data shards (tree.py:256-267,292-397), every
    # tree here trains on the FULL global dataset with Poisson bootstrap
    # weights under GSPMD
    _supports_multicontroller_fit = True

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return True

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        from ..evaluation import (
            MulticlassClassificationEvaluator,
            RegressionEvaluator,
        )

        if self._is_classification:
            return isinstance(evaluator, MulticlassClassificationEvaluator)
        return isinstance(evaluator, RegressionEvaluator)

    def _encode_labels(self, y: np.ndarray, valid: np.ndarray):
        raise NotImplementedError

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        logger = get_logger(type(self))
        is_classification = self._is_classification

        def _attrs(forest, inputs, max_depth, extra_attrs, draw):
            features, thresholds, leaf_values, node_counts, impurities = forest
            attrs = {
                "features_": features,
                "thresholds_": thresholds,
                "leaf_values_": leaf_values,
                "node_counts_": node_counts,
                "impurities_": impurities,
                "max_depth": max_depth,
                "n_cols": inputs.n_cols,
                "dtype": str(inputs.dtype),
                "bootstrap_draw_": draw,
            }
            attrs.update(extra_attrs)
            return attrs

        # The step spans of _fit and _single_fit tile srml.fit
        # (core.FitJob.run): init (edges, label stats, binning,
        # bootstrap draw), solve (growth: dispatches only on the MXU
        # builder), wait + fetch (core.fetch_fit_result: ONE batched fetch
        # of the forest), pack.
        def _single_fit(
            inputs: FitInputs, params: Dict[str, Any], get_bins, edges, stats, extra_attrs
        ) -> Dict[str, Any]:
            with profiling.span("srml.fit.init"):
                max_depth = int(params["max_depth"])
                if max_depth > _MAX_SUPPORTED_DEPTH:
                    raise ValueError(
                        f"maxDepth > {_MAX_SUPPORTED_DEPTH} is not supported by the dense "
                        f"TPU tree layout (got {max_depth})"
                    )
                n_trees = int(params["n_estimators"])
                n_bins = int(params["n_bins"])
                criterion = params.get("split_criterion")
                kind = (
                    "regression"
                    if not is_classification
                    else ("entropy" if criterion == "entropy" else "gini")
                )
                max_features = _resolve_max_features(
                    params.get("max_features", "auto"),
                    inputs.n_cols,
                    is_classification,
                    n_trees,
                )
                seed = params.get("random_state")
                seed = int(seed) & 0x7FFFFFFF if seed is not None else 42
                bootstrap = bool(params.get("bootstrap", True))
                grow_kwargs = dict(
                    max_depth=max_depth,
                    n_bins=n_bins,
                    kind=kind,
                    max_features=max_features,
                    min_samples_leaf=float(params.get("min_samples_leaf", 1)),
                    min_impurity_decrease=float(
                        params.get("min_impurity_decrease", 0.0)
                    ),
                )
                s_split = 2 if not is_classification else stats.shape[1]
                mxu = _mxu_eligible(inputs, n_bins, max_features, max_depth, s_split)
                profiling.incr_counter("forest.fits")
                if not is_classification:
                    profiling.incr_counter("forest.reg_fits")
                profiling.incr_counter("forest.subset_features", max_features)
                if mxu:
                    # the one-chip builder's subsets are copies of the rows
                    # they select (forest_hist.gather_rows_matmul)
                    profiling.incr_counter("forest.gather_copy_fits")
                    bins_rows = get_bins("fm", edges)
            if mxu:
                with profiling.span("srml.fit.solve"):
                    grown, plan = _grow_mxu_device(
                        inputs, bins_rows, stats, n_trees, bootstrap, seed,
                        is_classification, **grow_kwargs,
                    )
                # the edges go with the forest: one read of the device a fit
                profiling.incr_counter("forest.host_syncs")
                grown_h, edges_h = fetch_fit_result((grown, jnp.asarray(edges)))
                with profiling.span("srml.fit.pack"):
                    from ..ops.forest_mxu import pack_forest

                    del grown, bins_rows
                    forest = pack_forest(grown_h, plan, edges_h)
                    logger.info(
                        "grew %d trees on the MXU histogram path (depth<=%d, "
                        "bins=%d)", n_trees, max_depth, n_bins,
                    )
                    draw = (
                        _bootstrap_draw(seed, n_trees, _fm_rows(inputs), 0)
                        if bootstrap else None
                    )
                    return _attrs(forest, inputs, max_depth, extra_attrs, draw)
            # Mesh-parallel engine growth (ops/forest.grow_forest): trees
            # ride the scan-batched level-block kernels in CHUNKS sized so
            # the (combined, D) per-node feature-subset scores at the
            # deepest level and the (Tc, N, S) per-tree stats tensor each
            # stay within budget; a chunk of ONE tree still runs the batched
            # engine with its single fetch.
            with profiling.span("srml.fit.init"):
                if not isinstance(edges, np.ndarray):
                    profiling.incr_counter("forest.host_syncs")
                    edges = np.asarray(edges)
                n_pad = inputs.X.shape[0]
                t_sub = (
                    max(1, (512 << 20) // max(1, (2**max_depth) * inputs.n_cols * 4))
                    if max_features < inputs.n_cols
                    else n_trees
                )
                t_stats = max(1, (2 << 30) // max(1, n_pad * stats.shape[1] * 4))
                t_chunk = max(1, min(n_trees, t_sub, t_stats))
                # stage the level-block kernel compiles on the precompile pool
                # BEFORE binning runs, so XLA compiles while rows are binned.
                # The tree count rides every kernel aval shape, so a partial
                # final chunk is its own geometry — warm it too, or its blocks
                # cold-compile serially at the end of the fit
                warm_forest_kernels(
                    n_pad, inputs.n_cols, t_chunk, stats.shape[1],
                    mesh=inputs.mesh, dtype=stats.dtype, **grow_kwargs,
                )
                t_rem = n_trees % t_chunk
                if t_rem:
                    warm_forest_kernels(
                        n_pad, inputs.n_cols, t_rem, stats.shape[1],
                        mesh=inputs.mesh, dtype=stats.dtype, **grow_kwargs,
                    )
                Xb = get_bins("rm", edges)
            with profiling.span("srml.fit.solve"):
                draw = _bootstrap_draw(seed, n_trees, n_pad, t_chunk)
                parts = []
                for t0, tc, kt in bootstrap_chunks(draw):
                    stats_t = _per_tree_stats(stats, inputs.weight, kt, tc, bootstrap)
                    parts.append(
                        grow_forest(
                            Xb, stats_t, edges,
                            seed=(seed + 7919 * t0) & 0x7FFFFFFF,
                            mesh=inputs.mesh, **grow_kwargs,
                        )
                    )
            with profiling.span("srml.fit.pack"):
                if len(parts) == 1:
                    forest = parts[0]
                else:
                    forest = tuple(
                        np.concatenate([p[i] for p in parts]) for i in range(5)
                    )
                profiling.incr_counter(
                    "forest.nodes",
                    int(2 * (np.asarray(forest[0]) >= 0).sum() + n_trees),
                )
                logger.info("grew %d trees (depth<=%d, bins=%d)", n_trees, max_depth, n_bins)
                return _attrs(forest, inputs, max_depth, extra_attrs, draw if bootstrap else None)

        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            assert inputs.y is not None
            n_bins = int(params["n_bins"])
            # quantile edges from a bounded strided row sample (a full
            # np.asarray(inputs.X) round-trips the whole dataset over the
            # host link — 4.8 GB at the benchmark shape — and raises
            # outright multi-process).  Single-rank TPU fits keep the
            # sample AND the edges on the device (the 1.5 MB edge matrix
            # crosses the link with the finished forest); multi-rank/CPU
            # fits take the host gather path.
            X_host = None

            def edges_for(bins: int):
                if sample_dev is not None:
                    return _bin_edges_device_kernel(
                        sample_dev, n_bins=bins, n_cols=sample_dev.shape[1]
                    )
                return compute_bin_edges(X_host, bins)

            with profiling.span("srml.fit.init"):
                with profiling.span("forest.edges"):
                    sample_dev = _binning_sample_device(inputs)
                    if sample_dev is None:
                        profiling.incr_counter("forest.host_syncs")
                        X_host = _binning_sample(inputs)
                    edges = edges_for(n_bins)

            # Lazy per-route binning: the MXU route bins straight into the
            # feature-major int8 layout (bin_features_feature_major), a
            # feature a slice of whole tiles (the subset gather's layout,
            # forest_hist.tile_feature_rows), the scatter route row-major.
            # Binning eagerly row-major and
            # re-laying-out kept TWO full bin matrices resident — the copy
            # that OOM'd the 400k x 3000 depth-13 benchmark fit.  The cache
            # holds the edges OBJECT alongside each entry (id() alone can
            # be recycled after gc) and keeps only the CURRENT edges' bin
            # matrices — distinct-n_bins sweeps drop the previous matrices
            # instead of accumulating one full-size copy per override.
            bins_cache: Dict[str, Any] = {}

            def get_bins(layout: str, e):
                cached = bins_cache.get(layout)
                if cached is not None and cached[0] is e:
                    return cached[1]
                if any(held[0] is not e for held in bins_cache.values()):
                    bins_cache.clear()  # new edges: old matrices are dead
                with profiling.span("forest.bin"):
                    if layout == "fm":
                        out = bin_features_feature_major(
                            inputs.X, jnp.asarray(e), n_pad=_fm_rows(inputs),
                            tiled=True,
                        )
                    else:
                        out = bin_features(inputs.X, jnp.asarray(e))
                bins_cache[layout] = (e, out)
                return out

            with profiling.span("srml.fit.init"):
                stats, extra_attrs = self._label_stats(inputs)
            if extra_params:
                results = []
                for override in extra_params:
                    p = dict(params)
                    p.update(override)
                    e = edges
                    if int(p["n_bins"]) != n_bins:
                        with profiling.span("srml.fit.init"):
                            e = edges_for(int(p["n_bins"]))
                    results.append(
                        _single_fit(inputs, p, get_bins, e, stats, extra_attrs)
                    )
                return results
            return _single_fit(inputs, params, get_bins, edges, stats, extra_attrs)

        return _fit

    def _label_stats(self, inputs: FitInputs):
        raise NotImplementedError


class _RandomForestModelBase(_RandomForestParams, _TpuModelWithPredictionCol):
    """Shared forest model: dense arrays + vectorized traversal predict.

    A _combine'd multi-model stores every sub-model's trees concatenated
    along the tree axis with `_tree_counts` recording the per-model counts
    (the reference concatenates treelite handles the same way, tree.py:592);
    it only supports _transformEvaluate, not transform."""

    @property
    def _num_models(self) -> int:
        counts = getattr(self, "_tree_counts", None)
        return len(counts) if counts else 1

    @classmethod
    def _construct(cls, attrs):
        """A combined multi-model's sub-model split ('tree_counts') is an
        attribute, not a constructor argument — reattach it so the
        combined structure survives executor serialization and npz
        persistence."""
        tc = attrs.pop("tree_counts", None)
        model = cls(**attrs)
        if tc is not None:
            counts = [int(c) for c in np.asarray(tc).tolist()]
            model._tree_counts = counts
            model._model_attributes["tree_counts"] = counts
        return model

    @classmethod
    def _combine(cls, models: List["_RandomForestModelBase"]) -> "_RandomForestModelBase":
        assert models and all(isinstance(m, cls) for m in models)
        first = models[0]
        assert all(m.n_cols == first.n_cols for m in models)
        V = first.leaf_values_.shape[2]
        assert all(m.leaf_values_.shape[2] == V for m in models), (
            "cannot combine forests with different value widths"
        )
        # dense layouts may differ in depth (maxDepth in the param grid):
        # shallower trees embed unchanged in the deeper node indexing, so
        # pad every model's node axis to the largest layout
        M_max = max(m.features_.shape[1] for m in models)

        def pad_nodes(a: np.ndarray, fill=0) -> np.ndarray:
            if a.shape[1] == M_max:
                return a
            width = [(0, 0), (0, M_max - a.shape[1])] + [(0, 0)] * (a.ndim - 2)
            return np.pad(a, width, constant_values=fill)

        kwargs = dict(
            features_=np.concatenate([pad_nodes(m.features_, -1) for m in models]),
            thresholds_=np.concatenate([pad_nodes(m.thresholds_) for m in models]),
            leaf_values_=np.concatenate([pad_nodes(m.leaf_values_) for m in models]),
            node_counts_=np.concatenate([pad_nodes(m.node_counts_) for m in models]),
            impurities_=np.concatenate([pad_nodes(m.impurities_) for m in models]),
            max_depth=max(int(m.max_depth) for m in models),
            n_cols=first.n_cols,
            dtype=first.dtype,
        )
        if hasattr(first, "classes_"):
            assert all(
                np.array_equal(m.classes_, first.classes_) for m in models
            ), "cannot combine classifiers fit on different label sets"
            kwargs.update(classes_=first.classes_, num_classes=first.num_classes)
        combined = cls(**kwargs)
        combined._tree_counts = [m.features_.shape[0] for m in models]
        # record the split in the ATTRIBUTES too: serialize_model ships
        # _get_model_attributes() to the executors, and a combined model
        # arriving there without its sub-model split would score as ONE
        # forest (core._construct_model reattaches it)
        combined._model_attributes["tree_counts"] = combined._tree_counts
        first._copyValues(combined)
        combined._tpu_params.update(first._tpu_params)
        combined._float32_inputs = first._float32_inputs
        return combined

    def _per_model_values(self, features: np.ndarray) -> List[np.ndarray]:
        """Mean leaf values per sub-model, one (N, V) array each — a single
        device pass per sub-model tree slice over one resident feature batch."""
        features = np.atleast_2d(np.asarray(features))
        if features.shape[1] != self.n_cols:
            raise ValueError(
                f"feature width {features.shape[1]} != model n_cols {self.n_cols}"
            )
        np_dtype = self._transform_dtype(self.dtype)
        f, t, v = self._forest_arrays()
        n = features.shape[0]
        # pad the batch to its power-of-two row bucket ONCE, outside the
        # sub-model loop — a combined CV model would otherwise re-pad the
        # identical feature matrix per tree slice
        from ..ops.precompile import shape_bucket

        b = shape_bucket(n)
        feats_np = np.asarray(features, np_dtype)
        if b != n:
            feats_np = np.pad(feats_np, ((0, b - n), (0, 0)))
        feats_dev = jax.device_put(feats_np)
        counts = getattr(self, "_tree_counts", None) or [self.features_.shape[0]]
        out, off = [], 0
        for c in counts:
            sl = slice(off, off + c)
            off += c
            # cached-executable dispatch with power-of-two row bucketing:
            # repeat transforms at any partition size reuse one executable
            # per bucket instead of compiling per distinct batch length
            out.append(
                forest_predict_cached(
                    feats_dev, f[sl], t[sl], v[sl],
                    max_depth=int(self.max_depth),
                )[:n]
            )
        # dispatch every sub-model's kernel first, then ONE batched fetch: a
        # per-slice np.asarray blocked dispatch on each device round-trip
        return list(jax.device_get(out))

    def _forest_arrays(self):
        np_dtype = self._transform_dtype(self.dtype)
        return (
            jnp.asarray(self.features_),
            jnp.asarray(self.thresholds_.astype(np_dtype)),
            jnp.asarray(self.leaf_values_),
        )

    def _predict_values(self, features: np.ndarray) -> np.ndarray:
        assert self._num_models == 1, (
            "transform() on a combined multi-model is unsupported; use "
            "_transformEvaluate"
        )
        return self._per_model_values(features)[0]

    def _serving_values_entry(self, postprocess, out_cols: List[str], mesh):
        """Shared serving plumbing for both forest models: the mean-leaf-
        values traversal dispatched under the SAME 'forest_predict' cache
        name and statics as the batch transform path (ops/forest.
        forest_predict_cached), so serving and transform share compiled
        executables wherever their row buckets coincide."""
        assert self._num_models == 1, "combined multi-models are not servable"
        from ..ops.forest import forest_predict_kernel
        from ..serving.entry import kernel_entry

        f, t, v = self._forest_arrays()
        return kernel_entry(
            "forest_predict",
            forest_predict_kernel,  # module-level jit, static max_depth
            (f, t, v),
            {"max_depth": int(self.max_depth)},
            postprocess,
            dtype=self._transform_dtype(self.dtype),
            n_cols=self.n_cols,
            out_cols=out_cols,
            info={"num_trees": int(self.features_.shape[0])},
            mesh=mesh,
        )

    @property
    def getNumTrees(self) -> int:  # property for pyspark API parity
        return self.features_.shape[0]

    @property
    def treeWeights(self) -> List[float]:
        return [1.0] * self.features_.shape[0]

    @property
    def totalNumNodes(self) -> int:
        return int((self.features_ >= 0).sum() * 2 + (self.features_ >= 0).shape[0])

    def trees_to_dicts(self) -> List[Dict[str, Any]]:
        """Portable nested-dict forest export — the role the reference's
        treelite JSON plays for translate_trees (utils.py:385-447).

        The dense node arrays are converted to Python lists ONCE per forest
        (vectorized tolist) before the per-node walk: numpy scalar getitem
        inside the recursion costs ~1 us x 5 arrays x 131k nodes per
        depth-16 tree, which is felt the first time a 100-tree forest goes
        through cpu()."""
        feats = np.asarray(self.features_).tolist()
        thr = np.asarray(self.thresholds_).tolist()
        leaf = np.asarray(self.leaf_values_).tolist()
        cnt = np.asarray(self.node_counts_).tolist()
        imp = np.asarray(self.impurities_).tolist()
        out = []
        for t in range(len(feats)):
            f, th, lv, ct, im = feats[t], thr[t], leaf[t], cnt[t], imp[t]

            def node_dict(i: int) -> Dict[str, Any]:
                if f[i] < 0:
                    return {
                        "leaf_value": lv[i],
                        "instance_count": float(ct[i]),
                    }
                return {
                    "split_feature": int(f[i]),
                    "threshold": float(th[i]),
                    "gain": float(im[i]),
                    "instance_count": float(ct[i]),
                    "yes": node_dict(2 * i + 1),
                    "no": node_dict(2 * i + 2),
                }

            out.append(node_dict(0))
        return out


class RandomForestClassifier(_RandomForestEstimator):
    """Distributed random-forest classifier (API parity with
    classification.py:307-513)."""

    _is_classification = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._setDefault(impurity="gini")
        if "impurity" not in kwargs:
            self._set_tpu_value("split_criterion", "gini")

    @classmethod
    def _param_value_mapping(cls):
        mapping = dict(super()._param_value_mapping())
        mapping["split_criterion"] = lambda x: {"gini": "gini", "entropy": "entropy"}.get(x)
        return mapping

    def _label_stats(self, inputs: FitInputs):
        from ..core import discover_label_classes
        from ..ops.labels import encode_labels_kernel

        # int32 label cast parity (classification.py:483-496); discovery is
        # per-rank local + control-plane union, encode + one-hot stay on
        # device preserving the row sharding (multi-process safe)
        classes = discover_label_classes(inputs, cast=np.int32)
        y_idx = encode_labels_kernel(
            inputs.y.astype(jnp.int32), jnp.asarray(classes)
        )
        onehot = jax.nn.one_hot(y_idx, len(classes), dtype=inputs.X.dtype)
        return onehot, {"classes_": classes.astype(np.float64), "num_classes": len(classes)}

    def _create_model(self, result: Dict[str, Any]) -> "RandomForestClassificationModel":
        return RandomForestClassificationModel(**result)


class RandomForestClassificationModel(
    HasProbabilityCol,
    HasRawPredictionCol,
    _ClassificationModelEvaluationMixIn,
    _RandomForestModelBase,
):
    def __init__(
        self,
        features_: np.ndarray,
        thresholds_: np.ndarray,
        leaf_values_: np.ndarray,
        node_counts_: np.ndarray,
        impurities_: np.ndarray,
        max_depth: int,
        n_cols: int,
        dtype: str,
        classes_: np.ndarray,
        num_classes: int,
        bootstrap_draw_: Optional[np.ndarray] = None,
    ) -> None:
        super().__init__(
            features_=np.asarray(features_),
            thresholds_=np.asarray(thresholds_),
            leaf_values_=np.asarray(leaf_values_),
            node_counts_=np.asarray(node_counts_),
            impurities_=np.asarray(impurities_),
            max_depth=int(max_depth),
            n_cols=int(n_cols),
            dtype=str(dtype),
            classes_=np.asarray(classes_),
            num_classes=int(num_classes),
            **_draw_attr(bootstrap_draw_),
        )
        self.bootstrap_draw_ = self._model_attributes.get("bootstrap_draw_")
        self.features_ = np.asarray(features_)
        self.thresholds_ = np.asarray(thresholds_)
        self.leaf_values_ = np.asarray(leaf_values_)
        self.node_counts_ = np.asarray(node_counts_)
        self.impurities_ = np.asarray(impurities_)
        self.max_depth = int(max_depth)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        self.classes_ = np.asarray(classes_)
        self.num_classes = int(num_classes)

    @property
    def numClasses(self) -> int:
        return self.num_classes

    def _out_columns(self) -> List[str]:
        return [
            self.getOrDefault("predictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("rawPredictionCol"),
        ]

    def _get_tpu_transform_func(self, dataset: DataFrame):
        classes = self.classes_
        n_trees = self.features_.shape[0]
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            probs = self._predict_values(features)  # (N, C) mean leaf distributions
            probs = probs / np.maximum(probs.sum(axis=1, keepdims=True), 1e-12)
            idx = probs.argmax(axis=1)
            return {
                pred_col: classes[idx].astype(np.float64),
                prob_col: probs.astype(np.float64),
                raw_col: (probs * n_trees).astype(np.float64),
            }

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): one forest traversal per
        coalesced batch; class mapping and normalization on host, matching
        transform() exactly."""
        classes = self.classes_
        n_trees = self.features_.shape[0]
        pred_col = self.getOrDefault("predictionCol")
        prob_col = self.getOrDefault("probabilityCol")
        raw_col = self.getOrDefault("rawPredictionCol")

        def _post(values) -> Dict[str, Any]:
            probs = np.asarray(values)
            probs = probs / np.maximum(probs.sum(axis=1, keepdims=True), 1e-12)
            idx = probs.argmax(axis=1)
            return {
                pred_col: classes[idx].astype(np.float64),
                prob_col: probs.astype(np.float64),
                raw_col: (probs * n_trees).astype(np.float64),
            }

        return self._serving_values_entry(
            _post, [pred_col, prob_col, raw_col], mesh
        )

    def _get_eval_predict_func(self):
        classes = self.classes_

        def _predict_all(feats: np.ndarray):
            preds, probs = [], []
            for p in self._per_model_values(feats):
                p = p / np.maximum(p.sum(axis=1, keepdims=True), 1e-12)
                probs.append(p)
                preds.append(classes[p.argmax(axis=1)].astype(np.float64))
            return np.stack(preds), np.stack(probs)

        return _predict_all

    def predict(self, value: np.ndarray) -> float:
        probs = self._predict_values(np.asarray(value)[None, :])
        return float(self.classes_[int(probs[0].argmax())])

    def predictProbability(self, value: np.ndarray) -> np.ndarray:
        probs = self._predict_values(np.asarray(value)[None, :])[0]
        return probs / max(probs.sum(), 1e-12)

    def _transformEvaluate(self, dataset: Any, evaluator: Any, params=None) -> List[float]:
        return self._transform_evaluate(dataset, evaluator, self._num_models)

    def cpu(self):
        """Convert to pyspark.ml RandomForestClassificationModel via py4j
        tree construction (parity with tree.py:507-553 + classification.py
        cpu()); requires pyspark + an active SparkSession."""
        from ..spark.interop import to_spark_random_forest_model

        return to_spark_random_forest_model(self)


class RandomForestRegressor(_RandomForestEstimator):
    """Distributed random-forest regressor (API parity with
    regression.py:795-968)."""

    _is_classification = False

    def __init__(self, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._setDefault(impurity="variance")
        if "impurity" not in kwargs:
            self._set_tpu_value("split_criterion", "variance")

    @classmethod
    def _param_value_mapping(cls):
        mapping = dict(super()._param_value_mapping())
        mapping["split_criterion"] = lambda x: {"variance": "variance", "mse": "variance"}.get(x)
        return mapping

    def _label_stats(self, inputs: FitInputs):
        y = inputs.y
        stats = jnp.stack([jnp.ones_like(y), y, y * y], axis=1)
        return stats, {}

    def _create_model(self, result: Dict[str, Any]) -> "RandomForestRegressionModel":
        return RandomForestRegressionModel(**result)


class RandomForestRegressionModel(
    _RegressionModelEvaluationMixIn, _RandomForestModelBase
):
    def __init__(
        self,
        features_: np.ndarray,
        thresholds_: np.ndarray,
        leaf_values_: np.ndarray,
        node_counts_: np.ndarray,
        impurities_: np.ndarray,
        max_depth: int,
        n_cols: int,
        dtype: str,
        bootstrap_draw_: Optional[np.ndarray] = None,
    ) -> None:
        super().__init__(
            features_=np.asarray(features_),
            thresholds_=np.asarray(thresholds_),
            leaf_values_=np.asarray(leaf_values_),
            node_counts_=np.asarray(node_counts_),
            impurities_=np.asarray(impurities_),
            max_depth=int(max_depth),
            n_cols=int(n_cols),
            dtype=str(dtype),
            **_draw_attr(bootstrap_draw_),
        )
        self.bootstrap_draw_ = self._model_attributes.get("bootstrap_draw_")
        self.features_ = np.asarray(features_)
        self.thresholds_ = np.asarray(thresholds_)
        self.leaf_values_ = np.asarray(leaf_values_)
        self.node_counts_ = np.asarray(node_counts_)
        self.impurities_ = np.asarray(impurities_)
        self.max_depth = int(max_depth)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)

    def _get_tpu_transform_func(self, dataset: DataFrame):
        pred_col = self.getOrDefault("predictionCol")

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            preds = self._predict_values(features)[:, 0]
            return {pred_col: preds.astype(np.float64)}

        return _transform

    def _serving_entry(self, mesh: Any = None):
        """Online inference hook (serving/): one forest traversal per
        coalesced batch, first value column as the regression prediction."""
        pred_col = self.getOrDefault("predictionCol")
        return self._serving_values_entry(
            lambda values: {
                pred_col: np.asarray(values)[:, 0].astype(np.float64)
            },
            [pred_col],
            mesh,
        )

    def _get_eval_predict_func(self) -> Callable[[np.ndarray], np.ndarray]:
        def _predict_all(feats: np.ndarray) -> np.ndarray:
            return np.stack(
                [p[:, 0].astype(np.float64) for p in self._per_model_values(feats)]
            )

        return _predict_all

    def predict(self, value: np.ndarray) -> float:
        return float(self._predict_values(np.asarray(value)[None, :])[0, 0])

    def _transformEvaluate(self, dataset: Any, evaluator: Any, params=None) -> List[float]:
        return self._transform_evaluate(dataset, evaluator, self._num_models)

    def cpu(self):
        """Convert to pyspark.ml RandomForestRegressionModel via py4j tree
        construction (parity with tree.py:507-553 + regression.py cpu());
        requires pyspark + an active SparkSession."""
        from ..spark.interop import to_spark_random_forest_model

        return to_spark_random_forest_model(self)
