#
# UMAP estimator/model.
#
# Capability parity with the reference's UMAP/UMAPModel
# (/root/reference/python/src/spark_rapids_ml/umap.py:88-1321): the same 17
# solver params (umap.py:95-115) plus sample_fraction (umap.py:332-341), fit
# on (optionally sampled) data with the model carrying embedding_ + raw
# training data for transform (umap.py:831-910), and distributed transform
# that projects each batch against the broadcast model (umap.py:1147-1224).
# Supervised fit (labelCol set -> categorical simplicial-set intersection,
# the reference's y= branch at umap.py:939-947) is supported.
# Differences by design: the kNN graph is built by the mesh-distributed
# exact kNN kernel instead of single-GPU cuML, so fit itself scales across
# the mesh; graph assembly and the SGD layout epochs are mesh-parallel too
# (on-device symmetrize/dedupe/pad + head-block-sharded scan-batched
# epochs, ops/umap.py / docs/umap_engine.md — fixed seed gives the same
# embedding on any mesh shape); "spectral" init is the Laplacian eigenmap
# of the fuzzy graph;
# transform initializes at the weighted neighbor mean then runs the
# n_epochs//3 (or 100/30) SGD refinement epochs against the frozen training
# embedding, as cuml/umap-learn transform does.
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import numpy as np

from ..core import FitInputs, _TpuEstimator, _TpuModel
from ..dataframe import DataFrame, as_dataframe
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasOutputCol,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from ..parallel.mesh import get_mesh
from ..ops.knn import knn_search
from ..ops.umap import (
    find_ab_params,
    umap_fit_embedding,
    umap_transform_embedding,
)
from ..utils import get_logger


def _umap_ann_mode() -> str:
    """SRML_UMAP_ANN routes the graph phase's kNN self-join: "" (default)
    keeps the exact engine; "ivfflat" uses the srml-ann IVF-Flat engine."""
    import os

    mode = os.environ.get("SRML_UMAP_ANN", "")
    if mode not in ("", "ivfflat"):
        raise ValueError(
            f"SRML_UMAP_ANN={mode!r} is not supported (only 'ivfflat')"
        )
    return mode


def _ann_self_join(X: np.ndarray, k: int, mesh, seed: int):
    """(dists, ids) kNN self-join via the IVF-Flat engine (ann/ivfflat.py).
    nlist defaults to sqrt(n); nprobe defaults to HALF the lists — the
    graph phase feeds the layout's attraction edges, so it trades less
    speedup for recall headroom vs the serving default (a quarter).  Env
    overrides: SRML_UMAP_ANN_NLIST / SRML_UMAP_ANN_NPROBE."""
    import os

    from ..ann.ivfflat import (
        build_ivfflat_packed,
        default_nlist,
        index_from_packed,
        ivfflat_search_prepared,
    )

    n = X.shape[0]
    nlist = int(os.environ.get("SRML_UMAP_ANN_NLIST", 0)) or default_nlist(n)
    nprobe = int(os.environ.get("SRML_UMAP_ANN_NPROBE", 0)) or max(
        8, nlist // 2
    )
    packed = build_ivfflat_packed(
        X, np.arange(n, dtype=np.int64), nlist, seed=seed
    )
    index = index_from_packed(packed, mesh)
    dists, ids = ivfflat_search_prepared(
        index, X, k, nprobe, mesh, query_block=32768
    )
    if (ids < 0).any():
        # the graph assembly consumes ids as dense row indices; a -1
        # unfillable slot (probed lists held < k candidates for some row)
        # must fail loudly, not gather garbage edges
        raise RuntimeError(
            "IVF-Flat self-join returned unfillable neighbor slots at "
            f"nlist={nlist} nprobe={nprobe}; raise SRML_UMAP_ANN_NPROBE "
            "(or unset SRML_UMAP_ANN to use the exact graph)"
        )
    return dists, ids


class UMAPClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # identity mapping: the reference exposes the solver params directly
        # as Spark Params (umap.py:121-603), so any route that sets the Spark
        # Param (copy(extra), tuning param maps, set()) must reach the solver
        # dict too
        return {
            name: name
            for name in (
                "n_neighbors",
                "n_components",
                "metric",
                "n_epochs",
                "learning_rate",
                "init",
                "min_dist",
                "spread",
                "set_op_mix_ratio",
                "local_connectivity",
                "repulsion_strength",
                "negative_sample_rate",
                "transform_queue_size",
                "a",
                "b",
                "random_state",
            )
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_neighbors": 15,
            "n_components": 2,
            "metric": "euclidean",
            "n_epochs": None,
            "learning_rate": 1.0,
            "init": "spectral",
            "min_dist": 0.1,
            "spread": 1.0,
            "set_op_mix_ratio": 1.0,
            "local_connectivity": 1.0,
            "repulsion_strength": 1.0,
            "negative_sample_rate": 5,
            "transform_queue_size": 4.0,
            "a": None,
            "b": None,
            "precomputed_knn": None,
            "random_state": None,
            "verbose": False,
        }


class _UMAPParams(UMAPClass, HasFeaturesCol, HasFeaturesCols, HasLabelCol, HasOutputCol):
    n_neighbors = Param(_dummy(), "n_neighbors", "size of the local neighborhood", TypeConverters.toFloat)
    n_components = Param(_dummy(), "n_components", "dimension of the embedded space", TypeConverters.toInt)
    metric = Param(_dummy(), "metric", "distance metric (euclidean)", TypeConverters.toString)
    n_epochs = Param(_dummy(), "n_epochs", "number of optimization epochs", TypeConverters.toInt)
    learning_rate = Param(_dummy(), "learning_rate", "initial embedding learning rate", TypeConverters.toFloat)
    init = Param(_dummy(), "init", "low-dim initialization (spectral|random)", TypeConverters.toString)
    min_dist = Param(_dummy(), "min_dist", "minimum embedded point distance", TypeConverters.toFloat)
    spread = Param(_dummy(), "spread", "scale of the embedded points", TypeConverters.toFloat)
    set_op_mix_ratio = Param(_dummy(), "set_op_mix_ratio", "fuzzy union vs intersection mix", TypeConverters.toFloat)
    local_connectivity = Param(_dummy(), "local_connectivity", "local connectivity (nearest assumed-connected neighbors)", TypeConverters.toFloat)
    repulsion_strength = Param(_dummy(), "repulsion_strength", "weight of negative samples", TypeConverters.toFloat)
    negative_sample_rate = Param(_dummy(), "negative_sample_rate", "negative samples per positive", TypeConverters.toInt)
    transform_queue_size = Param(_dummy(), "transform_queue_size", "transform search queue factor", TypeConverters.toFloat)
    a = Param(_dummy(), "a", "embedding curve parameter a", TypeConverters.toFloat)
    b = Param(_dummy(), "b", "embedding curve parameter b", TypeConverters.toFloat)
    random_state = Param(_dummy(), "random_state", "random seed", TypeConverters.toInt)
    sample_fraction = Param(_dummy(), "sample_fraction", "fraction of rows used for fit (umap.py:332-341)", TypeConverters.toFloat)

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(sample_fraction=1.0, outputCol="embedding")

    def getSampleFraction(self) -> float:
        return self.getOrDefault("sample_fraction")

    def setSampleFraction(self, value: float):
        return self._set_params(sample_fraction=value)

    def setOutputCol(self, value: str):
        return self._set_params(outputCol=value)

    def setFeaturesCol(self, value: Union[str, List[str]]):
        if isinstance(value, str):
            self._set_params(featuresCol=value)
        else:
            self._set_params(featuresCols=value)
        return self


class UMAP(_UMAPParams, _TpuEstimator):
    """UMAP on a TPU mesh: exact mesh-distributed kNN graph, vectorized
    fuzzy-set calibration, one-jit SGD layout."""

    # single-node fit by design (reference umap.py:831-850 coalesces to one
    # partition); the fit func host-fetches the whole dataset.  On a >1-worker
    # Spark cluster the adapter degrades to the reference semantics — sample
    # with Spark, fit in a single barrier task, keep inference distributed —
    # instead of erroring (spark/adapter.barrier_fit_estimator).
    _supports_multicontroller_fit = False
    _cluster_fit_single_task = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    def _fit_label_col(self):
        # optionally supervised (reference umap.py:722-724, 939-947):
        # labels are consumed only when the user set labelCol explicitly
        return self.getOrDefault("labelCol") if self.isSet("labelCol") else None

    def _get_tpu_fit_func(self, dataset: DataFrame, extra_params=None):
        logger = get_logger(type(self))
        sample_fraction = self.getSampleFraction()

        def _fit(inputs: FitInputs, params: Dict[str, Any]):
            import jax as _jax

            valid = np.asarray(inputs.weight) > 0
            seed = params.get("random_state")
            seed = int(seed) & 0x7FFFFFFF if seed is not None else 42
            # device fast path: a from_device frame with no padding and no
            # sampling never round-trips the feature array through the
            # host link (the np.asarray fetch was 25 MB per fit at the
            # bench shape) — the kNN
            # self-join consumes the device handle and raw_data_ stays a
            # device array until save/serialize materializes it
            # device-resident frame with no padding/sampling: the kNN
            # self-join consumes the device handle for ANY dtype
            # (prepare_items casts on device).  raw_data_ additionally
            # stays a device array only for f32 frames — a bf16/f16
            # frame would need a full-size f32 device COPY (doubling
            # HBM), so those fetch raw_data_ to the host as before.
            # Trade of the f32 path: raw_data_ IS the frame's array (no
            # extra HBM, no fetch) and stays resident while the model is
            # alive; save/serialize materializes a host copy on demand.
            device_search = (
                isinstance(inputs.X, _jax.Array)
                and sample_fraction >= 1.0
                and int(valid.sum()) == inputs.X.shape[0]
            )
            device_fast = (
                device_search and inputs.X.dtype == _jax.numpy.float32
            )
            if device_fast:
                X: Any = inputs.X
                y = np.asarray(inputs.y)[valid] if inputs.y is not None else None
            else:
                X = np.asarray(inputs.X)[valid]
                y = np.asarray(inputs.y)[valid] if inputs.y is not None else None
                if sample_fraction < 1.0:
                    rng = np.random.default_rng(seed)
                    keep = rng.random(X.shape[0]) < sample_fraction
                    X = X[keep]
                    y = y[keep] if y is not None else None
            n = X.shape[0]
            if n == 0:
                raise RuntimeError(
                    "UMAP fit received 0 rows after sampling "
                    f"(sample_fraction={sample_fraction}); increase "
                    "sample_fraction or the dataset size"
                )
            k = int(min(params["n_neighbors"], n))
            mesh = get_mesh(self.num_workers)
            if params.get("precomputed_knn") is not None:
                # (knn_indices, knn_dists) as in cuML's precomputed_knn
                # (reference umap.py:95-115 param list)
                pre_ids, pre_dists = params["precomputed_knn"]
                ids = np.asarray(pre_ids)[:, :k]
                dists = np.asarray(pre_dists)[:, :k]
                if ids.shape[0] != n:
                    raise ValueError(
                        f"precomputed_knn has {ids.shape[0]} rows but the "
                        f"(sampled) training set has {n}"
                    )
            elif _umap_ann_mode() == "ivfflat":
                # Opt-in (SRML_UMAP_ANN=ivfflat): the graph self-join runs
                # through the IVF-Flat engine instead of the exact scan —
                # sub-linear in n, gated by the k=15 neighbor-preservation
                # test within the established 1% tolerance of the exact-
                # graph reference layout (tests/test_umap_engine.py).
                # SRML_UMAP_ANN_NLIST / SRML_UMAP_ANN_NPROBE override the
                # defaults (sqrt(n) lists, half of them probed — the graph
                # phase needs higher recall than online serving, so the
                # default probes deeper than ann.default_nprobe).
                dists, ids = _ann_self_join(
                    np.asarray(X, np.float32), k, mesh, seed
                )
            else:
                # query_block 32768: the graph build is a self-join of many
                # small-k blocks whose per-block host round-trips
                # dominate — 2 blocks at 50k beats 7.
                # When no row was filtered (no padding, no sampling) the
                # search consumes the DEVICE-resident FitInputs.X directly
                # instead of round-tripping it through the host link.
                search_X: Any = inputs.X if device_search else X
                dists, ids = knn_search(
                    search_X, np.arange(n, dtype=np.int64), search_X, k,
                    mesh, query_block=32768,
                )
            a, b = params.get("a"), params.get("b")
            if a is None or b is None:
                a, b = find_ab_params(
                    float(params["spread"]), float(params["min_dist"])
                )
            logger.info("UMAP graph built: n=%d k=%d (a=%.3f b=%.3f)", n, k, a, b)
            # the same mesh that served the kNN self-join drives the
            # sharded layout epochs: each device owns a head block of the
            # padded edge layout (ops/umap.optimize_layout_sharded)
            embedding = umap_fit_embedding(
                ids,
                dists,
                n_components=int(params["n_components"]),
                a=a,
                b=b,
                n_epochs=params.get("n_epochs"),
                learning_rate=float(params["learning_rate"]),
                init=str(params["init"]),
                set_op_mix_ratio=float(params["set_op_mix_ratio"]),
                local_connectivity=float(params["local_connectivity"]),
                repulsion_strength=float(params["repulsion_strength"]),
                negative_sample_rate=int(params["negative_sample_rate"]),
                seed=seed,
                y=y,
                mesh=mesh,
            )
            return {
                "embedding_": embedding.astype(np.float32),
                "raw_data_": X.astype(np.float32),
                "n_cols": inputs.n_cols,
                "dtype": str(inputs.dtype),
            }

        return _fit

    def _create_model(self, result: Dict[str, Any]) -> "UMAPModel":
        return UMAPModel(**result)


class UMAPModel(_UMAPParams, _TpuModel):
    def __init__(
        self,
        embedding_: np.ndarray,
        raw_data_: np.ndarray,
        n_cols: int,
        dtype: str,
    ) -> None:
        import jax as _jax

        # raw_data_ may arrive as a DEVICE array (the from_device fit fast
        # path): keep the handle — transform's prepare_items consumes it
        # on device, and _get_model_attributes materializes a host copy
        # only when persistence/serialization actually needs one
        raw = (
            raw_data_
            if isinstance(raw_data_, _jax.Array)
            else np.asarray(raw_data_)
        )
        super().__init__(
            embedding_=np.asarray(embedding_),
            raw_data_=raw,
            n_cols=int(n_cols),
            dtype=str(dtype),
        )
        self.embedding_ = np.asarray(embedding_)
        self.raw_data_ = raw
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)

    def _get_model_attributes(self) -> Dict[str, Any]:
        attrs = self._model_attributes
        if not isinstance(attrs.get("raw_data_"), np.ndarray):
            # materialize the device-resident training set on first
            # save/serialize; cached so repeat saves fetch once
            attrs["raw_data_"] = np.asarray(attrs["raw_data_"])
            self.raw_data_ = attrs["raw_data_"]
        return attrs

    @property
    def embedding(self) -> np.ndarray:
        return self.embedding_

    def _out_columns(self) -> List[str]:
        return [self.getOrDefault("outputCol")]

    def _get_tpu_transform_func(self, dataset: DataFrame):
        out_col = self.getOrDefault("outputCol")
        p = self._tpu_params
        k = int(min(p.get("n_neighbors", 15), self.raw_data_.shape[0]))
        local_connectivity = float(p.get("local_connectivity", 1.0))
        a, b = p.get("a"), p.get("b")
        if a is None or b is None:
            a, b = find_ab_params(
                float(p.get("spread", 1.0)), float(p.get("min_dist", 0.1))
            )
        seed = p.get("random_state")
        mesh = get_mesh(self.num_workers)
        from ..ops.knn import knn_search_prepared, prepare_items

        # shard the training set + upload the embedding to device ONCE;
        # reused by every partition
        prepared = prepare_items(
            self.raw_data_,
            np.arange(self.raw_data_.shape[0], dtype=np.int64),
            mesh,
        )
        import jax.numpy as jnp

        emb_f32 = self.embedding_.astype(np.float32)
        emb_dev = jnp.asarray(emb_f32)

        def _transform(features: np.ndarray) -> Dict[str, Any]:
            dists, ids = knn_search_prepared(prepared, features, k, mesh)
            emb = umap_transform_embedding(
                ids,
                dists,
                emb_f32,
                local_connectivity,
                train_embedding_dev=emb_dev,
                a=a,
                b=b,
                n_epochs=p.get("n_epochs"),
                learning_rate=float(p.get("learning_rate", 1.0)),
                repulsion_strength=float(p.get("repulsion_strength", 1.0)),
                negative_sample_rate=int(p.get("negative_sample_rate", 5)),
                seed=int(seed) & 0x7FFFFFFF if seed is not None else 42,
            )
            return {out_col: emb.astype(np.float64)}

        return _transform
