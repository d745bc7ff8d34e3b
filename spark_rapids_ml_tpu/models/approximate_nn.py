#
# ApproximateNearestNeighbors estimator/model (IVF-Flat + IVF-PQ).
#
# Param-surface parity with the reference's ApproximateNearestNeighbors
# (cuML algorithm='ivfflat'|'ivfpq'; algoParams={'nlist', 'nprobe'} plus
# the PQ keys {'M', 'n_bits', 'usePrecomputedTables'}): fit TRAINS the
# coarse quantizer (and, for ivfpq, the per-subspace codebooks) and packs
# the inverted lists (unlike the exact NearestNeighbors, whose fit only
# captures the frame — an ANN index is a real artifact), kneighbors runs
# the probed search, and `exactSearch=True` routes through the exact
# brute-force engine over the same packed items (a recall-vs-latency
# escape hatch that shares ids with the probed path).  The ivfpq tier
# additionally re-scores its top k*refine_ratio ADC candidates against the
# host-side f32 payload (the same array exactSearch scores) to recover
# recall — the device index itself stays ~32x compressed.  Unlike the
# exact model, this model IS persistable: the packed index rides the core
# npz persistence path and restages onto whatever mesh loads it.
#

from __future__ import annotations

import warnings
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd

from ..ann.ivfflat import (
    PackedIVF,
    build_ivfflat_packed,
    default_nlist,
    default_nprobe,
    index_from_packed,
    ivfflat_search_prepared,
    tiered_index_from_packed,
    warm_probe_kernels,
)
from ..ann.pq import (
    DEFAULT_N_BITS,
    DEFAULT_REFINE_RATIO,
    PackedPQ,
    build_ivfpq_packed,
    default_m_sub,
    index_from_packed_pq,
    ivfpq_search_prepared,
    tiered_index_from_packed_pq,
    warm_pq_probe_kernels,
)
from ..core import _TpuEstimatorSupervised, _TpuModel
from ..dataframe import DataFrame, as_dataframe
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    Param,
    TypeConverters,
    _dummy,
    _TpuParams,
)
from ..parallel.mesh import get_mesh

# per-algorithm algoParams surfaces (a typo'd key is a hard error, never a
# silent default); the PQ keys follow the upstream cuML names.
# 'hot_fraction' (both tiers) opts into the tiered HBM/host-RAM residency
# split (ann/tier.py); 'opq' (pq tier) trains a learned rotation before
# the subspace split (ann/pq.py _train_opq_rotation).
_ALGO_PARAM_KEYS = {
    "ivfflat": {"nlist", "nprobe", "hot_fraction"},
    "ivfpq": {
        "nlist", "nprobe", "M", "n_bits", "usePrecomputedTables",
        "refine_ratio", "opq", "hot_fraction",
    },
}


class ApproximateNearestNeighborsClass(_TpuParams):
    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_neighbors", "algorithm": "algorithm"}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_neighbors": 5,
            "verbose": False,
            "algorithm": "ivfflat",
            "metric": "euclidean",
        }


class _ApproximateNearestNeighborsParams(
    ApproximateNearestNeighborsClass, HasFeaturesCol, HasFeaturesCols
):
    k = Param(_dummy(), "k", "the number of nearest neighbors to retrieve (> 0)", TypeConverters.toInt)
    idCol = Param(_dummy(), "idCol", "id column name; if unset a monotonically increasing id column is generated", TypeConverters.toString)
    algorithm = Param(_dummy(), "algorithm", "the ANN algorithm: 'ivfflat' (raw f32 lists) or 'ivfpq' (product-quantized lists)", TypeConverters.toString)
    algoParams = Param(_dummy(), "algoParams", "algorithm parameters: {'nlist', 'nprobe', 'hot_fraction': HBM-resident list fraction} (both tiers) plus, for ivfpq, {'M': subspaces, 'n_bits': bits per code (4 packs two codes/byte and takes the fast-scan kernel), 'refine_ratio': f32 re-score factor (1 = ADC only), 'opq': train a learned rotation before the subspace split, 'usePrecomputedTables': ignored}", TypeConverters.identity)
    exactSearch = Param(_dummy(), "exactSearch", "route kneighbors through the exact brute-force engine over the indexed items (recall escape hatch)", TypeConverters.toBoolean)

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(k=5, algorithm="ivfflat", exactSearch=False)

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setK(self, value: int):
        return self._set_params(k=value)

    def getIdCol(self) -> str:
        return self.getOrDefault("idCol") if self.isDefined("idCol") else "unique_id"

    def setIdCol(self, value: str):
        self.set(self.getParam("idCol"), value)
        return self

    def getAlgorithm(self) -> str:
        return self.getOrDefault("algorithm")

    def setAlgorithm(self, value: str):
        return self._set_params(algorithm=value)

    def getAlgoParams(self) -> Optional[Dict[str, int]]:
        return self.getOrDefault("algoParams") if self.isDefined("algoParams") else None

    def setAlgoParams(self, value: Dict[str, int]):
        self.set(self.getParam("algoParams"), value)
        return self

    def getExactSearch(self) -> bool:
        return self.getOrDefault("exactSearch")

    def setExactSearch(self, value: bool):
        self.set(self.getParam("exactSearch"), value)
        return self

    def setInputCol(self, value: Union[str, List[str]]):
        if isinstance(value, str):
            self._set_params(featuresCol=value)
        else:
            self._set_params(featuresCols=value)
        return self

    def _validated_algo_params(self) -> Dict[str, Any]:
        algo = self.getAlgorithm()
        ap = dict(self.getAlgoParams() or {})
        known = _ALGO_PARAM_KEYS[algo]
        unknown = set(ap) - known
        if unknown:
            raise ValueError(
                f"unknown algoParams {sorted(unknown)} for algorithm "
                f"{algo!r}; supported: {sorted(known)}"
            )
        return ap

    def _resolved_algo_params(self, n_items: int, n_lists: int = None) -> Tuple[int, int]:
        """(nlist, nprobe) with the documented defaults (ann/ivfflat
        default_nlist/default_nprobe) filling unset keys; unknown keys are
        a hard error (a typo'd 'nprobes' must not silently probe 1/4)."""
        ap = self._validated_algo_params()
        nlist = int(ap.get("nlist", n_lists or default_nlist(n_items)))
        nprobe = int(ap.get("nprobe", default_nprobe(nlist)))
        if nlist < 1 or nprobe < 1:
            raise ValueError(
                f"nlist ({nlist}) and nprobe ({nprobe}) must be >= 1"
            )
        return nlist, nprobe

    def _resolved_pq_params(
        self, dim: int, warn: bool = False
    ) -> Tuple[int, int, int, bool]:
        """(M, n_bits, refine_ratio, opq) for algorithm='ivfpq' with the
        documented defaults (ann/pq default_m_sub, 8 bits, refine x4, no
        rotation).  refine_ratio semantics: 1 means "ADC only, no refine"
        (the probed scan IS the answer); >= 2 re-scores the top
        k*refine_ratio ADC candidates against the host f32 payload.  0 is
        a typed error — it used to slip through the old `>= 0` guard and
        then silently behave like 1 because the refine gate keys off
        `> 1`; an explicit ratio must name a real mode.
        usePrecomputedTables is accepted for upstream compatibility but
        IGNORED with a warning (once, at fit): the ADC formulation folds
        the list-dependent table term into the packed per-item scalar, so
        there is no separate precomputed-table mode to toggle."""
        ap = self._validated_algo_params()
        if warn and "usePrecomputedTables" in ap:
            warnings.warn(
                "algoParams['usePrecomputedTables'] is ignored: the IVF-PQ "
                "engine always folds the list-dependent ADC term into the "
                "packed per-item scalar (docs/ann_engine.md#ivf-pq)",
                stacklevel=3,
            )
        m = int(ap.get("M", default_m_sub(dim)))
        n_bits = int(ap.get("n_bits", DEFAULT_N_BITS))
        ratio = int(ap.get("refine_ratio", DEFAULT_REFINE_RATIO))
        opq = bool(ap.get("opq", False))
        if m < 1:
            raise ValueError(f"M ({m}) must be >= 1")
        if not 1 <= n_bits <= 8:
            raise ValueError(f"n_bits ({n_bits}) must be in [1, 8]")
        if ratio < 1:
            raise ValueError(
                f"refine_ratio ({ratio}) must be >= 1 (1 = ADC only, no "
                "f32 refine pass; >= 2 re-scores top k*ratio candidates)"
            )
        return m, n_bits, ratio, opq

    def _resolved_hot_fraction(self) -> float:
        """The tiered-residency knob for BOTH tiers: the fraction of each
        shard's lists pinned HBM-resident (ann/tier.py pages the rest from
        host RAM on probe demand).  algoParams['hot_fraction'], or 1.0
        (everything resident) where it is unset."""
        hf = float(self._validated_algo_params().get("hot_fraction", 1.0))
        if not 0.0 <= hf <= 1.0:
            raise ValueError(
                f"hot_fraction ({hf}) must be in [0, 1] (1 = fully "
                "HBM-resident, the default)"
            )
        return hf

    def _check_algorithm(self) -> None:
        if self.getAlgorithm() not in _ALGO_PARAM_KEYS:
            raise ValueError(
                f"algorithm={self.getAlgorithm()!r} is not supported; "
                f"implemented tiers: {sorted(_ALGO_PARAM_KEYS)}"
            )


class ApproximateNearestNeighbors(
    _ApproximateNearestNeighborsParams, _TpuEstimatorSupervised
):
    """IVF-Flat approximate kNN over the TPU mesh (ann/ivfflat.py): the
    kmeans engine trains the coarse quantizer, the fused distance+argmin
    kernel assigns lists, and probed search rides the kNN block pipeline
    with a recall knob (nprobe)."""

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._initialize_tpu_params()
        self._set_params(**kwargs)

    def _fit(self, dataset: Any) -> "ApproximateNearestNeighborsModel":
        from ..core import _use_executor_path, extract_partition_features
        from ..ops.precompile import ensure_compile_cache

        ensure_compile_cache()
        self._check_algorithm()
        if getattr(dataset, "_device_features", None) is not None:
            raise NotImplementedError(
                "ApproximateNearestNeighbors.fit does not take "
                "DataFrame.from_device frames (their features column is a "
                "placeholder); fit a host frame instead"
            )
        if _use_executor_path(dataset):
            raise NotImplementedError(
                "ApproximateNearestNeighbors builds its index in-process; "
                "collect the pyspark item dataframe (SRML_SPARK_COLLECT=1) "
                "before fitting"
            )
        df = as_dataframe(dataset)
        id_col = self.getIdCol()
        if id_col not in df.columns:
            df = df.with_row_id(id_col)
        self._validate_parameters(df)
        input_col, input_cols = self._get_input_columns()
        feats, ids = [], []
        for part in df.partitions:
            if len(part) == 0:
                continue
            feats.append(
                extract_partition_features(
                    part, input_col, input_cols, np.float32
                )
            )
            ids.append(np.asarray(part[id_col].to_numpy(), np.int64))
        if not feats:
            raise RuntimeError("Dataset is empty; cannot build an IVF index")
        X = np.concatenate(feats) if len(feats) > 1 else feats[0]
        item_ids = np.concatenate(ids) if len(ids) > 1 else ids[0]
        nlist, _nprobe = self._resolved_algo_params(X.shape[0])
        self._resolved_hot_fraction()  # fail fast on an out-of-range knob
        if self.getAlgorithm() == "ivfpq":
            m_sub, n_bits, _ratio, opq = self._resolved_pq_params(
                int(X.shape[1]), warn=True
            )
            pq = build_ivfpq_packed(
                X, item_ids, nlist, m_sub=m_sub, n_bits=n_bits, seed=0,
                opq=opq,
            )
            model = ApproximateNearestNeighborsModel(
                centroids_=pq.centroids,
                packed_items_=pq.items,
                packed_ids_=pq.ids,
                list_counts_=pq.counts,
                n_lists=pq.n_lists,
                n_items=pq.n_items,
                n_cols=int(X.shape[1]),
                dtype="float32",
                pq_codes_=pq.codes,
                pq_scalars_=pq.scalars,
                pq_codebooks_=pq.codebooks,
                pq_n_bits=pq.n_bits,
                pq_rotation_=pq.rotation,
            )
        else:
            packed = build_ivfflat_packed(X, item_ids, nlist, seed=0)
            model = ApproximateNearestNeighborsModel(
                centroids_=packed.centroids,
                packed_items_=packed.items,
                packed_ids_=packed.ids,
                list_counts_=packed.counts,
                n_lists=packed.n_lists,
                n_items=packed.n_items,
                n_cols=int(X.shape[1]),
                dtype="float32",
            )
        self._copyValues(model)
        model._tpu_params.update(self._tpu_params)
        model._num_workers = self._num_workers
        model._float32_inputs = self._float32_inputs
        model._item_df = df
        return model

    def fit(
        self, dataset: Any, params: Optional[Dict] = None
    ) -> "ApproximateNearestNeighborsModel":
        return self._fit(dataset)

    def _get_tpu_fit_func(self, dataset, extra_params=None):  # pragma: no cover
        raise NotImplementedError("ApproximateNearestNeighbors overrides _fit")

    def _create_model(self, result):  # pragma: no cover
        raise NotImplementedError("ApproximateNearestNeighbors overrides _fit")


class ApproximateNearestNeighborsModel(
    _ApproximateNearestNeighborsParams, _TpuModel
):
    """A fitted IVF-Flat index.  Persistable through the core npz path (the
    packed layout is mesh-independent; staging expands it per mesh); a
    loaded model answers kneighbors without the original item frame."""

    def __init__(
        self,
        centroids_: np.ndarray,
        packed_items_: np.ndarray,
        packed_ids_: np.ndarray,
        list_counts_: np.ndarray,
        n_lists: int,
        n_items: int,
        n_cols: int,
        dtype: str = "float32",
        pq_codes_: Optional[np.ndarray] = None,
        pq_scalars_: Optional[np.ndarray] = None,
        pq_codebooks_: Optional[np.ndarray] = None,
        pq_n_bits: Optional[int] = None,
        pq_rotation_: Optional[np.ndarray] = None,
    ) -> None:
        super().__init__(
            centroids_=np.asarray(centroids_),
            packed_items_=np.asarray(packed_items_),
            packed_ids_=np.asarray(packed_ids_),
            list_counts_=np.asarray(list_counts_),
            n_lists=int(n_lists),
            n_items=int(n_items),
            n_cols=int(n_cols),
            dtype=str(dtype),
            pq_codes_=None if pq_codes_ is None else np.asarray(pq_codes_),
            pq_scalars_=None if pq_scalars_ is None else np.asarray(pq_scalars_),
            pq_codebooks_=None
            if pq_codebooks_ is None
            else np.asarray(pq_codebooks_),
            pq_n_bits=None if pq_n_bits is None else int(pq_n_bits),
            pq_rotation_=None
            if pq_rotation_ is None
            else np.asarray(pq_rotation_),
        )
        self.centroids_ = np.asarray(centroids_, np.float32)
        self.packed_items_ = np.asarray(packed_items_, np.float32)
        self.packed_ids_ = np.asarray(packed_ids_, np.int64)
        self.list_counts_ = np.asarray(list_counts_, np.int64)
        self.n_lists = int(n_lists)
        self.n_items = int(n_items)
        self.n_cols = int(n_cols)
        self.dtype = str(dtype)
        # the PQ tier's extra payload (None on an ivfflat model): one-byte
        # codes, ADC item scalars, and the subspace codebooks — together
        # with the shared list layout they form the PackedPQ
        self.pq_codes_ = None if pq_codes_ is None else np.asarray(
            pq_codes_, np.uint8
        )
        self.pq_scalars_ = None if pq_scalars_ is None else np.asarray(
            pq_scalars_, np.float32
        )
        self.pq_codebooks_ = None if pq_codebooks_ is None else np.asarray(
            pq_codebooks_, np.float32
        )
        self.pq_n_bits = None if pq_n_bits is None else int(pq_n_bits)
        # the OPQ rotation (d_pad, d_pad) f32, or None when fit without
        # algoParams['opq']: codes encode ROTATED residuals, so the
        # rotation must persist with the payload — a load that dropped it
        # would decode against the wrong frame
        self.pq_rotation_ = None if pq_rotation_ is None else np.asarray(
            pq_rotation_, np.float32
        )
        self._item_df: Optional[DataFrame] = None
        # per-mesh staging caches (die with the model, like the exact
        # model's _staged_items): the probed index (flat or pq) and the
        # exactSearch prepared item set
        self._staged_index: Optional[Tuple[Any, Any]] = None
        self._staged_pq: Optional[Tuple[Any, Any]] = None
        self._staged_exact: Optional[Tuple[Any, Any]] = None
        # live-mutation holder (ann/mutable.py): once created via
        # mutable_index(), every staged-flat read — kneighbors AND the
        # serve.ann entry — snapshots it, so add/delete/repack are visible
        # to in-flight serving without re-registration
        self._mutable: Optional[Tuple[Any, Any]] = None

    def _packed(self) -> PackedIVF:
        return PackedIVF(
            self.packed_items_,
            self.packed_ids_,
            self.list_counts_,
            self.centroids_,
            self.n_lists,
            self.n_items,
        )

    def _packed_pq(self) -> PackedPQ:
        if self.pq_codes_ is None:
            raise ValueError(
                "this model was fit with algorithm='ivfflat'; it carries no "
                "PQ payload — refit with algorithm='ivfpq'"
            )
        return PackedPQ(
            self.pq_codes_,
            self.pq_scalars_,
            self.packed_ids_,
            self.packed_items_,
            self.list_counts_,
            self.centroids_,
            self.pq_codebooks_,
            self.n_lists,
            self.n_items,
            self.n_cols,
            self.pq_codes_.shape[1],
            self.pq_n_bits,
            rotation=self.pq_rotation_,
        )

    def _mesh_key(self, mesh) -> Tuple:
        from ..ops.precompile import mesh_fingerprint

        # value identity, not object identity: get_mesh builds fresh Mesh
        # objects per call, and a re-staged identical mesh must HIT
        return mesh_fingerprint(mesh)

    def _ensure_staged_index(self, mesh):
        hf = self._resolved_hot_fraction()
        key = (self._mesh_key(mesh), hf)
        if self._mutable is not None:
            if self._mutable[0] != key:
                raise ValueError(
                    "this model's index is live-mutable on a different "
                    "mesh; mutation is per-mesh — freeze_mutations() "
                    "before staging elsewhere"
                )
            return self._mutable[1].index
        if self._staged_index is None or self._staged_index[0] != key:
            if hf < 1.0:
                staged = tiered_index_from_packed(self._packed(), mesh, hf)
            else:
                staged = index_from_packed(self._packed(), mesh)
            self._staged_index = (key, staged)
        return self._staged_index[1]

    def mutable_index(self, mesh: Any = None):
        """The live-mutation holder for this model's IVF-Flat index
        (ann/mutable.MutableIVFIndex): created on first call (staging the
        packed payload on `mesh`), returned thereafter.  Once created,
        kneighbors and the serve.ann entry read the holder's atomic index
        snapshot, so add_items/delete_items/repack are immediately visible
        to serving traffic.  Flat-only: the PQ tier's codes are not
        incrementally mutable (docs/ann_engine.md §incremental-mutation)."""
        self._check_algorithm()
        if self.getAlgorithm() == "ivfpq":
            raise ValueError(
                "live mutation is IVF-Flat-only; the PQ tier requires "
                "codebook-consistent codes (refit to mutate an ivfpq model)"
            )
        from ..ann.mutable import MutableIVFIndex

        mesh = mesh or get_mesh(self.num_workers)
        hf = self._resolved_hot_fraction()
        key = (self._mesh_key(mesh), hf)
        if self._mutable is None:
            self._mutable = (
                key,
                MutableIVFIndex(self._packed(), mesh, hot_fraction=hf),
            )
            self._staged_index = None  # the holder owns staging now
        elif self._mutable[0] != key:
            raise ValueError(
                "mutable index already staged on a different mesh; "
                "freeze_mutations() and re-create to move meshes"
            )
        return self._mutable[1]

    def freeze_mutations(self):
        """Fold the live holder's state back into the model's persistable
        packed payload (compacted live rows) and drop the holder — after
        this, save()/staging behave exactly like a freshly-built index
        over the mutated item set."""
        if self._mutable is None:
            return self
        packed = self._mutable[1].to_packed()
        self.packed_items_ = packed.items
        self.packed_ids_ = packed.ids
        self.list_counts_ = packed.counts
        self.centroids_ = packed.centroids
        self.n_items = packed.n_items
        self._model_attributes["packed_items_"] = packed.items
        self._model_attributes["packed_ids_"] = packed.ids
        self._model_attributes["list_counts_"] = packed.counts
        self._model_attributes["centroids_"] = packed.centroids
        self._model_attributes["n_items"] = packed.n_items
        self._mutable = None
        self._staged_index = None
        self._staged_exact = None
        return self

    def _ensure_staged_pq(self, mesh):
        hf = self._resolved_hot_fraction()
        key = (self._mesh_key(mesh), hf)
        if self._staged_pq is None or self._staged_pq[0] != key:
            if hf < 1.0:
                staged = tiered_index_from_packed_pq(
                    self._packed_pq(), mesh, hf
                )
            else:
                staged = index_from_packed_pq(self._packed_pq(), mesh)
            self._staged_pq = (key, staged)
        return self._staged_pq[1]

    def _ensure_staged_exact(self, mesh):
        from ..ops.knn import prepare_items

        if self._mutable is not None:
            # the exact route stages from packed_items_/packed_ids_, which
            # live mutations do NOT update until freeze — serving a stale
            # payload here would return tombstoned ids and miss every
            # added one, silently
            raise ValueError(
                "exactSearch is unavailable while the index is live-"
                "mutable (the exact route reads the persistable packed "
                "payload, which mutations update only at "
                "freeze_mutations()); freeze first"
            )
        key = self._mesh_key(mesh)
        if self._staged_exact is None or self._staged_exact[0] != key:
            self._staged_exact = (
                key,
                prepare_items(self.packed_items_, self.packed_ids_, mesh),
            )
        return self._staged_exact[1]

    def kneighbors(
        self, query_df: Any
    ) -> Tuple[Optional[DataFrame], DataFrame, DataFrame]:
        """Probed approximate k nearest items for every query row (float32
        euclidean, same output frame as the exact model's kneighbors:
        (item_df — None on a loaded model, query_df_withid, knn_df with
        query_<id>, indices, distances)).  exactSearch=True routes through
        the exact engine over the same indexed items, so the two paths
        share the id space and recall_at_k can gate one against the
        other."""
        from ..core import _is_pyspark_dataframe, extract_partition_features

        self._check_algorithm()
        if _is_pyspark_dataframe(query_df):
            raise NotImplementedError(
                "ApproximateNearestNeighborsModel serves in-process query "
                "frames; collect the pyspark frame (SRML_SPARK_COLLECT=1) "
                "first"
            )
        qdf = as_dataframe(query_df)
        id_col = self.getIdCol()
        if id_col not in qdf.columns:
            qdf = qdf.with_row_id(id_col)
        input_col, input_cols = self._get_input_columns()
        mesh = get_mesh(self.num_workers)
        k = self.getK()
        _nlist, nprobe = self._resolved_algo_params(
            self.n_items, n_lists=self.n_lists
        )
        exact = self.getExactSearch()
        pq = not exact and self.getAlgorithm() == "ivfpq"
        if exact:
            from ..ops.knn import knn_search_prepared

            prepared = self._ensure_staged_exact(mesh)
        elif pq:
            index = self._ensure_staged_pq(mesh)
            _m, _b, refine_ratio, _opq = self._resolved_pq_params(self.n_cols)
        else:
            index = self._ensure_staged_index(mesh)
        from .. import profiling

        out_parts = []
        with profiling.trace_session("search-ApproximateNearestNeighbors"):
            for part in qdf.partitions:
                if len(part) == 0:
                    out_parts.append(
                        pd.DataFrame(
                            {f"query_{id_col}": [], "indices": [], "distances": []}
                        )
                    )
                    continue
                feats = extract_partition_features(
                    part, input_col, input_cols, np.float32
                )
                if exact:
                    dists, ids = knn_search_prepared(prepared, feats, k, mesh)
                elif pq:
                    dists, ids = ivfpq_search_prepared(
                        index, feats, k, nprobe, mesh,
                        refine_items=(
                            self.packed_items_ if refine_ratio > 1 else None
                        ),
                        refine_ratio=refine_ratio,
                    )
                else:
                    dists, ids = ivfflat_search_prepared(
                        index, feats, k, nprobe, mesh
                    )
                out_parts.append(
                    pd.DataFrame(
                        {
                            f"query_{id_col}": part[id_col].to_numpy(),
                            "indices": list(np.asarray(ids)),
                            "distances": list(np.asarray(dists, np.float32)),
                        }
                    )
                )
        return self._item_df, qdf, DataFrame(out_parts)

    def _get_tpu_transform_func(self, dataset):  # pragma: no cover
        raise NotImplementedError(
            "ApproximateNearestNeighborsModel has no transform; use "
            "kneighbors instead."
        )

    def _serving_entry(self, mesh: Any = None):
        """Online ANN hook (serving/): each coalesced batch is ONE probed
        search (flat or PQ per the algorithm param) against the staged
        index; warm submits the probe-kernel geometry for every engine
        bucket (the engine's pow2 buckets feed the search's own >=64
        query-block rule, same contract as the exact kNN entry) — served
        steady state performs zero new compilations on BOTH tiers."""
        from ..serving.entry import ServingEntry

        self._check_algorithm()
        mesh = mesh or get_mesh(self.num_workers)
        pq = self.getAlgorithm() == "ivfpq"
        k = self.getK()
        _nlist, nprobe = self._resolved_algo_params(
            self.n_items, n_lists=self.n_lists
        )
        dtype = np.dtype(np.float32)
        info = {
            "k": int(min(k, self.n_items)),
            "n_items": int(self.n_items),
            "nlist": int(self.n_lists),
            "nprobe": int(nprobe),
            "algorithm": self.getAlgorithm(),
        }
        if pq:
            index = self._ensure_staged_pq(mesh)
            _m, _b, refine_ratio, _opq = self._resolved_pq_params(self.n_cols)
            refine_items = (
                self.packed_items_ if refine_ratio > 1 else None
            )
            info["m_sub"] = int(index.m_sub)
            info["n_bits"] = int(index.n_bits)
            info["refine_ratio"] = int(refine_ratio)

            def call(batch: np.ndarray) -> Dict[str, np.ndarray]:
                dists, ids = ivfpq_search_prepared(
                    index, batch, k, nprobe, mesh,
                    refine_items=refine_items, refine_ratio=refine_ratio,
                )
                return {
                    "indices": np.asarray(ids),
                    "distances": np.asarray(dists, dtype=np.float32),
                }

            def warm(buckets) -> list:
                keys = []
                for b in sorted({max(int(x), 64) for x in buckets}):
                    keys.extend(
                        warm_pq_probe_kernels(
                            index, k, nprobe, mesh, n_queries=b,
                            refine=refine_items is not None,
                            refine_ratio=refine_ratio,
                        )
                    )
                return keys

        else:
            self._ensure_staged_index(mesh)  # stage (or validate holder mesh)

            def call(batch: np.ndarray) -> Dict[str, np.ndarray]:
                # re-read per batch: with a live-mutation holder this is
                # the atomic post-mutation snapshot (add/delete/repack are
                # serving-visible without re-registration); without one it
                # is the cached staged tuple — a dict lookup either way
                dists, ids = ivfflat_search_prepared(
                    self._ensure_staged_index(mesh), batch, k, nprobe, mesh
                )
                return {
                    "indices": np.asarray(ids),
                    "distances": np.asarray(dists, dtype=np.float32),
                }

            def warm(buckets) -> list:
                index = self._ensure_staged_index(mesh)
                holder = self._mutable[1] if self._mutable is not None else None
                keys = []
                for b in sorted({max(int(x), 64) for x in buckets}):
                    keys.extend(
                        warm_probe_kernels(index, k, nprobe, mesh, n_queries=b)
                    )
                    if holder is not None:
                        # a later repack re-warms exactly the geometries
                        # serving dispatches before swapping the index in
                        holder.register_warm(k, nprobe, b)
                return keys

        return ServingEntry(
            name="serve.ann",
            n_cols=int(self.n_cols),
            dtype=dtype,
            out_cols=["indices", "distances"],
            call=call,
            warm=warm,
            info=info,
        )

    def index_bytes_per_item(self, mesh: Any = None) -> float:
        """Device-resident index bytes per indexed item on this mesh — the
        flat-vs-PQ compression headline (host-side payloads — ids, the PQ
        refine f32 vectors — are deliberately excluded: device HBM is the
        capacity constraint the PQ tier exists to lift)."""
        self._check_algorithm()
        mesh = mesh or get_mesh(self.num_workers)
        if self.getAlgorithm() == "ivfpq":
            index = self._ensure_staged_pq(mesh)
        else:
            index = self._ensure_staged_index(mesh)
        return index.device_bytes() / max(self.n_items, 1)

    def index_residency(
        self, mesh: Any = None, hbm_budget_bytes: int = 16 << 30
    ) -> Dict[str, float]:
        """The residency breakdown behind index_bytes_per_item: where each
        indexed item's bytes actually live on this mesh, and how many
        items one device's HBM budget admits at this layout.

        - hbm_bytes_per_item: device-resident index bytes / item (the
          whole index for hot_fraction=1; hot lists + the pager pool for
          a tiered split)
        - host_bytes_per_item: host-RAM bytes / item — the tier's warm
          list planes plus the payloads that are ALWAYS host-side (ids
          and, on the pq tier, the f32 refine vectors)
        - items_per_device: floor(hbm_budget_bytes / per-device HBM bytes
          per item) — the headline capacity number at this (n_bits, M,
          hot_fraction) operating point
        """
        self._check_algorithm()
        mesh = mesh or get_mesh(self.num_workers)
        if self.getAlgorithm() == "ivfpq":
            index = self._ensure_staged_pq(mesh)
            host_extra = self.packed_items_.nbytes + self.packed_ids_.nbytes
        else:
            index = self._ensure_staged_index(mesh)
            host_extra = self.packed_ids_.nbytes
        n = max(self.n_items, 1)
        n_dev = max(int(np.prod(list(mesh.shape.values()))), 1)
        hbm_bpi = index.device_bytes() / n
        host_bpi = (
            getattr(index, "host_bytes", lambda: 0)() + host_extra
        ) / n
        per_dev_bpi = hbm_bpi / n_dev
        return {
            "hbm_bytes_per_item": float(hbm_bpi),
            "host_bytes_per_item": float(host_bpi),
            "items_per_device": float(
                np.floor(hbm_budget_bytes / max(per_dev_bpi, 1e-12))
            ),
        }
