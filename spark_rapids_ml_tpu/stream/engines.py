#
# Streaming incremental-fit engines (srml-stream).
#
# The partial_fit / merge / finalize contract over the batch estimators:
# each engine wraps one configured estimator, ingests row chunks (numpy
# blocks, pandas partitions, or facade DataFrames — frame chunks route
# through utils.materialize_feature_block, THE shared ingest
# materialization), stages every chunk device-resident through the
# existing pow2 shape buckets + AOT executable cache
# (ops/precompile.cached_kernel, so a steady stream of same-bucket chunks
# performs ZERO new compilations after the first bucket), and folds the
# chunk's device-computed partials into a small mergeable StreamState
# (stream/state.py).  finalize() materializes a REGULAR fitted model of
# the batch model class through the estimator's own _materialize_model
# bookkeeping — a streamed model persists, transforms, and serves exactly
# like its batch twin.
#
# Chunk math is SINGLE-DEVICE by design (the same mesh-independence
# argument as ann/ivfflat.train_coarse_quantizer): a chunk's partial
# statistics reduce in an order fixed by the chunk, never by a mesh, so
# streamed states are mesh-independent data and multi-rank scale-out
# comes from the state merge algebra across ranks (state.allgather_merge
# over the control plane), not from intra-chunk sharding.
#
# Equality contract (gated in tests/test_streaming.py and the CI 3o step;
# the full argument is docs/streaming.md §exactness):
#   - linreg / PCA: partial_fit over k chunks == batch fit on the union
#     BITWISE on the exact-arithmetic data families (integer-valued
#     features, pow2 row counts) — chunk partials are exact f32 sums, the
#     f64 host fold is exact, and finalize routes through the SAME solver
#     kernels (ops/glm.solve_linear / ops/linalg._pca_from_moments) the
#     batch fit dispatches.
#   - kmeans / logreg: quality-gated (inertia / classification metric) —
#     one-pass mini-batch Lloyd and warm-started chunk L-BFGS are online
#     approximations with no bitwise twin.
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiling
from ..ops.precompile import cached_kernel, shape_bucket
from ..utils import materialize_feature_block
from .state import StreamState

# smallest streamed-chunk row bucket: matches the ANN assign-block floor so
# tiny chunks do not shatter the executable cache into sub-256 geometries
# (SRML_STREAM_BUCKET_LO overrides; tests shrink it to exercise ladders)
_CHUNK_BUCKET_LO = 256
BUCKET_LO_ENV = "SRML_STREAM_BUCKET_LO"

H2D_COUNTER = "stream.h2d_transfers"
BYTES_COUNTER = "stream.bytes"


def chunk_bucket(n: int) -> int:
    """The ONE pow2 row bucket streamed chunks stage at (shared with every
    engine's warm/update dispatch so same-bucket chunks reuse executables)."""
    import os

    try:
        lo = int(os.environ.get(BUCKET_LO_ENV, _CHUNK_BUCKET_LO))
    except ValueError:
        lo = _CHUNK_BUCKET_LO
    return shape_bucket(n, lo=max(1, lo))


def _chunk_arrays(
    chunk: Any,
    y: Optional[Any],
    weight: Optional[Any],
    dtype: np.dtype,
    input_col: Optional[str],
    input_cols: Optional[List[str]],
    label_col: str,
    weight_col: str,
) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
    """Coerce one streamed chunk into host (X, y, w) arrays.  Frame chunks
    (facade DataFrame or a single pandas partition) materialize through
    utils.materialize_feature_block — the same zero-copy block path batch
    ingest rides — and read labels/weights from the configured columns;
    numpy chunks pass through with explicit y/weight."""
    import pandas as pd

    from ..core import _partition_feature_block
    from ..dataframe import DataFrame as _Facade

    if isinstance(chunk, _Facade):
        parts = [p for p in chunk.partitions if len(p)]
    elif isinstance(chunk, pd.DataFrame):
        parts = [chunk] if len(chunk) else []
    else:
        X = np.ascontiguousarray(np.asarray(chunk), dtype=dtype)
        if X.ndim != 2:
            raise ValueError(f"streamed chunk must be 2-D, got shape {X.shape}")
        yv = None if y is None else np.asarray(y)
        wv = None if weight is None else np.asarray(weight)
        for name, v in (("y", yv), ("weight", wv)):
            if v is not None and v.shape[0] != X.shape[0]:
                # a silent zero-pad here would fold fabricated labels into
                # the state with full weight — fail before any math
                raise ValueError(
                    f"chunk {name} has {v.shape[0]} rows but X has "
                    f"{X.shape[0]}"
                )
        return X, yv, wv
    if y is not None or weight is not None:
        raise ValueError(
            "frame chunks carry labels/weights in their own columns; pass "
            "y/weight only with numpy chunks"
        )
    if not parts:
        return np.zeros((0, 0), dtype=dtype), None, None
    Xs, ys, ws = [], [], []
    for part in parts:
        block = (
            _partition_feature_block(part, input_col)
            if input_col is not None and input_col in part.columns
            else None
        )
        Xs.append(
            materialize_feature_block(
                block,
                part,
                input_col if input_col in part.columns else None,
                input_cols,
                dtype,
            )
        )
        if label_col in part.columns:
            ys.append(np.asarray(part[label_col].to_numpy()))
        if weight_col in part.columns:
            ws.append(np.asarray(part[weight_col].to_numpy(), dtype))
    X = np.concatenate(Xs) if len(Xs) > 1 else Xs[0]
    yv = (np.concatenate(ys) if len(ys) > 1 else ys[0]) if ys else None
    wv = (np.concatenate(ws) if len(ws) > 1 else ws[0]) if ws else None
    for name, col, v in (("label", label_col, yv), ("weight", weight_col, wv)):
        if v is not None and v.shape[0] != X.shape[0]:
            # some partitions carried the column and some did not — a
            # silent zero-pad would fold fabricated values at full weight
            raise ValueError(
                f"frame chunk's {col!r} {name} column covers {v.shape[0]} "
                f"of {X.shape[0]} rows (column missing from some "
                "partitions?)"
            )
    return X, yv, wv


def _stage(arr: np.ndarray, bucket: int, dtype) -> jax.Array:
    """Zero-pad one host array to the chunk bucket and device_put it,
    counted under the stream.h2d_transfers / stream.bytes pair (the
    umap.h2d_transfers pattern) so ingest volume shows up in
    export_metrics() and the standings bytes column."""
    a = np.asarray(arr, dtype=dtype)
    pad = bucket - a.shape[0]
    if pad:
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    dev = jax.device_put(a)
    profiling.incr_counter(H2D_COUNTER)
    profiling.incr_counter(BYTES_COUNTER, int(a.nbytes))
    return dev


class StreamingEngine:
    """Shared partial_fit plumbing: column config from the wrapped
    estimator, chunk staging, row accounting, state wire helpers."""

    kind: str = ""

    def __init__(self, estimator: Any):
        self._estimator = estimator
        self._params: Dict[str, Any] = dict(estimator._tpu_params)
        input_col, input_cols = estimator._get_input_columns()
        self._input_col = input_col
        self._input_cols = input_cols
        self._label_col = (
            estimator.getOrDefault("labelCol")
            if estimator.hasParam("labelCol") and estimator.isDefined("labelCol")
            else "label"
        )
        self._weight_col = (
            estimator.getOrDefault("weightCol")
            if estimator.hasParam("weightCol") and estimator.isDefined("weightCol")
            else "weight"
        )
        self._dtype = np.dtype(np.float32)  # streaming is f32-only (docs)
        self._n_cols: Optional[int] = None
        self._rows: int = 0
        self._chunks: int = 0
        self._state: Optional[StreamState] = None

    # -- public surface ----------------------------------------------------
    @property
    def rows_ingested(self) -> int:
        return self._rows

    @property
    def chunks_ingested(self) -> int:
        return self._chunks

    @property
    def state(self) -> StreamState:
        if self._state is None:
            raise RuntimeError(
                f"Streaming{type(self._estimator).__name__} has ingested no "
                "chunks yet; call partial_fit first"
            )
        return self._state

    def state_dict(self) -> Dict[str, Any]:
        """JSON-able wire form of the accumulated state (the control-plane
        allGather payload; see state.allgather_merge)."""
        return self.state.to_dict()

    # state field whose trailing axis is the feature width — lets a FRESH
    # engine that adopts a peer's state (its own partition was empty, the
    # multicontroller uneven-rank case) recover n_cols without a chunk
    _N_COLS_FIELD = {
        "pca": "xwsum",
        "linreg": "xwsum",
        "logreg": "WS",
        "kmeans": "init_centers",
    }

    def merge(self, other: Any) -> "StreamingEngine":
        """Fold another stream's state into this engine: `other` may be a
        peer engine, a StreamState, or its wire dict.  Row/chunk accounting
        sums; engine-specific derived values refresh from the merged
        state.  A FRESH engine (zero chunks ingested — e.g. a rank whose
        partition was empty) adopts the peer state wholesale, identity
        anchors included."""
        if isinstance(other, StreamingEngine):
            peer_state, peer_rows, peer_chunks = (
                other.state, other._rows, other._chunks
            )
        elif isinstance(other, StreamState):
            peer_state, peer_rows, peer_chunks = other, 0, 0
        else:
            peer_state, peer_rows, peer_chunks = (
                StreamState.from_dict(other), 0, 0
            )
        if self._state is None:
            self._state = peer_state.copy()
        else:
            self._state = self._state.merge(peer_state)
        if self._n_cols is None:
            field = self._N_COLS_FIELD[self.kind]
            self._n_cols = int(self._state.arrays[field].shape[-1])
        self._rows += peer_rows
        self._chunks += peer_chunks
        self._post_merge()
        return self

    def partial_fit(
        self, chunk: Any, y: Any = None, weight: Any = None
    ) -> "StreamingEngine":
        """Ingest one chunk: stage device-resident at the pow2 bucket,
        dispatch the engine's update kernel through the AOT executable
        cache, fold the partials into the mergeable state."""
        X, yv, wv = _chunk_arrays(
            chunk, y, weight, self._dtype, self._input_col, self._input_cols,
            self._label_col, self._weight_col,
        )
        n = X.shape[0]
        if n == 0:
            return self
        if self._n_cols is None:
            self._n_cols = int(X.shape[1])
        elif int(X.shape[1]) != self._n_cols:
            raise ValueError(
                f"chunk feature width {X.shape[1]} != stream width "
                f"{self._n_cols}"
            )
        if wv is None:
            wv = np.ones(n, self._dtype)
        with profiling.span(
            "stream.update", rows=n, engine=self.kind
        ):
            self._update(X, yv, wv)
        self._rows += n
        self._chunks += 1
        profiling.incr_counter("stream.rows", n)
        profiling.incr_counter("stream.chunks")
        return self

    def finalize(self) -> Any:
        """Materialize a fitted model of the batch model class from the
        accumulated state (the estimator's own _materialize_model
        bookkeeping, so params/columns/dtype land exactly like a batch
        fit's)."""
        with profiling.span("stream.finalize", engine=self.kind):
            result = self._finalize_result()
            return self._estimator._materialize_model(result)

    # -- engine hooks ------------------------------------------------------
    def _update(self, X: np.ndarray, y, w: np.ndarray) -> None:
        raise NotImplementedError

    def _finalize_result(self) -> Dict[str, Any]:
        raise NotImplementedError

    def _post_merge(self) -> None:
        pass


class StreamingPCA(StreamingEngine):
    """PCA over an unbounded row stream: per-chunk weighted moments
    (ops/linalg.stream_moments_chunk_kernel) folded into f64 (wsum, xwsum,
    scatter); finalize routes the accumulated covariance through the SAME
    eigh derivation as the batch kernel (_pca_from_moments), device or
    native-host per the pca_fit routing rule."""

    kind = "pca"

    def _update(self, X, y, w) -> None:
        from ..ops.linalg import stream_moments_chunk_kernel

        bucket = chunk_bucket(X.shape[0])
        Xd = _stage(X, bucket, self._dtype)
        wd = _stage(w, bucket, self._dtype)
        wsum, xwsum, scatter = jax.device_get(
            cached_kernel("stream.pca_update", stream_moments_chunk_kernel, Xd, wd)
        )
        if self._state is None:
            d = self._n_cols
            self._state = StreamState(
                "pca",
                {
                    "wsum": np.zeros(()),
                    "xwsum": np.zeros(d),
                    "scatter": np.zeros((d, d)),
                },
            )
        self._state.add_(
            {"wsum": wsum, "xwsum": xwsum, "scatter": scatter}
        )

    def _finalize_result(self) -> Dict[str, Any]:
        from ..ops.linalg import pca_finalize_moments

        st = self.state.arrays
        d = self._n_cols
        k = self._params.get("n_components") or min(self._rows, d)
        k = min(int(k), d)
        # downcast the exact f64 fold to the compute dtype BEFORE the
        # derived divisions, so finalize's mean is the same single-rounded
        # f32 quotient the batch moment pass computes
        mean, components, var, ratio, sv = pca_finalize_moments(
            st["wsum"].astype(self._dtype),
            st["xwsum"].astype(self._dtype),
            st["scatter"].astype(self._dtype),
            k,
        )
        return {
            "mean_": np.asarray(mean, dtype=np.float64),
            "components_": np.asarray(components, dtype=np.float64),
            "explained_variance_": np.asarray(var, dtype=np.float64),
            "explained_variance_ratio_": np.asarray(ratio, dtype=np.float64),
            "singular_values_": np.asarray(sv, dtype=np.float64),
            "n_cols": self._n_cols,
            "dtype": str(np.dtype(self._dtype)),
        }


class StreamingLinearRegression(StreamingEngine):
    """Linear regression over a row stream: per-chunk unreduced sufficient
    statistics (ops/glm.stream_linreg_chunk_kernel) folded into f64;
    finalize solves the SAME closed-form / coordinate-descent kernels the
    batch fit dispatches (ops/glm.solve_linear / solve_elasticnet_cd) on
    the downcast stats, with the shared host-f64 intercept derivation."""

    kind = "linreg"

    def _update(self, X, y, w) -> None:
        from ..ops.glm import stream_linreg_chunk_kernel

        if y is None:
            raise ValueError(
                "StreamingLinearRegression chunks need labels (y= for numpy "
                f"chunks, a {self._label_col!r} column for frame chunks)"
            )
        bucket = chunk_bucket(X.shape[0])
        Xd = _stage(X, bucket, self._dtype)
        yd = _stage(np.asarray(y, self._dtype), bucket, self._dtype)
        wd = _stage(w, bucket, self._dtype)
        wsum, xwsum, G, ysum, c, y2 = jax.device_get(
            cached_kernel(
                "stream.linreg_update", stream_linreg_chunk_kernel, Xd, yd, wd
            )
        )
        if self._state is None:
            d = self._n_cols
            self._state = StreamState(
                "linreg",
                {
                    "wsum": np.zeros(()),
                    "xwsum": np.zeros(d),
                    "G": np.zeros((d, d)),
                    "ysum": np.zeros(()),
                    "c": np.zeros(d),
                    "y2": np.zeros(()),
                },
            )
        self._state.add_(
            {"wsum": wsum, "xwsum": xwsum, "G": G, "ysum": ysum, "c": c, "y2": y2}
        )

    def _finalize_result(self) -> Dict[str, Any]:
        from ..models.linear_regression import _host_intercept
        from ..ops.glm import LinregStats, solve_elasticnet_cd, solve_linear

        st = self.state.arrays
        dt = self._dtype
        wsum = st["wsum"].astype(dt)
        xwsum = st["xwsum"].astype(dt)
        ysum = st["ysum"].astype(dt)
        stats = LinregStats(
            wsum=jnp.asarray(wsum),
            x_mean=jnp.asarray(xwsum / wsum),  # single-rounded f32 quotient
            y_mean=jnp.asarray(ysum / wsum),
            G=jnp.asarray(st["G"].astype(dt)),
            c=jnp.asarray(st["c"].astype(dt)),
            y2=jnp.asarray(st["y2"].astype(dt)),
        )
        p = self._params
        alpha = float(p["alpha"])
        l1_ratio = float(p["l1_ratio"])
        fit_intercept = bool(p["fit_intercept"])
        normalize = bool(p["normalize"])
        # the batch _single_fit solver choice, verbatim
        if alpha == 0.0 or l1_ratio == 0.0:
            coef, _ = solve_linear(
                stats, alpha, fit_intercept=fit_intercept, normalize=normalize
            )
        else:
            coef, _, _ = solve_elasticnet_cd(
                stats,
                alpha,
                l1_ratio,
                fit_intercept=fit_intercept,
                normalize=normalize,
                max_iter=int(p["max_iter"]),
                tol=float(p["tol"]),
            )
        coef64 = np.asarray(jax.device_get(coef), dtype=np.float64)
        return {
            "coef_": coef64,
            "intercept_": _host_intercept(
                coef64, xwsum / wsum, ysum / wsum, fit_intercept
            ),
            "n_cols": self._n_cols,
            "dtype": str(np.dtype(dt)),
        }


class StreamingKMeans(StreamingEngine):
    """Mini-batch Lloyd over a row stream: the FIRST chunk trains the
    initial centers with the existing k-means|| init + Lloyd kernels
    (single-device, mesh-independent — the coarse-quantizer pattern);
    every chunk then assigns its rows to the CURRENT running centers
    (ops/kmeans.stream_kmeans_chunk_kernel) and folds count-weighted
    per-center sums into the state, so running centers are the exact
    weighted mean of every row ever assigned to them.  Merge adds
    per-center (sums, counts) — ranks must share the init anchor."""

    kind = "kmeans"

    def __init__(self, estimator: Any):
        super().__init__(estimator)
        self._centers: Optional[np.ndarray] = None  # running f64 centers
        self._init_centers: Optional[np.ndarray] = None
        self._cost: float = 0.0

    def _init_from_chunk(self, X: np.ndarray, w: np.ndarray) -> np.ndarray:
        from ..ops.kmeans import (
            lloyd_iterations,
            random_init,
            scalable_kmeans_pp_init,
        )
        from ..parallel.mesh import data_sharding, get_mesh

        p = self._params
        k = int(p["n_clusters"])
        seed = int(p["random_state"]) & 0x7FFFFFFF
        mesh1 = get_mesh(1)
        Xd = jax.device_put(np.asarray(X, self._dtype), data_sharding(mesh1))
        wd = jax.device_put(np.asarray(w, self._dtype), data_sharding(mesh1))
        if p["init"] == "random":
            centers0 = random_init(Xd, wd, k, seed)
        else:
            oversample = float(p["oversampling_factor"])
            round_size = max(1, min(int(oversample * k), X.shape[0]))
            centers0 = scalable_kmeans_pp_init(
                Xd, wd, k, seed, oversample, rounds=4, round_size=round_size
            )
        centers, _, _ = lloyd_iterations(
            Xd, wd, centers0, mesh1, int(p["max_iter"]), float(p["tol"]),
            min(int(p["max_samples_per_batch"]), X.shape[0]),
        )
        return np.asarray(jax.device_get(centers), np.float64)

    def _update(self, X, y, w) -> None:
        from ..ops.kmeans import stream_kmeans_chunk_kernel

        if self._centers is None:
            with profiling.span("stream.kmeans_init", rows=X.shape[0]):
                self._centers = self._init_from_chunk(X, w)
                self._init_centers = self._centers.copy()
        bucket = chunk_bucket(X.shape[0])
        Xd = _stage(X, bucket, self._dtype)
        wd = _stage(w, bucket, self._dtype)
        cd = jax.device_put(np.asarray(self._centers, self._dtype))
        sums, counts, cost = jax.device_get(
            cached_kernel(
                "stream.kmeans_update", stream_kmeans_chunk_kernel, Xd, wd, cd
            )
        )
        if self._state is None:
            k, d = self._centers.shape
            self._state = StreamState(
                "kmeans",
                {
                    "sums": np.zeros((k, d)),
                    "counts": np.zeros(k),
                    "cost": np.zeros(()),
                    "init_centers": self._init_centers,
                },
            )
        self._state.add_({"sums": sums, "counts": counts, "cost": cost})
        self._refresh_centers()

    def _refresh_centers(self) -> None:
        st = self.state.arrays
        counts = st["counts"]
        nonempty = counts > 0
        self._centers = np.where(
            nonempty[:, None],
            st["sums"] / np.maximum(counts, 1.0)[:, None],
            st["init_centers"],
        )

    def _post_merge(self) -> None:
        self._init_centers = self.state.arrays["init_centers"]
        self._refresh_centers()

    def _finalize_result(self) -> Dict[str, Any]:
        return {
            "cluster_centers_": np.asarray(self._centers, np.float64),
            "n_cols": self._n_cols,
            "dtype": str(np.dtype(self._dtype)),
            "n_iter_": self._chunks,
            "inertia_": float(self.state.arrays["cost"]),
        }


class StreamingLogisticRegression(StreamingEngine):
    """Logistic regression over a row stream: each chunk runs the batch
    objective's L-BFGS/OWL-QN WARM-STARTED from the running streamed
    coefficients (ops/logistic.logistic_warm_fit_kernel — identical
    objective, different starting point), and the state folds
    count-weighted coefficient sums (iterate averaging), so merge across
    ranks is the row-weighted mean of per-rank streams.  The class set is
    an identity anchor: declared up front (classes=) or discovered from
    the first chunk; later chunks with unseen labels fail loudly."""

    kind = "logreg"

    def __init__(self, estimator: Any, classes: Optional[Any] = None):
        super().__init__(estimator)
        self._classes = (
            None if classes is None else np.unique(np.asarray(classes, np.float64))
        )
        self._W: Optional[np.ndarray] = None  # running averaged (k, D)
        self._b: Optional[np.ndarray] = None

    def _update(self, X, y, w) -> None:
        from ..ops.logistic import logistic_warm_fit_kernel

        if y is None:
            raise ValueError(
                "StreamingLogisticRegression chunks need labels (y= for "
                f"numpy chunks, a {self._label_col!r} column for frame chunks)"
            )
        yv = np.asarray(y, np.float64)
        if self._classes is None:
            self._classes = np.unique(yv)
            if len(self._classes) < 2:
                raise ValueError(
                    "first chunk holds a single label class; declare the "
                    "full class set via streaming(classes=...) when early "
                    "chunks may be single-class"
                )
        idx = np.searchsorted(self._classes, yv)
        idx = np.clip(idx, 0, len(self._classes) - 1)
        if not np.array_equal(self._classes[idx], yv):
            unseen = sorted(set(np.unique(yv)) - set(self._classes))
            raise ValueError(
                f"chunk contains labels outside the stream's class set: "
                f"{unseen}; declare them up front via streaming(classes=...)"
            )
        num_classes = len(self._classes)
        kcls = 1 if num_classes == 2 else num_classes
        d = self._n_cols
        if self._W is None:
            self._W = np.zeros((kcls, d), np.float64)
            self._b = np.zeros((kcls,), np.float64)
        p = self._params
        C = float(p["C"])
        reg = 1.0 / C if C > 0 else 0.0
        l1_ratio = float(p.get("l1_ratio") or 0.0)
        use_owlqn = reg > 0 and l1_ratio > 0
        bucket = chunk_bucket(X.shape[0])
        Xd = _stage(X, bucket, self._dtype)
        yd = _stage(idx.astype(np.int32), bucket, np.int32)
        wd = _stage(w, bucket, self._dtype)
        W0 = jax.device_put(np.asarray(self._W, self._dtype))
        b0 = jax.device_put(np.asarray(self._b, self._dtype))
        W, b, _n_iter, _conv, _n_evals = jax.device_get(
            cached_kernel(
                "stream.logreg_update",
                logistic_warm_fit_kernel,
                Xd, yd, wd, W0, b0,
                jnp.asarray(reg, self._dtype),
                jnp.asarray(l1_ratio, self._dtype),
                jnp.asarray(float(p["tol"]), self._dtype),
                k=kcls,
                fit_intercept=bool(p["fit_intercept"]),
                max_iter=int(p["max_iter"]),
                use_owlqn=use_owlqn,
            )
        )
        cw = float(np.asarray(w, np.float64).sum())
        if self._state is None:
            self._state = StreamState(
                "logreg",
                {
                    "WS": np.zeros((kcls, d)),
                    "bs": np.zeros((kcls,)),
                    "wsum": np.zeros(()),
                    "classes": self._classes,
                },
            )
        self._state.add_(
            {"WS": cw * np.asarray(W, np.float64),
             "bs": cw * np.asarray(b, np.float64),
             "wsum": cw}
        )
        self._refresh_coefs()

    def _refresh_coefs(self) -> None:
        st = self.state.arrays
        wsum = max(float(st["wsum"]), 1e-30)
        self._W = st["WS"] / wsum
        self._b = st["bs"] / wsum

    def _post_merge(self) -> None:
        self._classes = self.state.arrays["classes"]
        self._refresh_coefs()

    def _finalize_result(self) -> Dict[str, Any]:
        return {
            "coef_": np.asarray(self._W, np.float64),
            "intercept_": np.asarray(self._b, np.float64),
            "classes_": np.asarray(self._classes, np.float64),
            "n_cols": self._n_cols,
            "dtype": str(np.dtype(self._dtype)),
            "num_iters": self._chunks,
        }


_ENGINES = {
    "KMeans": StreamingKMeans,
    "PCA": StreamingPCA,
    "LinearRegression": StreamingLinearRegression,
    "LogisticRegression": StreamingLogisticRegression,
}


def streaming_fit(estimator: Any, **kwargs: Any) -> StreamingEngine:
    """The streaming engine for a configured estimator — the functional
    form of the estimators' .streaming() hook."""
    name = type(estimator).__name__
    cls = _ENGINES.get(name)
    if cls is None:
        raise TypeError(
            f"{name} has no streaming engine; streamable estimators: "
            f"{sorted(_ENGINES)} (forest/UMAP streaming is a documented "
            "non-goal — docs/streaming.md)"
        )
    return cls(estimator, **kwargs)
