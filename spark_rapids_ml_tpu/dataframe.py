#
# Partitioned columnar DataFrame facade.
#
# The reference rides pyspark DataFrames end to end; its executors see Arrow
# batches via mapInPandas (/root/reference/python/src/spark_rapids_ml/core.py:558-632).
# This framework keeps that data model — a DataFrame is an ordered list of
# column-named row partitions — but owns it natively so the TPU runtime works
# with or without a Spark cluster: partitions are pandas DataFrames (Arrow
# interchangeable), and the Spark adapter (spark/ package) converts a real
# pyspark DataFrame into this facade at the executor boundary.
#
# Feature layouts supported everywhere (mirroring the reference tests'
# vector/array/multi_cols parametrization, python/tests/utils.py:77-117):
#   - "array":      one column whose cells are fixed-length numpy arrays/lists
#   - "vector":     alias of "array" (Spark VectorUDT becomes arrays here)
#   - "multi_cols": D scalar columns
#
# Like Spark DataFrames, instances are IMMUTABLE by convention: mutating the
# numpy data a DataFrame was built from (in place) after construction is
# undefined behavior — the runtime caches both host feature blocks and their
# device-resident shardings across fits.
#

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np
import pandas as pd

# pandas .attrs key under which a partition may carry a _FeatureBlock (a
# {col: contiguous 2-D array} holder) for zero-copy ingest — set by
# DataFrame.from_numpy; absent on partitions produced by generic
# transformations.  Consumers must validate the block still matches the
# partition (see core._partition_feature_block).
FEATURE_BLOCK_ATTR = "srml_feature_block"


class _FeatureBlock:
    """Identity-equality, identity-deepcopy wrapper.  pandas compares .attrs
    values with == when propagating them (pd.concat raises on raw ndarrays)
    and deep-copies .attrs in __finalize__ on every derived frame/column —
    without these overrides each column access would copy the whole block
    (measured 0.38 s per getitem on a 600 MB block)."""

    __slots__ = ("blocks",)

    def __init__(self, blocks: Dict[str, np.ndarray]):
        self.blocks = blocks

    def __eq__(self, other: Any) -> bool:
        return self is other

    def __hash__(self) -> int:
        return id(self)

    def __deepcopy__(self, memo: Any) -> "_FeatureBlock":
        return self

    def __copy__(self) -> "_FeatureBlock":
        return self


class Row:
    """Lightweight attribute/row access wrapper (pyspark.sql.Row stand-in)."""

    __slots__ = ("_data",)

    def __init__(self, data: Dict[str, Any]):
        object.__setattr__(self, "_data", data)

    def __getattr__(self, name: str) -> Any:
        try:
            return self._data[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __getitem__(self, key: Union[str, int]) -> Any:
        if isinstance(key, int):
            return list(self._data.values())[key]
        return self._data[key]

    def asDict(self) -> Dict[str, Any]:
        return dict(self._data)

    def __repr__(self) -> str:
        inner = ", ".join(f"{k}={v!r}" for k, v in self._data.items())
        return f"Row({inner})"

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, Row) and self._data == other._data


class DataFrame:
    """An ordered collection of pandas partitions with Spark-flavored methods."""

    def __init__(self, partitions: Sequence[pd.DataFrame]):
        parts = [p for p in partitions]
        if not parts:
            parts = [pd.DataFrame()]
        cols = list(parts[0].columns)
        for p in parts[1:]:
            if list(p.columns) != cols:
                raise ValueError("All partitions must share the same columns")
        self._partitions: List[pd.DataFrame] = parts
        # set by from_device: (X_dev, n_rows, n_cols, featuresCol) — a
        # device-resident feature array that fits consume directly
        self._device_features = None

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_pandas(cls, pdf: pd.DataFrame, num_partitions: int = 1) -> "DataFrame":
        return cls(_split_pandas(pdf, num_partitions))

    @classmethod
    def from_arrow(cls, table: Any, num_partitions: int = 1) -> "DataFrame":
        return cls.from_pandas(table.to_pandas(), num_partitions)

    @classmethod
    def from_device(
        cls,
        X: Any,                     # jax.Array (N_pad, D) or ops.sparse.EllMatrix, optionally sharded
        y: Optional[Any] = None,    # (n_rows,) jax or numpy
        weight: Optional[Any] = None,
        featuresCol: str = "features",
        labelCol: str = "label",
        weightCol: str = "weight",
        n_rows: Optional[int] = None,
    ) -> "DataFrame":
        """Facade backed by a DEVICE-RESIDENT feature array — jax-native
        ingest.  Estimator fits consume `X` directly (no host
        materialization, no upload): the TPU analog of the reference riding
        the spark-rapids plugin's GPU-resident columnar cache (its
        executors hand cuML device arrays when the DataFrame is cached on
        GPU).  `X` may already be sharded over a mesh; pass `n_rows` when
        trailing rows are padding.  A sparse table comes as an
        `ops.sparse.EllMatrix` (slot-major idx/val of (P, N_pad), row-sharded
        along axis 1 on a mesh): rows, columns and dtype are read from it,
        and the estimators with a sparse path (the GLMs) fit it where it
        lies: no host copy, no densification, no conversion.  Labels/weights
        are materialized host-side once, and a fit's device copies of them
        (and the label classes found there) are kept on the frame: they are
        O(N) scalars, not the O(N*D) features.

        FIT-INPUT ONLY: transform/kneighbors need per-partition host
        features and raise on a from_device frame — run inference through
        the host-facade or pyspark paths, or the ops-level kernels.  The one
        exception is CrossValidator.fit (tuning.py): a sweep the batched
        engine carries (LogisticRegression on a dense table, a regParam /
        elasticNetParam grid, MulticlassClassificationEvaluator) scores each
        fold's models on that fold's rows where the table lies, under the
        fold ids the training masks came from: no split frame, no toPandas,
        no upload.  Any other sweep on such a frame is refused before it
        fits anything."""
        n_valid = int(n_rows if n_rows is not None else X.shape[0])
        # the features column is a placeholder (readers must go through the
        # device array); keep it 1 byte/row
        cols: Dict[str, Any] = {featuresCol: np.zeros(n_valid, np.int8)}
        if y is not None:
            cols[labelCol] = np.asarray(y)[:n_valid]
        if weight is not None:
            cols[weightCol] = np.asarray(weight)[:n_valid]
        df = cls([pd.DataFrame(cols)])
        df._device_features = (X, n_valid, int(X.shape[1]), featuresCol)
        return df

    @classmethod
    def from_numpy(
        cls,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        feature_layout: str = "array",
        featuresCol: Union[str, List[str]] = "features",
        labelCol: str = "label",
        num_partitions: int = 1,
        weight: Optional[np.ndarray] = None,
        weightCol: str = "weight",
    ) -> "DataFrame":
        if hasattr(X, "toarray") and hasattr(X, "tocsr"):  # scipy sparse
            # Kept SPARSE: each partition carries a CSR block in .attrs and a
            # local-row-position placeholder column (the guard in
            # core._partition_feature_block keys on it).  Estimators that
            # support sparse input (the GLMs) ingest the CSR without
            # densification (reference sparse qn path,
            # classification.py:1206-1218); others densify per partition.
            if feature_layout not in ("array", "vector"):
                raise ValueError(
                    "sparse X requires feature_layout='array'/'vector'"
                )
            csr = X.tocsr()
            col = featuresCol if isinstance(featuresCol, str) else featuresCol[0]
            n = csr.shape[0]
            bounds = np.linspace(0, n, max(1, num_partitions) + 1, dtype=int)
            parts = []
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                block = csr[lo:hi]
                pdf = pd.DataFrame({col: np.arange(hi - lo, dtype=np.int64)})
                if y is not None:
                    pdf[labelCol] = np.asarray(y)[lo:hi]
                if weight is not None:
                    pdf[weightCol] = np.asarray(weight)[lo:hi]
                pdf.attrs[FEATURE_BLOCK_ATTR] = _FeatureBlock({col: block})
                parts.append(pdf)
            return cls(parts)
        X = np.asarray(X)
        if feature_layout in ("array", "vector"):
            # Build partitions directly so each carries a contiguous 2-D
            # feature block in .attrs: estimator ingest then skips the
            # 1-object-per-row np.stack (which costs ~50 s at 400k x 3000)
            # and reads the block zero-copy.  The object column stays — any
            # generic consumer still sees the Spark array<float> layout.
            col = featuresCol if isinstance(featuresCol, str) else featuresCol[0]
            n = X.shape[0]
            bounds = np.linspace(0, n, max(1, num_partitions) + 1, dtype=int)
            parts = []
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                block = np.ascontiguousarray(X[lo:hi])
                pdf = pd.DataFrame({col: list(block)})
                if y is not None:
                    pdf[labelCol] = np.asarray(y)[lo:hi]
                if weight is not None:
                    pdf[weightCol] = np.asarray(weight)[lo:hi]
                pdf.attrs[FEATURE_BLOCK_ATTR] = _FeatureBlock({col: block})
                parts.append(pdf)
            return cls(parts)
        if feature_layout == "multi_cols":
            names = (
                featuresCol
                if isinstance(featuresCol, list)
                else [f"{featuresCol}_{i}" for i in range(X.shape[1])]
            )
            data: Dict[str, Any] = {name: X[:, i] for i, name in enumerate(names)}
        else:
            raise ValueError(f"Unknown feature_layout: {feature_layout}")
        if y is not None:
            data[labelCol] = np.asarray(y)
        if weight is not None:
            data[weightCol] = np.asarray(weight)
        return cls.from_pandas(pd.DataFrame(data), num_partitions)

    # -- metadata ----------------------------------------------------------
    @property
    def columns(self) -> List[str]:
        return list(self._partitions[0].columns)

    @property
    def num_partitions(self) -> int:
        return len(self._partitions)

    @property
    def partitions(self) -> List[pd.DataFrame]:
        return self._partitions

    @property
    def dtypes(self) -> List[tuple]:
        p = self._partitions[0]
        return [(c, str(p[c].dtype)) for c in p.columns]

    def schema_of(self, col: str) -> str:
        return str(self._partitions[0][col].dtype)

    def count(self) -> int:
        return sum(len(p) for p in self._partitions)

    def isEmpty(self) -> bool:
        return self.count() == 0

    # -- layout ------------------------------------------------------------
    def repartition(self, n: int) -> "DataFrame":
        return DataFrame.from_pandas(self.toPandas(), n)

    def coalesce(self, n: int) -> "DataFrame":
        if n >= len(self._partitions):
            return self
        return self.repartition(n)

    # -- relational ops ----------------------------------------------------
    def select(self, *cols: str) -> "DataFrame":
        names = list(cols[0]) if len(cols) == 1 and isinstance(cols[0], (list, tuple)) else list(cols)
        return DataFrame([p[names] for p in self._partitions])

    def drop(self, *cols: str) -> "DataFrame":
        return DataFrame(
            [p.drop(columns=[c for c in cols if c in p.columns]) for p in self._partitions]
        )

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        return DataFrame([p.rename(columns={old: new}) for p in self._partitions])

    def filter(self, predicate: Callable[[pd.DataFrame], pd.Series]) -> "DataFrame":
        return DataFrame([p[predicate(p)].reset_index(drop=True) for p in self._partitions])

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self._partitions + other._partitions)

    def with_row_id(self, col: str = "unique_id") -> "DataFrame":
        """Monotonically-increasing globally unique row id (analog of the
        reference's _ensureIdCol, knn.py:231-258)."""
        out, offset = [], 0
        for p in self._partitions:
            q = p.copy()
            q[col] = np.arange(offset, offset + len(p), dtype=np.int64)
            offset += len(p)
            out.append(q)
        return DataFrame(out)

    def randomSplit(self, weights: List[float], seed: int = 0) -> List["DataFrame"]:
        pdf = self.toPandas()
        split_id = random_split_ids(len(pdf), weights, seed)
        nparts = max(1, len(self._partitions))
        return [
            DataFrame.from_pandas(
                pdf.iloc[np.flatnonzero(split_id == i)].reset_index(drop=True),
                nparts,
            )
            for i in range(len(weights))
        ]

    # -- execution ---------------------------------------------------------
    def mapInPandas(
        self, fn: Callable[[Iterable[pd.DataFrame]], Iterable[pd.DataFrame]], schema: Any = None
    ) -> "DataFrame":
        """Per-partition transform, same contract as pyspark mapInPandas: fn
        takes an iterator of batches and yields output batches."""
        out: List[Optional[pd.DataFrame]] = []
        for p in self._partitions:
            frames = list(fn(iter([p])))
            out.append(pd.concat(frames, ignore_index=True) if frames else None)
        # partitions with no output batches get the output schema of the
        # first non-empty partition (pyspark declares the schema up front)
        template = next((o for o in out if o is not None), pd.DataFrame())
        filled = [
            o if o is not None else template.iloc[0:0].copy() for o in out
        ]
        return DataFrame(filled)

    def toPandas(self) -> pd.DataFrame:
        return pd.concat(self._partitions, ignore_index=True)

    def to_arrow(self) -> Any:
        import pyarrow as pa

        return pa.Table.from_pandas(self.toPandas(), preserve_index=False)

    def collect(self) -> List[Row]:
        # to_dict("records") is vectorized per column; iterrows would build a
        # pandas Series per row (O(n) Python-object overhead per row)
        return [Row(d) for d in self.toPandas().to_dict("records")]

    def first(self) -> Optional[Row]:
        for p in self._partitions:
            if len(p):
                return Row({c: p.iloc[0][c] for c in p.columns})
        return None

    def cache(self) -> "DataFrame":
        return self

    def unpersist(self) -> "DataFrame":
        # releases the runtime's device-resident fit-input cache (the
        # persisted-on-accelerator state a Spark unpersist would drop)
        from .core import clear_fit_cache

        clear_fit_cache()
        return self

    def __repr__(self) -> str:
        return f"DataFrame[{', '.join(self.columns)}] ({self.num_partitions} partitions)"


def random_split_ids(
    n: int, weights: Union[int, List[float]], seed: int = 0
) -> np.ndarray:
    """Per-row split assignment of ``randomSplit(weights, seed)``: row r of
    the concatenated frame lands in split ``random_split_ids(...)[r]``.

    This is the ONE definition of the seeded-permutation split, shared by
    DataFrame.randomSplit (which materializes the split frames) and the
    batched sweep engine (ops/sweep), which folds with weight MASKS over one
    staged dataset — sharing the assignment here is what guarantees the two
    routes can never disagree on fold membership.  ``weights`` may be an
    int k, shorthand for k equal folds (the CrossValidator case)."""
    if isinstance(weights, int):
        weights = [1.0] * weights
    total = float(sum(weights))
    bounds = np.cumsum([w / total for w in weights])[:-1]
    cut = (bounds * n).astype(int)
    return _permutation_split(n, cut, seed)


def _permutation_split(n: int, cuts: np.ndarray, seed: int) -> np.ndarray:
    """The ONE seeded-permutation split assignment: permute rows with the
    seeded generator, cut the permutation at `cuts`, and label each row
    with its segment.  random_split_ids derives its cuts from fractional
    weights (the Spark randomSplit semantics); stream_chunk_ids derives
    EXACT integer cuts — both ride this identical permutation, so the two
    surfaces can never disagree on what 'seed s over n rows' means."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    split_id = np.empty(n, dtype=np.int32)
    for i, g in enumerate(np.split(perm, cuts)):
        split_id[g] = i
    return split_id


def stream_chunk_ids(n: int, chunk_rows: int, seed: int = 0) -> np.ndarray:
    """Per-row CHUNK assignment for a streamed replay of an n-row dataset:
    row r of the source belongs to streamed chunk ``stream_chunk_ids(...)[r]``
    (chunks 0..ceil(n/chunk_rows)-1, each of EXACTLY chunk_rows rows except
    a short tail — exact integer cuts, not randomSplit's fractional
    rounding, so chunk sizes can never drift a row across a pow2 bucket
    boundary and break the zero-compile steady-ingest contract).  Shares
    the ONE seeded-permutation split definition with random_split_ids
    (_permutation_split), so a replayed stream at the same (n, chunk_rows,
    seed) produces IDENTICAL chunk membership — the determinism
    precondition for srml-stream's streamed==batch equality gates
    (docs/streaming.md §determinism)."""
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    if n <= 0:
        return np.zeros(0, dtype=np.int32)
    cuts = np.arange(chunk_rows, n, chunk_rows, dtype=np.int64)
    return _permutation_split(n, cuts, seed)


def _split_pandas(pdf: pd.DataFrame, n: int) -> List[pd.DataFrame]:
    n = max(1, n)
    if len(pdf) == 0:
        return [pdf]
    idx = np.array_split(np.arange(len(pdf)), n)
    return [pdf.iloc[ix].reset_index(drop=True) for ix in idx]


def as_dataframe(dataset: Any, num_partitions: Optional[int] = None) -> DataFrame:
    """Coerce any supported input (our DataFrame, pandas, arrow Table, numpy
    (X,)| (X, y) tuple, or a live pyspark DataFrame) into the facade."""
    if isinstance(dataset, DataFrame):
        return dataset
    if isinstance(dataset, pd.DataFrame):
        return DataFrame.from_pandas(dataset, num_partitions or 1)
    try:
        import pyarrow as pa

        if isinstance(dataset, pa.Table):
            return DataFrame.from_arrow(dataset, num_partitions or 1)
    except ImportError:
        pass
    try:
        import pyspark

        if isinstance(dataset, pyspark.sql.DataFrame):
            from .spark.adapter import spark_to_facade

            return spark_to_facade(dataset)
    except ImportError:
        pass
    raise TypeError(f"Unsupported dataset type: {type(dataset)}")
