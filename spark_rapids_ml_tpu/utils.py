#
# Shared utilities: logging, dtype mapping, array layout helpers.
#
# Functional counterpart of the reference's utils
# (/root/reference/python/src/spark_rapids_ml/utils.py): get_logger (:250),
# dtype mapping (:233), memory-careful concat (:199).  GPU-id discovery
# (:98-130) has no TPU analog — device binding is the jax mesh's job
# (see parallel/mesh.py).
#

from __future__ import annotations

import logging
import os
import sys
from typing import Any, List, Optional, Union

import numpy as np


def env_float(name: str, default: float) -> float:
    """Float env knob with a default on unset/empty/garbage — the ONE
    parse-env-with-fallback helper (watch, serving, and the control plane
    each grew a private copy before this; a future tweak to the parsing
    must land once)."""
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def get_logger(cls: Union[type, str], level: int = logging.INFO) -> logging.Logger:
    """Per-class stderr logger with a standard format (reference utils.py:250-267)."""
    name = cls if isinstance(cls, str) else f"spark_rapids_ml_tpu.{cls.__name__}"
    logger = logging.getLogger(name)
    logger.setLevel(level)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
        logger.addHandler(handler)
        logger.propagate = False
    return logger


def _concat_and_free(array_list: List[np.ndarray], order: str = "F") -> np.ndarray:
    """Concatenate row chunks while freeing inputs incrementally to bound peak
    host memory (behavioral analog of reference utils.py:199-221).  C-order
    float matrices route through the threaded native runtime when built
    (native.concat_rows), the host-bandwidth half of ingest."""
    if len(array_list) == 1:
        arr = array_list.pop()
        return np.asarray(arr, order=order)  # type: ignore[call-overload]
    if (
        order == "C"
        and array_list[0].ndim == 2
        and array_list[0].dtype in (np.float32, np.float64)
    ):
        from . import native

        if native.available():
            out = native.concat_rows(array_list, array_list[0].dtype)
            array_list.clear()
            return out
    rows = sum(a.shape[0] for a in array_list)
    if array_list[0].ndim == 1:
        out = np.empty((rows,), dtype=array_list[0].dtype)
    else:
        out = np.empty((rows, array_list[0].shape[1]), dtype=array_list[0].dtype, order=order)  # type: ignore[call-overload]
    offset = 0
    while array_list:
        a = array_list.pop(0)
        out[offset : offset + a.shape[0]] = a
        offset += a.shape[0]
        del a
    return out


def stack_feature_cells(cells: Any, dtype: np.dtype) -> np.ndarray:
    """Column of array-like cells -> 2-D array.

    Accepts the Spark array<float> layout (ndarray/list cells), pyspark
    ``DenseVector``/``SparseVector`` cells (the reference ingests both,
    e.g. Vectors.sparse doctests at classification.py:418,435), and scipy
    sparse row matrices.  Sparse inputs are densified: the MXU wants dense
    tiles, and every solver here is a dense formulation."""
    n = len(cells)
    if n == 0:
        return np.zeros((0, 0), dtype=dtype)
    first = cells[0]
    if np.ndim(first) == 0 and np.issubdtype(np.asarray(first).dtype, np.integer):
        # scalar-int cells are the sparse-block placeholder column written by
        # DataFrame.from_numpy(csr) — fail loudly instead of returning row
        # positions as "features"
        raise TypeError(
            "feature column holds sparse-block placeholders, not vectors; "
            "read this partition via core.extract_partition_features (its "
            "features live in a CSR block in partition .attrs)"
        )
    if hasattr(first, "toArray"):  # pyspark Vector cells
        size = len(first)
        out = np.zeros((n, size), dtype=dtype)
        for i, c in enumerate(cells):
            idx = getattr(c, "indices", None)
            if idx is not None:  # SparseVector: fill nonzeros only
                out[i, np.asarray(idx, dtype=np.int64)] = c.values
            else:
                out[i] = c.toArray()
        return out
    if hasattr(first, "toarray") and hasattr(first, "tocsr"):  # scipy sparse rows
        import scipy.sparse as sp

        return np.asarray(sp.vstack(list(cells)).toarray(), dtype=dtype)
    try:
        out = np.stack(cells)
    except ValueError as e:
        raise ValueError(
            "feature column cells must all be arrays of the same length"
        ) from e
    return np.asarray(out, dtype=dtype)


def materialize_feature_block(
    block: Any,
    part: Any,
    input_col: Optional[str],
    input_cols: Optional[List[str]],
    dtype: np.dtype,
    densify_sparse: bool = True,
    on_densify: Optional[Any] = None,
) -> np.ndarray:
    """One partition's feature matrix from a stashed feature block (dense
    2-D or sparse CSR, or None) with a column fallback — THE shared ingest
    materialization: estimator ingest, model transform, and the standalone
    extract_partition_features all route here (it was triplicated across
    core.py before graftlint's duplicate-code finding).

    `block` is the partition's pre-validated feature block from
    core._partition_feature_block (None when absent or when reading
    input_cols).  Sparse blocks stay CSR when densify_sparse=False;
    otherwise they densify — the ONE sanctioned np.asarray(toarray())
    site (graftlint R1 allowlists this function) — calling `on_densify`
    first so callers can warn."""
    if block is not None and hasattr(block, "tocsr"):
        if not densify_sparse:
            return block  # CSR stays sparse through to ELL ingest
        if on_densify is not None:
            on_densify()
        return np.asarray(block.toarray(), dtype=dtype)
    if block is not None:
        return np.asarray(block, dtype=dtype)
    if input_col is not None:
        return stack_feature_cells(part[input_col].tolist(), dtype)
    assert input_cols is not None
    return np.asarray(part[input_cols].to_numpy(), dtype=dtype)


def pad_rows(arr: np.ndarray, multiple: int) -> np.ndarray:
    """Zero-pad rows so arr.shape[0] is a multiple of `multiple` (static shapes
    for XLA; padded rows are masked by zero weights downstream)."""
    n = arr.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return arr
    pad_shape = (rem,) + arr.shape[1:]
    return np.concatenate([arr, np.zeros(pad_shape, dtype=arr.dtype)], axis=0)
