#
# Shared utilities: logging, dtype mapping, array layout helpers.
#
# Functional counterpart of the reference's utils
# (/root/reference/python/src/spark_rapids_ml/utils.py): get_logger (:250),
# dtype mapping (:233).  The memory-careful concat (:199) has no counterpart
# since ingest stages batch by batch (core.stage_dense_batches).  GPU-id discovery
# (:98-130) has no TPU analog — device binding is the jax mesh's job
# (see parallel/mesh.py).
#

from __future__ import annotations

import itertools
import logging
import operator
import os
import sys
from typing import Any, List, Optional, Tuple, Union

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


def feature_cells_view(cells: np.ndarray, dtype: np.dtype) -> Optional[np.ndarray]:
    """A feature column whose cells lie one after another in one buffer, as
    ONE read-only 2-D view of that buffer: no copy, no Python object a row
    past the check.  This is what pyarrow's to_pandas() makes of a
    list<float> column (Spark's Arrow batches on an executor, an Arrow table
    handed to DataFrame.from_arrow): every cell a 1-D view into the batch's
    one values buffer, 12,000 bytes apart at 3000 float32 columns.

    The rule, all of it, or None (the caller then stacks the cells,
    stack_feature_cells, as for any other column): `cells` is an object
    array; EVERY cell is a numpy array of exactly `dtype` (a float64 column
    of a float32 fit is refused: a view cannot cast), 1-D, of the first
    cell's non-zero width, its one stride the item size; and EVERY cell's
    address is the first cell's plus its row times the width in bytes.  Every
    cell is looked at: a frame whose middle rows were swapped, filtered or
    sampled keeps its first, second and last cells where they were, and a
    view taken on their word would pair rows with other rows' labels in
    silence.  Cells allocated one by one, lists, Vectors and sparse rows
    fail the first test they meet.  The loop is the rule's price (1.5 us a
    cell: Python has no cheaper route to an array's address); a fit job
    that can fit again pays it while the solver runs (admit_feature_cells
    below, core.settle_deferred_proofs), everyone else here and now.

    The view borrows the cells' buffer: it is valid while the column (or
    its batch) is alive, which the caller sees to."""
    n = len(cells)
    if n == 0 or getattr(cells, "dtype", None) != object:
        return None
    first = cells[0]
    if type(first) is not np.ndarray or first.ndim != 1 or first.size == 0:
        return None
    width, item = first.shape[0], first.itemsize
    # one __array_interface__ a cell says all four: "strides" is None for a
    # C-contiguous array, "typestr" is the dtype with its byte order
    shape, typestr, step = (width,), dtype.str, width * item
    at = first.__array_interface__["data"][0]
    for c in cells:
        if type(c) is not np.ndarray:
            return None
        told = c.__array_interface__
        if (
            told["data"][0] != at or told["shape"] != shape
            or told["strides"] is not None or told["typestr"] != typestr
        ):
            return None
        at += step
    return _rows_view(first, n)


def _rows_view(first: np.ndarray, n: int) -> np.ndarray:
    """`n` rows of `first`'s width, one after another from `first`'s address
    on, read-only."""
    width, item = first.shape[0], first.itemsize
    view = np.lib.stride_tricks.as_strided(first, shape=(n, width), strides=(width * item, item))
    view.flags.writeable = False
    return view


_cell_base = operator.attrgetter("base")


def admit_feature_cells(cells: np.ndarray, dtype: np.dtype) -> Optional[np.ndarray]:
    """feature_cells_view's view on a cheap test, for a caller who will run
    the rule itself over `cells` before it lets anything computed from the
    view out (core.settle_deferred_proofs); None where the test fails, and
    the caller then asks the rule at once.

    The test is O(1) Python and one pass at C speed, under a millisecond for
    10,000 cells where the rule's loop takes 16: the rule on the first two
    cells and on the last (type, dtype, shape, stride, the second's address),
    the last cell's address the first's plus n - 1 widths, and EVERY cell's
    .base the first cell's and not None.  The last two make the view safe
    to read before it is proven: its first and last byte lie in the one
    allocation that owns every cell, so everything between does.  They prove
    nothing of the middle cells' positions (two of them traded pass), which is
    why admission is no substitute for the rule."""
    n = len(cells)
    head, tail = feature_cells_view(cells[:2], dtype), feature_cells_view(cells[-1:], dtype)
    if head is None or tail is None or head.shape[1] != tail.shape[1]:
        return None
    first, last = cells[0], cells[-1]
    apart = last.__array_interface__["data"][0] - first.__array_interface__["data"][0]
    if first.base is None or apart != (n - 1) * head.strides[0]:
        return None
    try:
        owned = all(map(operator.is_, map(_cell_base, cells), itertools.repeat(first.base)))
    except AttributeError:      # a middle cell that is no array
        return None
    return _rows_view(first, n) if owned else None


def materialize_feature_block(
    block: Any,
    part: Any,
    input_col: Optional[str],
    input_cols: Optional[List[str]],
    dtype: np.dtype,
    densify_sparse: bool = True,
    on_densify: Optional[Any] = None,
    on_cells: Optional[Any] = None,
    deferred: Optional[List[Tuple[np.ndarray, np.dtype]]] = None,
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
    first so callers can warn.  A column of array cells is ONE 2-D view
    where feature_cells_view's rule holds and stacked cell by cell where it
    does not; `on_cells(viewed)` tells the caller which (fit ingest counts
    them).

    `deferred` is the list of proofs a caller owes who can throw away what it
    computes from the view (core.FitJob.run alone): a column that passes
    admit_feature_cells is viewed on that test and (cells, dtype) appended,
    for the caller to hold to the rule before anything leaves it.  The cells
    hold their buffer, so what is proven later is what was read.  Without
    it, and for a column admission refuses, the rule is asked here."""
    if block is not None and hasattr(block, "tocsr"):
        if not densify_sparse:
            return block  # CSR stays sparse through to ELL ingest
        if on_densify is not None:
            on_densify()
        return np.asarray(block.toarray(), dtype=dtype)
    if block is not None:
        return np.asarray(block, dtype=dtype)
    if input_col is not None:
        cells, cell_dtype = part[input_col].to_numpy(), np.dtype(dtype)
        view = admit_feature_cells(cells, cell_dtype) if deferred is not None else None
        if view is not None:
            deferred.append((cells, cell_dtype))
        else:
            view = feature_cells_view(cells, cell_dtype)
        if on_cells is not None and len(cells):
            on_cells(view is not None)
        return view if view is not None else stack_feature_cells(cells.tolist(), dtype)
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
