#
# Core estimator/model machinery: ingest -> mesh-sharded jax arrays, fit
# dispatch, transform dispatch, persistence.
#
# Structural counterpart of the reference's core
# (/root/reference/python/src/spark_rapids_ml/core.py): _CumlCaller
# _pre_process_data/_call_cuml_fit_func (:344-640), _CumlEstimator._fit_internal
# (:856), _FitMultipleIterator (:649), _CumlModel transform/evaluate plumbing
# (:1126-1377), and the writer/reader pairs (:139-226).  The execution model is
# redesigned TPU-first rather than translated:
#
#   reference: driver builds a closure -> mapInPandas -> barrier task per GPU
#              -> NCCL rank per task -> cuML MG kernels all-reduce per iter
#   here:      ingest concatenates Arrow/pandas partitions into host numpy,
#              zero-pads rows, device_puts with NamedSharding(P("data")) over a
#              jax Mesh, and calls a pure jax fit function; XLA/GSPMD inserts
#              psum/all_gather collectives (ICI intra-host, DCN inter-host).
#              One *process* spans many chips (single-controller); multi-host
#              runs extend the same mesh via parallel/context.TpuContext.
#
# Padded rows are masked through the `weight` vector so every solver is
# weighted by construction (weightCol support falls out for free).
#

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import sys
import threading
from abc import abstractmethod
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np
import pandas as pd

import jax

from . import profiling
from .dataframe import DataFrame, FEATURE_BLOCK_ATTR, as_dataframe
from .params import Param, Params, _TpuParams
from .parallel.mesh import get_mesh, data_sharding
from .parallel.partition import PartitionDescriptor
from .utils import feature_cells_view, get_logger, materialize_feature_block


def is_tall_table(X: Any) -> bool:
    """Whether X is an ops.tall.TallMatrix, asked without importing that
    module: a table of the type exists only where somebody imported it, and
    its import starts a thread (ops/tall.py says why) that a fit of any other
    table should not pay for."""
    tall = sys.modules.get(f"{__package__}.ops.tall")
    return tall is not None and isinstance(X, tall.TallMatrix)


def _is_pyspark_dataframe(dataset: Any) -> bool:
    """True for live pyspark DataFrames, detected by module name so pyspark
    is never imported here (it is absent on plain TPU-VM installs)."""
    return (type(dataset).__module__ or "").startswith("pyspark.sql")


def _use_executor_path(dataset: Any) -> bool:
    """Whether a dataset should run on the Spark executors (barrier fit /
    mapInPandas transform) rather than driver-local: a live pyspark
    DataFrame, unless SRML_SPARK_COLLECT=1 forces the old collect path
    (single TPU-VM notebooks where the driver owns the chips)."""
    return _is_pyspark_dataframe(dataset) and os.environ.get(
        "SRML_SPARK_COLLECT", "0"
    ) != "1"


def _maybe_x64(dtype: Any):
    """jax x64 scope for float64 fits; a no-op for float32."""
    if np.dtype(dtype) == np.float64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _tree_nbytes(tree: Any) -> int:
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


def _device_put_counted(put: Callable[[], Any], sent: Any = None) -> Any:
    """Every host→device copy of ingest goes through here: `put()` runs
    inside a srml.device_put span that carries the bytes that went up, and
    the process-wide counter ingest.h2d_bytes adds them up per fit.  The
    bytes are the result's, or those of `sent` where the result is not what
    this process sent (a rank's share of a global array)."""
    with profiling.span("srml.device_put") as sp:
        out = put()
        nbytes = _tree_nbytes(out if sent is None else sent)
        sp.set(bytes=nbytes)
    profiling.incr_counter("ingest.h2d_bytes", nbytes)
    return out


def _label_dtype(dtype: Any) -> np.dtype:
    """dtype of the O(N) label and weight vectors: the features', but at
    least float32.  A low-precision FEATURE dtype (float32_inputs=False with
    f16/bf16 features, a bf16 from_device table) must not round them:
    integer class labels above the half-precision mantissa are not exact and
    would silently corrupt label discovery and the training targets."""
    dtype = np.dtype(dtype)
    return np.dtype(np.float32) if dtype.itemsize < 4 else dtype


def stage_mask_and_labels(
    n_rows: int,
    n_pad: int,
    dtype: Any,
    labels: Optional[np.ndarray],
    weights: Optional[np.ndarray],
    put: Callable[[np.ndarray], Any],
) -> Tuple[Any, Any]:
    """The tail of every ingest: the row mask (the user's weight on the
    `n_rows` valid rows, 1 without a weightCol, 0 on the padding up to
    `n_pad`) and the labels padded alike (None unsupervised), each sent up
    by `put(buffer)` as a counted copy."""
    ldtype = _label_dtype(dtype)

    def staged(values: Any) -> Any:
        buf = np.zeros(n_pad, dtype=ldtype)
        buf[:n_rows] = values
        return _device_put_counted(lambda: put(buf), sent=buf)

    ws = staged(1 if weights is None else weights)
    return ws, (staged(labels) if labels is not None else None)


# The most of a batch that goes up in one copy.  Spark's Arrow batches pass
# whole (10,000 x 3000 float32 is 120 MB); a partition of gigabytes is cut,
# so the device holds the table and two pieces, never the table twice.
_STAGE_PIECE_BYTES = 128 << 20


@functools.partial(jax.jit, donate_argnums=0)
def _place_rows(table: jax.Array, piece: jax.Array, start: Any) -> jax.Array:
    """`piece` over rows [start, start + len(piece)) of `table`, in the
    table's own buffer (donated).  One executable a (table, piece) shape: a
    fit's batches share one, its short last batch takes a second."""
    return jax.lax.dynamic_update_slice(table, piece, (start, np.int32(0)))


def _pieces(lo: int, hi: int, share: int, row_bytes: int) -> Iterator[Tuple[int, int]]:
    """Rows [lo, hi) cut at every multiple of `share` (where one device's
    rows end) and then into runs of at most _STAGE_PIECE_BYTES."""
    step = max(1, _STAGE_PIECE_BYTES // row_bytes)
    while lo < hi:
        cut = min(hi, (lo // share + 1) * share, lo + step)
        yield lo, cut
        lo = cut


class _LinkScope(threading.local):
    """Whether the thread is inside a fit job's srml.ingest (FitJob._ingest):
    only there does stage_dense_batches keep a landing journal of the copies
    it sends.  Anyone else's staging records nothing and starts no thread."""

    open = False


_LINK = _LinkScope()


def _count_landings(pieces: Optional[List[Sequence[Any]]]) -> None:
    """One journaled staging's pieces, (opened, landed, bytes) each, reduced
    once: the time at least one copy was on its way (fed), the rest of first
    enqueue to last landing (starved: nothing was), and the copies' own times
    added up (flight; over fed, how many were under way at once).  Nothing
    where the journal is void (LandingJournal.close gave none)."""
    if not pieces:
        return
    fed, starved, flight = profiling.interval_measures([(o, l) for o, l, _ in pieces])
    profiling.incr_counter("ingest.link_stagings")
    profiling.incr_counter("ingest.link_pieces", len(pieces))
    profiling.incr_counter("ingest.link_bytes", sum(nbytes for _, _, nbytes in pieces))
    profiling.incr_counter("ingest.link_fed_us", round(1e6 * fed))
    profiling.incr_counter("ingest.link_starved_us", round(1e6 * starved))
    profiling.incr_counter("ingest.link_flight_us", round(1e6 * flight))


def stage_dense_batches(batches: Iterable[np.ndarray], n_rows: int, mesh: Any) -> jax.Array:
    """The fit's dense table, built on the device batch by batch: `n_rows`
    rows (zero-padded to a multiple of the mesh's devices, the padding masked
    by the weight vector as ever) row-sharded over `mesh`, from 2-D host
    batches taken one at a time, in order.  THE way dense host rows reach a
    single-process mesh: the driver-local fit (_build_fit_inputs) and the
    executor's (parallel/runner, one rank) both end here.  Ranks of a
    multi-process mesh assemble their share on the host and sparse
    partitions go up as one ELL pair, as before.

    `batches` is consumed lazily, so the host holds the source and at most
    two extracted batches, the one going up and the one being prepared
    (nothing at all beyond the source where a batch is a view of its Arrow
    buffer, utils.feature_cells_view): no concatenated second copy.  A batch is cut where a device's rows end (_pieces); a piece
    that is all of a device's rows goes up as that device's shard, any other
    is copied up (srml.device_put, counted in ingest.h2d_bytes) and written
    into the shard, allocated once as zeros, by _place_rows (srml.ingest.place).
    jax's copies are asynchronous (a put returns before a byte has moved),
    so piece i goes up while the caller's iterator
    prepares piece i + 1, and srml.device_put times the enqueue alone.

    A view is only as good as its buffer: a piece's host array is kept in
    `in_flight` until its transfer has completed (block_until_ready on its
    device copy, one piece behind the newest, under srml.ingest.wait), which
    also bounds the device to the table and two pieces.  The function
    returns when every shard is whole, so srml.ingest ends with the table on
    the device.

    When a piece LANDS the host's spans cannot say (the wait ends when this
    thread next looks), so inside a fit job's srml.ingest each piece is also
    a record of a landing journal (profiling.LandingJournal: `opened` here,
    `landed` by the watcher thread that blocks on nothing else, span
    srml.link.h2d), reduced once when the table is whole (_count_landings:
    the counters ingest.link_*).  Outside a job nothing is journaled."""
    import jax.numpy as jnp

    devices = list(mesh.devices.flat)
    share = -(-n_rows // len(devices))
    shards: List[Any] = [None] * len(devices)
    in_flight: collections.deque = collections.deque()
    journal = profiling.LandingJournal("srml.link.h2d") if _LINK.open else None
    row, n_cols, dtype = 0, 0, None
    for batch in batches:
        rows = batch.shape[0]
        if rows == 0:
            continue
        if dtype is None:
            n_cols, dtype = batch.shape[1], batch.dtype
        elif batch.shape[1] != n_cols:
            raise ValueError(
                f"feature batches disagree on width: {batch.shape[1]} after {n_cols}"
            )
        profiling.incr_counter("ingest.batches")
        profiling.incr_counter("ingest.rows", rows)
        for lo, hi in _pieces(row, row + rows, share, n_cols * dtype.itemsize):
            s, at = divmod(lo, share)
            host = np.ascontiguousarray(batch[lo - row : hi - row])
            filled = min(n_rows, (s + 1) * share) - s * share
            whole = at == 0 and hi - lo == filled       # all of this device's rows
            if whole and filled < share:                # which end in padding
                host = np.concatenate([host, np.zeros((share - filled, n_cols), dtype)])
            opened = profiling.now()
            up = _device_put_counted(lambda: jax.device_put(host, devices[s]))
            if journal is not None:
                journal.sent(opened, up.nbytes, up)
            if whole:
                shards[s] = up
            else:
                if shards[s] is None:
                    shards[s] = jnp.zeros((share, n_cols), dtype, device=devices[s])
                with profiling.span("srml.ingest.place"):
                    shards[s] = _place_rows(shards[s], up, np.int32(at))
            in_flight.append((host, up))
            if len(in_flight) > 1:
                with profiling.span("srml.ingest.wait"):
                    in_flight.popleft()[1].block_until_ready()
        row += rows
    if row != n_rows or dtype is None:
        raise RuntimeError(f"staged {row} rows of the {n_rows} the partitions hold")
    for s, dev in enumerate(devices):
        if shards[s] is None:           # more devices than rows: all padding
            shards[s] = jnp.zeros((share, n_cols), dtype, device=dev)
    with profiling.span("srml.ingest.wait"):
        jax.block_until_ready(shards)
    if journal is not None:
        _count_landings(journal.close())
    return jax.make_array_from_single_device_arrays(
        (share * len(devices), n_cols), data_sharding(mesh), shards
    )


class _DeferredProofs(threading.local):
    """The view rule's proofs a fit job owes, on the thread the job runs on.
    `batches` is None where nobody stands ready to settle (every extraction
    then asks the rule at once), and inside FitJob.run the (cells, dtype) of
    each batch that went up on admission alone (utils.admit_feature_cells);
    `refuted` counts those the rule has since refused."""

    batches: Optional[List[Tuple[np.ndarray, np.dtype]]] = None
    refuted = 0


_PROOFS = _DeferredProofs()


def settle_deferred_proofs() -> None:
    """utils.feature_cells_view, the rule itself, over every batch this
    thread's job admitted on the cheap test: every cell of every one is
    looked at, here, where it was not in srml.ingest.  The list is released
    (it kept each batch's buffer alive), the refused are counted, and
    FitJob.run throws the fit away if there was one.  16 ms a batch of 10,000
    cells, which is why it runs where the host would otherwise be blocked on
    the solver (fetch_fit_result) and not while the device waits for rows."""
    owed = _PROOFS.batches
    with profiling.span("srml.ingest.verify") as sp:
        refuted = sum(feature_cells_view(cells, dtype) is None for cells, dtype in owed)
        sp.set(batches=len(owed), refuted=refuted)
    owed.clear()
    if refuted:
        _PROOFS.refuted += refuted
        profiling.incr_counter("ingest.refuted_batches", refuted)


def fetch_fit_result(tree: Any) -> Any:
    """The end of a fit function's device work, as two step spans: wait
    (srml.fit.wait: block until the solver's outputs are ready, so the
    device's time is not charged to the copy) and ONE batched fetch of the
    whole result tree (srml.fit.fetch, bytes= on the span; the process-wide
    counter fit.d2h_bytes adds them up).  Returns the host tree.

    The copies are queued behind the solver before the wait, where
    jax.device_get alone would queue them: they start when the solver ends,
    not one host wake-up later.  Between the two, with the solver dispatched
    and nothing of it needed yet, the host settles the proofs the job's
    ingest deferred (srml.ingest.verify; nothing where none is owed)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.copy_to_host_async()
    if _PROOFS.batches:
        settle_deferred_proofs()
    with profiling.span("srml.fit.wait"):
        jax.block_until_ready(tree)
    with profiling.span("srml.fit.fetch") as sp:
        host = jax.device_get(tree)
        nbytes = _tree_nbytes(host)
        sp.set(bytes=nbytes)
    profiling.incr_counter("fit.d2h_bytes", nbytes)
    return host


# Reserved key a fit result dict carries its TelemetrySnapshot under —
# attached executor-side (parallel/runner) or by the local fit dispatch,
# popped by _fit_internal before the attrs reach _create_model, exposed as
# model.fit_telemetry().  Never a model attribute.
TELEMETRY_ATTR = "__srml_telemetry__"

# single-slot device-input cache; see _TpuCaller._build_fit_inputs
_FIT_INPUT_CACHE: Dict[str, Any] = {}


def clear_fit_cache() -> None:
    """Release the device-resident fit-input cache (frees the pinned HBM
    shardings and the host block references).  Also reachable via
    DataFrame.unpersist()."""
    _FIT_INPUT_CACHE.pop("slot", None)


def _partition_feature_block(part: pd.DataFrame, input_col: str):
    """Zero-copy contiguous feature block stashed by DataFrame.from_numpy,
    or None.  Guarded on row count plus first/last cell equality so
    partitions derived by filtering/slicing/reordering (pandas attrs
    propagation is version-dependent) never read a stale block."""
    holder = part.attrs.get(FEATURE_BLOCK_ATTR)
    block = holder.blocks.get(input_col) if holder is not None else None
    if block is None or block.shape[0] != len(part) or len(part) == 0:
        return None
    col = part[input_col]
    if hasattr(block, "tocsr"):
        # sparse CSR block: the placeholder column holds local row positions
        # (DataFrame.from_numpy); any row slice/reorder breaks the 0..n-1
        # run and the stale block is rejected
        if int(col.iloc[0]) == 0 and int(col.iloc[-1]) == len(part) - 1:
            return block
        return None
    if np.array_equal(col.iloc[0], block[0]) and np.array_equal(
        col.iloc[-1], block[-1]
    ):
        return block
    return None

def extract_partition_features(
    part: pd.DataFrame,
    input_col: Optional[str],
    input_cols: Optional[List[str]],
    dtype: np.dtype,
    densify_sparse: bool = True,
):
    """Feature matrix for one partition, honoring a stashed feature block —
    dense 2-D or sparse CSR (DataFrame.from_numpy).  Model-side consumers
    (transform-evaluate, kneighbors ingest) MUST use this instead of reading
    the column directly: sparse partitions carry a placeholder column whose
    cells are row positions, not features."""
    block = (
        _partition_feature_block(part, input_col) if input_col is not None else None
    )
    return materialize_feature_block(
        block, part, input_col, input_cols, dtype, densify_sparse=densify_sparse
    )


_SinglePdDataFrameBatchType = Tuple[pd.DataFrame, Optional[pd.DataFrame]]


@dataclass
class FitInputs:
    """Device-resident, row-sharded training inputs handed to fit functions."""

    X: Any                            # (N_pad, D) jax.Array row-sharded over mesh "data" axis, an ops.sparse.EllMatrix, or an ops.tall.TallMatrix
    weight: jax.Array                 # (N_pad,) user weight * valid-row mask
    y: Optional[jax.Array]            # (N_pad,) labels (supervised only)
    n_rows: int                       # valid rows (N_pad >= n_rows)
    n_cols: int
    mesh: Any
    pdesc: PartitionDescriptor
    dtype: np.dtype
    # host copies of the (unpadded) labels/weights when ingest had them —
    # single-controller label discovery reads these instead of round-
    # tripping the device label shards over the host link per fit
    host_y: Optional[np.ndarray] = None
    host_w: Optional[np.ndarray] = None
    # multi-controller context: which rank this process is, how many ranks
    # cooperate, and the string control plane they share (None single-
    # controller).  Fit functions that need host-side views of the inputs
    # must go through the local-shard helpers below + a control-plane
    # gather instead of np.asarray on the global arrays (which raises on
    # arrays spanning non-addressable devices).
    rank: int = 0
    nranks: int = 1
    control_plane: Any = None
    # discover_label_classes' answers by cast: a from_device frame keeps its
    # FitInputs, so the pass over its labels runs once a frame, not once a fit
    label_classes: Dict[str, np.ndarray] = field(default_factory=dict)
    # ops/softmax_ell_pass.slot_steps of X, (kept, covered), fetched with the
    # frame's first fit through that kernel: the table's, so every fit's
    ell_slot_steps: Optional[Tuple[int, int]] = None


def _aligned_shard_objs(*arrays: jax.Array):
    """Device-aligned tuples of addressable Shard objects of row-aligned
    global arrays, ordered by global row offset.  In single-controller mode
    this walks every shard (covering the whole array); in multi-process mode
    it only ever touches this process's addressable shards.  Shard .data
    stays on device — callers choose what (if anything) to fetch."""
    primary = sorted(
        arrays[0].addressable_shards, key=lambda s: s.index[0].start or 0
    )
    others = [{s.device: s for s in a.addressable_shards} for a in arrays[1:]]
    for s in primary:
        yield (s,) + tuple(o[s.device] for o in others)


def _row_aligned_shards(*arrays: jax.Array):
    """Host-numpy view of _aligned_shard_objs (fetches every local shard)."""
    for shards in _aligned_shard_objs(*arrays):
        yield tuple(np.asarray(s.data) for s in shards)


def discover_label_classes(
    inputs: FitInputs, cast: Optional[Any] = None
) -> np.ndarray:
    """Globally-sorted unique label values: per-rank np.unique over the
    rank's LOCAL shards (masked by weight > 0), unioned across ranks through
    the control plane — the reference's per-worker label discovery merged
    over the barrier allGather (classification.py:936-1001).  Safe in
    multi-process fits: never touches a non-addressable shard."""
    assert inputs.y is not None
    # the no-cast target is y's own dtype so every rank returns the same
    # dtype even when some rank holds zero valid rows
    target = np.dtype(cast) if cast is not None else np.dtype(inputs.y.dtype)
    if inputs.nranks == 1 and inputs.host_y is not None:
        # single-controller: the ingest's host label copy avoids fetching
        # the device label shards back over the host link on EVERY fit
        # (labels are re-uploaded per fit, so the fetch never warms).  The
        # sort is seconds at tens of millions of rows: kept with the inputs
        found = inputs.label_classes.get(target.str)
        if found is None:
            vals = inputs.host_y
            if inputs.host_w is not None:
                vals = vals[inputs.host_w > 0]
            if cast is not None:
                vals = vals.astype(target)
            found = np.unique(vals).astype(target, copy=False)
            inputs.label_classes[target.str] = found
        return found
    locs = []
    for y_loc, w_loc in _row_aligned_shards(inputs.y, inputs.weight):
        vals = y_loc[w_loc > 0]
        if cast is not None:
            vals = vals.astype(target)
        if vals.size:
            locs.append(np.unique(vals))
    local = (
        np.unique(np.concatenate(locs)) if locs else np.zeros(0, dtype=target)
    )
    if inputs.nranks > 1 and inputs.control_plane is not None:
        from .parallel.runner import allgather_ndarray

        merged = [
            m
            for m in allgather_ndarray(inputs.control_plane, inputs.rank, local)
            if m.size
        ]
        if merged:
            local = np.unique(np.concatenate([m.astype(target) for m in merged]))
    return local.astype(target, copy=False)


# fit function: (inputs, params-dict) -> model attribute dict (or list of
# dicts when fitting multiple param maps in a single pass)
FitFunc = Callable[[FitInputs, Dict[str, Any]], Union[Dict[str, Any], List[Dict[str, Any]]]]
# transform function: feature batch -> {output column name: column values}
TransformFunc = Callable[[np.ndarray], Dict[str, Any]]


class FitJob:
    """One fit job on the calling thread: the one place that knows what makes
    a job observable and safe.  Every launcher enters it through fit_job():
    the driver-local fit below, the executor's (parallel/runner) and the
    batched sweep (tuning.CrossValidator._fit_batched).  Its step spans tile
    the job, so a profiler trace names what the host was doing in every gap
    the device idles in: srml.prepare, srml.ingest, srml.fit (tiled in turn
    by the fit function's init, solve, wait, fetch, pack; a sweep runs those
    under tuning.sweep and has no outer srml.fit) and srml.finish."""

    def __init__(self, estimator: Any, rank: int) -> None:
        self.estimator, self.rank = estimator, rank
        self.baseline: Dict[str, int] = {}
        # finish() leaves these for the launcher to attach to what it returns
        self.phase_times: Dict[str, float] = {}
        self.snapshot: Optional[profiling.TelemetrySnapshot] = None

    @contextlib.contextmanager
    def prepare(self) -> Iterator[None]:
        """srml.prepare: the compile cache, phase times from zero, the counter
        baseline; the launcher's own resolving (the body); then srml-shield's
        runner.fit injection site, so a fault plan written against the site
        name covers whichever launcher ran the fit (action=die is the chaos
        matrix's "rank killed mid-fit", action=raise exercises the
        abort-marker broadcast in TpuContext)."""
        from .ops.precompile import ensure_compile_cache
        from .parallel import faults

        with profiling.span("srml.prepare"):
            ensure_compile_cache()
            profiling.reset_phase_times()
            self.baseline = profiling.counters()
            yield
            faults.site("runner.fit", rank=self.rank)

    def _ingest(self, build: Callable[[Callable[[Any], None]], FitInputs], x64: Callable[[Any], None]) -> FitInputs:
        """srml.ingest around `build(x64)`: staged's, and run's second staging.
        While it lasts the job keeps the landing journal of the pieces its
        staging sends (_LINK; stage_dense_batches)."""
        outer, _LINK.open = _LINK.open, True
        try:
            with profiling.span("srml.ingest"):
                inputs = build(x64)
                get_logger(type(self.estimator)).info(
                    "Invoking TPU fit: %d rows x %d cols on %d-device mesh",
                    inputs.n_rows, inputs.n_cols, inputs.mesh.devices.size,
                )
        finally:
            _LINK.open = outer
        return inputs

    @contextlib.contextmanager
    def staged(self, build: Callable[[Callable[[Any], None]], FitInputs]) -> Iterator[FitInputs]:
        """srml.ingest around `build(x64)`, then its FitInputs for the solver's
        part of the job (the body), which runs under sanitize_scope.

        float64 fits genuinely run in float64 (reference core.py:363-401
        keeps f64 end-to-end): without x64, jax.device_put silently
        canonicalizes f64 -> f32.  The x64 scope must cover BOTH the uploads
        and the solver (trace-time dtypes), so the builder calls `x64(dtype)`
        when it knows the dtype (a rank without rows learns it from the
        others) and the scope lasts to the end of the body; it recompiles
        the kernels for f64, which TPUs execute via (slower) emulation."""
        from .sanitize import sanitize_scope

        with contextlib.ExitStack() as scope:
            held = [self._ingest(build, lambda dtype: scope.enter_context(_maybe_x64(dtype)))]
            with sanitize_scope():
                yield held.pop()        # the body's alone: a job that stages again frees the table first

    def run(self, build: Callable[..., FitInputs], fit_func: FitFunc, params: Dict[str, Any]) -> Any:
        """The two middle steps of a fit, in the order every launcher runs
        them: srml.ingest, then srml.fit around the fit function.

        Only here do both the staging and the fit stand ready to run again,
        so only here is the view rule's proof deferred (_PROOFS.batches is a
        list while the job runs: _build_fit_inputs hands it to the
        extraction).  Batches go up on admission; the proof is settled by the
        fit function's fetch_fit_result while the solver runs, and once more
        after it returns, for a fit function that never fetches.  A refuted
        batch voids the fit: nothing it computed is returned, the frame is
        staged again with the rule asked at once (the refused batches
        stacked, as the rule's refusal always went) inside the same x64 and
        sanitize scopes, and the fit function runs again."""
        outer, refuted = _PROOFS.batches, _PROOFS.refuted
        _PROOFS.batches = []
        try:
            with self.staged(build) as inputs:
                with profiling.span("srml.fit"):
                    result = fit_func(inputs, params)
                    if _PROOFS.batches:
                        settle_deferred_proofs()
                if _PROOFS.refuted == refuted:
                    return result
                del inputs, result
                _PROOFS.batches = None
                profiling.incr_counter("ingest.refits")
                get_logger(type(self.estimator)).warning(
                    "%d feature batch(es) admitted as views of their buffers failed the view "
                    "rule's proof; staging again cell by cell and fitting again",
                    _PROOFS.refuted - refuted,
                )
                inputs = self._ingest(build, lambda dtype: None)
                with profiling.span("srml.fit"):
                    return fit_func(inputs, params)
        finally:
            _PROOFS.batches, _PROOFS.refuted = outer, refuted

    @contextlib.contextmanager
    def finish(self) -> Iterator[None]:
        """srml.finish: the job's phase seconds and its TelemetrySnapshot (the
        counters' deltas since prepare), then the launcher's packing of what
        it returns (the body)."""
        with profiling.span("srml.finish"):
            self.phase_times = profiling.phase_times()
            self.snapshot = profiling.TelemetrySnapshot.capture(self.baseline, rank=self.rank)
            yield


@contextlib.contextmanager
def fit_job(estimator: Any, kind: str = "fit", rank: Optional[int] = None) -> Iterator[FitJob]:
    """A FitJob for `estimator`, tagged <kind>-<Estimator>[-rank<r>].
    watch.flight_scope: an unhandled exception anywhere in the job dumps the
    always-on flight ring (with the innermost failing span) to SRML_TRACE_DIR
    before propagating — the crash-time counterpart of the trace session,
    which only exports on success.  SRML_PROFILE captures <dir>/<Estimator>."""
    from . import watch

    name = type(estimator).__name__
    tag = f"{kind}-{name}" + ("" if rank is None else f"-rank{rank}")
    with watch.flight_scope(tag), profiling.trace_session(tag), profiling.maybe_trace(name):
        yield FitJob(estimator, rank or 0)


class _TpuCaller(_TpuParams):
    """Shared ingest + fit-dispatch (reference _CumlCaller core.py:327-647)."""

    def _use_dtype(
        self, df: DataFrame, input_col: Optional[str], input_cols: Optional[List[str]]
    ) -> np.dtype:
        dev = getattr(df, "_device_features", None)
        if dev is not None:
            return np.dtype(dev[0].dtype)
        if self._float32_inputs:
            return np.dtype(np.float32)
        # float32_inputs=False preserves the input dtype (reference
        # core.py:363-401 keeps f64 data in f64 and f32 in f32)
        for part in df.partitions:
            if len(part) == 0:
                continue
            if input_col is not None:
                block = _partition_feature_block(part, input_col)
                if block is not None:
                    dt = block.dtype  # also covers sparse CSR blocks, whose
                    # placeholder column would misreport int64
                else:
                    dt = np.asarray(part[input_col].iloc[0]).dtype
            else:
                assert input_cols is not None
                dt = np.result_type(*(part[c].dtype for c in input_cols))
            if np.issubdtype(dt, np.floating):
                return np.dtype(dt)
            break
        return np.dtype(np.float64)

    def _extract_partition_features(
        self,
        part: pd.DataFrame,
        input_col: Optional[str],
        input_cols: Optional[List[str]],
        dtype: np.dtype,
        deferred: Optional[List[Tuple[np.ndarray, np.dtype]]] = None,
    ) -> np.ndarray:
        block = (
            _partition_feature_block(part, input_col) if input_col is not None else None
        )
        owed = len(deferred) if deferred is not None else 0
        feats = materialize_feature_block(
            block,
            part,
            input_col,
            input_cols,
            dtype,
            densify_sparse=not self._supports_sparse_input,
            on_densify=lambda: get_logger(type(self)).warning(
                "%s has no sparse path; densifying the CSR partition",
                type(self).__name__,
            ),
            on_cells=lambda viewed: profiling.incr_counter(
                "ingest.view_batches" if viewed else "ingest.stacked_batches"
            ),
            deferred=deferred,
        )
        if deferred is not None and len(deferred) > owed:
            profiling.incr_counter("ingest.deferred_batches")
        return feats

    def _fit_label_col(self) -> Optional[str]:
        """Column to extract as ``FitInputs.y``, or None.  Supervised
        estimators always consume their labelCol; optionally-supervised
        estimators (UMAP, reference umap.py:939-947) override this to opt in
        only when the user set one."""
        if isinstance(self, _TpuEstimatorSupervised) and self.hasParam("labelCol"):
            return self.getOrDefault("labelCol")
        return None

    def _fit_weight_col(self) -> Optional[str]:
        """Column the row weights come from, or None (unset: every row 1)."""
        if self.hasParam("weightCol") and self.isSet("weightCol"):
            return self.getOrDefault("weightCol")
        return None

    def _pre_process_data(
        self, df: DataFrame, deferred: Optional[List[Tuple[np.ndarray, np.dtype]]] = None
    ) -> Tuple[Iterator[Any], Optional[List[np.ndarray]], Optional[List[np.ndarray]], np.dtype]:
        """Per-partition (features, label, weight) numpy extraction with dtype
        casting (reference core.py:344-422 + supervised label cast :918-952).
        The labels and weights are lists, a vector a partition; the features
        are an ITERATOR over the partitions' matrices, each extracted (inside
        a srml.ingest.extract span) when the consumer asks for it, so that a
        batch-wise consumer holds one at a time.  `deferred` is
        utils.materialize_feature_block's: None, as every caller but
        _build_fit_inputs leaves it, asks the view rule at each batch."""
        input_col, input_cols = self._get_input_columns()
        dtype = self._use_dtype(df, input_col, input_cols)
        label_col, weight_col = self._fit_label_col(), self._fit_weight_col()
        ldtype = _label_dtype(dtype)

        def column(name: Optional[str]) -> Optional[List[np.ndarray]]:
            if name is None:
                return None
            return [np.asarray(part[name].to_numpy(), dtype=ldtype) for part in df.partitions]

        def features() -> Iterator[Any]:
            for part in df.partitions:
                with profiling.span("srml.ingest.extract"):
                    feats = self._extract_partition_features(part, input_col, input_cols, dtype, deferred)
                yield feats

        return features(), column(label_col), column(weight_col), dtype

    def _build_fit_inputs(
        self, df: DataFrame, x64: Callable[[Any], None] = lambda dtype: None, mesh: Any = None
    ) -> FitInputs:
        """The frame's rows as FitInputs on this process's mesh (`mesh`: the
        executor's one-rank session hands its own; default
        get_mesh(num_workers)).  `x64` is FitJob.staged's: called with the
        dtype the fit computes in as soon as it is known, before anything
        goes up."""
        dev = getattr(df, "_device_features", None)
        if dev is not None:
            x64(dev[0].dtype)
            return self._build_fit_inputs_device(df, dev)
        # inside FitJob.run the view rule's proof may wait for the solver
        feats, labels, weights, dtype = self._pre_process_data(df, _PROOFS.batches)
        x64(dtype)
        partition_rows = [len(p) for p in df.partitions]
        n_rows = sum(partition_rows)
        if n_rows == 0:
            raise RuntimeError("Dataset is empty; cannot fit")
        mesh = mesh if mesh is not None else get_mesh(self.num_workers)

        # Device-resident input cache (single slot).  Repeated fits over the
        # same immutable block-backed DataFrame — fitMultiple, repeated
        # fit() calls in notebooks/benchmarks — reuse the sharded device
        # arrays instead of re-streaming GBs over PCIe/host link each fit.
        # This is the TPU analog of the reference riding spark-rapids'
        # GPU-resident columnar data (its executors hand cuML device-side
        # arrays when the plugin has the DataFrame cached on GPU).  Only
        # fits whose feature arrays ARE the DataFrame's zero-copy blocks
        # are cached (their ids are stable and pinned by the df itself);
        # partitions without one (pandas or Arrow batches, multi_cols, CV
        # fold splits) are extracted anew every fit and are never stored.
        # clear_fit_cache() / DataFrame.unpersist() releases the slot.
        # Only the FEATURE arrays are cached: labels/weights are re-extracted
        # per fit (they are O(N) host arrays whose identity is NOT stable —
        # to_numpy() returns fresh objects, and labelCol/weightCol can change
        # between fits over the same cached features).
        input_col, _input_cols = self._get_input_columns()
        blocks = [
            _partition_feature_block(p, input_col) if input_col is not None else None
            for p in df.partitions if len(p)
        ]
        sparse = self._supports_sparse_input and any(hasattr(b, "tocsr") for b in blocks)
        cacheable = all(
            b is not None and (sparse if hasattr(b, "tocsr") else b.dtype == dtype)
            for b in blocks
        )
        cache_key = (tuple(id(b) for b in blocks), str(dtype), id(mesh)) if cacheable else None
        cached = _FIT_INPUT_CACHE.get("slot")
        if cached is not None and cached[0] == cache_key:
            Xs, _host_refs = cached[1]
            profiling.incr_counter("ingest.cache_hit")
        else:
            # free the previous slot's device arrays BEFORE allocating the
            # new dataset so peak HBM is one dataset, not two
            _FIT_INPUT_CACHE.pop("slot", None)
            # ingest.staged counts DATASET uploads: the batched sweep's
            # "one staged dataset per sweep" contract is gated on it
            profiling.incr_counter("ingest.staged")
            if sparse:
                # sparse ingest: CSR partitions -> one padded ELL pair, row-
                # sharded like a dense block (ops/sparse.py).  No densification
                # at any point; nnz is the memory footprint.
                import scipy.sparse as sp

                from .ops.sparse import ell_device_from_scipy

                nonempty = [f for f in feats if f.shape[0] > 0]
                csr = sp.vstack(nonempty).tocsr() if len(nonempty) > 1 else nonempty[0]
                Xs = _device_put_counted(
                    lambda: ell_device_from_scipy(csr, dtype=dtype, mesh=mesh)
                )
            else:
                Xs = stage_dense_batches(feats, n_rows, mesh)
            if cacheable:
                _FIT_INPUT_CACHE["slot"] = (cache_key, (Xs, blocks))
        n_cols = int(Xs.shape[1])
        y_np = np.concatenate(labels) if labels is not None else None
        w_np = np.concatenate(weights) if weights is not None else None
        ws, ys = stage_mask_and_labels(
            n_rows, Xs.shape[0], dtype, y_np, w_np,
            lambda buf: jax.device_put(buf, data_sharding(mesh)),
        )
        pdesc = PartitionDescriptor.build(partition_rows, n_cols)
        return FitInputs(
            X=Xs,
            weight=ws,
            y=ys,
            n_rows=n_rows,
            n_cols=n_cols,
            mesh=mesh,
            pdesc=pdesc,
            dtype=dtype,
            host_y=y_np,
            host_w=w_np,
        )

    def _build_fit_inputs_device(self, df: DataFrame, dev: Any) -> FitInputs:
        """FitInputs straight from a DataFrame.from_device feature array:
        no feature extraction, no upload.  Labels/weights still come from
        the (host) partitions; padded rows are masked through the weight
        vector exactly like the host-ingest path.  The built inputs are
        cached ON THE FRAME (keyed by the consuming label/weight columns),
        so repeated fits skip the per-fit label/mask device_puts the way
        the host path's input cache does."""
        Xs, n_rows, n_cols, _fcol = dev
        if is_tall_table(Xs) and not self._supports_tall_input:
            raise TypeError(
                f"{type(self).__name__} has no pass over a feature-major table "
                "(ops.tall.TallMatrix), and nothing densifies or transposes a whole "
                "table behind a fit: hand it a row-major (rows, columns) device array "
                "or a host frame (KMeans with initMode='random' fits a TallMatrix)"
            )
        dtype = np.dtype(Xs.dtype)
        mesh = get_mesh(self.num_workers)
        label_col, weight_col = self._fit_label_col(), self._fit_weight_col()
        cache_key = (label_col, weight_col, id(mesh))
        cached = getattr(df, "_device_fit_inputs", None)
        if cached is not None and cached[0] == cache_key:
            return cached[1]
        ldtype = _label_dtype(dtype)

        def column(name: Optional[str]) -> Optional[np.ndarray]:
            if name is None:
                return None
            return np.concatenate(
                [np.asarray(p[name].to_numpy(), dtype=ldtype) for p in df.partitions]
            )

        y_np, w_np = column(label_col), column(weight_col)
        ws, ys = stage_mask_and_labels(
            n_rows, Xs.shape[0], dtype, y_np, w_np,
            lambda buf: jax.device_put(buf, data_sharding(mesh)),
        )
        inputs = FitInputs(
            X=Xs,
            weight=ws,
            y=ys,
            n_rows=n_rows,
            n_cols=n_cols,
            mesh=mesh,
            pdesc=PartitionDescriptor.build([n_rows], n_cols),
            dtype=dtype,
            host_y=y_np,
            host_w=w_np,
        )
        df._device_fit_inputs = (cache_key, inputs)
        return inputs

    def _call_tpu_fit_func(
        self,
        dataset: Any,
        paramMaps: Optional[List[Dict[Param, Any]]] = None,
        finish: Callable[[Any], Any] = lambda result: result,
    ) -> Any:
        """Dispatch one (or a batch of) fits on the device mesh (reference
        _call_cuml_fit_func core.py:488-640, single data load for all param
        maps as in _fit_internal core.py:723-752).  Returns `finish` of the
        model-attribute dict (or the list of them, one a param map):
        _fit_internal passes the model construction, which so runs inside
        the driver-local path's last step span.

        A live pyspark DataFrame routes through the Spark barrier stage so
        training happens INSIDE the executors over a pod-wide jax.distributed
        mesh — the dataset is never collected to the driver.  Set
        SRML_SPARK_COLLECT=1 to force the old driver-local collect path
        (single TPU-VM notebooks where the driver owns the chips)."""
        if _use_executor_path(dataset):
            from .spark.adapter import barrier_fit_estimator

            # driver-side input-column check BEFORE launching the barrier
            # stage (pyspark DataFrames expose .columns, which is all
            # _validate_parameters reads) — a missing column must fail here,
            # not as an opaque executor traceback
            self._validate_parameters(dataset)
            extra = (
                [self._paramMap_to_tpu_overrides(pm) for pm in paramMaps]
                if paramMaps is not None
                else None
            )
            results = barrier_fit_estimator(self, dataset, extra_params=extra)
            # the executors' merged telemetry snapshot rides the result wire
            # (parallel/runner attaches it); the driver-side phase view comes
            # from it — on live Spark the fit never ran on this thread
            telem = results[0].get(TELEMETRY_ATTR) if results else None
            self._last_fit_phase_times = (
                profiling.TelemetrySnapshot.from_dict(telem).phase_seconds()
                if telem
                else {}
            )
            return finish(results if paramMaps is not None else results[0])
        with fit_job(self) as job:
            with job.prepare():
                df = as_dataframe(dataset)
                self._validate_parameters(df)
                extra_params = None
                if paramMaps is not None:
                    extra_params = [
                        self._paramMap_to_tpu_overrides(pm) for pm in paramMaps
                    ]
                fit_func = self._get_tpu_fit_func(df, extra_params)
            build = functools.partial(self._build_fit_inputs, df)
            result = job.run(build, fit_func, dict(self._tpu_params))
            with job.finish():
                self._last_fit_phase_times = job.phase_times
                # telemetry rides the SAME attribute dicts the executor path
                # ships, so _fit_internal attaches model.fit_telemetry()
                # uniformly (the snapshot is shared across a single-pass
                # multi-model fit — one data load, one solver pass, one set
                # of phase timers)
                for r in result if isinstance(result, list) else [result]:
                    r[TELEMETRY_ATTR] = job.snapshot.to_dict()
                return finish(result)

    def _paramMap_to_tpu_overrides(self, paramMap: Dict[Param, Any]) -> Dict[str, Any]:
        mapping = self._param_mapping()
        overrides: Dict[str, Any] = {}
        for param, value in paramMap.items():
            solver = mapping.get(param.name)
            if solver:
                value_mapping = self._param_value_mapping()
                if solver in value_mapping:
                    mapped = value_mapping[solver](value)
                    if mapped is None:
                        raise ValueError(
                            f"Value '{value}' for param '{param.name}' is not supported on TPU"
                        )
                    value = mapped
                overrides[solver] = value
            elif solver is None and param.name in mapping:
                raise ValueError(f"Param '{param.name}' unsupported on TPU")
        return overrides

    def _validate_parameters(self, df: DataFrame) -> None:
        input_col, input_cols = self._get_input_columns()
        cols = df.columns
        missing = [
            c for c in ([input_col] if input_col else input_cols or []) if c not in cols
        ]
        if missing:
            raise ValueError(f"Input column(s) {missing} not found in dataset {cols}")

    # -- abstract ----------------------------------------------------------
    @abstractmethod
    def _get_tpu_fit_func(
        self, dataset: DataFrame, extra_params: Optional[List[Dict[str, Any]]] = None
    ) -> FitFunc:
        raise NotImplementedError


class _FitMultipleIterator:
    """Thread-safe (index, model) iterator over single-pass multi-model fits
    (reference core.py:649-721)."""

    def __init__(self, fit_multiple_models: Callable[[], List["_TpuModel"]], num_models: int):
        self.fit_multiple_models = fit_multiple_models
        self.num_models = num_models
        self.counter = 0
        self.lock = threading.Lock()
        self.models: Optional[List[_TpuModel]] = None

    def __iter__(self) -> "_FitMultipleIterator":
        return self

    def __next__(self) -> Tuple[int, "_TpuModel"]:
        with self.lock:
            index = self.counter
            if index >= self.num_models:
                raise StopIteration()
            self.counter += 1
            if self.models is None:
                self.models = self.fit_multiple_models()
        return index, self.models[index]


class _TpuEstimator(_TpuCaller):
    """Base estimator (reference _CumlEstimator core.py:717-916)."""

    # Whether this estimator's fit function runs correctly over a
    # multi-process (nranks > 1) mesh: it must never host-fetch the
    # row-sharded FitInputs arrays (np.asarray on an array spanning
    # non-addressable devices raises).  Estimators that do host-side label
    # discovery / binning mark themselves False until those steps move on
    # device or behind a gather.
    _supports_multicontroller_fit = True

    def __init__(self) -> None:
        super().__init__()
        self.logger = get_logger(type(self))

    # -- public API --------------------------------------------------------
    def fit(
        self, dataset: Any, params: Optional[Union[Dict[Param, Any], List[Dict[Param, Any]]]] = None
    ) -> Any:
        if isinstance(params, (list, tuple)):
            return [m for _, m in sorted(self.fitMultiple(dataset, list(params)))]
        if isinstance(params, dict) and params:
            return self.copy(params)._fit(dataset)
        return self._fit(dataset)

    def _fit(self, dataset: Any) -> "_TpuModel":
        return self._fit_internal(dataset, None)[0]

    def fitMultiple(
        self, dataset: Any, paramMaps: List[Dict[Param, Any]]
    ) -> Iterator[Tuple[int, "_TpuModel"]]:
        if self._enable_fit_multiple_in_single_pass():
            return _FitMultipleIterator(
                lambda: self._fit_internal(dataset, paramMaps), len(paramMaps)
            )
        return iter(
            [(i, self.copy(pm)._fit(dataset)) for i, pm in enumerate(paramMaps)]
        )

    def _fit_internal(
        self, dataset: Any, paramMaps: Optional[List[Dict[Param, Any]]]
    ) -> List["_TpuModel"]:
        def models_of(results: Any) -> List["_TpuModel"]:
            if paramMaps is None:
                results = [results] if isinstance(results, dict) else list(results)
                assert len(results) == 1
            models = []
            for i, attrs in enumerate(results if isinstance(results, list) else [results]):
                pm = paramMaps[i] if paramMaps is not None and i < len(paramMaps) else None
                models.append(self._materialize_model(attrs, pm))
            return models

        return self._call_tpu_fit_func(dataset, paramMaps, models_of)

    def _materialize_model(
        self, attrs: Dict[str, Any], paramMap: Optional[Dict[Param, Any]] = None
    ) -> "_TpuModel":
        """Model-attribute dict -> model, with the ONE materialization
        bookkeeping every fit route shares (_fit_internal's loop and the
        batched sweep's tuning._materialize_sweep_models): telemetry popped
        off the wire dict onto model._fit_telemetry, copied estimator
        values, synced solver params, and the param map's own grid values
        set through _set_params — so a sweep sub-model is indistinguishable
        from its sequential twin by construction, not by hand-synced
        copies."""
        telem = attrs.pop(TELEMETRY_ATTR, None)
        model = self._create_model(attrs)
        if telem is not None:
            model._fit_telemetry = profiling.TelemetrySnapshot.from_dict(telem)
        self._copyValues(model)
        model._tpu_params.update(self._tpu_params)
        model._num_workers = self._num_workers
        model._float32_inputs = self._float32_inputs
        if paramMap is not None:
            for p, v in paramMap.items():
                if model.hasParam(p.name):
                    # _set_params keeps the Spark param and the solver
                    # param dict in sync (raw set() would desync them)
                    model._set_params(**{p.name: v})
        return model

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        return False

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        return False

    # -- batched hyperparameter sweep (srml-sweep) -------------------------
    def _supportsBatchedSweep(
        self, df: DataFrame, paramMaps: List[Dict[Param, Any]], evaluator: Any
    ) -> bool:
        """Whether a CrossValidator sweep over `paramMaps` can run as the
        one-dispatch batched engine (docs/tuning_engine.md): every grid
        param must map onto a lane-batchable solver knob and the evaluator
        must ride the single-pass transform-evaluate.  Estimators with
        vmappable solvers (the GLMs) override this; the default keeps the
        classic per-fold loop."""
        return False

    def _fitBatchedSweep(
        self,
        inputs: FitInputs,
        paramMaps: List[Dict[Param, Any]],
        n_folds: int,
        seed: int,
        evaluator: Any = None,
    ) -> Tuple[List[List[Dict[str, Any]]], Optional[List[List[Any]]]]:
        """Fit every (fold, candidate) pair over ONE staged dataset (folds
        as weight masks, candidates as kernel lanes); returns n_folds lists
        of per-candidate model-attribute dicts and, beside them, None or the
        held-out metric partials in the same order.  CrossValidator passes
        `evaluator` for a device-resident frame (DataFrame.from_device: its
        rows exist nowhere else), and the estimator then scores every model
        on its fold's rows where the staged table lies, under the fold ids
        the training masks came from: the partials are the mergeable metric
        objects the host route's transform-evaluate builds (metrics/).  Only
        called when _supportsBatchedSweep returned True."""
        raise NotImplementedError

    def _sweep_sparse_input(self, df: DataFrame) -> bool:
        """True when any partition carries a sparse CSR feature block, or a
        device-resident frame's table is an EllMatrix — the batched sweep
        keeps those off its route (masked-fold ELL statistics are a
        documented non-goal, docs/tuning_engine.md)."""
        dev = getattr(df, "_device_features", None)
        if dev is not None:
            from .ops.sparse import EllMatrix

            return isinstance(dev[0], EllMatrix)
        input_col, _ = self._get_input_columns()
        if input_col is None:
            return False
        for part in df.partitions:
            block = _partition_feature_block(part, input_col)
            if block is not None and hasattr(block, "tocsr"):
                return True
        return False

    # -- abstract ----------------------------------------------------------
    @abstractmethod
    def _create_model(self, result: Dict[str, Any]) -> "_TpuModel":
        raise NotImplementedError

    # -- persistence -------------------------------------------------------
    def write(self) -> "_TpuEstimatorWriter":
        return _TpuEstimatorWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_TpuEstimatorReader":
        return _TpuEstimatorReader(cls)

    @classmethod
    def load(cls, path: str) -> "_TpuEstimator":
        return cls.read().load(path)


class _TpuEstimatorSupervised(_TpuEstimator):
    """Estimator consuming (features, label[, weight]) (reference
    _CumlEstimatorSupervised core.py:918-952)."""


class _TpuModel(_TpuParams):
    """Base model/transformer (reference _CumlModel core.py:954-1374)."""

    def __init__(self, **model_attributes: Any) -> None:
        super().__init__()
        self._model_attributes = model_attributes
        self._initialize_tpu_params()
        self.logger = get_logger(type(self))

    def _get_model_attributes(self) -> Dict[str, Any]:
        return self._model_attributes

    def fit_telemetry(self):
        """TelemetrySnapshot of the fit that produced this model — phase
        rollups, counter deltas, per-rank merge — on BOTH the local and the
        live-Spark (barrier executor) paths.  None for models built by
        hand, loaded from disk, or combined (telemetry describes one fit
        session, not a persisted artifact)."""
        return getattr(self, "_fit_telemetry", None)

    @classmethod
    def _construct(cls, attrs: Dict[str, Any]) -> "_TpuModel":
        """Rebuild a model from its (decoded) attribute dict.  Override
        when _get_model_attributes carries entries that are not
        constructor arguments (see _construct_model)."""
        return cls(**attrs)

    @property
    def hasSummary(self) -> bool:
        return False

    # -- transform ---------------------------------------------------------
    def transform(self, dataset: Any) -> DataFrame:
        """Column-appending inference (reference _CumlModelWithColumns._transform
        core.py:1277-1361): original columns are preserved, output columns
        named by the *Col params are appended.

        A live pyspark DataFrame runs partition-wise ON THE EXECUTORS via
        mapInPandas with the model riding the closure — the dataset is never
        collected to the driver (reference core.py:1277-1361; UMAP's
        distributed inference, umap.py:1147-1224).  SRML_SPARK_COLLECT=1
        forces the old driver-local collect path."""
        if _use_executor_path(dataset):
            from .spark.adapter import executor_transform

            return executor_transform(self, dataset)
        from .ops.precompile import ensure_compile_cache

        ensure_compile_cache()
        df = as_dataframe(dataset)
        if getattr(df, "_device_features", None) is not None:
            raise NotImplementedError(
                "DataFrame.from_device frames are fit-input only (their "
                "features column is a placeholder); transform host or "
                "pyspark frames instead"
            )
        input_col, input_cols = self._get_input_columns()
        dtype = self._transform_dtype(self._model_attributes.get("dtype"))
        transform_fn = self._get_tpu_transform_func(df)
        out_parts: List[Optional[pd.DataFrame]] = []
        out_col_names: Optional[List[str]] = None
        for part in df.partitions:
            if len(part) == 0:
                out_parts.append(None)  # filled once output columns are known
                continue
            block = (
                _partition_feature_block(part, input_col)
                if input_col is not None
                else None
            )
            # sparse partitions stay CSR when the model has a sparse path
            # (its transform converts CSR -> ELL)
            feats = materialize_feature_block(
                block,
                part,
                input_col,
                input_cols,
                dtype,
                densify_sparse=not self._supports_sparse_input,
            )
            new_part = part.copy()
            outputs = transform_fn(feats)
            for name, values in outputs.items():
                if isinstance(values, np.ndarray) and values.ndim == 2:
                    new_part[name] = list(values)
                else:
                    new_part[name] = values
            if out_col_names is None:
                out_col_names = list(outputs.keys())
            out_parts.append(new_part)
        # empty partitions get the same output columns (from the first
        # non-empty partition, falling back to the *Col params) so all
        # partitions share one schema
        if out_col_names is None:
            out_col_names = self._out_columns()
        filled = []
        for part, orig in zip(out_parts, df.partitions):
            if part is None:
                part = orig.copy()
                for name in out_col_names:
                    part[name] = []
            filled.append(part)
        return DataFrame(filled)

    def _out_columns(self) -> List[str]:
        cols = []
        for p in ("predictionCol", "probabilityCol", "rawPredictionCol", "outputCol"):
            if self.hasParam(p) and self.isDefined(p):
                cols.append(self.getOrDefault(p))
        return cols

    _OUT_COLUMN_DDL = {
        "predictionCol": "double",
        "probabilityCol": "array<double>",
        "rawPredictionCol": "array<double>",
        "outputCol": "array<double>",
    }

    def _out_schema_fields(self) -> List[Tuple[str, str]]:
        """(column name, Spark DDL type) per appended output column — the
        executor-transform mapInPandas schema (the reference's typed
        prediction columns, core.py:1294-1361).  Models whose outputs
        deviate from the defaults override _OUT_COLUMN_DDL."""
        return [
            (self.getOrDefault(p), self._OUT_COLUMN_DDL[p])
            for p in ("predictionCol", "probabilityCol", "rawPredictionCol", "outputCol")
            if self.hasParam(p) and self.isDefined(p)
        ]

    # -- abstract ----------------------------------------------------------
    @abstractmethod
    def _get_tpu_transform_func(self, dataset: DataFrame) -> TransformFunc:
        raise NotImplementedError

    # -- online serving -----------------------------------------------------
    def _serving_entry(self, mesh: Any = None):
        """ServingEntry for the online inference engine (serving/engine.py):
        a padded-batch dispatch through the AOT executable cache plus a
        bucket warm hook.  Served model classes override this; the base
        raises so serving.ModelServer gives an actionable error for models
        with no online path."""
        raise NotImplementedError(
            f"{type(self).__name__} has no serving entry; servable models "
            "are KMeans/PCA/LinearRegression/LogisticRegression/"
            "RandomForest*/NearestNeighbors/ApproximateNearestNeighbors"
        )

    # -- multi-model -------------------------------------------------------
    @classmethod
    def _combine(cls, models: List["_TpuModel"]) -> "_TpuModel":
        raise NotImplementedError

    def _transformEvaluate(self, dataset: Any, evaluator: Any) -> List[float]:
        raise NotImplementedError

    # -- persistence -------------------------------------------------------
    def write(self) -> "_TpuModelWriter":
        return _TpuModelWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_TpuModelReader":
        return _TpuModelReader(cls)

    @classmethod
    def load(cls, path: str) -> "_TpuModel":
        return cls.read().load(path)


class _TpuModelWithPredictionCol(_TpuModel):
    """Model appending a predictionCol (reference core.py:1377-1387)."""

    def setPredictionCol(self, value: str) -> "_TpuModelWithPredictionCol":
        self._set_params(predictionCol=value)
        return self


# ---------------------------------------------------------------------------
# Persistence (reference core.py:139-226; model attrs as npz instead of the
# reference's JSON-in-text-file to keep large arrays binary and chunk-free)
# ---------------------------------------------------------------------------

_METADATA_FILE = "metadata.json"
_ARRAYS_FILE = "model_arrays.npz"
_ATTRS_FILE = "model_attrs.json"


def _params_metadata(instance: _TpuParams) -> Dict[str, Any]:
    return {
        "class": f"{type(instance).__module__}.{type(instance).__name__}",
        "uid": instance.uid,
        "paramMap": {p.name: _jsonable(v) for p, v in instance._paramMap.items()},
        "defaultParamMap": {p.name: _jsonable(v) for p, v in instance._defaultParamMap.items()},
        "tpu_params": {k: _jsonable(v) for k, v in instance._tpu_params.items()},
        "num_workers": instance._num_workers,
        "float32_inputs": instance._float32_inputs,
        "sparkRapidsMlTpuVersion": _version(),
    }


def _version() -> str:
    from .version import __version__

    return __version__


def _jsonable(v: Any) -> Any:
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return v


def _apply_params_metadata(meta: Dict[str, Any], instance: _TpuParams) -> None:
    for name, value in meta.get("defaultParamMap", {}).items():
        if instance.hasParam(name):
            instance._defaultParamMap[instance.getParam(name)] = value
    for name, value in meta.get("paramMap", {}).items():
        if instance.hasParam(name):
            instance.set(instance.getParam(name), value)
    instance._tpu_params = dict(meta.get("tpu_params", {}))
    instance._num_workers = meta.get("num_workers")
    instance._float32_inputs = meta.get("float32_inputs", True)
    instance.uid = meta.get("uid", instance.uid)


def _resolve_class(qualname: str) -> type:
    import importlib

    module, _, name = qualname.rpartition(".")
    return getattr(importlib.import_module(module), name)


class _TpuEstimatorWriter:
    def __init__(self, instance: _TpuEstimator):
        self.instance = instance

    def overwrite(self) -> "_TpuEstimatorWriter":
        return self

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, _METADATA_FILE), "w") as f:
            json.dump(_params_metadata(self.instance), f, indent=2)


class _TpuEstimatorReader:
    def __init__(self, cls: type):
        self.cls = cls

    def load(self, path: str) -> _TpuEstimator:
        with open(os.path.join(path, _METADATA_FILE)) as f:
            meta = json.load(f)
        cls = _resolve_class(meta["class"])
        est = cls()
        _apply_params_metadata(meta, est)
        return est


class _TpuModelWriter:
    def __init__(self, instance: _TpuModel):
        self.instance = instance

    def overwrite(self) -> "_TpuModelWriter":
        return self

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, _METADATA_FILE), "w") as f:
            json.dump(_params_metadata(self.instance), f, indent=2)
        arrays, attrs = {}, {}
        for k, v in self.instance._get_model_attributes().items():
            if isinstance(v, np.ndarray):
                arrays[k] = v
            elif isinstance(v, jax.Array):
                arrays[k] = np.asarray(v)
            else:
                attrs[k] = _jsonable(v)
        np.savez(os.path.join(path, _ARRAYS_FILE), **arrays)
        with open(os.path.join(path, _ATTRS_FILE), "w") as f:
            json.dump(attrs, f)


def _construct_model(cls: type, attrs: Dict[str, Any]) -> "_TpuModel":
    """Instantiate a model from decoded attributes via the class's
    _construct hook — model classes whose attribute dict carries
    NON-constructor entries (e.g. a combined multi-model's sub-model
    split) override it to pop and reattach them, keeping this layer
    model-agnostic."""
    return cls._construct(dict(attrs))


class _TpuModelReader:
    def __init__(self, cls: type):
        self.cls = cls

    def load(self, path: str) -> _TpuModel:
        with open(os.path.join(path, _METADATA_FILE)) as f:
            meta = json.load(f)
        cls = _resolve_class(meta["class"])
        with open(os.path.join(path, _ATTRS_FILE)) as f:
            attrs = json.load(f)
        npz = np.load(os.path.join(path, _ARRAYS_FILE), allow_pickle=False)
        for k in npz.files:
            attrs[k] = npz[k]
        model = _construct_model(cls, attrs)
        _apply_params_metadata(meta, model)
        return model


def load(path: str) -> Union[_TpuEstimator, _TpuModel]:
    """Load any saved estimator/model, resolving the class from metadata."""
    with open(os.path.join(path, _METADATA_FILE)) as f:
        meta = json.load(f)
    cls = _resolve_class(meta["class"])
    return cls.load(path)
