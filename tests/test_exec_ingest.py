"""The batch-wise ingest (PR 47): rows handed over as the pandas batches Spark's
Arrow conversion yields (7 batches of 64 rows x 24 columns, the last of 40), through
the executor's entry (parallel.runner.run_distributed_fit, rank 0 of 1) and through
the public fit of DataFrame(parts).

Held here: utils.feature_cells_view's rule both ways (a view where the cells lie one
after another in one buffer, None for everything else, each refusal by name);
core.stage_dense_batches' table against chipbench/references/logreg_exec.assemble, bit
for bit, on one device and on eight; the models against the from_numpy route's and the
reference's limits; the three faults a batch-wise ingest can commit in silence; the
counters; and the host memory the staging holds.

And the rule's two moments (PR 48): inside core.FitJob.run a batch goes up on
utils.admit_feature_cells' cheap test and the rule itself runs over it while the solver
does (core.settle_deferred_proofs, from fetch_fit_result); a refuted batch voids the fit,
which is staged and fitted again; everyone else asks the rule at once.
"""
import json
import tracemalloc

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import jax

from chipbench.references import logreg as ref
from chipbench.references import logreg_exec as ref_exec
from spark_rapids_ml_tpu import KMeans, LinearRegression, LogisticRegression, core, profiling, utils
from spark_rapids_ml_tpu.core import TELEMETRY_ATTR, stage_dense_batches
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.parallel.mesh import get_mesh
from spark_rapids_ml_tpu.parallel.runner import decode_attrs, run_distributed_fit
from spark_rapids_ml_tpu.utils import admit_feature_cells, feature_cells_view, materialize_feature_block

ROWS, COLS, BATCH = 424, 24, 64            # 6 batches of 64 and one of 40
F32 = np.dtype(np.float32)
with open("chipbench/subjects/logreg_exec.fit_loop.json") as _f:
    LIMITS = json.load(_f)["limits"]


def _table(seed=5, rows=ROWS, cols=COLS):
    rng = np.random.default_rng(seed)
    X = (rng.standard_normal((rows, cols)) * 10.0 ** -rng.uniform(0, 2, cols)).astype(np.float32)
    y = (X @ rng.standard_normal(cols) + 0.05 * rng.standard_normal(rows) > 0).astype(np.float32)
    return X, y


def _arrow_batches(X, y, batch=BATCH):
    """What an executor holds: each batch a pandas frame made by pyarrow's to_pandas()
    from a list<float32> column and a float32 label column of its own buffers."""
    out = []
    for lo in range(0, len(X), batch):
        rows = np.array(X[lo : lo + batch])         # the batch's own buffer
        offsets = pa.array(np.arange(0, (len(rows) + 1) * X.shape[1], X.shape[1], dtype=np.int32))
        lists = pa.ListArray.from_arrays(offsets, pa.array(rows.reshape(-1)))
        out.append(pa.table({"features": lists, "label": pa.array(y[lo : lo + batch])}).to_pandas())
    return out


def _estimator(family, **kw):
    if family == "logreg":
        return LogisticRegression(maxIter=20, tol=1e-30, regParam=1e-3, **kw)
    if family == "linreg":
        return LinearRegression(**kw)        # ordinary least squares
    return KMeans(k=4, maxIter=5, tol=0.0, initMode="random", seed=3, **kw)


def _vector(attrs):
    """A model's numbers as one vector, from the attribute dict or the model."""
    get = attrs.get if isinstance(attrs, dict) else lambda k: getattr(attrs, k, None)
    parts = [get(k) for k in ("coef_", "intercept_", "cluster_centers_")]
    return np.concatenate([np.ravel(np.asarray(p, np.float64)) for p in parts if p is not None])


def _spy(est, monkeypatch):
    """Records the FitInputs every fit of `est` is handed."""
    handed, make = [], est._get_tpu_fit_func

    def spying(df, extra_params=None):
        fit = make(df, extra_params)

        def fit_func(inputs, params):
            handed.append(inputs)
            return fit(inputs, params)

        return fit_func

    monkeypatch.setattr(est, "_get_tpu_fit_func", spying)
    return handed


# -- the rule ------------------------------------------------------------------

def test_an_arrow_made_column_is_one_view_of_its_buffer():
    X, y = _table()
    for pdf, lo in zip(_arrow_batches(X, y), range(0, ROWS, BATCH)):
        cells = pdf["features"].to_numpy()
        view = feature_cells_view(cells, F32)
        assert view is not None and view.shape == (len(pdf), COLS) and not view.flags.writeable
        assert np.shares_memory(view, cells[0]) and np.shares_memory(view, cells[-1])       # no copy
        assert view.tobytes() == X[lo : lo + BATCH].tobytes()
    # rows of one contiguous array are such cells too, whoever made them
    assert feature_cells_view(np.array(list(X) + [None], object)[:-1], F32).tobytes() == X.tobytes()
    assert feature_cells_view(np.array(list(X.astype(np.float64)) + [None], object)[:-1], np.dtype(np.float64)) is not None


def _refusals():
    X, y = _table(rows=BATCH)
    pdf = _arrow_batches(X, y)[0]
    cells = pdf["features"].to_numpy()
    swapped = cells.copy()
    swapped[[20, 21]] = swapped[[21, 20]]
    short = cells.copy()
    short[30] = short[30][:-1]
    strided = np.array(list(X[:, ::2]) + [None], object)[:-1]
    return {
        "separately_allocated_cells": np.array([np.array(c) for c in cells] + [None], object)[:-1],
        "a_filtered_frame": pdf[pdf["label"].index != 17]["features"].to_numpy(),
        "reversed_rows": pdf.iloc[::-1]["features"].to_numpy(),
        "two_middle_rows_swapped": swapped,            # first, second and last cell are where they were
        "float64_cells": pd.Series(list(X.astype(np.float64)))._values,
        "a_short_cell": short,
        "cells_with_a_stride": strided,
        "lists": pd.Series([list(map(float, r)) for r in X])._values,
        "a_numeric_column": np.arange(BATCH, dtype=np.float32),
        "an_empty_column": np.empty(0, object),
        "cells_of_no_width": np.array([X[0][:0], X[1][:0], None], object)[:-1],
        "matrix_cells": np.array([X[:2], X[2:4], None], object)[:-1],
    }


@pytest.mark.parametrize("name", sorted(_refusals()))
def test_the_rule_refuses(name):
    cells = _refusals()[name]
    assert feature_cells_view(cells, F32) is None
    if name in ("a_numeric_column", "an_empty_column", "cells_of_no_width", "matrix_cells"):
        return          # not a column of vectors at all: no fit to hold it to
    X, _y = _table(rows=BATCH)
    part, seen = pd.DataFrame({"features": cells}), []
    if name == "a_short_cell":      # the fallback says what it always said
        with pytest.raises(ValueError, match="same length"):
            materialize_feature_block(None, part, "features", None, F32, on_cells=seen.append)
        assert seen == [False]
        return
    stacked = materialize_feature_block(None, part, "features", None, F32, on_cells=seen.append)
    assert seen == [False] and stacked.flags.owndata and stacked.dtype == F32
    want = {
        "a_filtered_frame": np.delete(X, 17, axis=0), "reversed_rows": X[::-1], "cells_with_a_stride": X[:, ::2],
        "two_middle_rows_swapped": X[[*range(20), 21, 20, *range(22, BATCH)]],
    }.get(name, X)
    assert np.array_equal(stacked, want)


# -- the table, the models, the counters ------------------------------------------

@pytest.mark.parametrize("family", ["logreg", "linreg", "kmeans"])
@pytest.mark.parametrize("entry", ["executor", "public"])
def test_batches_become_the_reference_table_and_the_from_numpy_model(entry, family, monkeypatch):
    X, y = _table()
    parts = _arrow_batches(X, y)
    A, yA = ref_exec.assemble(parts)
    assert A.tobytes() == X.tobytes() and yA.tobytes() == y.tobytes()
    workers = {} if entry == "executor" else {"num_workers": 2}       # the executor's one rank owns all eight devices
    est = _estimator(family, **workers)
    handed = _spy(est, monkeypatch)
    jobs = []
    for _job in range(2):       # a second job is handed the same frames and stages them again
        if entry == "executor":
            (attrs,) = run_distributed_fit(est, parts, 0, 1)
            moved = profiling.TelemetrySnapshot.from_dict(attrs.pop(TELEMETRY_ATTR)).counters
            jobs.append((_vector(decode_attrs(attrs)), moved))
        else:
            model = est.fit(DataFrame(parts))
            jobs.append((_vector(model), model.fit_telemetry().counters))
    devices = jax.device_count() if entry == "executor" else 2
    n_pad = -(-ROWS // devices) * devices
    for inputs, (theta, moved) in zip(handed, jobs):
        table = np.asarray(inputs.X)
        assert table.shape == (n_pad, COLS) and table[:ROWS].tobytes() == A.tobytes() and not table[ROWS:].any()
        assert np.array_equal(np.asarray(inputs.weight), np.r_[np.ones(ROWS), np.zeros(n_pad - ROWS)])
        if family != "kmeans":
            assert np.asarray(inputs.y)[:ROWS].tobytes() == yA.tobytes()
        vectors = 1 if family == "kmeans" else 2
        assert moved["ingest.rows"] == ROWS and moved["ingest.batches"] == moved["ingest.view_batches"] == 7
        assert moved["ingest.staged"] == 1 and "ingest.cache_hit" not in moved and "ingest.stacked_batches" not in moved
        # every valid row once, the padding a device's last rows end in, the vectors
        assert A.nbytes + vectors * n_pad * 4 <= moved["ingest.h2d_bytes"] <= (n_pad * COLS + vectors * n_pad) * 4
        assert np.array_equal(theta, jobs[0][0])
    # the from_numpy route (a block in .attrs, one partition): the same table, so the same model
    direct = _vector(_estimator(family, num_workers=devices).fit(DataFrame.from_numpy(X, y=None if family == "kmeans" else y)))
    np.testing.assert_allclose(jobs[0][0], direct, rtol=0, atol=0)
    if family == "logreg":      # and the reference's, by the cell's own numbers and limits
        Xd, yd = ref_exec.device_table(A, yA, jax.devices()[0])
        f = ref.objective(Xd, yd, 1e-3)
        theta_ref, iters = ref.lbfgs(f, COLS + 1, 20)
        assert iters == 20 and ref.score_gap(Xd[:256], jobs[0][0], theta_ref) <= LIMITS["score_gap"]
        assert abs(f(jobs[0][0])[0] - f(theta_ref)[0]) / f(theta_ref)[0] <= LIMITS["objective_gap"]
    elif family == "linreg":
        Z = np.c_[A.astype(np.float64), np.ones(ROWS)]
        np.testing.assert_allclose(jobs[0][0], np.linalg.lstsq(Z, yA.astype(np.float64), rcond=None)[0], rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("how", ["stacked", "float64_cells", "one_view_of_many", "mixed"])
def test_batches_the_rule_refuses_are_stacked_into_the_same_table(how):
    """Fallback batches go through the same staging, batch by batch."""
    X, y = _table()
    parts = _arrow_batches(X, y)
    if how == "stacked":
        parts = [pd.DataFrame({"features": [np.array(c) for c in p["features"]], "label": p["label"]}) for p in parts]
    elif how == "float64_cells":
        parts = [pd.DataFrame({"features": list(np.stack(list(p["features"])).astype(np.float64)), "label": p["label"]}) for p in parts]
    elif how == "one_view_of_many":
        parts = [p.iloc[::-1].reset_index(drop=True) if i == 3 else p for i, p in enumerate(parts)]
    else:
        parts[2] = pd.DataFrame({"features": [np.array(c) for c in parts[2]["features"]], "label": parts[2]["label"]})
    A, yA = ref_exec.assemble(parts)
    model = LogisticRegression(maxIter=10, tol=1e-30, regParam=1e-3, num_workers=1).fit(DataFrame(parts))
    moved = model.fit_telemetry().counters
    viewed = {"stacked": 0, "float64_cells": 0, "one_view_of_many": 6, "mixed": 6}[how]
    assert moved.get("ingest.view_batches", 0) == viewed and moved.get("ingest.stacked_batches", 0) == 7 - viewed
    assert moved["ingest.rows"] == ROWS and moved["ingest.batches"] == 7
    want = LogisticRegression(maxIter=10, tol=1e-30, regParam=1e-3, num_workers=1).fit(DataFrame.from_numpy(A.astype(np.float32), y=yA))
    assert np.array_equal(_vector(model), _vector(want))


@pytest.mark.parametrize("devices", [1, 2, 8])
@pytest.mark.parametrize("rows", [[64] * 6 + [40], [40, 0, 64, 1, 319], [5], [3, 2, 4]])
def test_the_staged_table_is_the_batches_in_order(devices, rows):
    """Batches that end inside a device's rows, span several devices, are empty or are
    fewer than the devices: the table is the rows in order and the padding is zeros."""
    n = sum(rows)
    X, _y = _table(rows=n)
    bounds = np.cumsum([0] + rows)
    mesh = get_mesh(devices)
    before = profiling.counters()
    table = stage_dense_batches((X[a:b] for a, b in zip(bounds, bounds[1:])), n, mesh)
    moved = profiling.counter_deltas(before)
    share = -(-n // devices)
    assert table.shape == (share * devices, COLS) and table.sharding.is_equivalent_to(core.data_sharding(mesh), 2)
    got = np.asarray(table)
    assert got[:n].tobytes() == X.tobytes() and not got[n:].any()
    assert moved["ingest.rows"] == n and moved["ingest.batches"] == sum(r > 0 for r in rows)
    assert X.nbytes <= moved["ingest.h2d_bytes"] <= got.nbytes


def test_a_piece_is_cut_at_a_devices_last_row_and_at_the_byte_cap(monkeypatch):
    assert list(core._pieces(0, 10, 4, 8)) == [(0, 4), (4, 8), (8, 10)]
    assert list(core._pieces(3, 9, 100, 8)) == [(3, 9)]
    monkeypatch.setattr(core, "_STAGE_PIECE_BYTES", 3 * 8)
    assert list(core._pieces(2, 12, 8, 8)) == [(2, 5), (5, 8), (8, 11), (11, 12)]
    # one partition larger than the cap goes up in pieces, and is the same table
    X, _y = _table()
    monkeypatch.setattr(core, "_STAGE_PIECE_BYTES", 100 * COLS * 4)
    got = np.asarray(stage_dense_batches(iter([X]), ROWS, get_mesh(1)))
    assert got.tobytes() == X.tobytes()


def test_batches_of_two_widths_or_too_few_rows_are_refused():
    X, _y = _table()
    with pytest.raises(ValueError, match="disagree on width"):
        stage_dense_batches(iter([X[:10], X[10:20, :5]]), 20, get_mesh(1))
    with pytest.raises(RuntimeError, match="staged 10 rows of the 20"):
        stage_dense_batches(iter([X[:10]]), 20, get_mesh(1))


@pytest.mark.parametrize("fault", ref_exec.FAULTS)
def test_a_planted_fault_fails_the_comparison(fault):
    """A dropped batch, two batches swapped against their labels, a batch viewed with
    the wrong stride: the table is not the reference's, and the model the program's own
    solver fits on it is outside the cell's limits."""
    X, y = _table()
    parts = _arrow_batches(X, y)
    A, yA = ref_exec.assemble(parts)
    Xf, yf = ref_exec.plant(fault, parts)
    rows = min(len(Xf), len(yf))
    assert yf.tobytes() == yA.tobytes() and (len(Xf) != len(A) or (Xf != A).sum() >= COLS)
    device = jax.devices()[0]
    table = stage_dense_batches(iter([Xf[lo : lo + BATCH] for lo in range(0, len(Xf), BATCH)]), len(Xf), get_mesh(1))
    Xd, yd = ref_exec.device_table(A, yA, device)
    assert ref_exec.elements_differing(table, Xd, ROWS) >= COLS
    sound = stage_dense_batches(iter([A[lo : lo + BATCH] for lo in range(0, ROWS, BATCH)]), ROWS, get_mesh(1))
    assert ref_exec.elements_differing(sound, Xd, ROWS) == 0
    est = LogisticRegression(maxIter=20, tol=1e-30, regParam=1e-3, num_workers=1)
    theta = _vector(est.fit(DataFrame.from_device(table, y=yf[:rows], n_rows=rows)))
    f = ref.objective(Xd, yd, 1e-3)
    theta_ref, _ = ref.lbfgs(f, COLS + 1, 20)
    gaps = (ref.score_gap(Xd[:256], theta, theta_ref), abs(f(theta)[0] - f(theta_ref)[0]) / f(theta_ref)[0])
    assert gaps[0] > LIMITS["score_gap"] and gaps[1] > LIMITS["objective_gap"]


def test_a_block_backed_frame_keeps_its_cache_slot_and_a_batch_frame_never_takes_one():
    X, y = _table()
    est = LogisticRegression(maxIter=5, tol=1e-30, regParam=1e-3, num_workers=2)
    core.clear_fit_cache()
    frame = DataFrame.from_numpy(X, y=y, num_partitions=3)
    first = est.fit(frame).fit_telemetry().counters
    again = est.fit(frame).fit_telemetry().counters
    assert first["ingest.staged"] == 1 and "ingest.cache_hit" not in first and first["ingest.h2d_bytes"] >= X.nbytes
    assert again["ingest.cache_hit"] == 1 and "ingest.staged" not in again and again["ingest.h2d_bytes"] < X.nbytes
    batches = DataFrame(_arrow_batches(X, y))
    for _fit in range(2):
        moved = est.fit(batches).fit_telemetry().counters
        assert moved["ingest.staged"] == 1 and "ingest.cache_hit" not in moved and moved["ingest.h2d_bytes"] >= X.nbytes
    assert core._FIT_INPUT_CACHE == {}          # and the block-backed frame's slot was released for it
    core.clear_fit_cache()


def test_from_arrow_reaches_the_rule():
    X, y = _table()
    lists = pa.ListArray.from_arrays(pa.array(np.arange(0, (ROWS + 1) * COLS, COLS, dtype=np.int32)), pa.array(X.reshape(-1)))
    frame = DataFrame.from_arrow(pa.table({"features": lists, "label": pa.array(y)}), num_partitions=3)
    model = LogisticRegression(maxIter=5, tol=1e-30, regParam=1e-3, num_workers=2).fit(frame)
    moved = model.fit_telemetry().counters
    assert moved["ingest.view_batches"] == 3 and "ingest.stacked_batches" not in moved and moved["ingest.rows"] == ROWS
    want = LogisticRegression(maxIter=5, tol=1e-30, regParam=1e-3, num_workers=2).fit(DataFrame.from_numpy(X, y=y))
    assert np.array_equal(_vector(model), _vector(want))


def test_staging_holds_a_few_extracted_batches_never_the_table():
    """Host memory allocated while batches that must be STACKED are staged: the one
    going up and the one being prepared (and, on the CPU backend, whose device arrays
    alias the host's, those a pending placement still reads), never the table.  The
    old route held every stacked batch and their concatenation: two tables."""
    rows, cols, batch = 32000, 64, 2000
    X, y = _table(rows=rows, cols=cols)
    parts = [pd.DataFrame({"features": [np.array(c) for c in X[lo : lo + batch]], "label": y[lo : lo + batch]}) for lo in range(0, rows, batch)]
    est = LogisticRegression(num_workers=1)
    batch_bytes = batch * cols * 4
    for _warm in range(2):       # the first pass compiles the placement
        feats, _labels, _weights, _dtype = est._pre_process_data(DataFrame(parts))
        tracemalloc.start()
        base = tracemalloc.get_traced_memory()[0]
        table = stage_dense_batches(feats, rows, get_mesh(1))
        peak = tracemalloc.get_traced_memory()[1] - base
        tracemalloc.stop()
    assert np.asarray(table).tobytes() == X.tobytes()
    assert batch_bytes <= peak < 5 * batch_bytes < X.nbytes / 3, (peak, batch_bytes)


# -- the rule's two moments: admission in srml.ingest, settlement under the solver ---------

DEFERRED = ("ingest.deferred_batches", "ingest.refuted_batches", "ingest.refits")


def _traded(parts, batch=3):
    """The frame with two middle rows of one batch traded, labels and all, as a pandas
    take leaves them: every cell still a view into the batch's buffer, the first, second
    and last where they were.  A view taken on their word pairs the two rows with each
    other's labels, so the two are chosen to differ in theirs."""
    part = parts[batch]
    i = next(i for i in range(10, len(part) - 2) if part["label"][i] != part["label"][i + 1])
    order = [*range(i), i + 1, i, *range(i + 2, len(part))]
    return parts[:batch] + [part.iloc[order].reset_index(drop=True)] + parts[batch + 1 :]


def _a_cell_copied(parts, batch=3):
    """A middle cell replaced by an equal-valued copy allocated elsewhere."""
    part = parts[batch]
    cells = part["features"].to_numpy().copy()
    cells[30] = cells[30].copy()
    return parts[:batch] + [pd.DataFrame({"features": cells, "label": part["label"]})] + parts[batch + 1 :]


def _fit(entry, est, parts):
    """(the model's numbers, the job's counters) through the executor's entry or the public fit."""
    if entry == "executor":
        (attrs,) = run_distributed_fit(est, parts, 0, 1)
        moved = profiling.TelemetrySnapshot.from_dict(attrs.pop(TELEMETRY_ATTR)).counters
        return _vector(decode_attrs(attrs)), moved
    model = est.fit(DataFrame(parts))
    return _vector(model), model.fit_telemetry().counters


@pytest.mark.parametrize("name", sorted(_refusals()))
def test_admission_lets_through_only_what_settlement_can_refute(name):
    """What the rule refuses, admission refuses too, but for a fault among the middle
    cells of the one buffer (two traded, one cut short): the first, second and last cell
    stand where they should and every cell is the buffer's, so the bytes between are
    safe to read, and the rule catches the fault at settlement."""
    cells = _refusals()[name]
    admitted = admit_feature_cells(cells, F32)
    if name in ("two_middle_rows_swapped", "a_short_cell"):
        assert admitted is not None and admitted.shape == (BATCH, COLS) and not admitted.flags.writeable
        assert np.shares_memory(admitted, cells[0]) and np.shares_memory(admitted, cells[-1])
    else:
        assert admitted is None


def test_admission_hands_the_rules_view_of_a_sound_column():
    X, y = _table()
    for pdf in _arrow_batches(X, y):
        cells = pdf["features"].to_numpy()
        admitted, proven = admit_feature_cells(cells, F32), feature_cells_view(cells, F32)
        assert admitted.__array_interface__ == proven.__array_interface__ and not admitted.flags.writeable
    one = _arrow_batches(X[:1], y[:1])[0]["features"].to_numpy()       # a batch of one row
    assert admit_feature_cells(one, F32).tobytes() == X[:1].tobytes()
    assert admit_feature_cells(one, np.dtype(np.float64)) is None


@pytest.mark.parametrize("entry", ["executor", "public"])
def test_a_sound_frame_defers_every_proof_and_fits_the_eager_routes_model(entry, monkeypatch):
    X, y = _table()
    parts = _arrow_batches(X, y)
    proven = []
    rule = core.feature_cells_view
    monkeypatch.setattr(core, "feature_cells_view", lambda cells, dtype: proven.append(len(cells)) or rule(cells, dtype))
    theta, moved = _fit(entry, _estimator("logreg", num_workers=2), parts)
    assert moved["ingest.deferred_batches"] == moved["ingest.view_batches"] == 7
    assert "ingest.refits" not in moved and "ingest.refuted_batches" not in moved and "ingest.stacked_batches" not in moved
    assert proven == [len(p) for p in parts]            # every cell of every batch was looked at, once
    assert moved["ingest.link_stagings"] == 1 and moved["ingest.link_bytes"] == X.nbytes      # the landing journal (PR 49)
    assert core._PROOFS.batches is None and core._PROOFS.refuted == 0
    # the eager route: admission refuses everything, so the rule is asked in srml.ingest as before PR 48
    monkeypatch.setattr(utils, "admit_feature_cells", lambda cells, dtype: None)
    eager, moved = _fit(entry, _estimator("logreg", num_workers=2), parts)
    assert moved["ingest.view_batches"] == 7 and not any(k in moved for k in DEFERRED)
    assert np.array_equal(theta, eager)


@pytest.mark.parametrize("entry", ["executor", "public"])
@pytest.mark.parametrize("family", ["logreg", "kmeans"])
def test_a_refuted_batch_voids_the_fit_which_is_staged_and_fitted_again(entry, family, monkeypatch, caplog):
    X, y = _table()
    parts = _traded(_arrow_batches(X, y))
    est = _estimator(family, num_workers=2)
    est.logger.propagate = True
    handed = _spy(est, monkeypatch)
    with caplog.at_level("WARNING"):
        theta, moved = _fit(entry, est, parts)
    assert moved["ingest.deferred_batches"] == 7 and moved["ingest.refuted_batches"] == 1 and moved["ingest.refits"] == 1
    # staged twice: 7 views on admission, then 6 views the rule proved at once and the refuted batch stacked
    assert moved["ingest.view_batches"] == 13 and moved["ingest.stacked_batches"] == 1 and moved["ingest.staged"] == 2
    # and journaled twice (PR 49): the second staging sends the same pieces and the same bytes again
    assert moved["ingest.link_stagings"] == 2 and moved["ingest.link_bytes"] == 2 * X.nbytes and moved["ingest.link_pieces"] % 2 == 0
    assert sum("failed the view rule's proof" in r.getMessage() for r in caplog.records) == 1
    A, yA = ref_exec.assemble(parts)
    first, again = (np.asarray(inputs.X)[:ROWS] for inputs in handed)
    assert again.tobytes() == A.tobytes() and (first != A).any(axis=1).sum() == 2       # what went up unproven was wrong
    assert core._PROOFS.batches is None and core._PROOFS.refuted == 0
    # the model is the one the same frame gives with every batch stacked cell by cell, as before any view
    monkeypatch.setattr(utils, "admit_feature_cells", lambda cells, dtype: None)
    monkeypatch.setattr(utils, "feature_cells_view", lambda cells, dtype: None)
    stacked, moved = _fit(entry, _estimator(family, num_workers=2), parts)
    assert moved["ingest.stacked_batches"] == 7 and "ingest.view_batches" not in moved and moved["ingest.link_stagings"] == 1
    assert np.array_equal(theta, stacked)
    if family == "logreg":       # and not the one the unproven table gave: the two rows' labels differ
        wrong = _vector(_estimator(family, num_workers=2).fit(DataFrame.from_numpy(first, y=yA)))
        assert not np.array_equal(theta, wrong)


def test_a_short_middle_cell_still_ends_in_the_stacks_own_error():
    """Admitted (its neighbours stand where they should), refuted at settlement, and
    the second staging says what the stack always said of such a column."""
    X, y = _table()
    parts = _arrow_batches(X, y)
    cells = parts[2]["features"].to_numpy().copy()
    cells[30] = cells[30][:-1]
    parts[2] = pd.DataFrame({"features": cells, "label": parts[2]["label"]})
    before = profiling.counters()
    with pytest.raises(ValueError, match="same length"):
        _estimator("logreg", num_workers=1).fit(DataFrame(parts))
    moved = profiling.counter_deltas(before)
    assert moved["ingest.refuted_batches"] == 1 and moved["ingest.refits"] == 1
    assert core._PROOFS.batches is None and core._PROOFS.refuted == 0


def test_a_cell_allocated_elsewhere_is_refused_at_admission_and_stacked_at_once():
    X, y = _table()
    parts = _a_cell_copied(_arrow_batches(X, y))
    theta, moved = _fit("public", _estimator("logreg", num_workers=2), parts)
    assert moved["ingest.deferred_batches"] == moved["ingest.view_batches"] == 6 and moved["ingest.stacked_batches"] == 1
    assert "ingest.refits" not in moved and "ingest.refuted_batches" not in moved and moved["ingest.staged"] == 1
    want = _estimator("logreg", num_workers=2).fit(DataFrame.from_numpy(X, y=y))
    assert np.array_equal(theta, _vector(want))


@pytest.mark.parametrize("route", ["staging", "extract_partition_features", "transform", "sweep"])
def test_where_nobody_stands_ready_to_settle_the_rule_is_asked_at_once(route, monkeypatch):
    """Outside FitJob.run (the benchmark's own staging, model-side readers, the sweep's
    FitJob.staged) no batch is admitted on the cheap test: the traded rows are refused
    where they are read and stacked, and nothing is left owing."""
    X, y = _table()
    parts = _traded(_arrow_batches(X, y))
    A, yA = ref_exec.assemble(parts)
    asked = []
    monkeypatch.setattr(utils, "admit_feature_cells", lambda cells, dtype: asked.append(len(cells)))
    before = profiling.counters()
    if route == "staging":           # as chipbench/subjects/logreg_exec.fit_loop._staged_again does
        feats, labels, _weights, dtype = LogisticRegression(num_workers=1)._pre_process_data(DataFrame(parts))
        table = stage_dense_batches(feats, ROWS, get_mesh(1))
        assert np.asarray(table).tobytes() == A.tobytes() and np.concatenate(labels).tobytes() == yA.tobytes() and dtype == F32
    elif route == "extract_partition_features":
        got = core.extract_partition_features(parts[3], "features", None, F32)
        assert got.flags.owndata and got.tobytes() == A[3 * BATCH : 4 * BATCH].tobytes()
    elif route == "transform":
        model = _estimator("logreg", num_workers=1).fit(DataFrame.from_numpy(A, y=yA))
        got = model.transform(DataFrame(parts)).toPandas()["prediction"].to_numpy()
        want = model.transform(DataFrame.from_numpy(A, y=yA)).toPandas()["prediction"].to_numpy()
        assert np.array_equal(got, want)
    else:
        from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
        from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

        est = LogisticRegression(maxIter=8, tol=1e-30, regParam=1e-3, num_workers=2)
        grid = ParamGridBuilder().addGrid(est.regParam, [1e-3, 1e-2]).build()
        cv = CrossValidator(estimator=est, estimatorParamMaps=grid, numFolds=2, collectSubModels=True,
                            evaluator=MulticlassClassificationEvaluator(metricName="logLoss"))
        swept = cv.fit(DataFrame(parts)).subModels[0][0].fit_telemetry().counters
        assert swept["ingest.view_batches"] == 6 and swept["ingest.stacked_batches"] == 1 and not any(k in swept for k in DEFERRED)
    # the landing journal (PR 49) is a job's: the bare staging and the reader keep none, transform neither (its
    # model's fit kept one), and a sweep is a job, whose one staging is journaled like any other's
    link = {k: v for k, v in profiling.counter_deltas(before).items() if k.startswith("ingest.link_")}
    if route in ("staging", "extract_partition_features"):
        assert link == {}
    else:
        assert (link if route == "transform" else swept)["ingest.link_stagings"] == 1
    if route != "sweep":            # the sweep's winner is refitted by a public fit of its own, which does ask
        assert asked == [] and not any(k in profiling.counter_deltas(before) for k in DEFERRED)
    assert core._PROOFS.batches is None and core._PROOFS.refuted == 0


def test_a_fit_function_that_raises_leaves_no_proof_owing_to_the_next_job(monkeypatch):
    X, y = _table()
    parts = _arrow_batches(X, y)
    est = _estimator("logreg", num_workers=2)
    make = est._get_tpu_fit_func

    def failing(df, extra_params=None):
        def fit_func(inputs, params):
            assert len(core._PROOFS.batches) == 7           # admitted, and not yet proven
            raise FloatingPointError("the solver gave up")
        return fit_func

    monkeypatch.setattr(est, "_get_tpu_fit_func", failing)
    with pytest.raises(FloatingPointError, match="gave up"):
        est.fit(DataFrame(parts))
    assert core._PROOFS.batches is None and core._PROOFS.refuted == 0
    monkeypatch.setattr(est, "_get_tpu_fit_func", make)
    theta, moved = _fit("public", est, parts)
    assert moved["ingest.deferred_batches"] == 7 and "ingest.refits" not in moved
    assert np.array_equal(theta, _vector(_estimator("logreg", num_workers=2).fit(DataFrame.from_numpy(X, y=y))))


def test_a_fit_function_that_never_fetches_is_settled_by_the_job(monkeypatch):
    """FitJob.run settles what fetch_fit_result did not: here a fit function that
    returns without calling it, on a frame the rule refutes."""
    X, y = _table()
    parts = _traded(_arrow_batches(X, y))
    est = _estimator("logreg", num_workers=1)
    calls = []

    def bare(df, extra_params=None):
        def fit_func(inputs, params):
            calls.append(np.asarray(inputs.X)[:ROWS].tobytes())
            return {"coef_": np.zeros(COLS), "intercept_": 0.0}
        return fit_func

    monkeypatch.setattr(est, "_get_tpu_fit_func", bare)
    before = profiling.counters()
    with core.fit_job(est) as job:
        with job.prepare():
            fit_func = est._get_tpu_fit_func(None)
        job.run(lambda x64: est._build_fit_inputs(DataFrame(parts), x64), fit_func, {})
    moved = profiling.counter_deltas(before)
    assert moved["ingest.refuted_batches"] == 1 and moved["ingest.refits"] == 1
    assert len(calls) == 2 and calls[0] != calls[1] and calls[1] == ref_exec.assemble(parts)[0].tobytes()


def test_the_proof_runs_between_the_queued_copies_and_the_wait(monkeypatch):
    """Inside fetch_fit_result: the leaves' copies are queued, the rule runs over the
    admitted batches (srml.ingest.verify), and only then does the host block on the
    solver (srml.fit.wait); in a fit, after srml.fit.solve has dispatched it."""
    import threading

    X, y = _table()
    parts = _arrow_batches(X, y)
    log = []

    class Leaf:
        nbytes = 12

        def copy_to_host_async(self):
            log.append("copy")

        def block_until_ready(self):
            log.append("ready")
            return self

        def __array__(self, dtype=None, copy=None):
            return np.zeros(3, np.float32)

    rule = core.feature_cells_view
    monkeypatch.setattr(core, "feature_cells_view", lambda cells, dtype: log.append("proof") or rule(cells, dtype))
    monkeypatch.setattr(core._PROOFS, "batches", [(p["features"].to_numpy(), F32) for p in parts[:2]])
    me = threading.get_ident()
    with profiling.collect_spans():
        core.fetch_fit_result([Leaf(), Leaf()])
        spans = [r[0] for r in sorted((r for r in profiling.span_records() if r[3] == me), key=lambda r: r[1])]
    assert log[:6] == ["copy", "copy", "proof", "proof", "ready", "ready"]
    assert spans == ["srml.ingest.verify", "srml.fit.wait", "srml.fit.fetch"] and core._PROOFS.batches == []
    monkeypatch.undo()
    with profiling.collect_spans():
        model = _estimator("logreg", num_workers=2).fit(DataFrame(parts))
        mine = sorted((r for r in profiling.span_records() if r[3] == me), key=lambda r: r[1])
    fit = next(r for r in mine if r[0] == "srml.fit")
    steps = [r for r in mine if r[6] == fit[5]]
    assert [r[0] for r in steps] == ["srml.fit.init", "srml.fit.solve", "srml.ingest.verify", "srml.fit.wait", "srml.fit.fetch", "srml.fit.pack"]
    assert steps[2][7] == {"batches": 7, "refuted": 0}
    phases = model.fit_telemetry().phase_seconds()
    assert "srml.ingest.verify" in phases
