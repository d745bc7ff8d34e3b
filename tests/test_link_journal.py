"""The landing journal (PR 49): a fit job's record of the host-to-device copies its
ingest sends piece by piece (core.stage_dense_batches), each piece's landing stamped
by the process's one watcher thread (profiling.LandingJournal, `srml-link-watch`) and
not where the job's thread next looks, reduced once a staging into the counters
ingest.link_* and, while a trace session collects, one srml.link.h2d record a piece.

Held here: the union arithmetic on made-up stamps; a job through
run_distributed_fit (the pieces sent, the table's bytes, in the job's own telemetry);
that staging outside a job journals nothing and starts no thread; the starved case (a
copy that lands while the sender is busy is stamped when it lands); that a staging
which raises leaves the watcher idle and no array held; and the trace session's
export.  The refit's second journal is held in tests/test_exec_ingest.py, the encode
span in tests/test_fit_steps.py.
"""
import gc
import json
import threading
import time
import weakref

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu import LogisticRegression, core, profiling
from spark_rapids_ml_tpu.core import TELEMETRY_ATTR, stage_dense_batches
from spark_rapids_ml_tpu.parallel.mesh import get_mesh
from spark_rapids_ml_tpu.parallel.runner import run_distributed_fit

ROWS, COLS, BATCH = 424, 24, 64            # 6 batches of 64 and one of 40
LINK = ("stagings", "pieces", "bytes", "fed_us", "starved_us", "flight_us")


def _link():
    return {k: profiling.counter("ingest.link_" + k) for k in LINK}


def _arrow_batches(seed=5):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((ROWS, COLS)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    out = []
    for lo in range(0, ROWS, BATCH):
        rows = np.array(X[lo : lo + BATCH])
        offsets = pa.array(np.arange(0, (len(rows) + 1) * COLS, COLS, dtype=np.int32))
        lists = pa.ListArray.from_arrays(offsets, pa.array(rows.reshape(-1)))
        out.append(pa.table({"features": lists, "label": pa.array(y[lo : lo + BATCH])}).to_pandas())
    return X, out


class _Copy:
    """A stand-in for a device array: lands `after` seconds from its making."""

    def __init__(self, after=0.0):
        self.lands_at = profiling.now() + after

    def block_until_ready(self):
        time.sleep(max(0.0, self.lands_at - profiling.now()))
        return self


def _watcher_is_idle():
    """A fresh journal's close returns only when the watcher has passed everything
    sent before it: it is blocked on nothing, and waits for the next piece."""
    probe = profiling.LandingJournal("test.probe")
    probe.sent(profiling.now(), 1, _Copy())
    return probe.close() is not None and profiling._landing_queue.empty()


# -- the arithmetic --------------------------------------------------------------

@pytest.mark.parametrize(
    "intervals,fed,starved,flight",
    [
        ([(0.0, 1.0), (2.0, 3.0), (5.0, 5.5)], 2.5, 3.0, 2.5),                 # one at a time, the link idle between
        ([(0.0, 10.0), (2.0, 3.0), (4.0, 9.0)], 10.0, 0.0, 16.0),              # nested
        ([(0.0, 2.0), (1.0, 2.0), (2.5, 4.5), (3.5, 4.5)], 4.0, 0.5, 6.0),     # pairs landing together
        ([(0.0, 1.0), (0.5, 2.0), (1.5, 3.0), (2.5, 4.0)], 4.0, 0.0, 5.5),     # a pipeline two deep, never dry
        ([(3.0, 3.25)], 0.25, 0.0, 0.25),                                      # one piece
        ([(1.0, 2.0), (2.0, 3.0)], 2.0, 0.0, 2.0),                             # back to back
        ([(4.0, 5.0), (0.0, 1.0), (0.5, 1.5)], 2.5, 2.5, 3.0),                 # in any order
        ([(7.0, 7.0), (7.0, 7.0)], 0.0, 0.0, 0.0),                             # copies that took no time
    ],
    ids=["disjoint", "nested", "pairs", "two_deep", "one_piece", "touching", "unordered", "instant"],
)
def test_fed_is_the_union_starved_the_rest_and_flight_the_sum(intervals, fed, starved, flight):
    got = profiling.interval_measures(intervals)
    assert got == pytest.approx((fed, starved, flight))
    first, last = min(s for s, _ in intervals), max(e for _, e in intervals)
    assert got[0] + got[1] == pytest.approx(last - first) and got[2] >= got[0] - 1e-12 and got[1] >= -1e-12


# -- a job keeps it, nobody else does ----------------------------------------------

def test_a_job_journals_every_piece_and_the_tables_bytes_in_its_own_telemetry():
    X, parts = _arrow_batches()
    before = _link()
    with profiling.collect_spans():
        (attrs,) = run_distributed_fit(LogisticRegression(maxIter=3, num_workers=2), parts, 0, 1)
        records = profiling.span_records()
    moved = profiling.TelemetrySnapshot.from_dict(attrs[TELEMETRY_ATTR]).counters
    puts = [r for r in records if r[0] == "srml.device_put"]
    pieces = len(puts) - 2                      # every copy of ingest but the mask and the labels
    assert moved["ingest.link_stagings"] == 1 and moved["ingest.link_pieces"] == pieces > len(parts)
    assert moved["ingest.link_bytes"] == X.nbytes == moved["ingest.h2d_bytes"] - 2 * ROWS * 4
    assert 0 < moved["ingest.link_fed_us"] <= moved["ingest.link_flight_us"] and moved.get("ingest.link_starved_us", 0) >= 0
    # the process's counters moved by the job's part and nothing else
    assert {k: v - before[k] for k, v in _link().items()} == {k: moved.get("ingest.link_" + k, 0) for k in LINK}
    # a record a piece, in the order sent, each from its enqueue to its landing, inside srml.ingest
    ingest = next(r for r in records if r[0] == "srml.ingest")
    landings = [r for r in records if r[0] == "srml.link.h2d"]
    assert [r[7]["piece"] for r in landings] == list(range(pieces))
    assert [r[7]["bytes"] for r in landings] == [r[7]["bytes"] for r in sorted(puts, key=lambda r: r[1])[:pieces]]
    assert all(ingest[1] <= r[1] <= r[2] <= ingest[2] and r[6] == ingest[5] for r in landings)
    assert {r[4] for r in landings} == {"srml-link-watch"} and landings[0][3] != ingest[3]
    fed, starved, flight = profiling.interval_measures([(r[1], r[2]) for r in landings])
    assert moved["ingest.link_fed_us"] == round(1e6 * fed) and moved["ingest.link_flight_us"] == round(1e6 * flight)
    assert moved.get("ingest.link_starved_us", 0) == round(1e6 * starved)


def test_staging_outside_a_job_journals_nothing_and_starts_no_thread(monkeypatch):
    def forbidden(*_a, **_k):
        raise AssertionError("a journal was opened outside a fit job")

    monkeypatch.setattr(profiling, "LandingJournal", forbidden)
    X, _parts = _arrow_batches()
    before, threads = _link(), {t.ident for t in threading.enumerate()}
    assert not core._LINK.open
    with profiling.span("srml.ingest"):         # the span alone does not make a job
        table = stage_dense_batches(iter([X[:200], X[200:]]), ROWS, get_mesh(2))
    assert np.asarray(table)[:ROWS].tobytes() == X.tobytes()
    assert _link() == before and {t.ident for t in threading.enumerate()} == threads


def test_the_journal_is_the_jobs_threads_alone(monkeypatch):
    """core._LINK is thread-local: a staging on another thread while a job's ingest is
    open on this one journals nothing."""
    X, _parts = _arrow_batches()
    monkeypatch.setattr(core._LINK, "open", True)
    before, seen = _link(), []
    other = threading.Thread(
        target=lambda: seen.append((core._LINK.open, stage_dense_batches(iter([X]), ROWS, get_mesh(1)).shape)), name="test-other"
    )
    other.start()
    other.join(60)
    assert not other.is_alive() and seen == [(False, (ROWS, COLS))] and _link() == before


# -- where the stamp is taken ------------------------------------------------------

def test_a_landing_is_stamped_when_it_lands_not_when_the_sender_next_looks():
    """The starved case: the sender enqueues two copies and is busy (bytecode without a
    pause, the interpreter lock held) long past both landings.  Its own next look comes
    at 0.25 s; the journal's stamps follow the copies' schedule."""
    journal = profiling.LandingJournal("test.link")
    t0 = profiling.now()
    journal.sent(t0, 100, _Copy(0.03))
    journal.sent(profiling.now(), 100, _Copy(0.06))
    while profiling.now() < t0 + 0.25:
        pass
    looked = profiling.now()
    (o1, l1, b1), (o2, l2, b2) = journal.close()
    assert (o1, b1, b2) == (t0, 100, 100) and o1 <= o2 < t0 + 0.02
    assert 0.03 <= l1 - t0 < 0.13 and 0.06 <= l2 - t0 < 0.16 and l1 < l2 < looked - 0.05
    fed, starved, flight = profiling.interval_measures([(o1, l1), (o2, l2)])
    assert fed == pytest.approx(l2 - o1) and starved == pytest.approx(0.0, abs=1e-9) and flight > fed


def test_the_watcher_takes_the_copies_in_the_order_sent_across_journals():
    first, second = profiling.LandingJournal("test.a"), profiling.LandingJournal("test.b")
    first.sent(profiling.now(), 1, _Copy(0.05))
    second.sent(profiling.now(), 2, _Copy(0.0))         # has landed, and is looked at after the one before it
    (_, landed_b, _), = second.close()
    (_, landed_a, _), = first.close()
    assert landed_a <= landed_b


def test_a_copy_that_raises_voids_its_journal_and_the_watcher_goes_on(caplog):
    class Lost(_Copy):
        def block_until_ready(self):
            raise RuntimeError("Array has been deleted")

    journal = profiling.LandingJournal("test.void")
    journal.sent(profiling.now(), 1, _Copy())
    journal.sent(profiling.now(), 1, Lost())
    with caplog.at_level("WARNING", logger="spark_rapids_ml_tpu.profiling"):
        assert journal.close() is None              # no journal rather than one with a guess in it
    assert any("void" in r.getMessage() for r in caplog.records)
    assert profiling.LandingJournal("test.empty").close() is None and _watcher_is_idle()


def test_a_staging_that_raises_leaves_the_watcher_idle_and_no_array_held(monkeypatch):
    X, _parts = _arrow_batches()
    sent, put = [], core._device_put_counted

    def spying(*a, **k):
        up = put(*a, **k)
        sent.append(weakref.ref(up))
        return up

    monkeypatch.setattr(core, "_device_put_counted", spying)
    monkeypatch.setattr(core._LINK, "open", True)
    before = _link()
    try:
        stage_dense_batches(iter([X[:100], X[100:300], X[300:, :5]]), ROWS, get_mesh(2))
    except ValueError as exc:
        assert "disagree on width" in str(exc)
    else:
        raise AssertionError("two widths were staged")
    assert len(sent) >= 3 and _watcher_is_idle()
    gc.collect()
    assert [ref() for ref in sent] == [None] * len(sent)
    assert _link() == before                    # half a staging is not journaled


# -- the trace session's export ----------------------------------------------------

def test_the_chrome_trace_holds_a_record_a_piece_under_srml_ingest(monkeypatch, tmp_path):
    _X, parts = _arrow_batches()
    monkeypatch.setenv("SRML_TRACE_DIR", str(tmp_path))
    (attrs,) = run_distributed_fit(LogisticRegression(maxIter=3, num_workers=2), parts, 0, 1)
    moved = profiling.TelemetrySnapshot.from_dict(attrs[TELEMETRY_ATTR]).counters
    (path,) = tmp_path.glob("fit-LogisticRegression-rank0-*.trace.json")
    events = json.loads(path.read_text())["traceEvents"]
    lanes = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    ingest, = (e for e in events if e["name"] == "srml.ingest")
    landings = [e for e in events if e["name"] == "srml.link.h2d"]
    assert len(landings) == moved["ingest.link_pieces"] and sum(e["args"]["bytes"] for e in landings) == moved["ingest.link_bytes"]
    assert all(e["args"]["parent_id"] == ingest["args"]["span_id"] for e in landings)
    assert {lanes[e["tid"]] for e in landings} == {"srml-link-watch"} and lanes[ingest["tid"]] != "srml-link-watch"
    assert all(ingest["ts"] <= e["ts"] and e["ts"] + e["dur"] <= ingest["ts"] + ingest["dur"] for e in landings)
    encode, = (e for e in events if e["name"] == "srml.finish.encode")
    assert encode["args"]["bytes"] > 0
