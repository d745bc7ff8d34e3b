"""What the landing journal costs and what it reads, measured where it runs:

    chiprun -- python3 tools/link_journal_cost.py

The journal has no switch (core.stage_dense_batches keeps one inside every fit
job's srml.ingest), so its cost is measured inside ONE process: the staging of
logreg_exec_fit's table (40 pieces of 10,000 x 3000 float32, views of one host
batch) as a job stages it and as anyone else does, turn about, and the journal's
own steps alone (a piece's sent() on the sender's thread, a staging's close() and
reduction, and the start of a thread, which a watcher a staging would pay where
the process's one watcher does not).  Prints one JSON line; beside the cost, what
the journal read in the journaled stagings: fed, starved and flight a staging,
and the milliseconds between one landing and the next, which say whether the
pieces land one at a time or in pairs.  PERF.md section 6 (PR 49) and
docs/observability.md quote it.  On the CPU it runs at a toy size and proves
nothing.
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _Landed:
    """A copy that has already landed: the journal's steps without the link."""

    def block_until_ready(self):
        return self


def _quartiles(values):
    q = statistics.quantiles(values, n=4) if len(values) > 1 else [values[0]] * 3
    return {"median": statistics.median(values), "q1": q[0], "q3": q[2], "n": len(values)}


def main() -> None:
    import jax
    import numpy as np

    from spark_rapids_ml_tpu import core, profiling
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    devices = jax.local_devices()
    on_chip = devices[0].platform == "tpu"
    batch_rows, cols, batches = (10_000, 3000, 40) if on_chip else (64, 24, 7)
    batch = np.random.default_rng(49).standard_normal((batch_rows, cols), np.float32)
    mesh = get_mesh(1)

    def stage(journaled: bool):
        core._LINK.open = journaled
        try:
            t0 = time.perf_counter()
            with profiling.span("srml.ingest"):
                table = core.stage_dense_batches(iter([batch] * batches), batch_rows * batches, mesh)
            seconds = time.perf_counter() - t0
        finally:
            core._LINK.open = False
        del table
        return seconds

    stage(True), stage(False)           # the placement's executable, the watcher's thread
    names = ("stagings", "pieces", "bytes", "fed_us", "starved_us", "flight_us")
    before = profiling.counters("ingest.link_")
    seconds = {True: [], False: []}
    gaps_ms, lengths_ms = [], []
    reps = 10 if on_chip else 3
    for rep in range(reps):
        for journaled in ((True, False) if rep % 2 == 0 else (False, True)):
            seconds[journaled].append(stage(journaled))
    moved = profiling.counter_deltas(before, "ingest.link_")
    per = {k: moved.get("ingest.link_" + k, 0) / reps for k in names}
    for _ in range(3):                  # untimed: each piece's record, for the landings' spacing
        with profiling.collect_spans():
            stage(True)
            pieces = sorted((r[2], r[1]) for r in profiling.span_records() if r[0] == "srml.link.h2d")
        gaps_ms.append([1e3 * (b[0] - a[0]) for a, b in zip(pieces, pieces[1:])])
        lengths_ms.append([1e3 * (landed - opened) for landed, opened in pieces])

    # the journal's own steps, without the link
    n = 2000
    journal = profiling.LandingJournal("cost.link")
    landed = _Landed()
    t0 = time.perf_counter()
    for _ in range(n):
        journal.sent(profiling.now(), 120_000_000, landed)
    sent_us = 1e6 * (time.perf_counter() - t0) / n
    journal.close()
    closes = []
    for _ in range(200):
        journal = profiling.LandingJournal("cost.link")
        for _ in range(batches):
            journal.sent(profiling.now(), 120_000_000, landed)
        time.sleep(0.002)               # the watcher has stamped them, as at a staging's end
        t0 = time.perf_counter()
        core._count_landings(journal.close())
        closes.append(1e6 * (time.perf_counter() - t0))
    starts = []
    for _ in range(200):
        t0 = time.perf_counter()
        t = threading.Thread(target=lambda: None, name="cost-thread", daemon=True)
        t.start()
        t.join()
        starts.append(1e6 * (time.perf_counter() - t0))

    flat = [g for gaps in gaps_ms for g in gaps]
    print(json.dumps({
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)},
        "table": {"pieces": batches, "piece_bytes": int(batch.nbytes)},
        "staging_ms": {"journaled": _quartiles([1e3 * s for s in seconds[True]]), "plain": _quartiles([1e3 * s for s in seconds[False]])},
        "journal_us": {"sent_per_piece": sent_us, "close_and_count_per_staging": _quartiles(closes), "thread_start_join": _quartiles(starts)},
        "per_journaled_staging": per,
        "fed_share": 100.0 * per["fed_us"] / max(1.0, per["fed_us"] + per["starved_us"]),
        "gb_per_s": 1e-3 * per["bytes"] / max(1.0, per["fed_us"]),
        "link_depth": per["flight_us"] / max(1.0, per["fed_us"]),
        "landing_to_landing_ms": {**_quartiles(flat), "min": min(flat), "max": max(flat), "under_2ms": sum(g < 2.0 for g in flat), "first_staging": gaps_ms[0]},
        "piece_in_flight_ms": {"first_staging": lengths_ms[0]},
    }))


if __name__ == "__main__":
    main()
