# srml-watch: the always-on health plane (docs/observability.md §7).
# Gates, in ISSUE order:
#   - induced-hang: a fit task blocking one mocked rank produces a watchdog
#     report naming the stalled RANK and its innermost open SPAN
#   - induced-exception: a failing fit dumps a Perfetto-loadable flight
#     recording whose FINAL event is the exception, naming the failing span
#   - overhead: always-on flight recording adds <2% to a warm kmeans fit
#   - memory accounting: per-phase peak-delta attribution merges through
#     TelemetrySnapshot; watermark gauges + serving health round-trip
#     through export_metrics()/render_prometheus()
import glob
import json
import threading
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import profiling, watch


@pytest.fixture
def fresh_recorder():
    """A private FlightRecorder installed as the profiling hook for one
    test (restoring the process recorder after), so ring/thread/memory
    assertions never race the rest of the suite's events."""
    prev = profiling._flight
    rec = watch.FlightRecorder(cap=64)
    profiling._flight = rec
    try:
        yield rec
    finally:
        profiling._flight = prev


# -- flight recorder ----------------------------------------------------------


def test_flight_recorder_always_on_without_any_session(fresh_recorder):
    """Span closes and counter increments land in the ring with NO trace
    session open — the whole point: nobody plans a crash."""
    rec = fresh_recorder
    with profiling.span("w.outer"):
        with profiling.span("w.inner"):
            profiling.incr_counter("w.ctr", 3)
    kinds = [r[0] for r in rec.records()]
    assert kinds == ["ctr", "span", "span"]
    ctr = rec.records()[0]
    assert ctr[1] == "w.ctr" and ctr[2] == 3
    inner, outer = rec.records()[1], rec.records()[2]
    assert inner[1] == "w.inner" and inner[6] == 1  # depth under outer
    assert outer[1] == "w.outer" and outer[6] == 0
    assert not inner[7] and not outer[7]  # no error flag


def test_flight_ring_is_bounded(fresh_recorder):
    rec = fresh_recorder
    for i in range(rec.cap * 2):
        profiling.incr_counter("w.ring", 1)
    recs = rec.records()
    assert len(recs) == rec.cap  # bounded
    assert rec.event_count() == rec.cap * 2  # lifetime count keeps going
    # oldest half overwritten: the surviving totals are the most recent
    assert recs[0][3] == rec.cap + 1 and recs[-1][3] == rec.cap * 2


def test_open_spans_and_innermost_cross_thread(fresh_recorder):
    """The recorder answers 'where is thread X right now' — the question a
    hang poses — from any other thread."""
    rec = fresh_recorder
    entered, release = threading.Event(), threading.Event()

    def wedged():
        with profiling.span("w.fit"):
            with profiling.span("w.fit.collective"):
                entered.set()
                release.wait(10.0)

    th = threading.Thread(target=wedged, name="w-wedged")
    th.start()
    try:
        assert entered.wait(10.0)
        spans = {name: stack for name, stack in rec.open_spans().values()}
        assert spans.get("w-wedged") == ["w.fit", "w.fit.collective"]
        assert rec.innermost(th.ident) == "w.fit.collective"
        assert rec.progress(th.ident) == 0  # nothing closed: wedged
    finally:
        release.set()
        th.join()
    assert rec.progress(th.ident) == 2


def test_ring_cap_clamps_to_one_never_crashes():
    """A zero/negative SRML_WATCH_RING must degrade to a tiny ring, never
    to IndexError inside the spans/counters the recorder watches."""
    rec = watch.FlightRecorder(cap=0)
    assert rec.cap == 1
    prev = profiling._flight
    profiling._flight = rec
    try:
        with profiling.span("w.tiny"):
            profiling.incr_counter("w.tiny.ctr")
    finally:
        profiling._flight = prev
    assert rec.event_count() == 2 and len(rec.records()) == 1


def test_recorder_installs_regardless_of_import_order():
    """Importing watch BEFORE profiling (a monitoring sidecar's natural
    first touch) must still leave the recorder installed — the circular
    bootstrap degrades on the partial module, and watch's own bottom
    install() covers it."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import spark_rapids_ml_tpu.watch as w; "
         "from spark_rapids_ml_tpu import profiling; "
         "assert w.recorder() is not None; "
         "assert profiling._flight is w.recorder(); "
         "print('installed')"],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "installed" in out.stdout


def test_disabled_recorder_restores_the_zero_hook_path(monkeypatch):
    monkeypatch.setattr(profiling, "_flight", None)
    with profiling.span("w.off"):
        profiling.incr_counter("w.off.ctr")
    # nothing to assert beyond "no crash": with _flight None the span path
    # is byte-for-byte the pre-watch branch (see also the overhead gate)
    assert profiling._flight is None


# -- induced exception: flight dump -------------------------------------------


def test_induced_exception_dumps_flight_with_failing_span_last(
    tmp_path, monkeypatch
):
    """A fit task that raises must leave a Perfetto-loadable flight dump
    whose final event is the exception instant naming the innermost
    failing span (the ISSUE acceptance gate)."""
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame

    monkeypatch.setenv(profiling.TRACE_ENV, str(tmp_path))

    def failing_fit(inputs, params):
        with profiling.span("fit.prep"):
            pass
        with profiling.span("fit.boom"):
            raise ValueError("induced failure")

    X = np.random.default_rng(0).standard_normal((64, 4)).astype(np.float32)
    est = KMeans(k=2, maxIter=2).setFeaturesCol("features")
    est._get_tpu_fit_func = lambda df, extra_params=None: failing_fit
    with pytest.raises(ValueError, match="induced failure"):
        est.fit(DataFrame.from_numpy(X, feature_layout="array"))

    dumps = glob.glob(str(tmp_path / "flight-fit-KMeans-*.json"))
    assert dumps, "no flight dump written"
    doc = json.load(open(dumps[0]))
    events = doc["traceEvents"]
    # Perfetto-loadable: complete events carry the ts/dur/pid/tid contract
    complete = [e for e in events if e.get("ph") == "X"]
    assert complete
    for e in complete:
        assert set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}
    names = {e["name"] for e in complete}
    assert {"fit.prep", "fit.boom", "srml.fit"} <= names
    errored = {e["name"] for e in complete if e["args"].get("error")}
    assert "fit.boom" in errored and "fit.prep" not in errored
    # the FINAL event is the exception, naming the innermost failing span
    last = events[-1]
    assert last["ph"] == "i" and last["name"] == "exception"
    assert last["args"]["failing_span"] == "fit.boom"
    assert last["args"]["type"] == "ValueError"


def test_flight_dump_noop_without_trace_dir(monkeypatch):
    monkeypatch.delenv(profiling.TRACE_ENV, raising=False)
    assert watch.dump("nowhere") is None


# -- induced hang: heartbeats + stall watchdog --------------------------------


def _rank_plane(root, rank, nranks=2):
    from spark_rapids_ml_tpu.parallel.runner import FileControlPlane

    return FileControlPlane(str(root), rank, nranks, timeout=30)


def test_control_plane_health_surface_is_non_collective(tmp_path):
    """publish_health/read_health never block and never consume gather
    rounds — rank 1 can read rank 0's payload without rank 0 waiting."""
    cp0 = _rank_plane(tmp_path, 0)
    cp1 = _rank_plane(tmp_path, 1)
    cp0.publish_health('{"rank": 0, "progress": 7}')
    assert json.loads(cp1.read_health()[0])["progress"] == 7
    assert 1 not in cp1.read_health()  # rank 1 never published
    cp0.publish_health('{"rank": 0, "progress": 8}')  # overwrite, not append
    assert json.loads(cp1.read_health()[0])["progress"] == 8


def test_local_control_plane_health_surface():
    from spark_rapids_ml_tpu.parallel.context import LocalControlPlane

    cp = LocalControlPlane()
    cp.publish_health(json.dumps({"rank": 0, "progress": 1}))
    assert json.loads(cp.read_health()[0])["progress"] == 1


def test_induced_hang_watchdog_names_stuck_rank_and_innermost_span(tmp_path):
    """Two thread-mocked ranks fit over a FileControlPlane; rank 1 wedges
    inside a span.  The watchdog must report rank 1 BY NAME with the
    innermost open span it is stuck in — and must NOT flag rank 0, whose
    fit keeps making progress (the ISSUE acceptance gate)."""
    assert watch.recorder() is not None, "flight recorder must be on"
    done, blocked_entered, release = (
        threading.Event(), threading.Event(), threading.Event(),
    )

    def rank0():
        cp = _rank_plane(tmp_path, 0)
        hb = watch.HeartbeatPublisher(cp, 0, interval_s=0.05)
        try:
            while not done.wait(0.01):  # keeps closing spans: alive
                with profiling.span("fit.work"):
                    pass
        finally:
            hb.stop()

    def rank1():
        cp = _rank_plane(tmp_path, 1)
        hb = watch.HeartbeatPublisher(cp, 1, interval_s=0.05)
        try:
            with profiling.span("runner.fit"):
                with profiling.span("fit.wedge.block"):
                    blocked_entered.set()
                    release.wait(30.0)  # the induced hang
        finally:
            hb.stop()

    threads = [
        threading.Thread(target=rank0, name="w-rank0"),
        threading.Thread(target=rank1, name="w-rank1"),
    ]
    for t in threads:
        t.start()
    dog = None
    try:
        assert blocked_entered.wait(10.0)
        reports = []
        dog = watch.StallWatchdog(
            _rank_plane(tmp_path, 0), nranks=2, stall_s=0.5, poll_s=0.1,
            on_stall=reports.append,
        )
        deadline = time.monotonic() + 15.0
        while not reports and time.monotonic() < deadline:
            time.sleep(0.05)
        assert reports, "watchdog never fired on the wedged rank"
        assert reports[0]["rank"] == 1
        assert reports[0]["span"] == "fit.wedge.block"
        assert reports[0]["reason"] == "progress frozen"
        # rank 0 keeps progressing: one stall episode, one report
        time.sleep(0.4)
        assert all(r["rank"] == 1 for r in dog.reports), dog.reports
        assert profiling.counter("watch.stalls") >= 1
    finally:
        if dog is not None:
            dog.stop()
        done.set()
        release.set()
        for t in threads:
            t.join(timeout=10.0)


def test_start_fit_health_noops_when_unsupported():
    class GatherOnlyPlane:  # live Spark's BarrierTaskContext shape
        def allGather(self, message):
            return [message]

        def barrier(self):
            return None

    h = watch.start_fit_health(GatherOnlyPlane(), rank=0, nranks=2)
    assert h.publisher is None and h.watchdog is None
    h.stop()  # must be safe
    h1 = watch.start_fit_health(object(), rank=0, nranks=1)
    assert h1.publisher is None
    h1.stop()


# -- overhead guard -----------------------------------------------------------


def test_always_on_recording_overhead_under_2pct_of_warm_fit():
    """The <2% gate, measured structurally: (per-event recorder cost) x
    (events a warm kmeans fit generates) must stay under 2% of the warm
    fit's wall clock.  Per-event cost is the on-vs-off difference of a
    span microbenchmark — this bounds the recorder's ADDED cost without
    racing two full fits against wall-clock noise."""
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame

    rec = watch.recorder()
    assert rec is not None

    N = 20000

    def span_bench():
        t0 = profiling.now()
        for _ in range(N):
            with profiling.span("w.ovh"):
                pass
        return (profiling.now() - t0) / N

    on = min(span_bench() for _ in range(3))
    try:
        watch.disable()
        off = min(span_bench() for _ in range(3))
    finally:
        watch.enable()
    per_event = max(on - off, 0.0)

    X = np.random.default_rng(1).standard_normal((256, 8)).astype(np.float32)
    df = DataFrame.from_numpy(X, feature_layout="array")
    est = KMeans(k=3, maxIter=4, seed=1).setFeaturesCol("features")
    est.fit(df)  # warm-up: compiles + staging out of the clock
    events0 = watch.recorder().event_count()
    t0 = profiling.now()
    est.fit(df)
    fit_s = profiling.now() - t0
    events = watch.recorder().event_count() - events0
    assert events > 0, "a fit must feed the flight ring"
    added = events * per_event
    assert added < 0.02 * fit_s, (
        f"always-on recording adds {added * 1e3:.3f} ms over {events} events "
        f"to a {fit_s * 1e3:.1f} ms warm fit "
        f"({100 * added / fit_s:.2f}% > 2%)"
    )


# -- device-memory accounting -------------------------------------------------


def test_phase_memory_attribution_with_injected_sampler(fresh_recorder):
    rec = fresh_recorder
    # fake backend: in_use grows inside the span, peak follows
    samples = iter([(100.0, 100.0), (150.0, 400.0)])
    rec.set_memory_sampler(lambda: next(samples, (150.0, 400.0)))
    with profiling.span("w.mem.phase"):
        pass
    mem = rec.phase_memory()
    assert mem["w.mem.phase"]["count"] == 1
    assert mem["w.mem.phase"]["peak_bytes"] == 400.0
    assert mem["w.mem.phase"]["sum_delta_bytes"] == 300.0  # peak - entry
    telem = rec.telemetry_memory()
    assert telem["mem.phase.w.mem.phase"]["peak_bytes"] == 400.0
    assert "mem.host" in telem  # RSS watermark always available


def _counting_sampler(rec):
    calls = []

    def sampler():
        calls.append(profiling.now())
        return (100.0 + len(calls), 200.0 + len(calls))

    rec.set_memory_sampler(sampler)
    return calls


def test_nested_spans_take_no_memory_sample_and_the_outermost_takes_two(
    fresh_recorder,
):
    calls = _counting_sampler(fresh_recorder)
    before = profiling.counter("watch.mem_samples")
    with profiling.span("w.mem.outer"):
        for _ in range(3):
            with profiling.span("w.mem.inner"):
                with profiling.span("w.mem.innermost"):
                    pass
    assert len(calls) == 2
    assert profiling.counter("watch.mem_samples") - before == 2
    assert set(fresh_recorder.phase_memory()) == {"w.mem.outer"}


def test_spans_that_tile_a_thread_share_the_sample_at_their_boundary(
    fresh_recorder, monkeypatch
):
    calls = _counting_sampler(fresh_recorder)
    for name in ("w.mem.a", "w.mem.b", "w.mem.c"):
        with profiling.span(name):
            pass
    # a's open and one close each: b and c open on their neighbour's close
    assert len(calls) == 4
    # the snapshot a fit takes right after a close rides that close's sample
    telem = fresh_recorder.telemetry_memory()
    assert len(calls) == 4 and telem["mem.hbm"]["peak_bytes"] == 204.0
    assert set(fresh_recorder.phase_memory()) == {"w.mem.a", "w.mem.b", "w.mem.c"}
    # an opening further than _MEM_SHARE_S from the last sample reads anew
    monkeypatch.setattr(watch, "_MEM_SHARE_S", 0.0)
    with profiling.span("w.mem.d"):
        pass
    assert len(calls) == 6


def test_telemetry_snapshot_carries_and_merges_memory(fresh_recorder):
    rec = fresh_recorder
    rec.set_memory_sampler(lambda: (10.0, 20.0))
    with profiling.span("w.mem.fit"):
        pass
    profiling.reset_phase_times()
    snap = profiling.TelemetrySnapshot.capture(rank=0)
    assert "mem.phase.w.mem.fit" in snap.memory
    a = profiling.TelemetrySnapshot(
        memory={"mem.hbm": {"count": 1, "peak_bytes": 70.0,
                            "sum_delta_bytes": 30.0}},
        meta={"ranks": [0]},
    )
    b = profiling.TelemetrySnapshot(
        memory={"mem.hbm": {"count": 2, "peak_bytes": 50.0,
                            "sum_delta_bytes": 25.0}},
        meta={"ranks": [1]},
    )
    m = a.merge(b)
    # watermark algebra: counts sum, peaks MAX (worst rank), deltas sum
    assert m.memory["mem.hbm"] == {
        "count": 3, "peak_bytes": 70.0, "sum_delta_bytes": 55.0,
    }
    assert a.merge(b) == b.merge(a)
    rt = profiling.TelemetrySnapshot.from_dict(
        json.loads(json.dumps(m.to_dict()))
    )
    assert rt == m  # memory survives the Spark wire


def test_executable_cache_stats_shape():
    from spark_rapids_ml_tpu.ops import precompile

    stats = precompile.executable_cache_stats()
    assert set(stats) == {"entries", "in_flight", "est_code_bytes", "kernels"}
    assert stats["entries"] >= 0
    for name, k in stats["kernels"].items():
        assert isinstance(name, str)
        assert k["entries"] >= 1
        assert isinstance(k["bucket_geometries"], list)


# -- health surface: serving states + SLO + gauges ----------------------------


def test_server_lifecycle_states_and_slo_health(model_zoo, monkeypatch):
    from spark_rapids_ml_tpu.serving import DRAINING, READY, ModelServer

    model, X = model_zoo("kmeans")
    with ModelServer("w_km", model, max_batch=16, max_wait_ms=1) as srv:
        assert srv.state() == READY
        for i in range(8):
            srv.predict(X[i])
        # generous SLO: everything attains
        monkeypatch.setenv("SRML_SERVE_SLO_MS", "60000")
        h = srv.health()
        assert h["state"] == READY
        assert h["attainment"] == 1.0 and h["burn"] == 0.0
        assert h["window_count"] >= 8 and h["p99_ms"] is not None
        # impossible SLO: full burn -> DEGRADED (state stays READY inside;
        # DEGRADED is an SLO verdict, not a lifecycle transition)
        monkeypatch.setenv("SRML_SERVE_SLO_MS", "0.000001")
        h = srv.health()
        assert h["state"] == "DEGRADED" and h["burn"] > 0.9
        # no SLO configured: vacuous attainment
        monkeypatch.delenv("SRML_SERVE_SLO_MS")
        assert srv.health()["attainment"] == 1.0
        srv.drain()
        assert srv.state() == DRAINING


def test_wedged_server_flips_unhealthy_and_sheds_then_recovers(
    model_zoo, monkeypatch
):
    """The PASSIVE wedge contract (pre-shield behavior, still the policy
    when the restart budget is zero): UNHEALTHY + shed while wedged, lazy
    recovery when the blocked dispatch finally returns.
    SRML_SERVE_MAX_RESTARTS=0 pins it; the ACTING watchdog (supersede +
    supervised restart) is gated in test_serving.py."""
    from spark_rapids_ml_tpu.serving import (
        READY,
        UNHEALTHY,
        ModelServer,
        ServerUnhealthy,
    )

    monkeypatch.setenv("SRML_SERVE_MAX_RESTARTS", "0")
    model, X = model_zoo("kmeans")
    srv = ModelServer("w_wedge", model, max_batch=16, max_wait_ms=1)
    try:
        release = threading.Event()
        real_call = srv._entry.call

        def wedged_call(batch):
            release.wait(30.0)
            return real_call(batch)

        srv._entry.call = wedged_call
        monkeypatch.setenv("SRML_WATCH_STALL_S", "0.2")
        fut = srv.submit(X[0])  # the worker blocks inside this dispatch
        deadline = time.monotonic() + 10.0
        while srv.state() != UNHEALTHY and time.monotonic() < deadline:
            time.sleep(0.05)
        assert srv.state() == UNHEALTHY
        with pytest.raises(ServerUnhealthy):  # shed, don't queue
            srv.submit(X[1])
        assert profiling.counter("serving.w_wedge.unhealthy") >= 1
        release.set()  # the dispatch comes back: recover
        assert fut.result(timeout=30.0)
        deadline = time.monotonic() + 10.0
        while srv.state() != READY and time.monotonic() < deadline:
            time.sleep(0.05)
        assert srv.state() == READY
        assert profiling.counter("serving.w_wedge.recovered") >= 1
    finally:
        release.set()
        monkeypatch.setenv("SRML_WATCH_STALL_S", "0")
        srv.shutdown(drain=False)


def test_registry_health_rolls_up_worst_state(model_zoo):
    from spark_rapids_ml_tpu.serving import ModelRegistry

    model, X = model_zoo("kmeans")
    with ModelRegistry(max_batch=16, max_wait_ms=1) as reg:
        reg.register("w_a", model)
        reg.get("w_a").predict(X[0])
        h = reg.health()
        assert h["state"] == "READY"
        assert h["models"]["w_a"]["state"] == "READY"
        assert h["models"]["w_a"]["attainment"] >= 0
    assert ModelRegistry().health()["state"] == "WARMING"  # empty = idle


def test_health_and_memory_round_trip_export_and_prometheus(model_zoo):
    """The CI acceptance gate in unit form: ModelRegistry.health() + memory
    watermarks flow through export_metrics() (JSON round-trip) and
    render_prometheus() (srml_health / srml_memory_bytes families)."""
    from spark_rapids_ml_tpu.serving import ModelRegistry

    model, X = model_zoo("kmeans")
    with ModelRegistry(max_batch=16, max_wait_ms=1) as reg:
        reg.register("w_rt", model)
        reg.get("w_rt").predict(X[0])
        m = profiling.export_metrics()
        assert json.loads(json.dumps(m)) == m
        g = m["gauges"]
        assert g["health.w_rt.state_code"] == 1.0  # READY
        assert g["health.w_rt.attainment"] >= 0.0
        assert any(k.startswith("mem.host.") for k in g)
        txt = profiling.render_prometheus(m)
        assert "# TYPE srml_health gauge" in txt
        assert "# TYPE srml_memory_bytes gauge" in txt
        assert 'srml_health{name="health.w_rt.state_code"} 1.0' in txt
    # shutdown unregisters the provider: the registry's gauges disappear
    assert not any(
        k.startswith("health.w_rt.")
        for k in profiling.export_metrics()["gauges"]
    )


def test_fit_then_serve_leaves_memory_health_and_ring_events(monkeypatch):
    """The health plane after real work, samplers as installed: a fit's
    telemetry carries the host watermark, a served model reads READY with a
    burn inside [0, 1] under a generous SLO, and the flight ring holds events."""
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.serving import ModelRegistry

    monkeypatch.setenv("SRML_SERVE_SLO_MS", "500")
    X = np.random.default_rng(0).standard_normal((512, 16)).astype(np.float32)
    model = KMeans(k=4, maxIter=5, seed=1).fit(DataFrame.from_numpy(X))
    memory = model.fit_telemetry().memory
    assert "mem.host" in memory, memory
    with ModelRegistry(max_batch=32, max_wait_ms=2) as reg:
        reg.register("w_smoke", model)
        for i in range(16):
            reg.get("w_smoke").predict(X[i])
        h = reg.health()
        assert h["state"] == "READY", h
        km = h["models"]["w_smoke"]
        assert km["attainment"] >= 0 and 0 <= km["burn"] <= 1, km
    assert watch.ring_stats()["events"] > 0


def test_ring_stats_self_description():
    stats = watch.ring_stats()
    assert stats["enabled"] is True
    assert stats["capacity"] > 0 and stats["events"] >= 0
    assert isinstance(stats["open_spans"], dict)
