# srml-scope (SURVEY.md §5: NVTX-range analog via
# jax.profiler.TraceAnnotation + coarse phase logging, reference
# RapidsRowMatrix.scala:62,70 and core.py:583,617): flat phase timers,
# hierarchical spans + Chrome-trace export, mergeable telemetry snapshots,
# and the export surface.
import json
import os
import threading
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import profiling


def test_phase_registry_accumulates():
    profiling.reset_phase_times()
    with profiling.phase("unit.a"):
        pass
    with profiling.phase("unit.a"):
        pass
    with profiling.phase("unit.b"):
        pass
    times = profiling.phase_times()
    assert set(times) == {"unit.a", "unit.b"}
    assert times["unit.a"] >= 0.0


def test_fit_records_phase_times():
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame

    rng = np.random.default_rng(0)
    X = rng.standard_normal((64, 8)).astype(np.float32)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=2)
    est = KMeans(k=3, maxIter=2).setFeaturesCol("features")
    est.fit(df)
    times = est._last_fit_phase_times
    assert "srml.ingest" in times and "srml.fit" in times
    assert times["srml.fit"] > 0.0


def test_forest_fit_records_phase_set():
    """The forest engine's phase timers mirror the knn.*/umap.* sets:
    forest.bin (edges + binning), forest.hist (level-block dispatches),
    forest.route (per-block early-stop flag syncs — where each block's
    routing state resolves), forest.split (the single forest fetch)."""
    from spark_rapids_ml_tpu import RandomForestRegressor
    from spark_rapids_ml_tpu.dataframe import DataFrame

    rng = np.random.default_rng(3)
    X = rng.standard_normal((256, 6))
    y = X @ np.ones(6) + 0.1 * rng.standard_normal(256)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    est = RandomForestRegressor(numTrees=3, maxDepth=3, maxBins=8, seed=1)
    est.fit(df)
    times = est._last_fit_phase_times
    for name in ("forest.bin", "forest.hist", "forest.route", "forest.split"):
        assert name in times and times[name] >= 0.0, (name, times)
    # phase_times prefix filtering (the benchmark reporting idiom)
    profiling.reset_phase_times()
    with profiling.phase("forest.bin"):
        pass
    with profiling.phase("other.x"):
        pass
    assert set(profiling.phase_times("forest.")) == {"forest.bin"}


def test_maybe_trace_writes_profile(tmp_path, monkeypatch):
    # opt-in whole-fit xprof capture via SRML_PROFILE (NCCL_DEBUG analog)
    monkeypatch.setenv(profiling.PROFILE_ENV, str(tmp_path))
    with profiling.maybe_trace("unittrace"):
        np.zeros(4).sum()
    target = tmp_path / "unittrace"
    assert target.is_dir()
    # jax writes a plugins/profile subtree with at least one trace artifact
    contents = [str(p) for p in target.rglob("*") if p.is_file()]
    assert contents, "expected xprof trace files"


def test_maybe_trace_noop_without_env(monkeypatch):
    monkeypatch.delenv(profiling.PROFILE_ENV, raising=False)
    with profiling.maybe_trace("x"):
        pass


def test_counters_are_process_wide_and_resettable():
    import threading

    profiling.reset_counters("test.ctr")
    profiling.incr_counter("test.ctr.a")
    profiling.incr_counter("test.ctr.a", 2)

    # increments from another thread land in the same registry (the
    # precompile worker-pool contract)
    t = threading.Thread(target=lambda: profiling.incr_counter("test.ctr.b"))
    t.start()
    t.join()
    assert profiling.counter("test.ctr.a") == 3
    assert profiling.counters("test.ctr") == {
        "test.ctr.a": 3,
        "test.ctr.b": 1,
    }
    profiling.reset_counters("test.ctr")
    assert profiling.counters("test.ctr") == {}


def test_percentiles_over_recorded_durations():
    profiling.reset_durations("t.lat")
    for ms in range(1, 101):  # 1..100 ms
        profiling.record_duration("t.lat.a", ms / 1000.0)
    stats = profiling.percentiles("t.lat.a")
    assert stats["count"] == 100
    assert abs(stats["p50"] - 0.0505) < 1e-9  # numpy linear interpolation
    assert stats["p95"] <= stats["p99"] <= stats["max"] == 0.1
    assert abs(stats["mean"] - 0.0505) < 1e-9
    profiling.reset_durations("t.lat")
    assert profiling.percentiles("t.lat") == {}


def test_percentiles_merge_prefix_and_cross_thread():
    import threading

    profiling.reset_durations("t.merge")
    profiling.record_duration("t.merge.a", 1.0)
    # worker-thread samples land in the same process-wide registry (the
    # serving dispatch-thread contract)
    t = threading.Thread(target=lambda: profiling.record_duration("t.merge.b", 3.0))
    t.start()
    t.join()
    merged = profiling.percentiles("t.merge")
    assert merged["count"] == 2 and merged["p50"] == 2.0
    only_a = profiling.percentiles("t.merge.a")
    assert only_a["count"] == 1 and only_a["p50"] == 1.0
    assert profiling.durations("t.merge") == {
        "t.merge.a": [1.0],
        "t.merge.b": [3.0],
    }
    profiling.reset_durations("t.merge")


def test_metric_ttl_evicts_stale_series(monkeypatch):
    """SRML_METRIC_TTL_S: a series untouched for the TTL is evicted by the
    amortized sweep inside record_duration, so a long-lived serving process
    cycling through model names cannot leak series (default: off)."""
    monkeypatch.setenv(profiling.METRIC_TTL_ENV, "0.05")
    monkeypatch.setattr(profiling, "_TTL_SWEEP_EVERY", 2)
    profiling.reset_durations("t.ttl")
    profiling.record_duration("t.ttl.stale", 1.0)
    import time as _time

    _time.sleep(0.12)  # let t.ttl.stale age past the TTL
    for _ in range(4):  # enough records to cross the sweep cadence
        profiling.record_duration("t.ttl.live", 2.0)
    series = profiling.durations("t.ttl")
    assert "t.ttl.live" in series and "t.ttl.stale" not in series
    # TTL off (default): nothing is ever evicted
    monkeypatch.setenv(profiling.METRIC_TTL_ENV, "")
    profiling.record_duration("t.ttl.stale", 1.0)
    _time.sleep(0.06)
    for _ in range(4):
        profiling.record_duration("t.ttl.live", 2.0)
    assert "t.ttl.stale" in profiling.durations("t.ttl")
    profiling.reset_durations("t.ttl")


def test_series_stats_reports_registry_footprint():
    profiling.reset_durations("t.ss")
    for _ in range(3):
        profiling.record_duration("t.ss.a", 0.01)
    stats = profiling.series_stats()
    assert stats["series_count"] >= 1 and stats["ring_samples"] >= 3
    assert stats["est_bytes"] >= stats["ring_samples"] * 8
    a = stats["series"]["t.ss.a"]
    assert a["ring_samples"] == 3 and a["lifetime_count"] == 3
    profiling.reset_durations("t.ss")


def test_duration_cap_is_a_ring_buffer(monkeypatch):
    monkeypatch.setattr(profiling, "_DURATION_CAP", 4)
    profiling.reset_durations("t.ring")
    for i in range(6):
        profiling.record_duration("t.ring", float(i))
    series = profiling.durations("t.ring")["t.ring"]
    assert len(series) == 4  # capped
    assert sorted(series) == [2.0, 3.0, 4.0, 5.0]  # oldest overwritten
    profiling.reset_durations("t.ring")


# -- hierarchical spans / trace export ---------------------------------------


def test_span_nesting_and_thread_attribution():
    """Span records carry parent ids (per-thread stack) and the recording
    thread's ident/name — the hierarchy the Chrome-trace export renders."""
    profiling.reset_phase_times()
    with profiling.collect_spans():
        with profiling.span("t.outer"):
            with profiling.span("t.inner", block=7) as sp:
                sp.set(bytes=123)
        def worker():
            with profiling.span("t.worker"):
                pass
        th = threading.Thread(target=worker, name="unit-worker")
        th.start()
        th.join()
        recs = {r[0]: r for r in profiling.span_records()}
    assert set(recs) == {"t.outer", "t.inner", "t.worker"}
    outer, inner, worker_r = recs["t.outer"], recs["t.inner"], recs["t.worker"]
    # parent: inner's parent_id is outer's span_id; outer and worker are roots
    assert inner[6] == outer[5]
    assert outer[6] == 0 and worker_r[6] == 0
    # timestamps nest: outer contains inner
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
    # thread attribution: the worker span carries ITS thread, not ours
    assert worker_r[3] != outer[3]
    assert worker_r[4] == "unit-worker"
    # attached counters (attrs) survive, including mid-span set()
    assert inner[7] == {"block": 7, "bytes": 123}
    # the flat registry still accumulated (phase() compatibility)
    assert "t.inner" in profiling.phase_times()
    # buffer cleared once the last collection scope exits
    assert profiling.span_records() == []


def test_span_disabled_path_has_zero_overhead(monkeypatch):
    """Spans off => no span records, no per-thread stack, no counters, and
    the null handle (no attrs dict allocated) — the hard zero-cost rule."""
    monkeypatch.delenv(profiling.TRACE_ENV, raising=False)
    counters_before = profiling.counters()
    seen = {}

    def worker():  # a FRESH thread proves no thread-local stack appears
        with profiling.span("t.off", bytes=1) as sp:
            sp.set(rows=2)  # must be a silent no-op
        seen["handle_attrs"] = sp.attrs
        seen["has_stack"] = hasattr(profiling._tls, "span_stack")

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    assert seen["handle_attrs"] is None  # null handle: nothing allocated
    assert seen["has_stack"] is False
    assert profiling.span_records() == []
    assert profiling.counters() == counters_before
    with profiling.trace_session("t-noop") as path:  # env unset -> no-op
        assert path is None


def test_trace_session_writes_valid_chrome_trace(tmp_path, monkeypatch):
    monkeypatch.setenv(profiling.TRACE_ENV, str(tmp_path))
    with profiling.trace_session("unit sess") as path:
        assert path is not None and str(tmp_path) in path
        with profiling.span("t.a", rows=4):
            with profiling.span("t.b"):
                pass
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    complete = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in complete} == {"t.a", "t.b"}
    for e in complete:
        # the Chrome trace-event contract Perfetto loads: microsecond
        # ts/dur, pid/tid, name, args
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(m["name"] == "thread_name" for m in meta)
    by = {e["name"]: e for e in complete}
    assert by["t.b"]["args"]["parent_id"] == by["t.a"]["args"]["span_id"]
    assert by["t.a"]["args"]["rows"] == 4
    # session tag is sanitized into the filename
    assert os.path.basename(path).startswith("unit-sess-")


def test_trace_session_records_its_clock_beside_the_wall_clock(tmp_path, monkeypatch):
    """What lays a span file over an xprof capture of the same run: the span
    clock's epoch, and its reading beside the wall clock at both ends."""
    monkeypatch.setenv(profiling.TRACE_ENV, str(tmp_path))
    wall0 = time.time()
    with profiling.trace_session("clock") as path:
        with profiling.span("t.clock"):
            time.sleep(0.002)
    with open(path) as f:
        doc = json.load(f)
    clock = doc["metadata"]["clock"]
    start, end = clock["start"], clock["end"]
    assert wall0 <= start["unix_s"] <= end["unix_s"] <= time.time()
    # one offset between the two clocks, read twice
    assert (end["unix_s"] - end["perf_counter_s"]) == pytest.approx(
        start["unix_s"] - start["perf_counter_s"], abs=5e-3
    )
    (event,) = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    at = clock["epoch_perf_counter_s"] + event["ts"] * 1e-6    # back on the span clock
    assert start["perf_counter_s"] <= at <= end["perf_counter_s"]


# -- telemetry snapshots ------------------------------------------------------


def _snap(**kw):
    return profiling.TelemetrySnapshot(**kw)


def test_telemetry_merge_is_commutative_and_associative():
    a = _snap(
        phases={"f.x": {"count": 1, "total_s": 2.0}},
        counters={"c.a": 3},
        durations={"d.l": {"count": 2, "sum_s": 1.0, "min_s": 0.25, "max_s": 0.75}},
        meta={"ranks": [0]},
    )
    b = _snap(
        phases={"f.x": {"count": 2, "total_s": 1.5}, "f.y": {"count": 1, "total_s": 0.5}},
        counters={"c.a": 1, "c.b": 7},
        durations={"d.l": {"count": 1, "sum_s": 3.0, "min_s": 3.0, "max_s": 3.0}},
        meta={"ranks": [1]},
    )
    c = _snap(counters={"c.b": 2}, meta={"ranks": [2]})
    assert a.merge(b) == b.merge(a)
    assert a.merge(b).merge(c) == a.merge(b.merge(c))
    m = a.merge(b)
    assert m.phases["f.x"] == {"count": 3, "total_s": 3.5}
    assert m.counters == {"c.a": 4, "c.b": 7}
    assert m.durations["d.l"] == {
        "count": 3, "sum_s": 4.0, "min_s": 0.25, "max_s": 3.0,
    }
    assert m.meta["ranks"] == [0, 1]
    # wire round-trip (the Spark result path ships snapshots as JSON)
    rt = profiling.TelemetrySnapshot.from_dict(
        json.loads(json.dumps(m.to_dict()))
    )
    assert rt == m
    assert m.phase_seconds("f.") == {"f.x": 3.5, "f.y": 0.5}


def test_telemetry_capture_deltas_counters():
    profiling.reset_counters("t.cap")
    profiling.reset_phase_times()
    before = profiling.counters()
    with profiling.phase("t.cap.phase"):
        profiling.incr_counter("t.cap.n", 5)
    snap = profiling.TelemetrySnapshot.capture(before, rank=3)
    assert snap.counters.get("t.cap.n") == 5
    # counters that did not move during the window are absent (delta form)
    assert all(k.startswith("t.cap") or v != 0 for k, v in snap.counters.items())
    assert snap.phases["t.cap.phase"]["count"] == 1
    assert snap.meta["ranks"] == [3]
    profiling.reset_counters("t.cap")


def test_local_fit_attaches_telemetry():
    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame

    rng = np.random.default_rng(1)
    X = rng.standard_normal((96, 6)).astype(np.float32)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=2)
    model = KMeans(k=2, maxIter=2).setFeaturesCol("features").fit(df)
    t = model.fit_telemetry()
    assert t is not None
    assert t.phases["srml.fit"]["count"] == 1
    assert t.phases["srml.fit"]["total_s"] > 0.0
    assert t.meta["ranks"] == [0]
    # the telemetry key never leaks into the model attribute dict
    from spark_rapids_ml_tpu.core import TELEMETRY_ATTR

    assert TELEMETRY_ATTR not in model._get_model_attributes()


def test_fit_and_serve_sessions_write_trace_files(tmp_path, monkeypatch):
    """End to end through the public path: with SRML_TRACE_DIR set, a fit and
    a serving session each leave a Chrome trace-event file of complete span
    events, and the registry's telemetry counts the requests it served."""
    import glob

    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.serving import ModelRegistry

    monkeypatch.setenv(profiling.TRACE_ENV, str(tmp_path))
    X = np.random.default_rng(0).standard_normal((512, 16)).astype(np.float32)
    model = KMeans(k=4, maxIter=5, seed=1).fit(DataFrame.from_numpy(X))
    assert model.fit_telemetry().phases["srml.fit"]["count"] == 1
    with ModelRegistry(max_batch=32, max_wait_ms=2) as reg:
        reg.register("trace_km", model)
        for i in range(8):
            reg.get("trace_km").predict(X[i])
        counters = reg.telemetry().counters
        assert counters.get("serving.trace_km.requests", 0) >= 8, counters
    traces = glob.glob(str(tmp_path / "*.trace.json"))
    tags = {os.path.basename(p).split("-")[0] for p in traces}
    assert {"fit", "serve"} <= tags, traces
    for path in traces:
        with open(path) as f:
            doc = json.load(f)
        complete = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert complete, f"{path}: no complete span events"
        for e in complete:
            assert set(e) >= {"name", "ts", "dur", "pid", "tid", "args"}, e
    assert "srml_counter{" in profiling.render_prometheus()


# -- export surface -----------------------------------------------------------


def test_export_metrics_roundtrips_json():
    profiling.reset_durations("t.em")
    profiling.reset_counters("t.em")
    profiling.incr_counter("t.em.c", 2)
    for v in (0.01, 0.02, 0.03):
        profiling.record_duration("t.em.lat", v)
    m = profiling.export_metrics("t.em")
    assert json.loads(json.dumps(m)) == m
    assert m["schema"] == "srml-scope/v1"
    assert m["counters"]["t.em.c"] == 2
    assert m["durations"]["t.em.lat"]["count"] == 3
    profiling.reset_durations("t.em")
    profiling.reset_counters("t.em")


def test_render_prometheus_exposition():
    m = {
        "counters": {"pre.compile": 4},
        "phases": {"srml.fit": {"count": 1, "total_s": 2.5}},
        "durations": {
            "serve.m.latency": {
                "count": 10, "mean": 0.02, "p50": 0.01, "p95": 0.05,
                "p99": 0.09, "max": 0.1,
            }
        },
    }
    txt = profiling.render_prometheus(m)
    assert 'srml_counter{name="pre.compile"} 4' in txt
    assert 'srml_phase_seconds_total{name="srml.fit"} 2.5' in txt
    assert 'srml_duration_seconds{name="serve.m.latency",quantile="0.5"} 0.01' in txt
    assert 'srml_duration_seconds_count{name="serve.m.latency"} 10' in txt
    # every non-comment line is name{labels} value — the exposition shape
    for line in txt.strip().splitlines():
        if not line.startswith("#"):
            assert " " in line and line.startswith("srml_"), line


def test_now_is_monotonic():
    a = profiling.now()
    b = profiling.now()
    assert b >= a


def test_event_log_order_and_reset():
    profiling.reset_events()
    profiling.record_event("t.dispatch", block=0)
    profiling.record_event("t.dispatch", block=1)
    profiling.record_event("t.collect", block=0)
    ev = profiling.events("t.")
    assert ev == [
        ("t.dispatch", {"block": 0}),
        ("t.dispatch", {"block": 1}),
        ("t.collect", {"block": 0}),
    ]
    assert profiling.events("t.collect") == [("t.collect", {"block": 0})]
    profiling.reset_events()
    assert profiling.events() == []
