# srml-serve gates (docs/serving.md): dynamic micro-batching, bucket-warmed
# executables (steady state = zero new compiles), admission control with
# fast ServerOverloaded rejection, per-request deadlines, clean drain, the
# registry's load path over core persistence, and serving-vs-transform
# output equivalence for every served model class.
#
# Counter-based assertions follow the PR2-4 idiom: profiling counters and
# duration percentiles, never wall-clock thresholds.
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.serving import (
    ModelRegistry,
    ModelServer,
    RequestTimeout,
    ServerOverloaded,
    ServingEntry,
    bucket_rows,
    serve_buckets,
)

SERVED_ARMS = ["kmeans", "pca", "linreg", "logreg", "rf_clf", "rf_reg"]


# -- a controllable fake model for policy tests ------------------------------


class _EchoModel:
    """Servable stub: echoes row sums; optional per-dispatch delay lets the
    policy tests hold the worker busy to build a backlog deterministically."""

    def __init__(self, n_cols=4, delay_s=0.0):
        self.n_cols = n_cols
        self.delay_s = delay_s
        self.calls = []

    def _serving_entry(self, mesh=None):
        def call(batch):
            if self.delay_s:
                time.sleep(self.delay_s)
            self.calls.append(batch.shape[0])
            return {"echo": batch.sum(axis=1)}

        return ServingEntry(
            name="serve.echo",
            n_cols=self.n_cols,
            dtype=np.dtype(np.float32),
            out_cols=["echo"],
            call=call,
            warm=lambda buckets: [],
        )


# -- bucket rules -------------------------------------------------------------


def test_bucket_rules():
    assert bucket_rows(1, 256) == 16  # SRML_SERVE_MIN_BUCKET default
    assert bucket_rows(17, 256) == 32
    assert bucket_rows(256, 256) == 256
    assert bucket_rows(300, 256) == 256  # clamped to the max-batch bucket
    assert serve_buckets(256) == [16, 32, 64, 128, 256]
    assert serve_buckets(100) == [16, 32, 64, 128]
    assert serve_buckets(8) == [16]


def test_submit_validation():
    srv = ModelServer("echo_val", _EchoModel(), max_batch=8, max_wait_ms=1)
    try:
        with pytest.raises(ValueError, match="features must be"):
            srv.submit(np.zeros((2, 3), np.float32))  # wrong width
        with pytest.raises(ValueError, match="empty request"):
            srv.submit(np.zeros((0, 4), np.float32))
        with pytest.raises(ValueError, match="exceeds max_batch"):
            srv.submit(np.zeros((9, 4), np.float32))
    finally:
        srv.shutdown()


# -- batching policy ----------------------------------------------------------


def test_single_row_requests_coalesce_into_one_device_batch():
    model = _EchoModel(delay_s=0.05)
    srv = ModelServer("echo_coal", model, max_batch=64, max_wait_ms=20)
    try:
        before = profiling.counters("serving.echo_coal.")
        # first request occupies the worker (delay_s); the rest pile up in
        # the queue and MUST flush as one multi-request batch
        futs = [
            srv.submit(np.full(4, i, np.float32)) for i in range(8)
        ]
        results = [f.result(timeout=30) for f in futs]
        delta = profiling.counter_deltas(before, "serving.echo_coal.")
        assert delta["serving.echo_coal.requests"] == 8
        assert delta["serving.echo_coal.batches"] < 8  # coalescing happened
        assert delta.get("serving.echo_coal.coalesced_batches", 0) >= 1
        # batch occupancy > 1 observed by the engine's own histogram
        occ = profiling.percentiles("serve.echo_coal.occupancy")
        assert occ["max"] > 1
        # scatter is per request, in order, with the right values
        for i, r in enumerate(results):
            assert r["echo"].shape == (1,)
            assert r["echo"][0] == pytest.approx(4.0 * i)
    finally:
        srv.shutdown()


def test_deadline_flush_of_partial_batch():
    srv = ModelServer("echo_partial", _EchoModel(), max_batch=64, max_wait_ms=5)
    try:
        before = profiling.counters("serving.echo_partial.")
        out = srv.predict(np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        delta = profiling.counter_deltas(before, "serving.echo_partial.")
        # one lone request under max_batch flushed at the deadline
        assert delta.get("serving.echo_partial.flush_deadline", 0) >= 1
    finally:
        srv.shutdown()


def test_full_batch_flushes_without_waiting():
    srv = ModelServer(
        "echo_full", _EchoModel(delay_s=0.05), max_batch=4, max_wait_ms=10_000
    )
    try:
        futs = [srv.submit(np.ones((2, 4), np.float32)) for _ in range(4)]
        for f in futs:
            f.result(timeout=30)  # would hang for 10 s if deadline-bound
        delta = profiling.counters("serving.echo_full.")
        assert delta.get("serving.echo_full.flush_full", 0) >= 1
    finally:
        srv.shutdown()


def test_padding_to_pow2_bucket():
    model = _EchoModel()
    srv = ModelServer("echo_pad", model, max_batch=64, max_wait_ms=1)
    try:
        srv.predict(np.ones((3, 4), np.float32))
    finally:
        srv.shutdown()
    # warmup dispatches every bucket (16, 32, 64); traffic adds one 16-pad
    assert model.calls[:3] == [16, 32, 64]
    assert model.calls[-1] == 16  # 3 rows padded to the min bucket


# -- admission control / deadlines -------------------------------------------


def test_overload_rejects_fast_instead_of_blocking():
    model = _EchoModel(delay_s=0.2)
    srv = ModelServer(
        "echo_over", model, max_batch=4, max_wait_ms=1, queue_depth=8
    )
    try:
        before = profiling.counters("serving.echo_over.")
        futs = []
        rejected = 0
        # worker is busy 200 ms per dispatch; queue bound is 8 rows — the
        # burst MUST hit ServerOverloaded, and the submit path must return
        # immediately either way (no blocking admission)
        t0 = time.perf_counter()
        for _ in range(64):
            try:
                futs.append(srv.submit(np.ones(4, np.float32)))
            except ServerOverloaded:
                rejected += 1
        submit_wall = time.perf_counter() - t0
        assert rejected > 0
        assert submit_wall < 1.0  # 64 admissions/rejections, zero dispatch waits
        delta = profiling.counter_deltas(before, "serving.echo_over.")
        assert delta["serving.echo_over.rejected"] == rejected
        for f in futs:
            f.result(timeout=30)  # admitted requests still complete
    finally:
        srv.shutdown()


def test_request_deadline_expires_in_queue():
    model = _EchoModel(delay_s=0.25)
    srv = ModelServer("echo_to", model, max_batch=2, max_wait_ms=1)
    try:
        first = srv.submit(np.ones((2, 4), np.float32))  # occupies the worker
        doomed = srv.submit(np.ones(4, np.float32), timeout_ms=50.0)
        survivor = srv.submit(np.ones(4, np.float32))  # no deadline
        assert first.result(timeout=30)
        with pytest.raises(RequestTimeout):
            doomed.result(timeout=30)
        assert survivor.result(timeout=30)["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("serving.echo_to.timeouts") >= 1
    finally:
        srv.shutdown()


def test_drain_and_shutdown_are_clean():
    srv = ModelServer("echo_drain", _EchoModel(delay_s=0.05), max_batch=4, max_wait_ms=50)
    futs = [srv.submit(np.ones(4, np.float32)) for _ in range(6)]
    srv.drain()  # flushes the partial batch immediately (quiescence)
    for f in futs:
        assert f.done()
    with pytest.raises(RuntimeError, match="shut down"):
        srv.submit(np.ones(4, np.float32))
    srv.shutdown()
    assert not srv._worker.is_alive()


def test_dispatch_error_fails_the_batch_not_the_server():
    class _Flaky(_EchoModel):
        def _serving_entry(self, mesh=None):
            entry = super()._serving_entry(mesh)
            calls = {"n": 0}
            inner = entry.call

            def call(batch):
                calls["n"] += 1
                if calls["n"] == 4:  # first post-warmup dispatch fails
                    raise RuntimeError("boom")
                return inner(batch)

            entry.call = call
            return entry

    srv = ModelServer("echo_flaky", _Flaky(), max_batch=64, max_wait_ms=1)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            srv.predict(np.ones(4, np.float32))
        assert profiling.counter("serving.echo_flaky.errors") == 1
        # the worker survives and serves the next request
        out = srv.predict(np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
    finally:
        srv.shutdown()


# -- srml-shield: self-healing serving (docs/robustness.md) -------------------


def _wait_state(srv, want, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if srv.state() == want:
            return True
        time.sleep(0.02)
    return srv.state() == want


def test_injected_worker_death_fails_requests_retryable_and_recovers(
    armed_faults,
):
    """Worker death mid-stream (SRML_FAULTS kill at serving.dispatch): every
    affected request resolves with the typed RETRYABLE ServerRecovering —
    never a hang — and the supervisor restarts the worker back to READY."""
    from spark_rapids_ml_tpu.serving import READY, ServerRecovering

    armed_faults("serving.dispatch:tag=shield_die:call=1:action=kill")
    srv = ModelServer(
        "shield_die", _EchoModel(), max_batch=4, max_wait_ms=5
    )
    try:
        futs = [srv.submit(np.ones(4, np.float32)) for _ in range(3)]
        for f in futs:
            with pytest.raises(ServerRecovering) as exc_info:
                f.result(timeout=30)  # resolves with the typed error, fast
            assert exc_info.value.retryable is True
        assert _wait_state(srv, READY), srv.state()
        assert profiling.counter("serving.shield_die.worker_deaths") == 1
        assert profiling.counter("serving.shield_die.restarts") == 1
        # the recovery window is a recorded duration series
        rec = profiling.percentiles("serve.shield_die.recovery")
        assert rec and rec["count"] >= 1
        # post-recovery the same request succeeds (the retryable contract)
        out = srv.predict(np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert srv.health()["restarts"] == 1
    finally:
        srv.shutdown(drain=False)


def test_worker_death_recovery_adds_zero_new_compiles(model_zoo, armed_faults):
    """The acceptance gate: recovery re-warms buckets from the RETAINED AOT
    executable cache, so a real model's death->restart->serve cycle
    performs ZERO new executable compilations and steady state stays
    clean."""
    from spark_rapids_ml_tpu.serving import READY, ServerRecovering

    model, X = model_zoo("kmeans")
    srv = ModelServer("shield_km", model, max_batch=32, max_wait_ms=2)
    try:
        srv.predict(X[:3])  # healthy traffic first
        # arming RESETS arrival counters (reload), so the next dispatch of
        # this server is call #1 of the new plan
        armed_faults("serving.dispatch:tag=shield_km:call=1:action=kill")
        before = profiling.counters("precompile.")
        with pytest.raises(ServerRecovering):
            srv.predict(X[:3])  # this dispatch dies; future gets typed error
        assert _wait_state(srv, READY), srv.state()
        out = srv.predict(X[:3])  # post-recovery traffic
        assert out["prediction"].shape == (3,)
        delta = profiling.counter_deltas(before, "precompile.")
        assert delta.get("precompile.compile", 0) == 0, delta
        assert delta.get("precompile.fallback", 0) == 0, delta
        srv.drain()
        srv.assert_steady_state()
        assert profiling.counter("serving.shield_km.steady_compiles") == 0
    finally:
        srv.shutdown(drain=False)


def test_wedge_then_recover_via_acting_watchdog(armed_faults, monkeypatch):
    """The PR 8 watchdog wired to ACT: a dispatch wedged past
    SRML_WATCH_STALL_S flips UNHEALTHY, and the supervisor SUPERSEDES the
    stuck worker (its in-flight request gets the typed retryable error)
    and restarts back to READY — the wedged thread's eventual return is a
    harmless no-op exit."""
    from spark_rapids_ml_tpu.serving import READY, ServerRecovering

    monkeypatch.setenv("SRML_WATCH_STALL_S", "0.3")
    armed_faults("serving.dispatch:tag=shield_wedge:call=1:delay=2.5")
    srv = ModelServer(
        "shield_wedge", _EchoModel(), max_batch=4, max_wait_ms=2
    )
    try:
        fut = srv.submit(np.ones(4, np.float32))  # worker wedges 2.5 s
        # wedge detection is lazy: polling state() is what notices, and
        # the restart counter is the proof the watchdog ACTED
        deadline = time.monotonic() + 15.0
        while (
            profiling.counter("serving.shield_wedge.restarts") < 1
            and time.monotonic() < deadline
        ):
            srv.state()
            time.sleep(0.05)
        assert profiling.counter("serving.shield_wedge.restarts") == 1
        assert _wait_state(srv, READY, timeout_s=15.0), srv.state()
        with pytest.raises(ServerRecovering):
            fut.result(timeout=30)
        assert profiling.counter("serving.shield_wedge.unhealthy") >= 1
        assert profiling.counter("serving.shield_wedge.restarts") == 1
        out = srv.predict(np.ones((2, 4), np.float32))
        assert out["echo"].shape == (2,)
        # give the superseded worker time to wake and exit cleanly; the
        # server must still be READY afterwards (no state clobber)
        time.sleep(3.0)
        assert srv.state() == READY
    finally:
        monkeypatch.setenv("SRML_WATCH_STALL_S", "0")
        srv.shutdown(drain=False)


def test_drain_during_recovery_resolves(armed_faults):
    """Queued requests shed by a recovery resolve immediately, so a drain
    racing the restart returns instead of timing out (quiescence counts
    EVERY admitted request, shed or served)."""
    from spark_rapids_ml_tpu.serving import ServerRecovering

    armed_faults("serving.dispatch:tag=shield_drain:call=1:action=kill")
    srv = ModelServer(
        "shield_drain", _EchoModel(delay_s=0.02), max_batch=2, max_wait_ms=1
    )
    try:
        futs = [srv.submit(np.ones(4, np.float32)) for _ in range(4)]
        srv.drain(timeout_s=20.0)  # must NOT raise TimeoutError
        for f in futs:
            assert f.done()
            with pytest.raises((ServerRecovering, RuntimeError)):
                f.result(timeout=0)
    finally:
        srv.shutdown(drain=False)


def test_restart_budget_exhaustion_goes_unhealthy(armed_faults, monkeypatch):
    """Bounded restarts: a server that dies on EVERY dispatch burns its
    budget and lands UNHEALTHY for good — submits then shed with
    ServerUnhealthy (fail over), never an infinite restart storm."""
    from spark_rapids_ml_tpu.serving import (
        UNHEALTHY,
        ServerRecovering,
        ServerUnhealthy,
    )

    monkeypatch.setenv("SRML_SERVE_MAX_RESTARTS", "1")
    armed_faults("serving.dispatch:tag=shield_budget:action=kill")
    srv = ModelServer(
        "shield_budget", _EchoModel(), max_batch=4, max_wait_ms=2
    )
    try:
        from spark_rapids_ml_tpu.serving import READY

        with pytest.raises(ServerRecovering):
            srv.predict(np.ones(4, np.float32))  # death #1: restart
        assert _wait_state(srv, READY), srv.state()
        with pytest.raises(ServerRecovering):
            srv.predict(np.ones(4, np.float32))  # death #2: budget spent
        assert _wait_state(srv, UNHEALTHY), srv.state()
        with pytest.raises((ServerUnhealthy, ServerRecovering)):
            srv.submit(np.ones(4, np.float32))
        assert profiling.counter("serving.shield_budget.restarts") == 1
        assert profiling.counter("serving.shield_budget.worker_deaths") == 2
    finally:
        srv.shutdown(drain=False)


def test_registry_rolls_up_recovering_severity_and_restarts(model_zoo):
    """RECOVERING outranks DRAINING in the registry's worst-state rollup,
    and registry.health() carries the plane-wide restart total."""
    from spark_rapids_ml_tpu.serving import (
        DRAINING,
        ModelRegistry,
        RECOVERING,
        SEVERITY,
        UNHEALTHY,
    )

    assert SEVERITY.index(RECOVERING) > SEVERITY.index(DRAINING)
    assert SEVERITY.index(UNHEALTHY) > SEVERITY.index(RECOVERING)
    model, X = model_zoo("kmeans")
    with ModelRegistry(max_batch=16, max_wait_ms=1) as reg:
        reg.register("shield_roll", model)
        h = reg.health()
        assert h["state"] == "READY"
        assert h["restarts"] == 0
        assert h["models"]["shield_roll"]["restarts"] == 0


# -- real models: equivalence + zero-new-compiles steady state ----------------


def _direct_transform(model, X):
    from spark_rapids_ml_tpu.dataframe import DataFrame

    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=1)
    if model.hasParam("featuresCol"):
        model.setFeaturesCol("features")
    out = model.transform(df)
    return {
        c: np.asarray(list(out.partitions[0][c]))
        for c in out.columns
        if c != "features"
    }


@pytest.mark.parametrize("arm", SERVED_ARMS)
def test_served_outputs_match_batch_transform(arm, model_zoo):
    model, X = model_zoo(arm)
    expect = _direct_transform(model, X[:10])
    with ModelServer(f"eq_{arm}", model, max_batch=32, max_wait_ms=2) as srv:
        got = srv.predict(X[:10])
        assert sorted(got) == sorted(expect)
        for col in expect:
            np.testing.assert_allclose(
                np.asarray(got[col], np.float64),
                np.asarray(expect[col], np.float64),
                rtol=1e-5,
                atol=1e-5,
                err_msg=f"{arm}: column {col!r} diverged from transform()",
            )
        srv.drain()
        srv.assert_steady_state()


def test_served_ann_matches_probed_search(model_zoo):
    """Served ANN == batch probed search (the srml-ann serving gate): the
    online entry answers from the same staged index + cached executables
    the batch kneighbors path dispatches, so ids are exactly equal."""
    model, X = model_zoo("ann")
    _, _, knn_df = model.kneighbors(
        __import__("spark_rapids_ml_tpu.dataframe", fromlist=["DataFrame"])
        .DataFrame.from_numpy(X[:8], num_partitions=1)
    )
    expect_ids = np.asarray(list(knn_df.partitions[0]["indices"]))
    expect_d = np.asarray(list(knn_df.partitions[0]["distances"]))
    with ModelServer("eq_ann", model, max_batch=32, max_wait_ms=2) as srv:
        got = srv.predict(X[:8])
        assert np.array_equal(got["indices"], expect_ids)
        np.testing.assert_allclose(got["distances"], expect_d, rtol=1e-5, atol=1e-5)
        srv.drain()
        srv.assert_steady_state()


def test_served_ivfpq_matches_probed_search(model_zoo):
    """Served IVF-PQ == batch probed+refined search (the srml-pq serving
    gate): the online entry answers from the same staged code index,
    cached probe executables, and host refine the batch kneighbors path
    uses — ids exactly equal, steady state zero new compiles."""
    model, X = model_zoo("ivfpq")
    _, _, knn_df = model.kneighbors(
        __import__("spark_rapids_ml_tpu.dataframe", fromlist=["DataFrame"])
        .DataFrame.from_numpy(X[:8], num_partitions=1)
    )
    expect_ids = np.asarray(list(knn_df.partitions[0]["indices"]))
    expect_d = np.asarray(list(knn_df.partitions[0]["distances"]))
    with ModelServer("eq_ivfpq", model, max_batch=32, max_wait_ms=2) as srv:
        assert srv._entry.info["algorithm"] == "ivfpq"
        got = srv.predict(X[:8])
        assert np.array_equal(got["indices"], expect_ids)
        np.testing.assert_allclose(got["distances"], expect_d, rtol=1e-5, atol=1e-5)
        srv.drain()
        srv.assert_steady_state()


def test_served_knn_matches_kneighbors(model_zoo):
    model, X = model_zoo("knn")
    _, _, knn_df = model.kneighbors(
        __import__("spark_rapids_ml_tpu.dataframe", fromlist=["DataFrame"])
        .DataFrame.from_numpy(X[:8], num_partitions=1)
    )
    expect_ids = np.asarray(list(knn_df.partitions[0]["indices"]))
    expect_d = np.asarray(list(knn_df.partitions[0]["distances"]))
    with ModelServer("eq_knn", model, max_batch=32, max_wait_ms=2) as srv:
        got = srv.predict(X[:8])
        assert np.array_equal(got["indices"], expect_ids)
        np.testing.assert_allclose(got["distances"], expect_d, rtol=1e-5, atol=1e-5)
        srv.drain()
        srv.assert_steady_state()


@pytest.mark.parametrize("arm", ["kmeans", "linreg"])
def test_steady_state_zero_new_compiles(arm, model_zoo):
    """The acceptance gate: after warmup, a mixed stream of single-row and
    small-batch requests across every bucket performs ZERO new executable
    compilations (precompile compile/fallback counters frozen)."""
    model, X = model_zoo(arm)
    name = f"steady_{arm}"
    srv = ModelServer(name, model, max_batch=64, max_wait_ms=2)
    try:
        before = profiling.counters("precompile.")
        rng = np.random.default_rng(3)
        for size in (1, 1, 3, 17, 33, 64, 5, 1, 64):
            srv.predict(
                rng.standard_normal((size, X.shape[1])).astype(np.float32)
            )
        delta = profiling.counter_deltas(before, "precompile.")
        assert delta.get("precompile.compile", 0) == 0, delta
        assert delta.get("precompile.fallback", 0) == 0, delta
        srv.drain()
        srv.assert_steady_state()
        assert profiling.counter(f"serving.{name}.steady_compiles") == 0
    finally:
        srv.shutdown()


def test_latency_percentiles_surface(model_zoo):
    model, X = model_zoo("linreg")
    with ModelServer("slo_lin", model, max_batch=32, max_wait_ms=2) as srv:
        for i in range(12):
            srv.predict(X[i])
        stats = srv.stats()
    lat = stats["latency"]
    assert lat["count"] >= 12
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    assert stats["counters"]["serving.slo_lin.requests"] >= 12
    assert stats["buckets"] == serve_buckets(32)


def test_failed_server_init_releases_trace_scope(model_zoo, monkeypatch, tmp_path):
    """A ModelServer whose warmup fails must close its lifetime trace
    session on the way out — a leaked collection scope would silently
    starve every later fit/search trace in the process."""
    from spark_rapids_ml_tpu import profiling
    import spark_rapids_ml_tpu.serving.engine as engine_mod

    model, X = model_zoo("kmeans")
    monkeypatch.setenv(profiling.TRACE_ENV, str(tmp_path))
    depth0 = profiling._collect_depth

    class Boom(RuntimeError):
        pass

    def bad_warm(self):
        raise Boom("warm failed")

    monkeypatch.setattr(engine_mod.ModelServer, "_warm_buckets", bad_warm)
    with pytest.raises(Boom):
        ModelServer("leaky", model, max_batch=16, max_wait_ms=2)
    assert profiling._collect_depth == depth0


def test_registry_telemetry_snapshot_and_delta(model_zoo):
    """registry.telemetry() is a mergeable TelemetrySnapshot of the serving
    plane; telemetry(since=prev) reports only what moved in the window —
    the scrape/ship surface that works on live Spark executors (snapshots
    merge driver-side like fit telemetry)."""
    model, X = model_zoo("kmeans")
    with ModelRegistry(max_batch=32, max_wait_ms=2) as reg:
        reg.register("telem_km", model)
        reg.get("telem_km").predict(X[:2])
        snap0 = reg.telemetry()
        assert snap0.counters.get("serving.telem_km.requests", 0) >= 1
        assert any(
            k.startswith("serve.telem_km.") for k in snap0.durations
        ), snap0.durations
        for i in range(5):
            reg.get("telem_km").predict(X[i : i + 1])
        delta = reg.telemetry(since=snap0)
        assert delta.counters.get("serving.telem_km.requests") == 5
        lat = delta.durations.get("serve.telem_km.latency")
        assert lat is not None and lat["count"] == 5
        # snapshots from different "processes" merge associatively
        merged = snap0.merge(delta)
        assert merged.counters["serving.telem_km.requests"] == (
            snap0.counters["serving.telem_km.requests"] + 5
        )


# -- registry -----------------------------------------------------------------


def test_registry_register_get_unregister(model_zoo):
    model, X = model_zoo("kmeans")
    with ModelRegistry(max_batch=32, max_wait_ms=2) as reg:
        srv = reg.register("km", model)
        assert "km" in reg and reg.get("km") is srv
        with pytest.raises(ValueError, match="already registered"):
            reg.register("km", model)
        out = reg.get("km").predict(X[:3])
        assert out["prediction"].shape == (3,)
        assert reg.names() == ["km"]
        assert "km" in reg.stats()
        reg.unregister("km")
        with pytest.raises(KeyError):
            reg.get("km")


def test_registry_loads_saved_models_and_serves(model_zoo, tmp_path):
    """The registry's load path: core.load resolves the class from
    metadata, the server warms at load, outputs match the in-memory
    model's transform (the persistence-matrix fixture doing double duty)."""
    with ModelRegistry(max_batch=32, max_wait_ms=2) as reg:
        for arm in ("kmeans", "rf_clf"):
            model, X = model_zoo(arm)
            path = str(tmp_path / arm)
            model.save(path)
            srv = reg.load(arm, path)
            got = srv.predict(X[:6])
            expect = _direct_transform(model, X[:6])
            for col in expect:
                np.testing.assert_allclose(
                    np.asarray(got[col], np.float64),
                    np.asarray(expect[col], np.float64),
                    rtol=1e-5,
                    atol=1e-5,
                )
            srv.drain()
            srv.assert_steady_state()


def test_registry_rejects_estimators(tmp_path):
    from spark_rapids_ml_tpu import KMeans

    est = KMeans(k=2)
    path = str(tmp_path / "est")
    est.save(path)
    with ModelRegistry() as reg:
        with pytest.raises(TypeError, match="not a fitted model"):
            reg.load("est", path)


def test_unservable_model_gives_actionable_error(model_zoo):
    model, _X = model_zoo("umap")  # no serving entry (transform-only)
    with pytest.raises(NotImplementedError, match="no serving entry"):
        ModelServer("umap", model)
