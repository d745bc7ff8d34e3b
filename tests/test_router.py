# srml-router gates (docs/serving.md §router): sliced-mesh replica sets,
# priority-class admission / load shedding, least-outstanding health-aware
# dispatch with failover, depth-2 continuous batching, zero-downtime rolling
# swap, and the router-plane health/Prometheus surface.
#
# The scheduler policy tests are pure-function unit tests (no replicas);
# the router gates use the _EchoModel stub for policy behaviour and the
# model_zoo fixture for the real-compile gates (chaos re-admit warm, swap
# at zero new compiles) — same idiom split as test_serving.py.
import threading
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import profiling, watch
from spark_rapids_ml_tpu.serving import (
    DEGRADED,
    READY,
    UNHEALTHY,
    ModelServer,
    NoReplicaAvailable,
    RequestShed,
    ServerOverloaded,
    Router,
    ServingEntry,
)
from spark_rapids_ml_tpu.serving import scheduler


class _EchoModel:
    """Servable stub (test_serving.py idiom): echoes row sums; optional
    delay holds a replica's worker busy to build backlog deterministically."""

    def __init__(self, n_cols=4, delay_s=0.0, out_col="echo"):
        self.n_cols = n_cols
        self.delay_s = delay_s
        self.out_col = out_col
        self.calls = []

    def _serving_entry(self, mesh=None):
        def call(batch):
            if self.delay_s:
                time.sleep(self.delay_s)
            self.calls.append(batch.shape[0])
            return {self.out_col: batch.sum(axis=1)}

        return ServingEntry(
            name="serve.echo",
            n_cols=self.n_cols,
            dtype=np.dtype(np.float32),
            out_cols=[self.out_col],
            call=call,
            warm=lambda buckets: [],
        )


def _wait(pred, timeout_s=15.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# -- mesh slice carving -------------------------------------------------------


def test_slice_meshes_disjoint_and_oversubscribed():
    import jax

    from spark_rapids_ml_tpu.parallel.mesh import slice_meshes

    n = jax.device_count()
    slices = slice_meshes(2)
    assert len(slices) == 2
    d0 = {d.id for d in slices[0].devices.flat}
    d1 = {d.id for d in slices[1].devices.flat}
    assert d0.isdisjoint(d1)  # the load-bearing property
    assert len(d0) == len(d1) == n // 2
    # more slices than devices: one device each, round-robin
    over = slice_meshes(n + 3)
    assert all(m.devices.size == 1 for m in over)
    with pytest.raises(ValueError, match="n_slices"):
        slice_meshes(0)


def test_slice_meshes_topology_aware_never_straddles_host_group(monkeypatch):
    """Simulated 2x4 topology (SRML_TOPO groups by device ID), shuffled
    device list: the group-major carve (parallel/topology.py) must land
    every replica slice entirely inside ONE host group — a replica
    spanning DCN would pay the slow link on every dispatch."""
    import jax

    from spark_rapids_ml_tpu.parallel.mesh import slice_meshes

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    monkeypatch.setenv("SRML_TOPO", "2:4")
    devs = list(jax.devices())
    shuf = [devs[j] for j in (3, 7, 0, 5, 2, 6, 1, 4)]
    slices = slice_meshes(2, devices=shuf)
    groups = [{d.id // 4 for d in m.devices.flat} for m in slices]
    assert all(len(g) == 1 for g in groups), groups  # no straddling
    assert groups[0] != groups[1]  # and still disjoint across hosts
    # four slices of two: still one host group each
    for m in slice_meshes(4, devices=shuf):
        assert len({d.id // 4 for d in m.devices.flat}) == 1


# -- scheduler policy units (pure functions, no replicas) --------------------


def test_shed_fractions_env_parsing(monkeypatch):
    monkeypatch.delenv(scheduler.SHED_FRACTIONS_ENV, raising=False)
    assert scheduler.shed_fractions() == (1.0, 0.75, 0.5)
    monkeypatch.setenv(scheduler.SHED_FRACTIONS_ENV, "0.9,0.6,0.3")
    assert scheduler.shed_fractions() == (0.9, 0.6, 0.3)
    # short lists repeat the last value; values clamp into [0, 1]
    monkeypatch.setenv(scheduler.SHED_FRACTIONS_ENV, "0.8")
    assert scheduler.shed_fractions() == (0.8, 0.8, 0.8)
    monkeypatch.setenv(scheduler.SHED_FRACTIONS_ENV, "2.0,-1.0")
    assert scheduler.shed_fractions() == (1.0, 0.0, 0.0)
    # junk never raises — admission policy must not take a server down
    monkeypatch.setenv(scheduler.SHED_FRACTIONS_ENV, "lots,of,junk")
    assert scheduler.shed_fractions() == (1.0, 0.75, 0.5)


def test_admission_sheds_in_priority_order(monkeypatch):
    monkeypatch.delenv(scheduler.SHED_FRACTIONS_ENV, raising=False)
    # below every ceiling: everyone admitted
    assert all(scheduler.admit(c, 0.2) for c in scheduler.PRIORITY_CLASSES)
    # half-full: batch sheds first, the rest ride
    assert scheduler.admit("interactive", 0.6)
    assert scheduler.admit("standard", 0.6)
    assert not scheduler.admit("batch", 0.6)
    # three-quarters: standard sheds too
    assert scheduler.admit("interactive", 0.8)
    assert not scheduler.admit("standard", 0.8)
    # hard-full: even interactive sheds (fill < 1.0 fails)
    assert not scheduler.admit("interactive", 1.0)
    with pytest.raises(ValueError, match="unknown priority class"):
        scheduler.admit("junk", 0.0)


class _FakeReplica:
    def __init__(self, name, state, outstanding, queued=0, depth=64):
        self.name = name
        self._state = state
        self._outstanding = outstanding
        self._queued = queued
        self._depth = depth

    def effective_state(self):
        return self._state

    def state(self):
        return self._state

    def outstanding(self):
        return self._outstanding

    def queued_rows(self):
        return self._queued

    def queue_depth(self):
        return self._depth


def test_pick_least_outstanding_then_degraded_then_typed_error():
    r0 = _FakeReplica("m-r0", READY, 5)
    r1 = _FakeReplica("m-r1", READY, 2)
    r2 = _FakeReplica("m-r2", DEGRADED, 0)
    rep, mode = scheduler.pick([r0, r1, r2])
    assert rep is r1 and mode == "ready"  # least outstanding among READY
    # nothing READY: degraded mode beats hard failure
    rep, mode = scheduler.pick([_FakeReplica("m-r0", UNHEALTHY, 0), r2])
    assert rep is r2 and mode == "degraded"
    # nothing dispatchable: the typed retryable error names every state
    with pytest.raises(NoReplicaAvailable, match="m-r0=UNHEALTHY") as ei:
        scheduler.pick([_FakeReplica("m-r0", UNHEALTHY, 0)])
    assert ei.value.retryable is True


def test_aggregate_fill_counts_dark_capacity():
    live = _FakeReplica("m-r0", READY, 0, queued=32, depth=64)
    dark = _FakeReplica("m-r1", UNHEALTHY, 0, queued=0, depth=64)
    # the dark replica's provisioned depth stays in the denominator …
    assert scheduler.aggregate_fill([live, dark]) == pytest.approx(0.25)
    # … so the same backlog on a half-dead set reads as fuller
    assert scheduler.aggregate_fill([live]) == pytest.approx(0.5)
    # no capacity at all reads as hard-full, not a ZeroDivisionError
    assert scheduler.aggregate_fill([]) == 1.0


# -- router: deployment + request path ---------------------------------------


def test_router_serves_replicas_and_routes_requests():
    with Router(replicas=2, max_batch=8, max_wait_ms=1) as router:
        reps = router.serve("echo", _EchoModel())
        assert [r.name for r in reps] == ["echo-r0", "echo-r1"]
        assert "echo" in router and router.names() == ["echo"]
        # replicas sit on DISJOINT mesh slices
        slices = router._sets["echo"].slices
        d0 = {d.id for d in slices[0].devices.flat}
        d1 = {d.id for d in slices[1].devices.flat}
        assert d0.isdisjoint(d1)
        out = router.predict("echo", np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("router.echo.admitted") >= 1
        assert profiling.counter("router.echo.dispatched") >= 1
        with pytest.raises(ValueError, match="already routed"):
            router.serve("echo", _EchoModel())
        with pytest.raises(KeyError, match="no routed model"):
            router.submit("nope", np.ones(4, np.float32))
        with pytest.raises(ValueError, match="unknown priority class"):
            router.serve("echo2", _EchoModel(), priority="junk")
        assert "echo2" not in router  # failed deploy leaves no reservation
        with pytest.raises(ValueError, match="unknown priority class"):
            router.submit("echo", np.ones(4, np.float32), priority="junk")


def test_router_least_outstanding_spreads_load_across_replicas():
    model = _EchoModel(delay_s=0.05)
    with Router(
        replicas=2, inflight_depth=1, max_batch=4, max_wait_ms=1
    ) as router:
        reps = router.serve("spread", model)
        futs = [
            router.submit("spread", np.ones(4, np.float32)) for _ in range(8)
        ]
        for f in futs:
            assert f.result(timeout=30)["echo"][0] == pytest.approx(4.0)
        # with r0's worker busy (50 ms per dispatch) the balancer must have
        # dispatched to BOTH replicas — least-outstanding, not sticky
        dispatched = {
            r.name: profiling.percentiles(f"serve.{r.name}.dispatch").get(
                "count", 0
            )
            for r in reps
        }
        assert all(v > 0 for v in dispatched.values()), dispatched


def test_router_sheds_batch_class_first_under_queue_pressure():
    model = _EchoModel(delay_s=0.05)
    with Router(
        replicas=2, inflight_depth=1, max_batch=4, max_wait_ms=200,
        queue_depth=8,
    ) as router:
        router.serve("shedme", model)
        # build a real backlog: 8 queued rows over 16 aggregate depth = 0.5
        # (the 200 ms coalescing window keeps the rows QUEUED while the
        # admission probes below run)
        futs = []
        try:
            for _ in range(10):
                futs.append(
                    router.submit("shedme", np.ones(4, np.float32))
                )
                if scheduler.aggregate_fill(router.replicas("shedme")) >= 0.5:
                    break
            assert scheduler.aggregate_fill(router.replicas("shedme")) >= 0.5
            # batch traffic sheds at the half-full ceiling …
            with pytest.raises(RequestShed) as ei:
                router.submit(
                    "shedme", np.ones(4, np.float32), priority="batch"
                )
            assert ei.value.retryable is True
            assert profiling.counter("router.shedme.shed_batch") >= 1
            # … while interactive traffic is still admitted
            futs.append(
                router.submit(
                    "shedme", np.ones(4, np.float32), priority="interactive"
                )
            )
        finally:
            for f in futs:
                try:
                    f.result(timeout=30)
                except Exception:  # noqa: BLE001 - only quiescence matters here
                    pass


def test_router_degraded_mode_and_no_replica_typed_error(monkeypatch):
    with Router(replicas=2, max_batch=8, max_wait_ms=1) as router:
        router.serve("deg", _EchoModel())
        # force the SLO-burn verdict: both replicas report DEGRADED — the
        # router serves anyway (single-replica degraded mode, counted)
        monkeypatch.setattr(
            ModelServer, "effective_state", lambda self: DEGRADED
        )
        out = router.predict("deg", np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("router.deg.degraded_mode") >= 1
        assert router.health()["models"]["deg"]["in_rotation"] == 0
        # nothing dispatchable at all: the typed retryable error, resolved
        # through the future (submit itself only sheds/raises KeyError)
        monkeypatch.setattr(
            ModelServer, "effective_state", lambda self: UNHEALTHY
        )
        fut = router.submit("deg", np.ones(4, np.float32))
        with pytest.raises(NoReplicaAvailable) as ei:
            fut.result(timeout=30)
        assert ei.value.retryable is True
        assert profiling.counter("router.deg.no_replica") >= 1


# -- chaos: replica death under load -----------------------------------------


def test_replica_death_is_rerouted_never_client_visible(armed_faults):
    """The router chaos gate (policy half, echo model): kill replica r0's
    worker mid-batch under a stream of requests — every client future
    still resolves with a RESULT (the router absorbs the typed retryable
    failure and re-routes to the survivor), and the killed replica is
    re-admitted after its supervised restart."""
    armed_faults("serving.dispatch:tag=chaos-r0:call=1:action=kill")
    with Router(replicas=2, max_batch=4, max_wait_ms=2) as router:
        reps = router.serve("chaos", _EchoModel())
        futs = [
            router.submit("chaos", np.ones(4, np.float32)) for _ in range(12)
        ]
        for f in futs:  # ZERO client-visible errors — the acceptance bar
            assert f.result(timeout=30)["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("router.chaos.rerouted") >= 1
        assert profiling.counter("serving.chaos-r0.worker_deaths") == 1
        # the dead replica re-admits: supervised restart back to READY,
        # and the router dispatches to it again
        assert _wait(lambda: reps[0].state() == READY), reps[0].state()
        n0 = profiling.percentiles("serve.chaos-r0.dispatch").get("count", 0)
        for _ in range(8):
            router.predict("chaos", np.ones(4, np.float32))
        assert (
            profiling.percentiles("serve.chaos-r0.dispatch").get("count", 0)
            > n0
        )


def test_chaos_readmit_is_warm_zero_new_compiles(model_zoo, armed_faults):
    """The full chaos acceptance gate on a REAL model: with 2 replicas
    under load, killing one produces no client-visible errors, the
    survivor absorbs traffic, and the killed replica re-admits warm —
    zero new executable compilations across death, restart, re-warm, and
    resumed traffic (the retained AOT cache covers its slice's buckets)."""
    model, X = model_zoo("kmeans")
    with Router(replicas=2, max_batch=16, max_wait_ms=2) as router:
        reps = router.serve("ckm", model)
        router.predict("ckm", X[:3])  # healthy traffic, warm verified
        armed_faults("serving.dispatch:tag=ckm-r0:call=1:action=kill")
        before = profiling.counters("precompile.")
        futs = [router.submit("ckm", X[i : i + 2]) for i in range(10)]
        for f in futs:
            assert f.result(timeout=60)["prediction"].shape == (2,)
        assert profiling.counter("router.ckm.rerouted") >= 1
        assert _wait(lambda: reps[0].state() == READY), reps[0].state()
        out = router.predict("ckm", X[:3])  # post-recovery traffic
        assert out["prediction"].shape == (3,)
        delta = profiling.counter_deltas(before, "precompile.")
        assert delta.get("precompile.compile", 0) == 0, delta
        assert delta.get("precompile.fallback", 0) == 0, delta
        for r in router.replicas("ckm"):
            r.drain()
            r.assert_steady_state()


# -- depth-2 continuous batching ---------------------------------------------


def test_depth2_pipeline_overlaps_assembly_with_dispatch():
    """inflight_depth=2 splits assembly from dispatch: under a burst the
    assembler stages the NEXT batch while the worker has one on device,
    so the serve.<n>.inflight_depth series must reach 2 — and outputs
    stay identical to the depth-1 path."""
    model = _EchoModel(delay_s=0.05)
    srv = ModelServer(
        "d2", model, max_batch=4, max_wait_ms=1, inflight_depth=2
    )
    try:
        assert srv.inflight_depth == 2
        assert srv.stats()["inflight_depth"] == 2
        futs = [srv.submit(np.ones(4, np.float32)) for _ in range(10)]
        outs = [f.result(timeout=30)["echo"][0] for f in futs]
        assert outs == pytest.approx([4.0] * 10)
        depths = profiling.durations("serve.d2.inflight_depth").get(
            "serve.d2.inflight_depth", []
        )
        assert depths and max(depths) >= 2.0, depths
    finally:
        srv.shutdown()


def test_depth2_drain_and_shutdown_resolve_everything():
    model = _EchoModel(delay_s=0.02)
    srv = ModelServer(
        "d2drain", model, max_batch=4, max_wait_ms=1, inflight_depth=2
    )
    futs = [srv.submit(np.ones(4, np.float32)) for _ in range(9)]
    srv.drain()
    srv.shutdown()
    # a drained depth-2 server resolved EVERY admitted request (none
    # stranded in the assembly pipe)
    assert all(f.done() for f in futs)
    assert [f.result(timeout=0)["echo"][0] for f in futs] == (
        pytest.approx([4.0] * 9)
    )


def test_depth2_worker_death_flushes_pipe_and_recovers(armed_faults):
    """Depth-2 recovery: a worker death fails the on-device batch AND any
    assembled-but-undispatched batches with the typed retryable error
    (never a hang), the superseded assembler exits without consuming the
    new generation's work, and the restarted pipeline serves again."""
    from spark_rapids_ml_tpu.serving import ServerRecovering

    armed_faults("serving.dispatch:tag=d2die:call=2:action=kill")
    model = _EchoModel(delay_s=0.05)
    srv = ModelServer(
        "d2die", model, max_batch=4, max_wait_ms=1, inflight_depth=2
    )
    try:
        srv.predict(np.ones(4, np.float32))  # call 1 survives
        futs = [srv.submit(np.ones(4, np.float32)) for _ in range(8)]
        resolved = 0
        for f in futs:
            try:
                f.result(timeout=30)
                resolved += 1
            except ServerRecovering:
                resolved += 1
        assert resolved == len(futs)  # typed error or result — no hangs
        assert _wait(lambda: srv.state() == READY), srv.state()
        out = srv.predict(np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("serving.d2die.restarts") == 1
    finally:
        srv.shutdown(drain=False)


def test_batcher_cancelled_sentinel_leaves_queue_intact():
    from spark_rapids_ml_tpu.serving.batcher import CANCELLED, MicroBatcher

    b = MicroBatcher(
        n_cols=4,
        dtype=np.dtype(np.float32),
        counter_ns="serving.cansent",
        max_batch=8,
        max_wait_ms=1,
        queue_depth=64,
    )
    fut = b.submit(np.ones((1, 4), np.float32))
    # a superseded consumer leaves WITHOUT consuming …
    assert b.take(cancelled=lambda: True) is CANCELLED
    # … so the successor generation still gets the queued request
    batch, _reason = b.take()
    assert len(batch) == 1
    from spark_rapids_ml_tpu.serving.batcher import resolve_future

    resolve_future(batch[0].future, {"ok": np.ones(1)})
    assert fut.result(timeout=5)
    b.stop()


def test_batcher_hold_keeps_deadline_expired_batch_open():
    """take(hold=...) — iteration-level continuous batching: while the
    depth>1 staging slot is occupied a deadline-expired partial batch
    stays open to late arrivals (full/drain still flush immediately), and
    kick() releases a held take the moment the slot frees."""
    import threading

    from spark_rapids_ml_tpu.serving.batcher import MicroBatcher

    b = MicroBatcher(
        n_cols=4,
        dtype=np.dtype(np.float32),
        counter_ns="serving.holdopen",
        max_batch=4,
        max_wait_ms=1,
        queue_depth=64,
    )
    held = threading.Event()
    held.set()
    out = {}

    def consume():
        out["batch"], out["reason"] = b.take(hold=held.is_set)

    b.submit(np.ones((1, 4), np.float32))
    t = threading.Thread(target=consume, name="test-hold-consumer")
    t.start()
    time.sleep(0.1)  # deadline (1 ms) long expired — held open, not flushed
    assert t.is_alive(), out
    # late arrivals still join the held batch; reaching max_batch flushes
    # regardless of hold
    for _ in range(3):
        b.submit(np.ones((1, 4), np.float32))
    t.join(timeout=5)
    assert not t.is_alive()
    assert len(out["batch"]) == 4 and out["reason"] == "full", out
    assert profiling.counter("serving.holdopen.held_open") > 0

    # releasing the hold + kick() flushes an expired partial immediately
    b.submit(np.ones((1, 4), np.float32))
    t = threading.Thread(target=consume, name="test-hold-consumer2")
    t.start()
    time.sleep(0.1)
    assert t.is_alive(), out
    held.clear()
    b.kick()
    t.join(timeout=5)
    assert not t.is_alive()
    assert len(out["batch"]) == 1 and out["reason"] == "deadline", out

    # drain overrides hold: an expired held batch flushes at begin_drain()
    held.set()
    b.submit(np.ones((1, 4), np.float32))
    t = threading.Thread(target=consume, name="test-hold-consumer3")
    t.start()
    time.sleep(0.1)
    assert t.is_alive(), out
    b.begin_drain()
    t.join(timeout=5)
    assert not t.is_alive()
    assert len(out["batch"]) == 1 and out["reason"] == "drain", out
    b.stop()


def test_depth2_goodput_dominates_depth1_at_equal_offered_load():
    """THE deterministic continuous-batching gate: at equal
    offered load against the same device-leg duration, depth-2 delivers
    at least one full batch MORE goodput than depth-1 before shedding.

    The device leg is a GIL-releasing wall-clock sleep — what a real
    accelerator looks like from the host — so the margin is structural
    (the staged pipe batch plus the held-open assembling batch admit work
    a depth-1 server must shed while its worker is on device) and immune
    to the CPU weather that makes live throughput races on a 2-core box
    unscoreable (see bench_serving's paired confirm)."""
    results = {}
    for depth in (1, 2):
        model = _EchoModel(delay_s=0.25)
        srv = ModelServer(
            f"gd{depth}", model, max_batch=4, max_wait_ms=1,
            queue_depth=8, inflight_depth=depth,
        )
        try:
            first = srv.submit(np.ones(4, np.float32))
            # pre-block: the worker must be ON DEVICE with the probe before
            # the burst, so both depths see an identical starting state
            assert _wait(
                lambda: srv._batcher.queued_requests() == 0
                and not first.done()
            )
            admitted, shed = [first], 0
            for _ in range(24):  # equal offered load, far above capacity
                try:
                    admitted.append(srv.submit(np.ones(4, np.float32)))
                except ServerOverloaded:
                    shed += 1
                # open-loop pacing: a GIL-releasing inter-arrival gap lets
                # the assembly thread actually run between arrivals (a
                # 0-gap burst never yields the GIL, so BOTH depths degrade
                # to the queue bound).  24 * 5 ms = 120 ms, well inside the
                # 250 ms device leg — depth-1 still cannot take() mid-burst
                time.sleep(0.005)
            outs = [f.result(timeout=30)["echo"][0] for f in admitted]
            assert outs == pytest.approx([4.0] * len(admitted))
            results[depth] = len(admitted)
            assert shed == 25 - len(admitted)
        finally:
            srv.shutdown()
    # depth-1 admits the device batch + the queue; depth-2 additionally
    # holds a staged batch (and an assembling one) — >= one max_batch of
    # extra goodput at the same offered load, deterministically
    assert results[1] >= 9, results
    assert results[2] >= results[1] + 4, results


# -- zero-downtime rolling swap ----------------------------------------------


def test_router_swap_under_load_zero_errors(model_zoo):
    """The swap() acceptance gate: rolling hot-swap across the replica set
    under continuous load — zero dropped/errored requests, zero new
    compiles at cut-over (same-shape successor re-warms from the retained
    AOT cache), and traffic lands on the new generation afterwards."""
    model, X = model_zoo("kmeans")
    with Router(replicas=2, max_batch=16, max_wait_ms=2) as router:
        router.serve("swkm", model)
        router.predict("swkm", X[:3])
        stop = threading.Event()
        failures: list = []
        n_ok = [0]

        def pump():
            while not stop.is_set():
                try:
                    out = router.predict("swkm", X[:2], timeout_ms=10_000)
                    assert out["prediction"].shape == (2,)
                    n_ok[0] += 1
                except Exception as exc:  # noqa: BLE001 - the gate counts these
                    failures.append(exc)

        pumper = threading.Thread(
            target=pump, name="test-swap-pump", daemon=True
        )
        pumper.start()
        try:
            time.sleep(0.2)  # load flowing before the swap begins
            before = profiling.counters("precompile.")
            swapped = router.swap("swkm", model)  # same-shape successor
            delta = profiling.counter_deltas(before, "precompile.")
            time.sleep(0.2)  # load flowing after
        finally:
            stop.set()
            pumper.join(timeout=30)
        assert not failures, failures[:3]  # ZERO client-visible errors
        assert n_ok[0] > 0
        # zero new compiles at cut-over: the incoming generation warmed
        # entirely from the retained AOT cache
        assert delta.get("precompile.compile", 0) == 0, delta
        assert delta.get("precompile.fallback", 0) == 0, delta
        assert profiling.counter("router.swkm.replica_swaps") == 2
        assert profiling.counter("router.swkm.swaps") == 1
        # the set now IS the new generation, still healthy and steady
        assert router.replicas("swkm") == swapped
        assert router.health()["models"]["swkm"]["state"] == READY
        for r in swapped:
            r.drain()
            r.assert_steady_state()


def test_submit_racing_a_draining_replica_fails_over():
    """The cut-over race: a submit that lands on a replica AFTER its drain
    began gets the typed ServerDraining — and the router fails over to a
    live replica instead of surfacing it (zero-downtime depends on it)."""
    from spark_rapids_ml_tpu.serving import ServerDraining

    with Router(replicas=2, max_batch=8, max_wait_ms=1) as router:
        reps = router.serve("drace", _EchoModel())
        # the worst-case interleaving, made deterministic: r0's batcher has
        # begun draining but its lifecycle state still reads READY, so the
        # scheduler picks it (tie on outstanding) and submit() raises the
        # typed error INSIDE the router's dispatch attempt
        reps[0]._batcher.begin_drain()
        with pytest.raises(ServerDraining):  # the bare-replica behaviour
            reps[0].submit(np.ones(4, np.float32))
        out = router.predict("drace", np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("router.drace.failover") >= 1


def test_router_swap_incompatible_model_fails_before_cutover():
    with Router(replicas=2, max_batch=8, max_wait_ms=1) as router:
        reps = router.serve("swbad", _EchoModel(n_cols=4))
        with pytest.raises(ValueError, match="n_cols 4 -> 6"):
            router.swap("swbad", _EchoModel(n_cols=6))
        # the set is untouched: same replica objects, still serving
        assert router.replicas("swbad") == reps
        out = router.predict("swbad", np.ones(4, np.float32))
        assert out["echo"][0] == pytest.approx(4.0)
        assert profiling.counter("router.swbad.replica_swaps") == 0


# -- health rollup + Prometheus families --------------------------------------


def test_router_health_rollup_is_capacity_aware(monkeypatch):
    with Router(replicas=2, max_batch=8, max_wait_ms=1) as router:
        reps = router.serve("hrr", _EchoModel())
        h = router.health()
        assert h["state"] == READY
        m = h["models"]["hrr"]
        assert (m["replicas"], m["in_rotation"]) == (2, 2)
        assert set(m["models"]) == {"hrr-r0", "hrr-r1"}
        # one replica out: DEGRADED capacity, an alert — not an outage
        orig = ModelServer.effective_state
        monkeypatch.setattr(
            ModelServer,
            "effective_state",
            lambda self: UNHEALTHY if self is reps[0] else orig(self),
        )
        m = router.health()["models"]["hrr"]
        assert m["state"] == DEGRADED and m["in_rotation"] == 1
        # every replica out: the model is UNHEALTHY, and so is the plane
        monkeypatch.setattr(
            ModelServer, "effective_state", lambda self: UNHEALTHY
        )
        h = router.health()
        assert h["models"]["hrr"]["state"] == UNHEALTHY
        assert h["state"] == UNHEALTHY


def test_router_prometheus_families_round_trip(armed_faults):
    """The exposition round-trip for the new layer: router capacity gauges
    render as the srml_router family, per-REPLICA health (including
    restart counts — the restart-storm signal) as srml_health, and the
    router.<model>.* counters ride export_metrics/telemetry."""
    armed_faults("serving.dispatch:tag=prom-r1:call=1:action=kill")
    with Router(replicas=2, max_batch=4, max_wait_ms=2) as router:
        reps = router.serve("prom", _EchoModel())
        futs = [
            router.submit("prom", np.ones(4, np.float32)) for _ in range(6)
        ]
        for f in futs:
            f.result(timeout=30)  # r1's death rerouted, zero errors
        assert _wait(lambda: reps[1].state() == READY)
        assert _wait(
            lambda: router.health()["models"]["prom"]["restarts"] == 1
        )
        gauges = profiling.export_metrics()["gauges"]
        assert gauges["router.prom.replicas"] == 2.0
        assert gauges["router.prom.state_code"] >= 0.0
        assert "router.prom.in_rotation" in gauges
        assert "router.prom.fill" in gauges
        # per-replica health through the shared srml-watch flattening,
        # restart counts included
        assert gauges["health.prom-r1.restarts"] == 1.0
        assert "health.prom-r0.state_code" in gauges
        text = profiling.render_prometheus()
        assert 'srml_router{name="router.prom.replicas"} 2.0' in text
        assert 'srml_health{name="health.prom-r1.restarts"} 1.0' in text
        # router counters ride the telemetry snapshot surface
        snap = router.telemetry()
        assert snap.counters.get("router.prom.rerouted", 0) >= 1
        assert snap.counters.get("router.prom.admitted", 0) >= 6
        stats = router.stats()["prom"]
        assert set(stats["replicas"]) == {"prom-r0", "prom-r1"}
        assert stats["counters"]["router.prom.dispatched"] >= 6
    # shutdown unregisters the weak gauge provider
    assert not any(
        k.startswith("router.prom.")
        for k in profiling.export_metrics()["gauges"]
    )


def test_registry_health_gauges_include_restarts(model_zoo, armed_faults):
    """Satellite: the registry side of the shared flattening — a restarted
    registry server's restart count reaches the srml_health family."""
    from spark_rapids_ml_tpu.serving import ModelRegistry, ServerRecovering

    model, X = model_zoo("kmeans")
    reg = ModelRegistry(max_batch=16, max_wait_ms=2)
    try:
        reg.register("regkm", model)
        reg.get("regkm").predict(X[:2])
        armed_faults("serving.dispatch:tag=regkm:call=1:action=kill")
        with pytest.raises(ServerRecovering):
            reg.get("regkm").predict(X[:2])
        assert _wait(lambda: reg.get("regkm").state() == READY)
        assert reg.health()["models"]["regkm"]["restarts"] == 1
        assert reg.health()["restarts"] == 1
        gauges = profiling.export_metrics()["gauges"]
        assert gauges["health.regkm.restarts"] == 1.0
        text = profiling.render_prometheus()
        assert 'srml_health{name="health.regkm.restarts"} 1.0' in text
    finally:
        reg.shutdown(drain=False)


def test_health_gauges_flattening_rule():
    # the ONE rule shared by registry and router (watch.health_gauges)
    out = watch.health_gauges(
        {
            "m": {
                "state_code": 0,
                "attainment": 0.5,
                "burn": 0.5,
                "queued_rows": 3,
                "p99_ms": 12.5,
                "restarts": 2,
            },
            "bare": {"state_code": 4},
        }
    )
    assert out == {
        "health.m.state_code": 0.0,
        "health.m.attainment": 0.5,
        "health.m.burn": 0.5,
        "health.m.queued_rows": 3.0,
        "health.m.p99_ms": 12.5,
        "health.m.restarts": 2.0,
        "health.bare.state_code": 4.0,
    }
