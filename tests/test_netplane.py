# srml-wire gates (docs/robustness.md §wire plane), in ISSUE order:
#   - frame codec: length-prefixed binary frames fail LOUDLY on corruption
#     (magic, bounds, meta JSON) — never decode garbage
#   - pushed aborts: a blocked gather wakes in ~one RTT (≪ the file
#     plane's 50 ms poll floor), naming origin rank / etype / span
#   - leases: a member that falls silent (SIGKILL, wedge, partition) is
#     declared dead within the lease and every survivor's gather raises
#     RemoteRankError naming it
#   - session-epoch fencing: a zombie from a previous incarnation (stale
#     epoch, or any rejoin of a dead rank) is rejected with the typed
#     StaleEpochError — never silently readmitted
#   - coordinator loss: a dead/partitioned coordinator surfaces as the
#     typed CoordinatorLost in bounded time, never a hang or bare OSError
#   - coordinator-allocated jax.distributed ports: never handed out twice
#   - wire fault sites (cp.net.send/recv): drop, partition, corrupt
#   - teardown: no orphaned sockets, threads, or files after close()
#   - THE CHAOS MATRIX on real OS processes over real sockets:
#     SIGKILL'd rank, partitioned rank, killed coordinator — each surfaces
#     as a typed error naming the culprit within 2 heartbeat intervals
#     (wall-clock asserted)
import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.parallel import faults
from spark_rapids_ml_tpu.parallel.context import (
    ControlPlaneTimeout,
    RemoteRankError,
)
from spark_rapids_ml_tpu.parallel.netplane import (
    CoordinatorLost,
    CoordinatorServer,
    ProtocolError,
    StaleEpochError,
    TcpControlPlane,
    _pack_frame,
    _reparse_frame,
    bootstrap_tcp_plane,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the chaos heartbeat cadence: 2 s heartbeats => 3 s lease => the asserted
# detection bound is 2 heartbeat intervals = 4 s (lease + scan poll = 3.75)
_HB_S = 2.0
_DETECT_BOUND_S = 2 * _HB_S


def _netcp_threads():
    return [
        t.name for t in threading.enumerate()
        if t.name.startswith("srml-netcp")
    ]


@pytest.fixture
def coordinator():
    """A running coordinator + client factory; teardown asserts the
    no-orphan-threads contract for everything the test built."""
    made = []

    def build(nranks, lease_s=1.0, timeout=10.0):
        srv = CoordinatorServer(
            nranks, host="127.0.0.1", advertise_host="127.0.0.1",
            lease_s=lease_s,
        )
        addr = srv.start()
        made.append(srv)

        def client(rank, **kw):
            kw.setdefault("timeout", timeout)
            cp = TcpControlPlane(addr, rank, nranks, **kw)
            made.append(cp)
            return cp

        return srv, addr, client

    yield build
    for m in reversed(made):
        with contextlib.suppress(Exception):
            (m.close if isinstance(m, TcpControlPlane) else m.stop)()
    deadline = time.monotonic() + 10.0
    while _netcp_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert _netcp_threads() == [], "orphaned netplane threads after teardown"


# -- frame codec --------------------------------------------------------------


def test_frame_codec_round_trip_and_loud_corruption():
    frame = _pack_frame(b"G", {"round": 3, "rank": 1}, b"\x00\xffpayload")
    ftype, meta, blob = _reparse_frame(frame)
    assert (ftype, meta["round"], blob) == (b"G", 3, b"\x00\xffpayload")
    # flipped magic: the fail-loud contract for wire corruption
    bad = bytearray(frame)
    bad[0] ^= 0xFF
    with pytest.raises(ProtocolError, match="magic"):
        _reparse_frame(bytes(bad))
    # implausible length field
    bad2 = bytearray(frame)
    bad2[7] = 0xFF  # meta-length high byte
    with pytest.raises(ProtocolError):
        _reparse_frame(bytes(bad2))
    # garbled meta JSON
    bad3 = bytearray(frame)
    bad3[len(bad3) - len(b"\x00\xffpayload") - 2] ^= 0xFF
    with pytest.raises(ProtocolError):
        _reparse_frame(bytes(bad3))


# -- pushed aborts ------------------------------------------------------------


def test_pushed_abort_beats_the_poll_floor(coordinator):
    """The wire plane's reason to exist: an abort marker reaches a blocked
    gather as a coordinator PUSH — survivors raise RemoteRankError naming
    rank/etype/span in well under the file plane's 50 ms poll interval."""
    _srv, _addr, client = coordinator(3)
    planes = {r: client(r) for r in range(3)}
    errs = {}
    t_abort = [0.0]

    def waiter(rank):
        try:
            planes[rank].allGather("never-completes")
        except RemoteRankError as exc:
            errs[rank] = (exc, time.monotonic() - t_abort[0])

    threads = [
        threading.Thread(target=waiter, args=(r,), name=f"wire-r{r}")
        for r in (0, 2)
    ]
    for t in threads:
        t.start()
    time.sleep(0.2)  # both are blocked in the gather wait
    pushed0 = profiling.counter("cp.net.pushed_aborts")
    t_abort[0] = time.monotonic()
    planes[1].abort(json.dumps({
        "rank": 1, "etype": "ValueError",
        "message": "induced failure", "span": "exchange.ring",
    }))
    for t in threads:
        t.join(timeout=10.0)
    assert set(errs) == {0, 2}, "survivors never raised"
    for rank, (exc, dt) in errs.items():
        assert exc.rank == 1 and exc.etype == "ValueError"
        assert exc.span == "exchange.ring"
        assert dt < 0.05, (
            f"rank {rank} took {dt * 1e3:.1f} ms — a push must beat the "
            "file plane's 50 ms poll floor"
        )
    assert planes[0].check_abort()["rank"] == 1  # non-blocking surface too
    assert profiling.counter("cp.net.pushed_aborts") > pushed0


# -- leases + fencing ---------------------------------------------------------


def test_lease_expiry_names_the_silent_rank(coordinator):
    _srv, _addr, client = coordinator(2, lease_s=0.5)
    cp0, cp1 = client(0), client(1)
    # silence rank 1 without closing its socket: the wedge/partition shape
    # (a SIGKILL would close the socket and be detected even faster)
    cp1._stop.set()
    got = {}
    t0 = time.monotonic()

    def waiter():
        try:
            cp0.allGather("x")
        except RemoteRankError as exc:
            got["e"] = (exc, time.monotonic() - t0)

    w = threading.Thread(target=waiter, name="wire-lease-waiter")
    w.start()
    w.join(timeout=10.0)
    exc, dt = got["e"]
    assert exc.rank == 1 and "lease expired" in str(exc)
    assert "SRML_CP_LEASE_S" in str(exc)  # the error names its knob
    assert dt < 2 * 0.5 + 0.5, f"detection took {dt:.2f}s"


def test_stale_epoch_rejoin_is_fenced(coordinator):
    """THE fencing acceptance gate: after a rank is declared dead, neither
    its old incarnation (stale epoch) nor a fresh rejoin is readmitted —
    both get the typed StaleEpochError, because its peers have already
    been told it is gone."""
    srv, addr, client = coordinator(2, lease_s=0.4)
    cp0, cp1 = client(0), client(1)
    zombie_epoch = cp1.epoch
    cp1._stop.set()  # fall silent; the lease declares rank 1 dead
    with pytest.raises(RemoteRankError, match="rank 1"):
        cp0.allGather("x")
    before = profiling.counter("cp.net.fenced_rejoins")
    with pytest.raises(StaleEpochError, match="fenced"):
        TcpControlPlane(addr, 1, 2, timeout=5, resume_epoch=zombie_epoch)
    with pytest.raises(StaleEpochError, match="fenced"):
        TcpControlPlane(addr, 1, 2, timeout=5)  # fresh rejoin: also fenced
    assert profiling.counter("cp.net.fenced_rejoins") - before == 2


def test_duplicate_live_rank_join_is_fenced(coordinator):
    _srv, addr, client = coordinator(2)
    client(0)
    client(1)
    with pytest.raises(StaleEpochError, match="duplicate"):
        TcpControlPlane(addr, 1, 2, timeout=5)


# -- coordinator loss ---------------------------------------------------------


def test_coordinator_death_is_typed_and_bounded(coordinator):
    srv, _addr, client = coordinator(2, lease_s=0.5)
    cp0 = client(0)
    got = {}
    t0 = time.monotonic()

    def waiter():
        try:
            cp0.allGather("x")
        except CoordinatorLost as exc:
            got["e"] = (exc, time.monotonic() - t0)

    w = threading.Thread(target=waiter, name="wire-lost-waiter")
    w.start()
    time.sleep(0.2)
    srv.stop(grace_s=0.0)  # hard stop mid-gather: the killed coordinator
    w.join(timeout=10.0)
    exc, dt = got["e"]
    assert "coordinator" in str(exc) and dt < 2.0


# -- port reservation ---------------------------------------------------------


def test_allocated_ports_are_never_reissued(coordinator):
    _srv, _addr, client = coordinator(1)
    cp = client(0)
    ports = [cp.allocate_port() for _ in range(16)]
    assert len(set(ports)) == 16, "coordinator reissued a reserved port"
    assert all(1024 <= p <= 65535 for p in ports)


def test_tpu_context_uses_coordinator_allocated_port(monkeypatch):
    """TpuContext rank 0 must route its jax.distributed port pick through
    the plane's allocate_port when the surface exists (the rebind-race
    fix): the advertised coordinator address must carry the port the
    ledger reserved, not an unreserved _free_port pick."""
    import jax

    class _PortPlane:
        def __init__(self):
            self.handed = []

        def allGather(self, message):
            return [message]

        def barrier(self):
            return None

        def allocate_port(self):
            self.handed.append(45713)
            return 45713

    captured = {}
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: captured.update(kw),
    )
    from spark_rapids_ml_tpu.parallel.context import TpuContext

    cp = _PortPlane()
    ctx = TpuContext(rank=0, nranks=2, control_plane=cp)
    ctx.__enter__()
    try:
        assert cp.handed == [45713]
        assert captured["coordinator_address"].endswith(":45713")
    finally:
        ctx._initialized_distributed = False  # initialize was a stub
        ctx.__exit__(None, None, None)


# -- wire fault sites ---------------------------------------------------------


def test_wire_drop_and_partition_grammar(armed_faults):
    armed_faults("cp.net.send:rank=0:call=1:action=drop")
    assert faults.site("cp.net.send", rank=0, payload=b"f") is faults.DROPPED
    assert faults.site("cp.net.send", rank=0, payload=b"g") == b"g"
    # partition is sticky and bidirectional across the cp.net.* family
    armed_faults("cp.net.send:rank=1:action=partition")
    assert faults.site("cp.net.send", rank=1, payload=b"a") is faults.DROPPED
    assert faults.site("cp.net.recv", rank=1, payload=b"b") is faults.DROPPED
    assert faults.site("cp.net.send", rank=0, payload=b"c") == b"c"
    assert faults.plan().partitioned() == {1}
    # drop/partition outside the wire family is a strict-parse error
    with pytest.raises(ValueError, match="wire sites"):
        faults.parse_plan("cp.gather:action=drop")
    with pytest.raises(ValueError, match="wire sites"):
        faults.parse_plan("cp.barrier:action=partition")


def test_partitioned_rank_is_named_by_survivor(coordinator, armed_faults):
    """An injected partition (sticky drop of every cp.net.* frame for rank
    1) must surface exactly like a real one: the survivor's gather raises
    RemoteRankError naming rank 1 via lease expiry, and the partitioned
    rank itself loses the coordinator (typed, bounded)."""
    armed_faults("cp.net.send:rank=1:action=partition")
    _srv, _addr, client = coordinator(2, lease_s=0.5)
    cp0, cp1 = client(0), client(1)
    out = {}

    def r0():
        try:
            for i in range(50):
                cp0.allGather(f"r0-{i}")
        except RemoteRankError as exc:
            out[0] = exc

    def r1():
        try:
            for i in range(50):
                cp1.allGather(f"r1-{i}")
        except (CoordinatorLost, RemoteRankError) as exc:
            out[1] = exc

    threads = [
        threading.Thread(target=r0, name="wire-part-r0"),
        threading.Thread(target=r1, name="wire-part-r1"),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=15.0)
    assert isinstance(out.get(0), RemoteRankError) and out[0].rank == 1
    assert "lease expired" in str(out[0])
    assert isinstance(out.get(1), CoordinatorLost)


def test_corrupt_frame_kills_the_sender_loudly(coordinator, armed_faults):
    """corrupt on cp.net.send garbles rank 1's wire frames: the
    coordinator's codec must refuse the frame (protocol violation), declare
    rank 1 dead, and the survivor must learn WHO — never decode garbage
    into a gather round."""
    armed_faults("cp.net.send:rank=1:action=corrupt")
    _srv, _addr, client = coordinator(2, lease_s=5.0)
    cp0, cp1 = client(0), client(1)
    out = {}

    def r0():
        try:
            cp0.allGather("r0")
        except RemoteRankError as exc:
            out[0] = exc

    t = threading.Thread(target=r0, name="wire-corrupt-r0")
    t.start()
    time.sleep(0.1)
    with pytest.raises((CoordinatorLost, RemoteRankError, StaleEpochError)):
        cp1.allGather("r1")  # its own corrupt frame severs the connection
        cp1.allGather("r1-again")  # at worst the next round surfaces it
    t.join(timeout=10.0)
    assert isinstance(out.get(0), RemoteRankError) and out[0].rank == 1
    assert "protocol violation" in str(out[0])


# -- timeout typing -----------------------------------------------------------


def test_gather_timeout_is_typed_and_names_missing_ranks(coordinator):
    _srv, _addr, client = coordinator(3)
    cp0, cp2 = client(0, timeout=0.5), client(2, timeout=0.5)
    errs = {}

    def run(rank, cp):
        try:
            cp.allGather("present")
        except ControlPlaneTimeout as exc:
            errs[rank] = exc

    threads = [
        threading.Thread(target=run, args=(r, cp), name=f"wire-to-r{r}")
        for r, cp in ((0, cp0), (2, cp2))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    for rank in (0, 2):
        exc = errs[rank]
        assert isinstance(exc, TimeoutError)  # compatibility contract
        assert exc.round_no == 0 and exc.missing_ranks == [1]
        assert exc.knob == "SRML_CP_ROUND_TIMEOUT_S"
        assert "ranks [1]" in str(exc)


# -- bootstrap + teardown -----------------------------------------------------


def test_bootstrap_via_shared_directory(tmp_path):
    planes = {}
    results = {}

    def run(rank):
        cp = bootstrap_tcp_plane(str(tmp_path), rank, 3, timeout=20)
        planes[rank] = cp
        results[rank] = cp.allGather(f"boot-{rank}")

    threads = [
        threading.Thread(target=run, args=(r,), name=f"wire-boot-r{r}")
        for r in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20.0)
    assert set(planes) == {0, 1, 2}
    for r in range(3):
        assert results[r] == ["boot-0", "boot-1", "boot-2"]
    assert os.path.exists(tmp_path / "coordinator.addr")
    for r in (1, 2, 0):  # rank 0 (the server owner) closes LAST
        planes[r].close()
        planes[r].close()  # close is idempotent
    # no orphan files (the addr file is reaped), threads, or sockets
    assert not os.path.exists(tmp_path / "coordinator.addr")
    deadline = time.monotonic() + 10.0
    while _netcp_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert _netcp_threads() == []


# -- the chaos matrix: real OS processes over real sockets --------------------


def _spawn_netchaos(root, nranks, env_extra, rounds=4):
    env = dict(os.environ)
    env.pop("SRML_FAULTS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["SRML_CP"] = "tcp"
    env["SRML_WATCH_HEARTBEAT_S"] = str(_HB_S)  # lease = 1.5 hb = 3 s
    env.update(env_extra)
    return [
        subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tests", "netchaos_worker.py"),
             str(r), str(nranks), str(root), str(rounds)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(nranks)
    ]


def _communicate_all(procs, timeout=120):
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT: killed by driver>"
        outs.append(out)
    return outs


def _shield_line(out):
    for line in out.splitlines():
        if line.startswith("SHIELD ") and "culprit=" in line:
            return dict(
                kv.split("=", 1) for kv in line.split()[1:] if "=" in kv
            )
    return None


def test_netchaos_clean_run_no_orphans(tmp_path):
    """3 real OS processes over real sockets, no faults: every rank
    completes every round; teardown leaves no coordinator.addr, no
    presence files, nothing."""
    procs = _spawn_netchaos(tmp_path, nranks=3, env_extra={})
    outs = _communicate_all(procs)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    assert os.listdir(tmp_path / "cp") == []


def test_netchaos_sigkilled_rank_named_within_two_heartbeats(tmp_path):
    """Acceptance gate 1: rank 1 of 3 dies mid-collective (os._exit — the
    SIGKILL shape: no marker, no teardown, kernel FIN only).  Both
    survivors must raise RemoteRankError NAMING rank 1 within 2 heartbeat
    intervals, wall-clock asserted."""
    procs = _spawn_netchaos(
        tmp_path, nranks=3,
        env_extra={"SRML_FAULTS": "cp.gather:rank=1:call=3:action=die"},
    )
    outs = _communicate_all(procs)
    from spark_rapids_ml_tpu.parallel.faults import DIE_EXIT_CODE

    assert procs[1].returncode == DIE_EXIT_CODE, outs[1]
    for r in (0, 2):
        assert procs[r].returncode == 7, f"rank {r}:\n{outs[r]}"
        info = _shield_line(outs[r])
        assert info is not None, outs[r]
        assert info["kind"] == "remote" and info["culprit"] == "1"
        assert float(info["dt"]) < _DETECT_BOUND_S, (
            f"rank {r} took {info['dt']}s (> 2 heartbeat intervals = "
            f"{_DETECT_BOUND_S}s) to notice the killed rank"
        )
    # the surviving coordinator owner (rank 0) reaps the session's files
    assert os.listdir(tmp_path / "cp") == []


def test_netchaos_partitioned_rank_named_within_two_heartbeats(tmp_path):
    """Acceptance gate 2: rank 2 of 3 is PARTITIONED (injected sticky
    cp.net drop, both directions — the process is alive but unreachable).
    Survivors name rank 2 via lease expiry within 2 heartbeat intervals;
    the partitioned rank itself exits with the typed plane-lost error."""
    procs = _spawn_netchaos(
        tmp_path, nranks=3,
        env_extra={
            "SRML_FAULTS": "cp.net.send:rank=2:call=6:action=partition",
            # lease pinned BELOW the 1.5x-heartbeat default so worst-case
            # expiry + scan poll (2.5 + 0.625 s) clears the 2-heartbeat
            # bound with CI-scheduler headroom
            "SRML_CP_LEASE_S": "2.5",
        },
        rounds=40,
    )
    outs = _communicate_all(procs)
    for r in (0, 1):
        assert procs[r].returncode == 7, f"rank {r}:\n{outs[r]}"
        info = _shield_line(outs[r])
        assert info["kind"] == "remote" and info["culprit"] == "2"
        assert float(info["dt"]) < _DETECT_BOUND_S, (
            f"rank {r} took {info['dt']}s (> {_DETECT_BOUND_S}s) to notice "
            "the partitioned rank"
        )
    assert procs[2].returncode == 8, f"rank 2:\n{outs[2]}"
    assert _shield_line(outs[2])["etype"] == "CoordinatorLost"


def test_netchaos_killed_coordinator_surfaces_typed_and_bounded(tmp_path):
    """Acceptance gate 3: the COORDINATOR (hosted in rank 0's process) is
    SIGKILLed mid-matrix.  Ranks 1 and 2 must fail with the typed
    CoordinatorLost within 2 heartbeat intervals — never a hang, never a
    bare socket error."""
    procs = _spawn_netchaos(tmp_path, nranks=3, env_extra={}, rounds=0)
    # wait until the cohort is demonstrably gathering (every worker prints
    # its join line after bootstrap), then kill the coordinator host: a
    # rank still importing when the coordinator dies was never in the
    # cohort, and waits out its bootstrap timeout instead of a heartbeat
    for p in procs:
        for line in p.stdout:
            if line.startswith("SHIELD ") and line.rstrip().endswith("joined"):
                break
    time.sleep(1.0)  # let a few rounds complete
    os.kill(procs[0].pid, signal.SIGKILL)
    outs = _communicate_all(procs)
    assert procs[0].returncode == -signal.SIGKILL
    for r in (1, 2):
        assert procs[r].returncode == 8, f"rank {r}:\n{outs[r]}"
        info = _shield_line(outs[r])
        assert info["kind"] == "plane"
        assert info["etype"] == "CoordinatorLost"
        assert float(info["dt"]) < _DETECT_BOUND_S, (
            f"rank {r} took {info['dt']}s (> {_DETECT_BOUND_S}s) to notice "
            "the dead coordinator"
        )
