#
# Launcher-agnostic multi-controller fit execution.
#
# This is the executor-side half of the reference's barrier fit
# (/root/reference/python/src/spark_rapids_ml/core.py:488-640): one process
# per Spark barrier task (= TPU-VM worker), each holding its own row
# partitions, cooperating through a small string control plane
# (BarrierTaskContext.allGather on Spark; FileControlPlane for plain process
# launchers and tests).  The flow per rank:
#
#   1. TpuContext bootstraps jax.distributed (coordinator address allGathered
#      like the reference's NCCL uid, cuml_context.py:75-103)
#   2. a GLOBAL 1-D mesh is built over every device in the pod, ordered
#      process-major so rank r's rows land on rank r's chips
#   3. per-rank partition sizes are allGathered into a PartitionDescriptor
#      (reference utils.py:159-196) to size the global padded array
#   4. each rank's local rows become its process-local shards of one global
#      row-sharded jax.Array (jax.make_array_from_process_local_data), padded
#      rows masked through the weight vector
#   5. the SAME pure-jax fit function used single-controller runs on every
#      rank; GSPMD collectives ride ICI within a host and DCN across ranks
#   6. results are replicated; every rank materializes them, rank 0's are
#      yielded to the driver (JSON-safe encoded)
#
# Unlike the reference there is no second code path for the distributed
# case — the solvers cannot tell a pod mesh from a single-host mesh.
#

from __future__ import annotations

import base64
import contextlib
import functools
import json
import os
import random
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np
import pandas as pd

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import faults
from .context import ControlPlane, LocalControlPlane, RemoteRankError, TpuContext
from .mesh import DATA_AXIS
from .partition import PartitionDescriptor

from .. import profiling

# -- srml-shield control-plane knobs (docs/robustness.md) ---------------------
# Per-ROUND bounded timeout: every gather round gets its own budget instead
# of one session-wide 300 s cliff, so a wedged round is diagnosed at round
# granularity.  Retries: shared-FS I/O (NFS on TPU-VM pods) throws transient
# OSErrors under churn; each read/write retries with exponential backoff and
# deterministic per-rank jitter before giving up.  The knobs and the parsed
# RetryPolicy live in parallel/context.py (ONE policy shared by the file and
# TCP planes); the names are re-exported here for compatibility.
from .context import (  # noqa: E402 - knob re-exports
    BACKOFF_ENV,
    ControlPlaneTimeout,
    RETRIES_ENV,
    ROUND_TIMEOUT_ENV,
    RetryPolicy,
    _DEFAULT_ROUND_TIMEOUT_S,
)

from ..utils import env_float as _env_float  # noqa: E402 - knob parsing

# which control plane make_control_plane builds: "file" (default, shared
# filesystem) or "tcp" (srml-wire socket plane, parallel/netplane.py)
CP_ENV = "SRML_CP"


def make_control_plane(
    root: str, rank: int, nranks: int, timeout: Optional[float] = None
):
    """Control-plane factory honoring SRML_CP: the process launchers and
    multicontroller workers build their plane through this ONE chokepoint,
    so the whole fit/kneighbors matrix reruns on the TCP plane by flipping
    an env var (the conformance contract: same surface, same math,
    bitwise-equal results — tests/test_multicontroller.py gates it)."""
    kind = os.environ.get(CP_ENV, "file").strip().lower() or "file"
    if kind == "file":
        return FileControlPlane(root, rank, nranks, timeout=timeout)
    if kind == "tcp":
        from .netplane import bootstrap_tcp_plane

        return bootstrap_tcp_plane(root, rank, nranks, timeout=timeout)
    raise ValueError(f"{CP_ENV}={kind!r}: known planes are 'file' and 'tcp'")


class FileControlPlane:
    """Control plane over a shared filesystem: allGather by atomic per-rank
    message files in numbered rounds, barrier as an empty gather.

    Stands in for Spark's BarrierTaskContext wherever there is no Spark —
    subprocess launchers, mpirun-style deployments with a shared FS, and the
    multi-controller tests.  Rendezvous root must be empty per job.

    srml-shield fast-abort surface (docs/robustness.md):

      - every plane writes an `alive_rank<k>.pid` liveness file at
        construction and holds an EXCLUSIVE flock on it for the process
        lifetime; gather waits probe peers' locks (the kernel releases a
        dead process's locks even while it is an unreaped zombie, which a
        bare `kill(pid, 0)` cannot see) with a pid check as fallback, so a
        rank KILLED mid-collective (no marker, no teardown — the
        SIGKILL/OOM shape) is detected within one poll interval and
        surfaces as RemoteRankError naming the dead rank, not as a
        round-timeout 300 s later.
      - abort(payload) atomically publishes an `abort-r<k>.json` marker (the
        encoded exception + failing span, written by TpuContext.__exit__ on
        the exception path); gather waits poll for foreign markers and raise
        RemoteRankError quoting the origin rank, exception type, and span.
      - close() removes this rank's presence files (alive + heartbeat) and
        reaps those of peers whose process is gone — the no-orphan-files
        half of the teardown contract."""

    def __init__(self, root: str, rank: int, nranks: int,
                 timeout: Optional[float] = None, poll: float = 0.02):
        self._root = root
        self._rank = rank
        self._nranks = nranks
        self._round = 0
        self._timeout = (
            timeout
            if timeout is not None
            else _env_float(ROUND_TIMEOUT_ENV, _DEFAULT_ROUND_TIMEOUT_S)
        )
        self._poll = poll
        # deterministic per-rank backoff jitter (explicitly seeded: R4);
        # the retry policy is parsed ONCE here (matching _timeout) and
        # shared-by-contract with the TCP plane (context.RetryPolicy)
        self._jitter = random.Random(10007 + rank)
        self._retry = RetryPolicy.from_env()
        os.makedirs(root, exist_ok=True)
        # liveness: pid + an exclusive flock held for the process lifetime.
        # The LOCK is the primary death signal — the kernel releases it the
        # instant the process exits, including the unreaped-zombie window
        # where kill(pid, 0) still succeeds.  The pid is the fallback (and
        # the error message's evidence) for filesystems without working
        # flock, recorded in the file so peers know which probe to trust.
        self._alive_fd: Optional[int] = None
        self._register_alive()

    # -- file paths ----------------------------------------------------------
    def _alive_path(self, rank: int) -> str:
        return os.path.join(self._root, f"alive_rank{rank:05d}.pid")

    def _abort_path(self, rank: int) -> str:
        return os.path.join(self._root, f"abort-r{rank:05d}.json")

    def _register_alive(self) -> None:
        """Publish `<pid> flock|nolock` and (where the FS supports it) hold
        an exclusive flock on the file for the process lifetime — the mode
        word tells peers which death probe to trust.  A sibling plane
        instance of this SAME process (thread-mocked rank harnesses) may
        already hold the path's lock; replacing its inode would orphan
        that lock, so an entry already naming our pid is left alone."""
        path = self._alive_path(self._rank)
        try:
            with open(path) as f:
                parts = f.read().split()
            if parts and parts[0] == str(os.getpid()):
                return  # a sibling instance of this process registered us
        except OSError:
            pass
        self._write_atomic(path, f"{os.getpid()} nolock")
        try:
            import fcntl

            fd = os.open(path, os.O_RDWR)
            try:
                fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(fd)
                return
            self._alive_fd = fd  # held until close() / process death
            content = f"{os.getpid()} flock".encode()
            os.pwrite(fd, content, 0)
            os.ftruncate(fd, len(content))
        except (ImportError, OSError):
            pass

    # -- retrying I/O ---------------------------------------------------------
    def _retry_io(self, fn, what: str):
        """Run `fn` retrying transient OSErrors with exponential backoff +
        deterministic jitter — the construction-parsed RetryPolicy
        (SRML_CP_RETRIES / SRML_CP_BACKOFF_S), NOT a per-call env re-read."""
        return self._retry.run(fn, self._jitter)

    def _write_atomic(self, path: str, text_or_bytes) -> None:
        data = (
            text_or_bytes.encode("utf-8")
            if isinstance(text_or_bytes, str)
            else text_or_bytes
        )
        tmp = path + f".tmp{os.getpid()}"

        def _write():
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, path)  # atomic publish

        self._retry_io(_write, path)

    def _read_bytes(self, path: str) -> bytes:
        def _read():
            with open(path, "rb") as f:
                return f.read()

        return self._retry_io(_read, path)

    # -- the gather protocol --------------------------------------------------
    def allGather(self, message: str) -> List[str]:
        return [
            b.decode("utf-8")
            for b in self._gather_round(message.encode("utf-8"))
        ]

    def allGatherBytes(self, message: bytes) -> List[bytes]:
        """Binary gather round — shared-FS planes move raw frames without
        the base64 detour the string-only Spark RPC transport needs
        (parallel/exchange.py picks this path up by hasattr)."""
        return self._gather_round(message)

    def _gather_round(self, message: bytes) -> List[bytes]:
        r = self._round
        self._round += 1
        message = faults.site("cp.gather", rank=self._rank, payload=message)
        path = os.path.join(self._root, f"round{r:05d}_rank{self._rank:05d}.msg")
        self._write_atomic(path, message)
        expected = [
            os.path.join(self._root, f"round{r:05d}_rank{i:05d}.msg")
            for i in range(self._nranks)
        ]
        deadline = time.monotonic() + self._timeout
        while not all(os.path.exists(p) for p in expected):
            missing = [
                i for i, p in enumerate(expected) if not os.path.exists(p)
            ]
            # fast-abort scan: a foreign abort marker or a dead peer ends
            # the wait within ONE poll interval, naming the culprit —
            # instead of the full round timeout naming nobody
            self._raise_if_aborted()
            self._raise_if_peer_dead(missing)
            if time.monotonic() > deadline:
                raise ControlPlaneTimeout(
                    "FileControlPlane", r, missing, self._timeout
                )
            time.sleep(self._poll)
        out = []
        for p in expected:
            out.append(self._read_bytes(p))
        return out

    def barrier(self) -> None:
        faults.site("cp.barrier", rank=self._rank)
        self.allGather("")

    # -- srml-shield abort protocol -------------------------------------------
    def abort(self, payload: str) -> None:
        """Atomically publish this rank's abort marker (JSON: rank, etype,
        message, span).  Fire-and-forget like publish_health: no rank ever
        waits on it — peers polling in a gather wait pick it up and raise
        RemoteRankError within one poll interval."""
        profiling.incr_counter("cp.abort_markers")
        self._write_atomic(self._abort_path(self._rank), payload)

    def check_abort(self) -> Optional[Dict[str, Any]]:
        """First foreign abort marker's decoded payload, or None.  Never
        blocks; a torn/garbled marker degrades to a minimal payload naming
        the origin rank (the marker's existence IS the abort signal)."""
        for i in range(self._nranks):
            if i == self._rank:
                continue
            p = self._abort_path(i)
            if not os.path.exists(p):
                continue
            try:
                info = json.loads(self._read_bytes(p).decode("utf-8"))
                if isinstance(info, dict):
                    info.setdefault("rank", i)
                    return info
            except (OSError, ValueError):
                pass
            return {"rank": i}
        return None

    def _raise_if_aborted(self) -> None:
        info = self.check_abort()
        if info is None:
            return
        profiling.incr_counter("cp.remote_aborts")
        raise RemoteRankError(
            rank=int(info.get("rank", -1)),
            message=info.get("message", "aborted"),
            span=info.get("span"),
            etype=info.get("etype"),
        )

    @staticmethod
    def _pid_alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        except (PermissionError, OSError):
            return True  # exists but not ours (or unknowable): assume alive
        return True

    def _peer_dead_reason(self, rank: int) -> Optional[str]:
        """Why rank `rank` is believed dead, or None (alive / not yet
        registered).  Primary signal: its liveness flock is FREE (the
        kernel releases it at process exit — including the unreaped-zombie
        window where kill(pid, 0) still succeeds); fallback for nolock
        registrations: the pid is gone."""
        path = self._alive_path(rank)
        try:
            with open(path) as f:
                parts = f.read().split()
        except OSError:
            return None  # not registered yet (or already cleanly closed)
        try:
            pid = int(parts[0])
        except (IndexError, ValueError):
            return None  # torn write: the retry-backed publisher fixes it
        if len(parts) > 1 and parts[1] == "flock":
            # the mode word says the registrant HOLDS the lock: the probe is
            # authoritative (and works across hosts on lock-honoring shared
            # FS).  The local pid check must NOT run first here — on a
            # multi-host deployment a remote rank's pid means nothing to
            # this kernel and kill(pid, 0) would declare a healthy peer
            # dead.  Only an unprobeable lock falls through to the pid.
            try:
                import fcntl

                fd = os.open(path, os.O_RDONLY)
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                except OSError:
                    return None  # lock held: alive
                else:
                    fcntl.flock(fd, fcntl.LOCK_UN)
                    return (
                        f"process (pid {pid}) released its liveness lock "
                        "(exited; possibly an unreaped zombie)"
                    )
                finally:
                    os.close(fd)
            except (ImportError, OSError):
                pass  # cannot probe: fall through to the pid best-effort
        if not self._pid_alive(pid):
            return f"process (pid {pid}) is gone"
        return None

    def _raise_if_peer_dead(self, missing_ranks: List[int]) -> None:
        """A rank that REGISTERED (alive file present) but is provably gone
        died without a marker — killed, OOMed, segfaulted.  Only ranks we
        are actually waiting on are scanned; a rank that has not
        registered yet is merely slow (the round timeout still bounds
        it)."""
        for i in missing_ranks:
            reason = self._peer_dead_reason(i)
            if reason is None:
                continue
            profiling.incr_counter("cp.dead_peers")
            raise RemoteRankError(
                rank=i,
                message=(
                    f"{reason} mid-collective without an abort marker "
                    "(killed / OOM / segfault)"
                ),
            )

    def close(self) -> None:
        """Release this rank's liveness lock, remove its presence files
        (alive pid + heartbeat), and — ONLY once no other survivor remains
        — reap dead peers' too.  A dead rank's alive file is the death
        EVIDENCE every still-blocked survivor polls to raise its own
        RemoteRankError: the first survivor to close must not destroy it,
        or the slower survivors ride out the full round timeout (the exact
        hang this plane exists to kill).  The LAST closer sees no live
        registered peer left and sweeps, so after every surviving rank
        closes, no alive_*/health_* file remains for any rank (the
        no-orphan-files teardown contract; gated by the chaos tests).
        Round messages and abort markers are the session's record and are
        left for the per-job rendezvous root to be deleted wholesale."""
        for path in (
            self._alive_path(self._rank),
            os.path.join(self._root, f"health_rank{self._rank:05d}.json"),
        ):
            with contextlib.suppress(OSError):
                os.remove(path)
        if self._alive_fd is not None:
            with contextlib.suppress(OSError):
                os.close(self._alive_fd)  # releases the flock
            self._alive_fd = None
        # a peer whose alive file is present AND whose death probe says
        # "alive" is a survivor that has not closed yet: leave the dead
        # ranks' evidence for it
        for i in range(self._nranks):
            if i == self._rank:
                continue
            if (
                os.path.exists(self._alive_path(i))
                and self._peer_dead_reason(i) is None
            ):
                return
        for i in range(self._nranks):
            if i == self._rank:
                continue
            for path in (
                self._alive_path(i),
                os.path.join(self._root, f"health_rank{i:05d}.json"),
            ):
                with contextlib.suppress(OSError):
                    os.remove(path)

    # -- srml-watch health surface (NON-collective, unlike the gathers) ------
    def publish_health(self, payload: str) -> None:
        """Atomically overwrite this rank's heartbeat file.  Unlike the
        numbered gather rounds this is fire-and-forget: no rank ever waits
        on it, so a wedged rank cannot stall the health plane — which is
        the whole point (watch.HeartbeatPublisher calls this on its own
        thread while the fit thread may be stuck in a collective)."""
        path = os.path.join(self._root, f"health_rank{self._rank:05d}.json")
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)

    def read_health(self) -> Dict[int, str]:
        """Latest heartbeat payload per rank (missing ranks absent) — the
        watchdog's read side; never blocks."""
        out: Dict[int, str] = {}
        for i in range(self._nranks):
            p = os.path.join(self._root, f"health_rank{i:05d}.json")
            try:
                with open(p) as f:
                    out[i] = f.read()
            except OSError:
                continue
        return out


def global_mesh() -> Mesh:
    """1-D data mesh over EVERY device in the (possibly multi-process)
    runtime, ordered process-major so the row sharding assigns rank r's
    contiguous global row block to rank r's local devices."""
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return Mesh(np.array(devs), (DATA_AXIS,))


# -- JSON-safe model-attribute transport -------------------------------------
# The driver gets model attributes back through Spark rows (strings), so
# arrays ride as base64 raw bytes + dtype/shape (the reference ships cuML
# attrs as JSON text rows the same way, core.py:625-630).

def _encode_value(v: Any) -> Any:
    if isinstance(v, jax.Array):
        v = jax.device_get(v)  # explicit fetch: sanitize-scope clean
    if isinstance(v, np.ndarray):
        return {
            "__ndarray__": base64.b64encode(
                np.ascontiguousarray(v).tobytes()
            ).decode("ascii"),
            "dtype": str(v.dtype),
            "shape": list(v.shape),
        }
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, dict):
        return {k: _encode_value(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_encode_value(x) for x in v]
    return v


def _decode_value(v: Any) -> Any:
    if isinstance(v, dict):
        if "__ndarray__" in v:
            return (
                np.frombuffer(
                    base64.b64decode(v["__ndarray__"]), dtype=np.dtype(v["dtype"])
                )
                .reshape(v["shape"])
                .copy()
            )
        return {k: _decode_value(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_decode_value(x) for x in v]
    return v


def encode_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: _encode_value(v) for k, v in attrs.items()}


def _encoded_nbytes(v: Any) -> int:
    """The base64 characters of every array under an encoded value."""
    if isinstance(v, dict):
        if "__ndarray__" in v:
            return len(v["__ndarray__"])
        return sum(_encoded_nbytes(x) for x in v.values())
    if isinstance(v, list):
        return sum(_encoded_nbytes(x) for x in v)
    return 0


def allgather_ndarray(
    control_plane: Any, rank: int, arr: np.ndarray
) -> List[np.ndarray]:
    """Rank-ordered allGather of one ndarray over the string control plane,
    riding the same base64 codec as the model-attribute transport (the
    reference ships whole serialized models through its barrier allGather
    the same way, tree.py:316-363).  Every rank receives the identical
    rank-ordered list, so derived quantities (bin edges, class sets) are
    bitwise-consistent across ranks."""
    msg = json.dumps({"rank": rank, "v": _encode_value(np.asarray(arr))})
    blocks = sorted(
        (json.loads(m) for m in control_plane.allGather(msg)),
        key=lambda g: g["rank"],
    )
    return [_decode_value(g["v"]) for g in blocks]


def decode_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: _decode_value(v) for k, v in attrs.items()}


# -- the distributed fit session ---------------------------------------------

class DistributedFitSession:
    """One jax.distributed lifetime; fits any number of estimators over the
    pod-wide mesh (the per-fit NCCL create/destroy of the reference,
    cuml_context.py:109-166, generalized so callers can amortize the
    bootstrap across fits)."""

    def __init__(self, rank: int, nranks: int, control_plane: ControlPlane):
        self.rank = rank
        self.nranks = nranks
        self.control_plane = control_plane
        self.mesh = global_mesh()

    def build_fit_inputs(
        self, estimator: Any, df: Any, x64: Callable[[Any], None] = lambda dtype: None
    ) -> Any:
        """FitInputs over the pod mesh.  One rank is the single-controller
        case and takes _TpuCaller._build_fit_inputs on the session's mesh;
        several ranks agree on sizes and dtype over the control plane and
        each sends its padded share of the global arrays.  `x64` is
        core.FitJob.staged's: called with the dtype the ranks agreed on,
        before anything goes up."""
        from ..core import FitInputs, _device_put_counted, stage_mask_and_labels

        if self.nranks == 1:
            # one rank owns every row and every device: the single-controller
            # ingest, batch by batch (core.stage_dense_batches), on this mesh
            inputs = estimator._build_fit_inputs(df, x64, mesh=self.mesh)
            inputs.control_plane = self.control_plane
            return inputs
        # A rank can legitimately hold ZERO rows (fewer rows than barrier
        # tasks, skewed repartition).  It must still join every gather —
        # bailing out locally would hang the other ranks — so it reports
        # empty sizes and takes its dtype from the data-bearing ranks.
        rank_has_rows = any(len(p) > 0 for p in df.partitions)
        if rank_has_rows:
            feats, labels, weights, dtype = estimator._pre_process_data(df)
            feats = list(feats)     # a rank's share is assembled on the host
        else:
            feats, weights, dtype = [], None, None
            labels = [] if estimator._fit_label_col() is not None else None
        partition_rows = [f.shape[0] for f in feats]
        nonempty = [f for f in feats if f.shape[0] > 0]
        n_loc = sum(partition_rows)
        n_cols_loc = nonempty[0].shape[1] if nonempty else 0
        pdesc = PartitionDescriptor.gather(
            partition_rows, n_cols_loc, self.rank, self.nranks,
            self.control_plane,
            extra={"dtype": str(dtype) if dtype is not None else ""},
        )
        if pdesc.m == 0:
            raise RuntimeError("Dataset is empty; cannot fit")
        n_cols = pdesc.n
        dtypes = {e["dtype"] for e in pdesc.extras if e.get("dtype")}
        if len(dtypes) > 1:
            raise ValueError(f"ranks disagree on input dtype: {sorted(dtypes)}")
        if dtype is None:
            dtype = np.dtype(dtypes.pop())

        n_total_dev = self.mesh.devices.size
        if n_total_dev % self.nranks != 0:
            raise RuntimeError(
                f"{n_total_dev} devices do not divide evenly over "
                f"{self.nranks} ranks"
            )
        local_dev = n_total_dev // self.nranks
        # every rank contributes the same padded share so the global array is
        # evenly row-sharded; the share covers the LARGEST rank (unbalanced
        # partitions cost padding, not correctness — Spark's repartition
        # keeps them near-equal anyway)
        max_rank_rows = max(pdesc.rank_rows(r) for r in range(self.nranks))
        share = -(-max_rank_rows // local_dev) * local_dev
        n_pad = share * self.nranks

        x64(dtype)

        def to_global(buf: np.ndarray):
            """This rank's padded share of a global row-sharded array."""
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P(DATA_AXIS)),
                buf,
                global_shape=(n_pad,) + buf.shape[1:],
            )

        X_loc = np.zeros((share, n_cols), dtype=dtype)
        if nonempty:
            if nonempty[0].shape[1] != n_cols:
                raise ValueError(
                    f"rank {self.rank} has {nonempty[0].shape[1]} feature "
                    f"columns, other ranks have {n_cols}"
                )
            np.concatenate(nonempty, axis=0, out=X_loc[:n_loc])
        Xs = _device_put_counted(lambda: to_global(X_loc), sent=X_loc)
        y_loc = None
        if labels is not None:  # [] on a rank without rows: all padding
            y_loc = np.concatenate(labels) if labels else np.zeros(0, dtype=dtype)
        ws, ys = stage_mask_and_labels(
            n_loc, share, dtype, y_loc,
            np.concatenate(weights) if weights else None,  # None or []
            to_global,
        )

        return FitInputs(
            X=Xs,
            weight=ws,
            y=ys,
            n_rows=pdesc.m,
            n_cols=n_cols,
            mesh=self.mesh,
            pdesc=pdesc,
            dtype=dtype,
            rank=self.rank,
            nranks=self.nranks,
            control_plane=self.control_plane,
        )

    def fit(
        self,
        estimator: Any,
        partitions: Sequence[pd.DataFrame],
        extra_params: Optional[List[Dict[str, Any]]] = None,
    ) -> List[Dict[str, Any]]:
        """Run the estimator's fit function over the pod mesh; returns the
        JSON-safe encoded model-attribute dict(s) (one per param map)."""
        from ..dataframe import DataFrame

        if self.nranks > 1 and not getattr(
            estimator, "_supports_multicontroller_fit", True
        ):
            raise NotImplementedError(
                f"{type(estimator).__name__} does not yet support "
                "multi-process (barrier) training: its fit function "
                "host-fetches row-sharded inputs. Train with num_workers=1 "
                "or SRML_SPARK_COLLECT=1 (driver-local fit)."
            )
        df = DataFrame(list(partitions))
        from .. import watch
        from ..core import TELEMETRY_ATTR, fit_job

        # srml-watch: every rank heartbeats through the control plane's
        # non-collective publish surface (rank 0 also runs the stall
        # watchdog when SRML_WATCH_STALL_S > 0); the job's flight scope
        # covers the other failure mode that used to die silent, a crash
        health = watch.start_fit_health(self.control_plane, self.rank, self.nranks)
        try:
            with fit_job(estimator, rank=self.rank) as job:
                with job.prepare():
                    fit_func = estimator._get_tpu_fit_func(df, extra_params)
                build = functools.partial(self.build_fit_inputs, estimator, df)
                result = job.run(build, fit_func, dict(estimator._tpu_params))
                with job.finish(), profiling.span("srml.finish.encode") as sp:
                    # a base64 copy of every attribute: 3001 floats for a linear
                    # model, every node of every tree for a forest
                    encoded = [
                        encode_attrs(r)
                        for r in (result if isinstance(result, list) else [result])
                    ]
                    sp.set(bytes=_encoded_nbytes(encoded))
        finally:
            health.stop()
        # The job's telemetry snapshot, merged ACROSS RANKS through the
        # control plane before rank 0's results leave for the driver —
        # this is how the driver-side model sees where every executor's fit
        # spent its time (the reference's per-task NVTX/log lines die on the
        # executors; a mergeable rollup is the only thing that can ride the
        # model-attribute wire).  One extra string gather round; every rank
        # participates (collective contract).
        merged = job.snapshot
        if self.nranks > 1:
            gathered = self.control_plane.allGather(json.dumps(merged.to_dict()))
            snaps = sorted(
                (json.loads(m) for m in gathered),
                key=lambda d: d.get("meta", {}).get("ranks", [0]),
            )
            merged = profiling.TelemetrySnapshot.from_dict(snaps[0])
            for d in snaps[1:]:
                merged = merged.merge(profiling.TelemetrySnapshot.from_dict(d))
        self.control_plane.barrier()
        for e in encoded:
            e[TELEMETRY_ATTR] = merged.to_dict()
        return encoded


@contextlib.contextmanager
def distributed_session(
    rank: int, nranks: int, control_plane: Optional[ControlPlane] = None
) -> Iterator[DistributedFitSession]:
    cp = control_plane or LocalControlPlane()
    try:
        with TpuContext(rank, nranks, cp):
            yield DistributedFitSession(rank, nranks, cp)
    finally:
        # srml-shield teardown contract: remove this rank's control-plane
        # presence files (alive pid, heartbeat) and reap dead peers' — runs
        # AFTER TpuContext.__exit__ so an abort marker broadcast on the
        # exception path is already published
        closer = getattr(cp, "close", None)
        if closer is not None:
            closer()


def run_distributed_fit(
    estimator: Any,
    partitions: Sequence[pd.DataFrame],
    rank: int,
    nranks: int,
    control_plane: Optional[ControlPlane] = None,
    extra_params: Optional[List[Dict[str, Any]]] = None,
) -> List[Dict[str, Any]]:
    """One-shot: bootstrap the distributed runtime, fit, tear down.  This is
    what the Spark barrier UDF calls per task (spark/adapter.run_barrier_fit);
    the reference equivalent is the body of _train_udf at core.py:558-632."""
    with distributed_session(rank, nranks, control_plane) as session:
        return session.fit(estimator, partitions, extra_params)
