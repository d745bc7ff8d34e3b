#
# Distributed-runtime lifecycle management.
#
# TPU-native replacement for the reference's CumlContext
# (/root/reference/python/src/spark_rapids_ml/common/cuml_context.py:35-192),
# which creates a raft Handle, has rank 0 mint an NCCL uid, spreads it via
# BarrierTaskContext.allGather, and injects NCCL/UCX comms.  Here the same
# three-phase shape holds, but the data plane is jax.distributed + XLA
# collectives over ICI/DCN:
#
#   1. rank 0 picks a coordinator address (host:port) — analog of the NCCL uid
#   2. the address is allGathered over the *control plane* (Spark barrier RPC
#      in the Spark adapter; trivial in single-controller local mode)
#   3. every rank calls jax.distributed.initialize(coordinator, nranks, rank);
#      afterwards jax.devices() spans the pod and a global Mesh is built, so
#      psum/all_gather/ppermute ride ICI within a host and DCN across hosts.
#
# __exit__ tears down jax.distributed the way CumlContext.__exit__ destroys or
# aborts the NCCL comm (cuml_context.py:149-166).
#

from __future__ import annotations

import json
import random
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Protocol, Sequence, Tuple

import jax

from ..utils import env_float as _env_float
from ..utils import get_logger
from . import faults

# -- srml-shield / srml-wire control-plane knobs (docs/robustness.md) ---------
# Shared by EVERY plane implementation (FileControlPlane, TcpControlPlane):
# per-ROUND bounded timeout instead of one session-wide cliff, and retrying
# I/O with exponential backoff + deterministic per-rank jitter for transient
# transport errors (NFS burps on the file plane, connection resets on the
# socket plane).
ROUND_TIMEOUT_ENV = "SRML_CP_ROUND_TIMEOUT_S"
RETRIES_ENV = "SRML_CP_RETRIES"
BACKOFF_ENV = "SRML_CP_BACKOFF_S"
# jax.distributed coordination-service heartbeat timeout (seconds): bounds
# how long any jax-layer teardown can dangle on a dead peer
JAX_HEARTBEAT_TIMEOUT_ENV = "SRML_JAX_HEARTBEAT_TIMEOUT_S"
_DEFAULT_JAX_HEARTBEAT_TIMEOUT_S = 10
_DEFAULT_ROUND_TIMEOUT_S = 300.0
_DEFAULT_RETRIES = 3
_DEFAULT_BACKOFF_S = 0.05


@dataclass(frozen=True)
class RetryPolicy:
    """The SRML_CP_RETRIES / SRML_CP_BACKOFF_S contract, parsed ONCE at
    plane construction (a per-I/O env re-parse was the old file-plane shape)
    and shared verbatim by the file and TCP planes.  `run` retries `fn` on
    the given transient exception types with exponential backoff and
    deterministic per-rank jitter (explicitly seeded: graftlint R4)."""

    retries: int
    backoff_s: float

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        return cls(
            retries=int(_env_float(RETRIES_ENV, _DEFAULT_RETRIES)),
            backoff_s=_env_float(BACKOFF_ENV, _DEFAULT_BACKOFF_S),
        )

    def run(
        self,
        fn,
        jitter: random.Random,
        retry_on: Tuple[type, ...] = (OSError,),
        counter: str = "cp.io_retries",
    ):
        from .. import profiling

        attempt = 0
        while True:
            try:
                return fn()
            except retry_on:
                if attempt >= self.retries:
                    raise
                delay = self.backoff_s * (2 ** attempt) * (
                    1.0 + 0.25 * jitter.random()
                )
                profiling.incr_counter(counter)
                attempt += 1
                time.sleep(delay)


class ControlPlaneTimeout(TimeoutError):
    """A gather round ran out its per-round budget with ranks still missing.
    Typed (vs the old builtin TimeoutError) so callers can distinguish "the
    collective never completed" from arbitrary stdlib timeouts, and
    self-describing: it carries the round number, the ranks that never
    posted, and the knob that bounds the budget.  Still a TimeoutError
    subclass so existing `except TimeoutError` handlers keep working."""

    def __init__(
        self,
        plane: str,
        round_no: int,
        missing_ranks: Sequence[int],
        timeout_s: float,
        knob: str = ROUND_TIMEOUT_ENV,
    ):
        self.plane = plane
        self.round_no = int(round_no)
        self.missing_ranks = sorted(int(r) for r in missing_ranks)
        self.timeout_s = float(timeout_s)
        self.knob = knob
        super().__init__(
            f"{plane} round {self.round_no}: ranks {self.missing_ranks} "
            f"never posted within {self.timeout_s}s ({knob} bounds each "
            "round)"
        )


class RemoteRankError(RuntimeError):
    """Another rank of the cooperating job failed (orderly abort) or died
    (no marker — killed/OOMed) while this rank waited on a collective.
    Raised by the control plane's gather waits within one poll interval of
    the abort marker / dead pid appearing, instead of the full round
    timeout — and it NAMES the culprit: origin rank, its exception type,
    and the innermost span it was in (from the srml-watch health surface),
    so the survivor's traceback reads "rank 1 died in exchange.ring", not
    "TimeoutError after 300 s"."""

    def __init__(
        self,
        rank: int,
        message: str,
        span: Optional[str] = None,
        etype: Optional[str] = None,
    ):
        self.rank = int(rank)
        self.span = span
        self.etype = etype
        where = f" in span {span!r}" if span else ""
        what = f"{etype}: {message}" if etype else message
        super().__init__(f"remote rank {self.rank}{where}: {what}")


class ControlPlane(Protocol):
    """Minimal control-plane contract: Spark's BarrierTaskContext satisfies it
    (allGather of strings + barrier), as does the local trivial impl.

    ORDERING REQUIREMENT: allGather must return messages indexed by rank
    (result[r] = rank r's message) — Spark's BarrierTaskContext orders by
    partition id, FileControlPlane by rank-numbered files.  The binary
    collectives (parallel/exchange.py) and the kneighbors exchange index
    results positionally and would silently mis-attribute payloads on an
    arrival-ordered plane.

    Planes MAY additionally provide ``allGatherBytes(bytes) -> List[bytes]``
    (same semantics, binary frames); exchange.py uses it to skip base64
    where the transport allows raw bytes."""

    def allGather(self, message: str) -> List[str]: ...

    def barrier(self) -> None: ...


class LocalControlPlane:
    """Single-controller control plane: one process drives the whole mesh, so
    gather/barrier are identities."""

    def __init__(self) -> None:
        self._health: dict = {}

    def allGather(self, message: str) -> List[str]:
        return [message]

    def allGatherBytes(self, message: bytes) -> List[bytes]:
        return [message]

    def barrier(self) -> None:
        return None

    # srml-watch health surface (non-collective): trivial in-process store
    # so thread-mocked rank harnesses can exercise the heartbeat/watchdog
    # contract without a shared filesystem
    def publish_health(self, payload: str) -> None:
        import json as _json

        try:
            rank = int(_json.loads(payload).get("rank", 0))
        except (ValueError, TypeError):
            rank = 0
        self._health[rank] = payload

    def read_health(self) -> dict:
        return dict(self._health)

    # srml-shield abort surface (single-controller: no peers to warn, but
    # the conformance suite holds every plane to the same method shape)
    def abort(self, payload: str) -> None:
        return None

    def check_abort(self) -> Optional[Dict[str, Any]]:
        return None

    def close(self) -> None:
        return None


class TpuContext:
    """Context manager bootstrapping the distributed jax runtime for one fit.

    In single-controller mode (nranks == 1 processes) this is a cheap no-op
    that exposes the local device mesh.  In multi-controller mode (one process
    per Spark barrier task / TPU-VM worker) it initializes jax.distributed
    with a coordinator address exchanged over the control plane, mirroring the
    NCCL-uid handshake of the reference (cuml_context.py:75-103).
    """

    def __init__(
        self,
        rank: int,
        nranks: int,
        control_plane: Optional[ControlPlane] = None,
        require_dcn: bool = False,
    ):
        self._rank = rank
        self._nranks = nranks
        self._cp = control_plane or LocalControlPlane()
        self._require_dcn = require_dcn
        self._initialized_distributed = False
        self._logger = get_logger(type(self))

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def nranks(self) -> int:
        return self._nranks

    def __enter__(self) -> "TpuContext":
        faults.site("context.init", rank=self._rank)
        if self._nranks > 1:
            # rank 0 advertises coordinator host:port; everyone gathers it.
            # A port-allocating control plane (TcpControlPlane) hands out a
            # coordinator-reserved port — no two sessions through the same
            # coordinator can collide, killing the _free_port rebind race
            # between sibling jobs on one host.  Planes without the surface
            # (file / Spark barrier) keep the best-effort ephemeral pick.
            from .netplane import _free_port, _local_ip

            if self._rank == 0:
                if hasattr(self._cp, "allocate_port"):
                    port = self._cp.allocate_port()
                else:
                    port = _free_port()
                addr = f"{_local_ip()}:{port}"
            else:
                addr = ""
            gathered = self._cp.allGather(json.dumps({"rank": self._rank, "addr": addr}))
            coordinator = ""
            for msg in gathered:
                info = json.loads(msg)
                if info["rank"] == 0:
                    coordinator = info["addr"]
            assert coordinator, "rank 0 coordinator address missing from allGather"
            self._logger.info(
                "rank %d/%d connecting to coordinator %s",
                self._rank, self._nranks, coordinator,
            )
            # Coordination-service heartbeat timeout tightened from the
            # stock 100 s: that was how long a survivor's teardown dangled
            # on a dead peer before the client's missed-heartbeat handler
            # fired (srml-wire chaos drive).  The control plane still owns
            # FAST detection (ms-scale markers/leases); this bounds the
            # jax-layer tail.
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=self._nranks,
                process_id=self._rank,
                heartbeat_timeout_seconds=max(
                    2,
                    int(
                        _env_float(
                            JAX_HEARTBEAT_TIMEOUT_ENV,
                            _DEFAULT_JAX_HEARTBEAT_TIMEOUT_S,
                        )
                    ),
                ),
            )
            self._initialized_distributed = True
        return self

    def __exit__(self, exc_type: Any, exc_val: Any, exc_tb: Any) -> None:
        # Abort-vs-clean semantics — the reference deliberately
        # distinguishes NCCL abort()-on-error from destroy()-on-clean
        # (cuml_context.py:149-166); here the exception path BROADCASTS an
        # abort marker through the control plane FIRST, so peers blocked
        # in a collective wait raise RemoteRankError within one poll
        # interval instead of riding out the round timeout.  A
        # RemoteRankError is itself a relayed abort: re-broadcasting it
        # would cascade markers around the ring, so only ORIGINAL failures
        # publish.
        if (
            exc_type is not None
            and self._nranks > 1
            and not isinstance(exc_val, RemoteRankError)
            and hasattr(self._cp, "abort")
        ):
            try:
                from .. import watch

                self._cp.abort(json.dumps({
                    "rank": self._rank,
                    "etype": exc_type.__name__,
                    "message": str(exc_val)[:512],
                    "span": watch.failing_span(),
                }))
            except Exception as abort_exc:  # noqa: BLE001 - best effort
                # the abort broadcast must never mask the real error, but
                # its failure is LOGGED, not swallowed (graftlint R9)
                self._logger.warning("abort broadcast failed: %s", abort_exc)
        if self._initialized_distributed:
            if exc_type is not None:
                # The abort-vs-destroy contract, for real:
                # jax.distributed.shutdown() runs a COLLECTIVE shutdown
                # barrier.  On any abort path a peer is dead or about to
                # be (it is unwinding this same path), so the barrier can
                # never complete — and the client LOG(FATAL)s the whole
                # process after the coordination heartbeat timeout,
                # killing the typed RemoteRankError before it reaches the
                # user (found by the srml-wire chaos drive).
                # Abort therefore means detach WITHOUT the barrier: skip
                # the call, let process teardown reclaim the sockets —
                # exactly NCCL abort() vs destroy().
                self._logger.warning(
                    "abort path (%s unwinding): skipping the collective "
                    "jax.distributed.shutdown barrier — it cannot "
                    "complete once a peer is gone",
                    exc_type.__name__,
                )
            else:
                jax.distributed.shutdown()
        return None
