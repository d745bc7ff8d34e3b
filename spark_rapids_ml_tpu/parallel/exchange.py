#
# Length-prefixed binary array codec + bulk collectives over a string (or
# bytes-capable) control plane.
#
# TPU-native stand-in for the reference's UCX data-plane transfers inside
# NearestNeighborsMG (knn.py:452-560, cuml_context.py:99-146): where cuML
# ships query blocks and per-rank (Q, k) candidate lists as binary UCX
# frames point-to-point, this module frames ndarrays into length-prefixed
# binary payloads and moves them over whatever allGather the cluster offers
# (Spark's BarrierTaskContext RPC, the shared-FS FileControlPlane, or an
# in-process mock).
#
# Why not JSON+base64 per array (the round-4 transport): at reference scale
# (Q=1M, k=200, 8 ranks) round 2 of distributed_kneighbors made every rank
# parse ~8 x 2.4 GB of base64-JSON it mostly discarded.  Here
# (a) arrays ride one binary frame — no JSON parse, no per-array base64 on
#     bytes-capable planes, and
# (b) alltoall_bytes frames chunks PER DESTINATION, so a receiver only
#     materializes (base64-decodes + joins + unpacks) the chunks addressed
#     to it: per-rank decode volume is O(own share), matching the p2p shape
#     of the reference exchange even though a broadcast allGather carries
#     the wire bytes underneath.
#
# Every helper is a COLLECTIVE: all ranks must call it the same number of
# times, empty payloads included (a bailing rank would hang the barrier).
#

from __future__ import annotations

import base64
import contextlib
import json
import struct
from typing import Any, Iterator, List, Optional, Sequence

import numpy as np

from .. import profiling
from . import faults

# per-frame chunk bound: Spark's allGather rides the RPC channel
# (spark.rpc.message.maxSize default 128 MiB); 8 MiB keeps each frame far
# under the limit with base64 overhead (same bound as knn._allgather_large)
CHUNK_BYTES = 8 << 20

_MAGIC = b"SRX1"


# -- the ONE collective reporting wrapper -------------------------------------
# Every exchange primitive — host control-plane collective or in-mesh device
# collective — reports through section(): uniform `exchange.<name>.bytes` /
# `exchange.<name>.time_ns` / `exchange.<name>.calls` process counters plus a
# hierarchical span named `exchange.<name>` (srml-scope), so per-section
# byte/time accounting is one namespace regardless of which idiom moved the
# data (the first concrete step of ROADMAP item 5's unified comms layer).
#
# Host sections measure wall clock.  Device sections (psum_parts,
# allgather_rows, psum_merge_parts) run at TRACE time inside shard_map
# bodies, where wall clock is meaningless — they report the STATIC payload
# bytes of the traced shapes plus a trace count, and wrap the collective in
# jax.named_scope so the section shows up by name in xprof/HLO instead.
# Counters therefore move once per compiled geometry for device sections and
# once per call for host sections; docs/observability.md spells this out.


@contextlib.contextmanager
def section(name: str, nbytes: Optional[int] = None) -> Iterator[None]:
    """Host-side collective section: span + byte/time/call counters."""
    full = f"exchange.{name}"
    t0 = profiling.now()
    with profiling.span(full, **({"bytes": int(nbytes)} if nbytes else {})):
        yield
    dt = profiling.now() - t0
    profiling.incr_counter(f"{full}.calls")
    profiling.incr_counter(f"{full}.time_ns", int(dt * 1e9))
    if nbytes:
        profiling.incr_counter(f"{full}.bytes", int(nbytes))


def _static_nbytes(*arrays: Any) -> int:
    """Payload bytes of traced (or concrete) arrays from their STATIC
    shape/dtype — safe on tracers inside shard_map bodies."""
    total = 0
    for a in arrays:
        n = 1
        for s in a.shape:
            n *= int(s)
        total += n * np.dtype(a.dtype).itemsize
    return total


def device_section(name: str, *arrays: Any):
    """Device-side collective section: called at trace time inside a
    shard_map body.  Records the static payload bytes + a trace count and
    returns a jax.named_scope so the section is named in device traces
    (wall-clock for device sections lives in the xprof timeline, not the
    host counters)."""
    import jax

    full = f"exchange.{name}"
    profiling.incr_counter(f"{full}.traces")
    profiling.incr_counter(f"{full}.bytes", _static_nbytes(*arrays))
    return jax.named_scope(full)


def _record_link_bytes(name: str, ici: int, dcn: int) -> None:
    """Per-link split counters, ADDITIVE to the legacy `.bytes` total:
    `exchange.<name>.ici_bytes` / `.dcn_bytes` are whole-mesh byte MODELS
    of the schedule that traced (topology.link_split_*), where `.bytes`
    stays the per-shard payload.  The `_bytes` suffix keeps them out of
    byte_totals()'s `.bytes` scan; link_totals() rolls them up."""
    if ici:
        profiling.incr_counter(f"exchange.{name}.ici_bytes", int(ici))
    if dcn:
        profiling.incr_counter(f"exchange.{name}.dcn_bytes", int(dcn))


def pack_arrays(arrays: Sequence[np.ndarray]) -> bytes:
    """One binary frame: magic, array count, then per array a dtype/shape
    header followed by the raw C-order buffer.  No base64, no JSON."""
    parts = [_MAGIC, struct.pack("<I", len(arrays))]
    bufs = []
    for a in arrays:
        a = np.asarray(a)
        if not a.flags.c_contiguous:
            # (ascontiguousarray would also promote 0-dim to 1-dim)
            a = np.ascontiguousarray(a)
        dt = a.dtype.str.encode("ascii")  # e.g. b'<f4' — endian-explicit
        parts.append(struct.pack("<B", len(dt)))
        parts.append(dt)
        parts.append(struct.pack("<B", a.ndim))
        parts.append(struct.pack(f"<{a.ndim}q", *a.shape))
        parts.append(struct.pack("<q", a.nbytes))
        bufs.append(a.tobytes())
    return b"".join(parts) + b"".join(bufs)


def unpack_arrays(buf: bytes) -> List[np.ndarray]:
    if buf[:4] != _MAGIC:
        raise ValueError("not an SRX1 frame")
    (count,) = struct.unpack_from("<I", buf, 4)
    off = 8
    metas = []
    for _ in range(count):
        (dl,) = struct.unpack_from("<B", buf, off)
        off += 1
        dt = np.dtype(buf[off : off + dl].decode("ascii"))
        off += dl
        (nd,) = struct.unpack_from("<B", buf, off)
        off += 1
        shape = struct.unpack_from(f"<{nd}q", buf, off)
        off += 8 * nd
        (nb,) = struct.unpack_from("<q", buf, off)
        off += 8
        metas.append((dt, shape, nb))
    out = []
    for dt, shape, nb in metas:
        out.append(
            np.frombuffer(buf, dtype=dt, count=nb // dt.itemsize, offset=off)
            .reshape(shape)
            .copy()
        )
        off += nb
    return out


def _chunks(payload: bytes, chunk: int) -> List[bytes]:
    return [payload[i : i + chunk] for i in range(0, len(payload), chunk)] or [
        b""
    ]


def _send(cp: Any, data: bytes, use_bytes: bool) -> List[Any]:
    if use_bytes:
        return cp.allGatherBytes(data)
    return cp.allGather(base64.b64encode(data).decode("ascii"))


def _recv(frame: Any, use_bytes: bool) -> bytes:
    if use_bytes:
        return frame
    out = base64.b64decode(frame)
    return out


def allgather_bytes(
    cp: Any, payload: bytes, chunk: int = CHUNK_BYTES
) -> List[bytes]:
    """Broadcast allGather of one binary payload per rank (every receiver
    materializes every rank's payload — use for data all sides need, e.g.
    the query broadcast).  Chunked under the transport frame limit.
    Wall-clock and payload bytes land in the "exchange.allgather" section
    (span + counters) so control-plane time is separable from device compute
    in fit reports and telemetry snapshots."""
    with section("allgather", nbytes=len(payload)):
        use_bytes = hasattr(cp, "allGatherBytes")
        mine = _chunks(payload, chunk)
        counts = [int(c) for c in cp.allGather(str(len(mine)))]
        parts: List[List[bytes]] = [[] for _ in counts]
        for r in range(max(counts)):
            got = _send(cp, mine[r] if r < len(mine) else b"", use_bytes)
            for s, g in enumerate(got):
                if r < counts[s]:
                    parts[s].append(_recv(g, use_bytes))
        return [b"".join(p) for p in parts]


# -- device-side collectives: typed sections -----------------------------------
# The helpers above move HOST bytes over whatever allGather the cluster
# control plane offers.  DeviceSection is their IN-MESH analog for code
# running inside shard_map bodies (jax collectives over ICI/DCN), as TYPED
# SECTIONS: every engine names its call site (`device_collective("umap.
# layout_rows")`, `device_collective("knn.ring_q")`, ...) and gets the same
# uniform `exchange.<name>.bytes/traces` counters regardless of which idiom
# moved the data — the consolidated comms layer of ROADMAP item 5.  The
# legacy module-level functions (allgather_rows/psum_parts/psum_merge_parts)
# remain as un-named-section shims over the same implementations.
#
# ring_shift is the one NEW idiom: a +shift neighbor permute along the mesh
# ring.  On TPU hardware it lowers to a Pallas `pltpu.make_async_remote_copy`
# kernel (neighbor-to-neighbor ICI DMA, the SNIPPETS.md exemplar) — the ONLY
# module allowed to touch the remote-DMA API (graftlint R8).  Every other
# backend (XLA:CPU meshes, interpret mode, remote-DMA disabled via
# SRML_EXCHANGE_REMOTE_DMA=0) takes the identical-semantics lax.ppermute
# fallback, which is what the tier-1 parity gates run everywhere.


class DeviceSection:
    """Typed handle for one named in-mesh collective section.  Construct
    via device_collective(name[, topo]); every method must be called ONLY
    inside a shard_map body bound over `axis_name` (default DATA_AXIS).

    With a hierarchical `topology.TopologyMap` attached, the gather-class
    collectives run the two-level schedule (gather within the host group,
    ONE gateway exchange across groups, broadcast back within the group)
    and ring_shift follows the gateway-aware cycle; every method also
    splits its modeled traffic into `.ici_bytes`/`.dcn_bytes`.  The map is
    STATIC data — callers must carry it in their jit/cache keys (the kNN
    kernels pass it through kernel_cache_key statics), never read it from
    the environment at trace time."""

    __slots__ = ("name", "topo")

    def __init__(self, name: str, topo=None):
        self.name = name
        self.topo = topo

    def _resolved(self, n_dev: int):
        """The attached map when it matches this mesh's axis size, else
        the trivial flat map (a mismatched map would mis-schedule; the
        kNN dispatch derives per-mesh so this only guards foreign
        reuse)."""
        from . import topology

        if self.topo is not None and self.topo.n_devices == int(n_dev):
            return self.topo
        return topology.flat_topology(int(n_dev))

    def _hier_slab(self, x, axis: str, topo):
        """The (n_dev, ...) all-shards slab via the two-level schedule:
        gather within the host group (ICI), scatter the group's blocks
        into a zeros slab on the GATEWAY only, then ONE full-axis psum so
        each group's slab-part crosses DCN once and lands replicated
        (which also keeps shard_map's replication inference sound —
        grouped gathers alone are opaque to it).  Every slab element is
        one shard's value plus zeros exactly like the flat zeros-slab
        psum, so the result is BITWISE equal to the flat schedule."""
        import jax
        import jax.numpy as jnp

        gmat = jnp.asarray(np.asarray(topo.groups, dtype=np.int32))
        gof = jnp.asarray(np.asarray(topo.group_of, dtype=np.int32))
        gate = jnp.asarray(np.asarray(topo.gateways, dtype=np.int32))
        idx = jax.lax.axis_index(axis)
        gid = jnp.take(gof, idx)
        intra = jax.lax.all_gather(
            x, axis, axis_index_groups=[list(g) for g in topo.groups]
        )
        rows = jnp.take(gmat, gid, axis=0)
        slab = (
            jnp.zeros((topo.n_devices,) + x.shape, x.dtype)
            .at[rows].set(intra)
        )
        part = jnp.where(
            (idx == jnp.take(gate, gid)).reshape((1,) * slab.ndim),
            slab,
            jnp.zeros_like(slab),
        )
        return jax.lax.psum(part, axis)

    def allgather_rows(self, x, axis_name: str = None):
        """Concatenate per-device row blocks along axis 0 (tiled)."""
        import jax

        from . import topology
        from .mesh import DATA_AXIS

        axis = axis_name or DATA_AXIS
        with device_section(self.name, x):
            n_dev = jax.lax.psum(1, axis)
            topo = self._resolved(n_dev)
            _record_link_bytes(
                self.name, *topology.link_split_gather(topo, _static_nbytes(x))
            )
            if topo.is_hierarchical:
                slab = self._hier_slab(x, axis, topo)
                return slab.reshape((n_dev * x.shape[0],) + x.shape[1:])
            return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    def gather_stack(self, x, axis_name: str = None):
        """Stack per-device blocks into a leading (n_dev, ...) axis —
        the candidate-list gather shape of the exact kNN block kernel."""
        import jax

        from . import topology
        from .mesh import DATA_AXIS

        axis = axis_name or DATA_AXIS
        with device_section(self.name, x):
            n_dev = jax.lax.psum(1, axis)
            topo = self._resolved(n_dev)
            _record_link_bytes(
                self.name, *topology.link_split_gather(topo, _static_nbytes(x))
            )
            if topo.is_hierarchical:
                return self._hier_slab(x, axis, topo)
            return jax.lax.all_gather(x, axis)

    def psum(self, x, axis_name: str = None):
        """Element-wise sum of per-device partials (lax.psum).  The
        hierarchical schedule reduces within the group first and crosses
        DCN with the group-reduced partial; summation is re-associated, so
        (unlike the movement-only collectives) it is NOT bitwise-pinned to
        the flat schedule for non-exact dtypes — the forest/stat engines
        that need exactness keep the flat default."""
        import jax
        import jax.numpy as jnp

        from . import topology
        from .mesh import DATA_AXIS

        axis = axis_name or DATA_AXIS
        leaves = jax.tree_util.tree_leaves(x)
        with device_section(self.name, *leaves):
            n_dev = jax.lax.psum(1, axis)
            topo = self._resolved(n_dev)
            _record_link_bytes(
                self.name,
                *topology.link_split_reduce(topo, _static_nbytes(*leaves)),
            )
            if topo.is_hierarchical:
                gof = jnp.asarray(np.asarray(topo.group_of, dtype=np.int32))
                gate = jnp.asarray(np.asarray(topo.gateways, dtype=np.int32))
                idx = jax.lax.axis_index(axis)
                is_gate = idx == jnp.take(gate, jnp.take(gof, idx))
                groups = [list(g) for g in topo.groups]

                def _leaf(leaf):
                    intra = jax.lax.all_gather(
                        leaf, axis, axis_index_groups=groups
                    )
                    part = jnp.sum(intra, axis=0)
                    part = jnp.where(
                        is_gate.reshape((1,) * part.ndim),
                        part,
                        jnp.zeros_like(part),
                    )
                    return jax.lax.psum(part, axis)

                return jax.tree_util.tree_map(_leaf, x)
            return jax.lax.psum(x, axis)

    def psum_merge(self, x, axis_name: str = None):
        """Stack per-device candidate blocks into one (n_dev, ...) slab via
        a single psum (zeros-slab scatter; exact as a gather — every element
        receives one shard's value plus zeros, and x + 0.0 is exact for the
        finite/+inf distances and int32 positions the merges carry).  The
        hierarchical schedule (_hier_slab) keeps the identical one-value-
        plus-zeros summand structure, so both schedules are BITWISE equal."""
        import jax
        import jax.numpy as jnp

        from . import topology
        from .mesh import DATA_AXIS

        axis = axis_name or DATA_AXIS
        with device_section(self.name, x):
            n_dev = jax.lax.psum(1, axis)
            topo = self._resolved(n_dev)
            _record_link_bytes(
                self.name, *topology.link_split_gather(topo, _static_nbytes(x))
            )
            if topo.is_hierarchical:
                return self._hier_slab(x, axis, topo)
            idx = jax.lax.axis_index(axis)
            slab = jnp.zeros((n_dev,) + x.shape, x.dtype).at[idx].set(x)
            return jax.lax.psum(slab, axis)

    def ring_shift(self, x, axis_name: str = None, shift: int = 1):
        """Send this shard's block to its ring successor and receive its
        predecessor's — the ring-permute hop of the kNN candidate
        exchange.  Counters record the per-hop payload, so a full ring
        pass shows n_dev x block bytes (vs the n_dev^2 x block an
        all-gather replicates).  With a hierarchical topology the cycle
        tours each host group's ICI neighbors consecutively with exactly
        one gateway edge per group pair on DCN (topology.ring_cycle);
        flat keeps the +shift rotation (mesh.ring_permutation, the ONE
        flat ring order).  TPU: Pallas remote-DMA kernel for the uniform
        flat rotation; the hierarchical cycle and every non-TPU backend
        ride lax.ppermute (identical semantics, the tier-1/parity path)."""
        import jax

        from . import topology
        from .mesh import DATA_AXIS

        axis = axis_name or DATA_AXIS
        with device_section(self.name, x):
            n_dev = jax.lax.psum(1, axis)
            if n_dev == 1:
                return x
            topo = self._resolved(n_dev)
            _record_link_bytes(
                self.name,
                *topology.link_split_ring_hop(topo, _static_nbytes(x)),
            )
            if topo.is_hierarchical:
                # the remote-DMA kernel computes dst = my + shift analytically,
                # which only matches the uniform rotation; the gateway cycle
                # rides ppermute on every backend (XLA schedules TPU ppermute
                # over ICI fine — the dedicated gateway DMA kernel is
                # accelerator-session work)
                return jax.lax.ppermute(
                    x, axis, topology.ring_cycle(topo, shift)
                )
            if _remote_dma_enabled():
                return _ring_shift_remote_dma(x, axis, shift, n_dev)
            from .mesh import ring_permutation

            return jax.lax.ppermute(x, axis, ring_permutation(n_dev, shift))


def device_collective(name: str, topo=None) -> DeviceSection:
    """The typed-section constructor: one named handle per call site.
    `topo` (a topology.TopologyMap) opts the section into the
    hierarchical schedules — pass it ONLY from code that also carries it
    in its compilation cache key."""
    return DeviceSection(name, topo)


# remote-DMA gate: TPU hardware with pallas enabled, unless explicitly
# disabled.  Interpret-mode and CPU meshes cannot run remote copies, so the
# ppermute fallback is also what every tier-1 test exercises; the two paths
# are semantics-identical by construction (one block in, the left
# neighbor's block out).
_REMOTE_DMA_ENV = "SRML_EXCHANGE_REMOTE_DMA"


def _remote_dma_enabled() -> bool:
    import os

    import jax

    from ..ops.pallas_tpu import pallas_enabled

    if os.environ.get(_REMOTE_DMA_ENV, "1") == "0":
        return False
    return jax.default_backend() == "tpu" and pallas_enabled()


def _ring_shift_remote_dma(x, axis_name: str, shift: int, n_dev: int):
    """+shift ring permute as a Pallas remote-DMA kernel (the SNIPPETS.md
    `make_async_remote_copy` exemplar, generalized to any shift): the whole
    block rides one neighbor-to-neighbor ICI DMA with send/recv semaphores
    providing the synchronization — no cross-chip collective schedule, no
    replication.  Runs on TPU hardware only (guarded by callers); this
    module is the single audited home of the remote-DMA API (graftlint
    R8)."""
    import jax
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, send_sem, recv_sem):
        my = jax.lax.axis_index(axis_name)
        dst = jax.lax.rem(my + shift + n_dev, n_dev)
        src = jax.lax.rem(my - shift + n_dev, n_dev)
        # handshake with both ring neighbors before any remote write: a
        # DMA into a chip that has not ENTERED this kernel yet could land
        # in memory its previous op still owns
        barrier = pltpu.get_barrier_semaphore()
        for peer in (dst, src):
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=(peer,),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
        pltpu.semaphore_wait(barrier, 2)
        copy = pltpu.make_async_remote_copy(
            src_ref=x_ref,
            dst_ref=o_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        copy.start()
        # the wait covers BOTH directions: send_sem fires when the local
        # block has left, recv_sem when the left neighbor's block landed in
        # o_ref — the hop's compute/communicate overlap happens at the
        # caller (the next hop's block is in flight while this hop merges)
        copy.wait()  # graftlint: disable=R9 (DMA completion has no timeout; R8 requires the start/wait pair)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )(x)


# -- legacy un-named-section shims ---------------------------------------------


def allgather_rows(x, axis_name: str = None, section: str = "allgather_rows"):
    """Concatenate per-device row blocks along axis 0 (lax.all_gather,
    tiled).  Call ONLY inside a shard_map body bound over `axis_name`."""
    return device_collective(section).allgather_rows(x, axis_name)


def psum_parts(x, axis_name: str = None, section: str = "psum_parts"):
    """Element-wise sum of per-device partial arrays (lax.psum) — the
    "partial result per shard -> full result everywhere" reduction shape of
    the forest engine's histogram combine: each device builds per-node
    histograms over ITS row shard and one psum per level yields the global
    histograms replicated on every device (ops/forest._forest_block_kernel,
    ops/forest_hist.node_histograms_sharded).  Call ONLY inside a shard_map
    body bound over `axis_name`."""
    return device_collective(section).psum(x, axis_name)


def psum_merge_parts(x, axis_name: str = None, section: str = "psum_merge_parts"):
    """Stack per-device candidate blocks into one (n_dev, ...) slab via a
    single psum — the IVF-Flat probed search's ONE cross-shard collective.
    Call ONLY inside a shard_map body bound over `axis_name`."""
    return device_collective(section).psum_merge(x, axis_name)


def ring_shift(x, axis_name: str = None, shift: int = 1,
               section: str = "ring_shift"):
    """Module-level shim over DeviceSection.ring_shift (docstring there)."""
    return device_collective(section).ring_shift(x, axis_name, shift)


def byte_totals(prefix: str = "exchange."):
    """(total_bytes, {section: bytes}) over every exchange section counter —
    host sections count per call, device sections per compiled geometry
    (trace time).  Snapshot it around a search to read the bytes each
    route moved (all-gather against ring).  The per-LINK rollup of
    the same namespace lives in link_totals() — the `.ici_bytes`/
    `.dcn_bytes` split counters carry an underscore suffix precisely so
    this scan never double-counts them."""
    per = {}
    for name, v in profiling.counters(prefix).items():
        if name.endswith(".bytes"):
            per[name[len(prefix):-len(".bytes")]] = int(v)
    return sum(per.values()), per


def link_totals(prefix: str = "exchange."):
    """{"ici": bytes, "dcn": bytes} rollup of the per-section link-split
    counters (`exchange.<name>.ici_bytes` / `.dcn_bytes`) — the link-
    pressure view of byte_totals().  Surfaced continuously through
    export_metrics()["gauges"] (the `exchange.link.*` provider below) and
    rendered as the `srml_exchange_bytes{link="ici|dcn"}` Prometheus
    family, so the serving plane's dashboards see DCN pressure without a
    bench round."""
    out = {"ici": 0, "dcn": 0}
    for name, v in profiling.counters(prefix).items():
        if name.endswith(".ici_bytes"):
            out["ici"] += int(v)
        elif name.endswith(".dcn_bytes"):
            out["dcn"] += int(v)
    return out


def _link_gauges():
    links = link_totals()
    return {
        "exchange.link.ici_bytes": float(links["ici"]),
        "exchange.link.dcn_bytes": float(links["dcn"]),
    }


profiling.register_gauges("exchange.link", _link_gauges)


def ring_pass_bytes(
    cp: Any,
    rank: int,
    nranks: int,
    payload: bytes,
    chunk: int = CHUNK_BYTES,
    src: Optional[int] = None,
    link: Optional[str] = None,
) -> bytes:
    """One ring hop over the control plane: contribute `payload` and
    return the payload received from `src` (default the flat-ring
    predecessor, (rank - 1) % nranks) — the HOST-plane analog of
    DeviceSection.ring_shift, used by distributed_kneighbors' ring route
    to rotate query blocks + running candidate lists between ranks as
    binary frames.  A non-default `src` lets the caller follow a
    topology-aware cycle (topology.ring_cycle over ranks): every rank
    must apply the SAME cycle and pass its own predecessor in it — the
    broadcast transport carries every frame regardless, so routing IS the
    receiver's decode choice.  `link` ("ici" | "dcn") attributes this
    hop's outgoing payload to the `exchange.ring.<link>_bytes` split
    counter when the caller knows the edge's link class.

    The wire rides the broadcast allGather (the only collective a Spark
    barrier offers) but the decode is p2p-shaped: a receiver b64-decodes /
    joins ONLY its predecessor's chunks and drops the rest by reference, so
    per-rank decode volume is O(one neighbor's payload) per hop instead of
    O(sum of all ranks').  COLLECTIVE: every rank must call it once per
    hop, empty payloads included."""
    # srml-shield: corrupt here flips bytes in the outgoing frame (the
    # receiver's SRX1 magic check must fail loudly); die/raise simulate a
    # rank lost mid-ring
    payload = faults.site("exchange.ring_pass", rank=rank, payload=payload)
    if link in ("ici", "dcn") and payload:
        profiling.incr_counter(f"exchange.ring.{link}_bytes", len(payload))
    with section("ring", nbytes=len(payload)):
        use_bytes = hasattr(cp, "allGatherBytes")
        if src is None:
            src = (rank - 1) % nranks
        mine = _chunks(payload, chunk)
        counts = [int(c) for c in cp.allGather(str(len(mine)))]
        parts: List[bytes] = []
        for r in range(max(counts)):
            got = _send(cp, mine[r] if r < len(mine) else b"", use_bytes)
            if r < counts[src]:
                parts.append(_recv(got[src], use_bytes))
        return b"".join(parts)


def alltoall_bytes(
    cp: Any,
    rank: int,
    nranks: int,
    dests: Sequence[bytes],
    chunk: int = CHUNK_BYTES,
) -> List[bytes]:
    """All-to-all of per-destination binary payloads: rank s passes
    dests[d] for every destination d and receives the nranks payloads
    addressed to IT (result[s] = what rank s sent to this rank).

    The wire rides the broadcast allGather (the only collective a Spark
    barrier offers), but chunks are framed dest-major with a counts
    round first, so a receiver b64-decodes/joins ONLY the chunk rounds
    addressed to it and drops the rest by reference — per-rank decode
    volume is O(own share), the p2p shape of the reference's UCX return
    (knn.py:549-560: each query partition's results land only on their
    owning rank)."""
    if len(dests) != nranks:
        raise ValueError(f"need {nranks} destination payloads, got {len(dests)}")
    with section("alltoall", nbytes=sum(len(d) for d in dests)):
        use_bytes = hasattr(cp, "allGatherBytes")
        frames = [_chunks(d, chunk) for d in dests]
        meta = json.dumps([len(f) for f in frames])
        all_meta = [json.loads(s) for s in cp.allGather(meta)]  # [src][dest]
        # canonical send order: dest-major concatenation of each source's
        # chunks
        my_seq = [c for f in frames for c in f]
        # position range of (src -> me) chunks inside src's send sequence
        lo = [sum(all_meta[s][:rank]) for s in range(nranks)]
        hi = [lo[s] + all_meta[s][rank] for s in range(nranks)]
        rounds = max(sum(m) for m in all_meta)
        mine: List[List[bytes]] = [[] for _ in range(nranks)]
        for r in range(rounds):
            got = _send(cp, my_seq[r] if r < len(my_seq) else b"", use_bytes)
            for s in range(nranks):
                if lo[s] <= r < hi[s]:
                    mine[s].append(_recv(got[s], use_bytes))
        return [b"".join(p) for p in mine]
