# Binary array codec + bulk collectives (parallel/exchange.py) — the TPU
# stand-in for the reference's UCX data-plane frames (knn.py:452-560).
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu.parallel.exchange import (
    allgather_bytes,
    alltoall_bytes,
    pack_arrays,
    unpack_arrays,
)


class StringBarrier:
    """In-process mock of Spark's BarrierTaskContext.allGather: STRING-only
    frames (forces the base64 path), rank-ordered results, true barrier
    semantics via threading.Barrier."""

    def __init__(self, nranks):
        self.nranks = nranks
        self._barrier = threading.Barrier(nranks)
        self._slots = [None] * nranks
        self._lock = threading.Lock()
        self.wire_chars = 0  # total characters that crossed the wire

    def plane(self, rank):
        outer = self

        class _P:
            def allGather(self, message):
                assert isinstance(message, str)
                with outer._lock:
                    outer._slots[rank] = message
                    outer.wire_chars += len(message)
                outer._barrier.wait()
                out = list(outer._slots)
                outer._barrier.wait()
                return out

            def barrier(self):
                self.allGather("")

        return _P()


def _run_ranks(nranks, fn):
    results, errors = {}, {}

    def run(r):
        try:
            results[r] = fn(r)
        except Exception as e:  # surfaced below
            errors[r] = e

    ts = [threading.Thread(target=run, args=(r,)) for r in range(nranks)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    return results


# -- codec -------------------------------------------------------------------

@pytest.mark.parametrize(
    "arrays",
    [
        [np.arange(12, dtype=np.float32).reshape(3, 4)],
        [np.zeros((0, 7), np.float64), np.arange(5, dtype=np.int64)],
        [np.array(3.5, np.float32), np.ones((2, 3, 4), np.int8)],
        [np.array([], np.int32)],
    ],
)
def test_pack_unpack_roundtrip(arrays):
    out = unpack_arrays(pack_arrays(arrays))
    assert len(out) == len(arrays)
    for a, b in zip(arrays, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_unpack_rejects_garbage():
    with pytest.raises(ValueError):
        unpack_arrays(b"not a frame at all")


# -- alltoall ----------------------------------------------------------------

def test_alltoall_bytes_routes_per_destination():
    nranks = 4
    bar = StringBarrier(nranks)
    # dests[s][d]: distinct sizes to catch any offset slip, incl. empties
    payload = {
        (s, d): (bytes([65 + s]) * (100 * s + 37 * d) if (s + d) % 3 else b"")
        for s in range(nranks)
        for d in range(nranks)
    }

    def fn(rank):
        return alltoall_bytes(
            bar.plane(rank), rank, nranks,
            [payload[(rank, d)] for d in range(nranks)],
            chunk=64,
        )

    results = _run_ranks(nranks, fn)
    for d in range(nranks):
        for s in range(nranks):
            assert results[d][s] == payload[(s, d)], (s, d)


def test_alltoall_decode_volume_is_owner_only(monkeypatch):
    """The p2p-shape contract (reference knn.py:549-560): a receiver must
    only materialize (b64-decode + join) the bytes addressed to IT, not
    every rank's full result matrix.  Metered by instrumenting the decode
    hook per thread-rank."""
    import spark_rapids_ml_tpu.parallel.exchange as ex

    nranks = 4
    bar = StringBarrier(nranks)
    rows = [100, 0, 300, 50]  # rank d owns rows[d] query rows
    q_total = sum(rows)
    k = 16
    rng = np.random.default_rng(0)
    full = {
        s: (rng.normal(size=(q_total, k)).astype(np.float32),
            rng.integers(0, 1 << 40, size=(q_total, k)).astype(np.int64))
        for s in range(nranks)
    }
    offs = np.cumsum([0] + rows)

    real_recv = ex._recv
    decoded = {}  # thread ident -> bytes materialized

    def metered_recv(frame, use_bytes):
        out = real_recv(frame, use_bytes)
        tid = threading.get_ident()
        decoded[tid] = decoded.get(tid, 0) + len(out)
        return out

    monkeypatch.setattr(ex, "_recv", metered_recv)
    tid_of = {}

    def fn(rank):
        tid_of[rank] = threading.get_ident()
        d_mine, i_mine = full[rank]
        dests = [
            pack_arrays([d_mine[offs[r]:offs[r + 1]],
                         i_mine[offs[r]:offs[r + 1]]])
            for r in range(nranks)
        ]
        got = alltoall_bytes(bar.plane(rank), rank, nranks, dests, chunk=4096)
        return [unpack_arrays(fr) for fr in got]

    results = _run_ranks(nranks, fn)
    for d in range(nranks):
        got = results[d]
        # correctness: the owner got exactly its rows from every source
        for s in range(nranks):
            np.testing.assert_array_equal(
                got[s][0], full[s][0][offs[d]:offs[d + 1]]
            )
            np.testing.assert_array_equal(
                got[s][1], full[s][1][offs[d]:offs[d + 1]]
            )
        # decode volume: O(own_Q x k x nranks) + frame headers, NOT the
        # O(q_total x k x nranks) the full-matrix broadcast used to pay
        own_share = rows[d] * k * 12 * nranks  # 12B per (f32, i64) cell
        assert decoded[tid_of[d]] <= own_share + 1024 * nranks, (
            d, decoded[tid_of[d]], own_share
        )
    # sanity: the big owner really did materialize its share
    assert decoded[tid_of[2]] >= rows[2] * k * 12 * nranks


def test_alltoall_empty_rank_keeps_collective_shape():
    nranks = 3
    bar = StringBarrier(nranks)

    def fn(rank):
        dests = [b"" for _ in range(nranks)]
        if rank == 2:
            dests = [b"x" * 10, b"", b"yy"]
        return alltoall_bytes(bar.plane(rank), rank, nranks, dests, chunk=4)

    results = _run_ranks(nranks, fn)
    assert results[0][2] == b"x" * 10
    assert results[2][2] == b"yy"
    assert results[1] == [b"", b"", b""]


def test_allgather_bytes_string_plane_uses_base64():
    nranks = 2
    bar = StringBarrier(nranks)
    payloads = [b"\x00\xffbinary\x01" * 100, b"tiny"]

    def fn(rank):
        return allgather_bytes(bar.plane(rank), payloads[rank], chunk=128)

    results = _run_ranks(nranks, fn)
    for r in range(nranks):
        assert results[r] == payloads
    # wire carried ascii-safe frames only (base64), never raw bytes
    assert bar.wire_chars > 0


# -- section(): the ONE collective reporting wrapper --------------------------


def test_host_sections_report_uniform_byte_time_counters():
    """Every host collective reports exchange.<name>.bytes/time_ns/calls
    through section() — the uniform namespace of ROADMAP item 5."""
    from spark_rapids_ml_tpu import profiling

    profiling.reset_counters("exchange.")
    nranks = 2
    bar = StringBarrier(nranks)
    payloads = [b"a" * 300, b"b" * 50]

    def fn(rank):
        out = allgather_bytes(bar.plane(rank), payloads[rank], chunk=128)
        return alltoall_bytes(
            bar.plane(rank), rank, nranks, [b"x" * 10, b"y" * 20], chunk=16
        ) and out

    _run_ranks(nranks, fn)
    ctr = profiling.counters("exchange.")
    assert ctr["exchange.allgather.calls"] == nranks
    assert ctr["exchange.allgather.bytes"] == sum(len(p) for p in payloads)
    assert ctr["exchange.allgather.time_ns"] > 0
    assert ctr["exchange.alltoall.calls"] == nranks
    assert ctr["exchange.alltoall.bytes"] == nranks * 30
    assert ctr["exchange.alltoall.time_ns"] > 0
    # wall-clock also lands in the per-thread phase registry as before
    profiling.reset_counters("exchange.")


def test_device_sections_report_static_bytes_at_trace_time():
    """psum_parts/allgather_rows/psum_merge_parts report exchange.<name>
    bytes + trace counts through the same section namespace.  Device
    sections move counters at TRACE time (shapes are static; wall clock is
    meaningless inside a traced body) — a fresh jit trace moves them, a
    cached re-execution does not."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import profiling
    from jax import shard_map
    from spark_rapids_ml_tpu.parallel.exchange import (
        allgather_rows,
        psum_merge_parts,
        psum_parts,
    )
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

    mesh = get_mesh()
    n_dev = mesh.devices.size
    profiling.reset_counters("exchange.")
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def f(x):
        def body(xs):
            s = psum_parts(xs.sum())
            g = allgather_rows(xs)
            m = psum_merge_parts(xs)
            return (s + g.sum() + m.sum()).reshape(1)

        return shard_map(
            body, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS)
        )(x)

    x = jnp.arange(4 * n_dev, dtype=jnp.float32)
    f(x)
    ctr = profiling.counters("exchange.")
    per_shard_bytes = 4 * 4  # (4,) f32 per shard
    assert ctr["exchange.psum_parts.traces"] == 1
    assert ctr["exchange.allgather_rows.traces"] == 1
    assert ctr["exchange.psum_merge_parts.traces"] == 1
    assert ctr["exchange.allgather_rows.bytes"] == per_shard_bytes
    assert ctr["exchange.psum_merge_parts.bytes"] == per_shard_bytes
    assert ctr["exchange.psum_parts.bytes"] == 4  # scalar partial
    # cached re-execution: no new trace, counters frozen
    f(x)
    assert profiling.counters("exchange.") == ctr
    profiling.reset_counters("exchange.")


def test_distributed_kneighbors_binary_exchange_end_to_end():
    """4 thread-ranks over the string-only mock: the full kneighbors
    exchange (binary frames both rounds) must reproduce a single-process
    exact search, including an empty-query rank and k > one rank's items."""
    from sklearn.neighbors import NearestNeighbors as SkNN

    from spark_rapids_ml_tpu.ops.knn import distributed_kneighbors

    nranks = 4
    rng = np.random.default_rng(3)
    n, d, k = 700, 9, 11
    items = rng.normal(size=(n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64) * 7 + 3
    queries = rng.normal(size=(37, d)).astype(np.float32)
    item_split = np.array_split(np.arange(n), nranks)
    # rank 2 owns NO queries
    q_split = [np.arange(0, 20), np.arange(20, 30), np.arange(0, 0), np.arange(30, 37)]
    bar = StringBarrier(nranks)

    def fn(rank):
        ip = [(items[item_split[rank]], ids[item_split[rank]])]
        qp = [(queries[q_split[rank]], q_split[rank].astype(np.int64))]
        # no mesh arg: thread-mocked ranks get DISJOINT per-rank submeshes
        # (sharing one mesh across rank-threads deadlocks XLA:CPU's
        # collective rendezvous — see distributed_kneighbors)
        return distributed_kneighbors(
            ip, qp, k, rank, nranks, bar.plane(rank)
        )

    results = _run_ranks(nranks, fn)
    sk_d, sk_i = SkNN(n_neighbors=k).fit(items).kneighbors(queries)
    for rank in range(nranks):
        (d_out, i_out), = results[rank]
        rows = q_split[rank]
        assert d_out.shape == (len(rows), k)
        np.testing.assert_allclose(d_out, sk_d[rows], rtol=1e-4, atol=1e-4)
        if len(rows):
            assert (i_out == ids[sk_i[rows]]).mean() > 0.99
