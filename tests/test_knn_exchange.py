# The kNN candidate-exchange routes (ops/knn.knn_block_kernel_exchange +
# the distributed_kneighbors ring protocol): the bitwise 1/2/8-device
# parity matrix — ring-permute exchange == all-gather exchange ==
# single-device reference — plus routing, zero-recompile, and byte-counter
# gates.  Runs on the virtual 8-device CPU mesh (conftest), where
# DeviceSection.ring_shift takes the lax.ppermute fallback with semantics
# identical to the TPU remote-DMA kernel.
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops.knn import (
    _exchange_geometry,
    knn_block_kernel_exchange,
    knn_search_prepared,
    lex_topk,
    prepare_items,
)
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


def _mesh(n_dev: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n_dev]), (DATA_AXIS,))


def _make_data(n=4096, d=48, q=512, seed=0):
    rng = np.random.default_rng(seed)
    items = rng.standard_normal((n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    queries = rng.standard_normal((q, d)).astype(np.float32)
    return items, ids, queries


# -- lex_topk oracle ----------------------------------------------------------


def test_lex_topk_matches_numpy_lexsort():
    rng = np.random.default_rng(5)
    Qn, C, k = 32, 3000, 17
    d2 = rng.integers(0, 50, size=(Qn, C)).astype(np.float32)  # many ties
    pos = rng.permutation(C).astype(np.int32)[None].repeat(Qn, 0)
    sd, sp = lex_topk(jnp.asarray(d2), jnp.asarray(pos), k)
    order = np.lexsort((pos, d2), axis=1)[:, :k]
    np.testing.assert_array_equal(
        np.asarray(sd), np.take_along_axis(d2, order, axis=1)
    )
    np.testing.assert_array_equal(
        np.asarray(sp), np.take_along_axis(pos, order, axis=1)
    )


# -- the bitwise parity matrix ------------------------------------------------


def test_exchange_parity_matrix_bitwise():
    """ring == gather == 1-device reference, BITWISE, on 1/2/8-device
    meshes: the lex (d2, pos) key is a total order and the fixed-tile
    scans keep every distance tile identically shaped, so any route and
    any mesh must land on the same bits (the acceptance gate)."""
    from sklearn.neighbors import NearestNeighbors as SkNN

    items, ids, queries = _make_data()
    k = 17
    qd = jnp.asarray(queries)
    handles = {}
    for n_dev in (1, 2, 8):
        mesh = _mesh(n_dev)
        prepared = prepare_items(items, ids, mesh, shuffle=False)
        n_loc = prepared.items.shape[0] // n_dev
        for route in ("ring", "gather"):
            chunk, qt = _exchange_geometry(n_loc, len(queries), n_dev, route)
            handles[(n_dev, route)] = knn_block_kernel_exchange(
                prepared.items, prepared.norm, prepared.pos, prepared.valid,
                qd, mesh, k, route, chunk, qt,
            )
    results = {key: jax.device_get(v) for key, v in handles.items()}
    ref_d, ref_p = results[(1, "ring")]
    for key, (dist, pos) in results.items():
        np.testing.assert_array_equal(dist, ref_d, err_msg=str(key))
        np.testing.assert_array_equal(pos, ref_p, err_msg=str(key))
    # and the reference is exact vs sklearn
    sd, si = SkNN(n_neighbors=k, algorithm="brute").fit(items).kneighbors(
        queries
    )
    np.testing.assert_allclose(ref_d, sd, rtol=1e-4, atol=1e-4)
    assert (ref_p == si).mean() > 0.999


def test_exchange_parity_with_invalid_rows_and_k_over_items():
    """Padding rows (valid=False) and k > n_items: every route must mask
    identically and mark unfillable slots with inf distance."""
    rng = np.random.default_rng(9)
    n, d, q = 512, 32, 128
    items = rng.standard_normal((n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    queries = rng.standard_normal((q, d)).astype(np.float32)
    k = n + 13  # more neighbors than items
    qd = jnp.asarray(queries)
    handles = {}
    for n_dev in (1, 8):
        mesh = _mesh(n_dev)
        prepared = prepare_items(items, ids, mesh, shuffle=False)
        n_loc = prepared.items.shape[0] // n_dev
        for route in ("ring", "gather"):
            chunk, qt = _exchange_geometry(n_loc, q, n_dev, route)
            handles[(n_dev, route)] = knn_block_kernel_exchange(
                prepared.items, prepared.norm, prepared.pos, prepared.valid,
                qd, mesh, k, route, chunk, qt,
            )
    outs = {key: jax.device_get(v) for key, v in handles.items()}
    ref = outs[(1, "ring")]
    for key, (dist, pos) in outs.items():
        np.testing.assert_array_equal(dist, ref[0], err_msg=str(key))
        np.testing.assert_array_equal(pos, ref[1], err_msg=str(key))
    assert np.isinf(ref[0][:, n:]).all(), "unfillable slots must be inf"
    assert np.isfinite(ref[0][:, :n]).all()


# -- route plumbing through knn_search_prepared -------------------------------


def test_search_prepared_ring_equals_gather_and_legacy(monkeypatch):
    """The full pipelined search must give identical distances (and ids,
    data has no ties) on every exchange route of the same mesh."""
    items, ids, queries = _make_data(n=2048, d=24, q=300, seed=3)
    k = 9
    mesh = _mesh(8)
    out = {}
    for route in ("ring", "gather", "legacy"):
        monkeypatch.setenv("SRML_KNN_EXCHANGE", route)
        prepared = prepare_items(items, ids, mesh, shuffle=False)
        d, i = knn_search_prepared(prepared, queries, k, mesh)
        out[route] = (d, i)
    for route in ("gather", "legacy"):
        np.testing.assert_allclose(
            out["ring"][0], out[route][0], rtol=1e-5, atol=1e-6
        )
        np.testing.assert_array_equal(out["ring"][1], out[route][1])


def test_ring_route_zero_new_compiles_on_repeat_search():
    """Repeat same-shape search over the ring route: every kernel rides
    the AOT executable cache, so the second search performs ZERO new
    compilations (the steady-state contract the bench smoke asserts)."""
    items, ids, queries = _make_data(n=2048, d=24, q=256, seed=4)
    mesh = _mesh(8)
    prepared = prepare_items(items, ids, mesh, shuffle=False)
    d1, i1 = knn_search_prepared(prepared, queries, 9, mesh)
    c0 = profiling.counters("precompile")
    d2_, i2 = knn_search_prepared(prepared, queries, 9, mesh)
    c1 = profiling.counters("precompile")
    assert c1.get("precompile.compile", 0) == c0.get("precompile.compile", 0)
    assert c1.get("precompile.fallback", 0) == c0.get(
        "precompile.fallback", 0
    )
    assert c1.get("precompile.aot_hit", 0) > c0.get("precompile.aot_hit", 0)
    np.testing.assert_array_equal(d1, d2_)
    np.testing.assert_array_equal(i1, i2)


def test_warm_covers_ring_dispatch_key():
    """warm_search_kernels must submit the EXACT executable the routed
    ring dispatch later looks up — sharded query aval included — so a
    warmed search is compile-free from its very first block (no
    input-incompat fallback, straight aot_hit)."""
    from spark_rapids_ml_tpu.ops.knn import warm_search_kernels
    from spark_rapids_ml_tpu.ops.precompile import global_precompiler

    items, ids, queries = _make_data(n=2048, d=24, q=256, seed=7)
    mesh = _mesh(8)
    prepared = prepare_items(items, ids, mesh, shuffle=False)
    keys = warm_search_kernels(prepared, 7, mesh, n_queries=256, d_query=24)
    assert keys, "exact ring route submitted no warm keys"
    global_precompiler().wait(keys)
    c0 = profiling.counters("precompile")
    knn_search_prepared(prepared, queries, 7, mesh)
    c1 = profiling.counters("precompile")
    assert c1.get("precompile.compile", 0) == c0.get("precompile.compile", 0)
    assert c1.get("precompile.fallback", 0) == c0.get(
        "precompile.fallback", 0
    )
    assert c1.get("precompile.aot_hit", 0) > c0.get("precompile.aot_hit", 0)


def test_ring_sections_report_bytes():
    """The ring exchange reports per-hop payload bytes through the typed
    exchange sections (exchange.knn.ring_q / exchange.knn.ring_cand) — the
    counters the bench `bytes moved` column totals."""
    profiling.reset_counters("exchange.knn.ring")
    items, ids, queries = _make_data(n=1024, d=16, q=128, seed=6)
    mesh = _mesh(8)
    prepared = prepare_items(items, ids, mesh, shuffle=False)
    n_loc = prepared.items.shape[0] // 8
    chunk, qt = _exchange_geometry(n_loc, len(queries), 8, "ring")
    knn_block_kernel_exchange(
        prepared.items, prepared.norm, prepared.pos, prepared.valid,
        jnp.asarray(queries), mesh, 5, "ring", chunk, qt,
    )
    ctr = profiling.counters("exchange.knn.ring")
    # 8 hops x per-shard (16, 16) f32 query block
    assert ctr["exchange.knn.ring_q.bytes"] == 8 * (128 // 8) * 16 * 4
    # 8 hops x per-shard (16, 5) f32 + (16, 5) i32 running candidates
    assert ctr["exchange.knn.ring_cand.bytes"] == 8 * 2 * (128 // 8) * 5 * 4
    profiling.reset_counters("exchange.knn.ring")


# -- distributed_kneighbors: host-plane ring route ----------------------------


class _StringBarrier:
    """String-only allGather mock with true barrier semantics (the same
    shape as Spark's BarrierTaskContext; see tests/test_exchange.py)."""

    def __init__(self, nranks):
        self.nranks = nranks
        self._barrier = threading.Barrier(nranks)
        self._slots = [None] * nranks
        self._lock = threading.Lock()

    def plane(self, rank):
        outer = self

        class _P:
            def allGather(self, message):
                assert isinstance(message, str)
                with outer._lock:
                    outer._slots[rank] = message
                outer._barrier.wait()
                out = list(outer._slots)
                outer._barrier.wait()
                return out

        return _P()


def _run_ranks(nranks, fn):
    results, errors = {}, {}

    def run(r):
        try:
            results[r] = fn(r)
        except Exception as e:  # surfaced below
            errors[r] = e

    ts = [
        threading.Thread(target=run, args=(r,), name=f"knnx-rank{r}")
        for r in range(nranks)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    return results


def _distributed_case(route_env, monkeypatch, budget=None):
    from sklearn.neighbors import NearestNeighbors as SkNN

    from spark_rapids_ml_tpu.ops.knn import distributed_kneighbors

    monkeypatch.setenv("SRML_KNN_EXCHANGE", route_env)
    if budget is not None:
        monkeypatch.setenv("SRML_KNN_HBM_BUDGET", str(budget))
    nranks = 4
    rng = np.random.default_rng(3)
    n, d, k = 700, 9, 11
    items = rng.normal(size=(n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64) * 7 + 3
    queries = rng.normal(size=(37, d)).astype(np.float32)
    item_split = np.array_split(np.arange(n), nranks)
    # rank 2 owns NO queries; rank 1 owns none of the items
    q_split = [
        np.arange(0, 20), np.arange(20, 30), np.arange(0, 0),
        np.arange(30, 37),
    ]
    item_split[1] = np.arange(0)
    bar = _StringBarrier(nranks)

    def fn(rank):
        ip = [(items[item_split[rank]], ids[item_split[rank]])]
        qp = [(queries[q_split[rank]], q_split[rank].astype(np.int64))]
        return distributed_kneighbors(
            ip, qp, k, rank, nranks, bar.plane(rank)
        )

    results = _run_ranks(nranks, fn)
    sk_d, sk_i = SkNN(n_neighbors=k).fit(
        items[np.concatenate([item_split[r] for r in range(nranks)])]
    ).kneighbors(queries)
    return results, q_split, sk_d, ids[
        np.concatenate([item_split[r] for r in range(nranks)])
    ][sk_i]


def test_distributed_ring_route_matches_reference(monkeypatch):
    """4 thread-ranks over the string plane, ring route: every rank's
    query partitions must come back exactly as a single-process search
    would give them — including the empty-query and empty-item ranks."""
    profiling.reset_counters("exchange.")
    results, q_split, sk_d, sk_ids = _distributed_case("ring", monkeypatch)
    for rank in range(4):
        ((d_out, i_out),) = results[rank]
        rows = q_split[rank]
        assert d_out.shape == (len(rows), 11)
        np.testing.assert_allclose(d_out, sk_d[rows], rtol=1e-4, atol=1e-4)
        if len(rows):
            assert (i_out == sk_ids[rows]).mean() > 0.99
    ctr = profiling.counters("exchange.")
    # the ring route never broadcast queries: exactly nranks ring passes
    # per rank, and round-2 alltoall never ran
    assert ctr.get("exchange.ring.calls", 0) == 4 * 4
    assert ctr.get("exchange.alltoall.calls", 0) == 0
    profiling.reset_counters("exchange.")


def test_distributed_ring_and_allgather_routes_agree(monkeypatch):
    res_ring, q_split, sk_d, _ = _distributed_case("ring", monkeypatch)
    res_ag, _, _, _ = _distributed_case("gather", monkeypatch)
    for rank in range(4):
        ((dr, ir),) = res_ring[rank]
        ((da, ia),) = res_ag[rank]
        np.testing.assert_allclose(dr, da, rtol=1e-5, atol=1e-6)
        # data has no distance ties -> ids must agree exactly
        np.testing.assert_array_equal(ir, ia)


def test_distributed_ring_falls_back_when_any_rank_overflows(monkeypatch):
    """A rank whose items exceed its device budget publishes ring_ok=0 in
    the metadata round, so EVERY rank takes the allgather route — the
    route decision is collective, never split-brain."""
    profiling.reset_counters("exchange.")
    # 175 items x 9 cols x 4B = 6300 B/rank > 4096-byte budget -> no ring
    results, q_split, sk_d, sk_ids = _distributed_case(
        "ring", monkeypatch, budget=2048
    )
    for rank in range(4):
        ((d_out, i_out),) = results[rank]
        rows = q_split[rank]
        np.testing.assert_allclose(d_out, sk_d[rows], rtol=1e-4, atol=1e-4)
    ctr = profiling.counters("exchange.")
    assert ctr.get("exchange.ring.calls", 0) == 0
    assert ctr.get("exchange.alltoall.calls", 0) == 4
    profiling.reset_counters("exchange.")


# -- the public estimator on the mesh -----------------------------------------


@pytest.mark.parametrize(
    "topo_env, pin",
    [(None, False), ("2:4", False), ("2:4", True)],
    ids=["flat", "hier_2x4", "flat_pinned_2x4"],
)
def test_public_kneighbors_on_the_mesh_repeats_without_compiles(
    topo_env, pin, monkeypatch
):
    """`NearestNeighbors.fit(...).kneighbors(...)` over the whole 8-device mesh
    (2000 x 16, k=10): a repeat search compiles nothing, an exchange route ran
    and was counted, and its sections moved bytes (`exchange.byte_totals`),
    under no topology, a simulated 2x4 and the flat pin on it.  A topology is
    a static of the exchange kernels, so each case traces its own."""
    from spark_rapids_ml_tpu import NearestNeighbors
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.parallel import topology
    from spark_rapids_ml_tpu.parallel.exchange import byte_totals, link_totals

    monkeypatch.delenv(topology.TOPO_ENV, raising=False)
    monkeypatch.delenv(topology.EXCHANGE_TOPO_ENV, raising=False)
    if topo_env:
        monkeypatch.setenv(topology.TOPO_ENV, topo_env)
    if pin:
        monkeypatch.setenv(topology.EXCHANGE_TOPO_ENV, "flat")
    rng = np.random.default_rng(28)
    centers = rng.standard_normal((8, 16)) * 6
    X = (centers[rng.integers(0, 8, 2000)] + rng.standard_normal((2000, 16))).astype(
        np.float32
    )
    df = DataFrame.from_numpy(X)
    _, bytes0 = byte_totals()
    links0 = link_totals()
    routes0 = profiling.counters("knn.exchange_route")
    model = NearestNeighbors(k=10).setInputCol("features").fit(df)
    model.kneighbors(df)  # stages the items, compiles every geometry
    before = profiling.counters("precompile.")
    _, _, knn_df = model.kneighbors(df)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    ids = np.concatenate(
        [np.asarray(list(p["indices"])) for p in knn_df.partitions if len(p)]
    )
    np.testing.assert_array_equal(ids[:, 0], np.arange(2000))  # self-join
    assert profiling.counter_deltas(routes0, "knn.exchange_route"), "no route ran"
    _, bytes1 = byte_totals()
    moved = {k: v - bytes0.get(k, 0) for k, v in bytes1.items() if v > bytes0.get(k, 0)}
    assert any(name.startswith("knn.ring") for name in moved), moved
    links = {k: v - links0[k] for k, v in link_totals().items()}
    if topo_env and not pin:
        assert links["ici"] > 0 and links["dcn"] > 0, links
    elif pin:
        # flat on a multi-group topology accounts everything as DCN
        assert links["dcn"] > 0 and links["ici"] == 0, links
