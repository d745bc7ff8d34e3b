# The topology-aware exchange plane (parallel/topology.py + the
# hierarchical DeviceSection schedules in parallel/exchange.py + the kNN
# adoption): TopologyMap derivation / SRML_TOPO override semantics, the
# single-n-cycle ring property, BITWISE parity of the hierarchical
# collectives vs the flat schedule (and of the kNN exchange kernels across
# simulated topologies 1x8 / 2x4 / 4x2 on 1/2/8-device meshes), the
# ici/dcn counter split with the O(n_hosts) DCN headline bound, the
# cache-key staticness of the map, and the host-plane distributed ring
# cycle.  Runs on the virtual 8-device CPU mesh (conftest).
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu import profiling
from jax import shard_map
from spark_rapids_ml_tpu.parallel import topology
from spark_rapids_ml_tpu.parallel.exchange import (
    device_collective,
    link_totals,
)
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


def _mesh(n_dev: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n_dev]), (DATA_AXIS,))


# -- TopologyMap derivation ---------------------------------------------------


def test_topology_map_default_is_flat(monkeypatch):
    monkeypatch.delenv(topology.TOPO_ENV, raising=False)
    monkeypatch.delenv(topology.EXCHANGE_TOPO_ENV, raising=False)
    topo = topology.topology_map(mesh=_mesh(8))
    assert topo.n_groups == 1 and topo.n_devices == 8
    assert topo.schedule == "flat" and not topo.is_hierarchical
    assert topo.describe() == "1x8/flat"
    assert topology.topology_map(n_devices=4).describe() == "1x4/flat"


def test_topology_map_env_override_and_pin(monkeypatch):
    monkeypatch.setenv(topology.TOPO_ENV, "2:4")
    monkeypatch.delenv(topology.EXCHANGE_TOPO_ENV, raising=False)
    topo = topology.topology_map(mesh=_mesh(8))
    assert topo.source == "env"
    assert topo.groups == ((0, 1, 2, 3), (4, 5, 6, 7))
    assert topo.gateways == (0, 4)
    assert topo.group_of == (0, 0, 0, 0, 1, 1, 1, 1)
    assert topo.is_hierarchical and topo.describe() == "2x4/hier"
    # the pin keeps the derived groups (honest link attribution) but
    # forces the flat schedule — the parity comparator's escape hatch
    monkeypatch.setenv(topology.EXCHANGE_TOPO_ENV, "flat")
    pinned = topology.topology_map(mesh=_mesh(8))
    assert pinned.groups == topo.groups
    assert pinned.schedule == "flat" and pinned.describe() == "2x4/flat-pinned"


def test_topology_map_malformed_override_raises(monkeypatch):
    for bad in ("2x4", "2:", ":4", "2:4:1", "0:4", "2:-1", "a:b"):
        monkeypatch.setenv(topology.TOPO_ENV, bad)
        with pytest.raises(ValueError):
            topology.topology_map(n_devices=8)


def test_topology_map_uneven_groups_degenerate_to_flat_schedule(monkeypatch):
    # 8 devices at 3 per host -> groups of 3/3/2: the hierarchical
    # schedules refuse unequal groups (group_size == 0) and run flat
    monkeypatch.setenv(topology.TOPO_ENV, "3:3")
    topo = topology.topology_map(mesh=_mesh(8))
    assert topo.n_groups == 3 and topo.group_size == 0
    assert topo.schedule == "flat"


def test_topology_map_groups_by_device_id_not_position(monkeypatch):
    # a SHUFFLED device list must still group by physical id — that is
    # what makes the simulated topology genuinely non-contiguous in
    # logical axis positions
    monkeypatch.setenv(topology.TOPO_ENV, "2:4")
    devs = list(jax.devices())
    shuf = [devs[j] for j in (3, 7, 0, 5, 2, 6, 1, 4)]
    topo = topology.topology_map(devices=shuf)
    # positions of ids 0..3 in shuf: 2, 6, 4, 0 -> group ordered ascending
    assert topo.groups == ((0, 2, 4, 6), (1, 3, 5, 7))


def test_ring_cycle_is_single_n_cycle_with_g_gateway_edges():
    for groups in (
        ((0, 1, 2, 3), (4, 5, 6, 7)),
        ((0, 2, 4, 6), (1, 3, 5, 7)),   # interleaved
        ((0, 1), (2, 3), (4, 5), (6, 7)),
        ((0, 1, 2, 3, 4, 5, 6, 7),),
    ):
        topo = topology.TopologyMap(groups=groups, source="env")
        cycle = topology.ring_cycle(topo)
        n = topo.n_devices
        nxt = dict(cycle)
        assert sorted(nxt) == list(range(n))
        assert sorted(nxt.values()) == list(range(n))
        # single n-cycle: following nxt from 0 visits all n exactly once
        seen, at = [], 0
        for _ in range(n):
            seen.append(at)
            at = nxt[at]
        assert at == 0 and sorted(seen) == list(range(n))
        # exactly one cross-group edge per adjacent group pair
        gof = topo.group_of
        cross = sum(1 for s, d in cycle if gof[s] != gof[d])
        assert cross == (topo.n_groups if topo.n_groups > 1 else 0)


def test_group_major_devices_and_slice_meshes_never_straddle(monkeypatch):
    monkeypatch.setenv(topology.TOPO_ENV, "2:4")
    devs = list(jax.devices())
    shuf = [devs[j] for j in (3, 7, 0, 5, 2, 6, 1, 4)]
    ordered = topology.group_major_devices(shuf)
    assert [d.id for d in ordered] == [3, 0, 2, 1, 7, 5, 6, 4]


# -- hierarchical collective parity (shard_map level) -------------------------


def _apply_collective(mesh, topo, op, x):
    def body(xs):
        sec = device_collective(f"topo_test.{op}", topo)
        if op == "allgather_rows":
            return sec.allgather_rows(xs, DATA_AXIS)
        if op == "gather_stack":
            return sec.gather_stack(xs, DATA_AXIS)
        if op == "psum_merge":
            return sec.psum_merge(xs, DATA_AXIS)
        if op == "psum":
            return sec.psum(xs, DATA_AXIS)
        raise AssertionError(op)

    f = shard_map(
        body, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(),
        check_vma=False,
    )
    return np.asarray(jax.jit(f)(x))


@pytest.mark.parametrize(
    "groups",
    [
        ((0, 1, 2, 3), (4, 5, 6, 7)),      # 2x4 contiguous
        ((0, 1), (2, 3), (4, 5), (6, 7)),  # 4x2 contiguous
        ((0, 2, 4, 6), (1, 3, 5, 7)),      # 2x4 interleaved
    ],
)
def test_hier_collectives_bitwise_match_flat(groups):
    """allgather_rows / gather_stack / psum_merge: the hierarchical
    schedule keeps the one-value-plus-zeros summand structure of the flat
    zeros-slab psum, so results are BITWISE identical.  psum carries
    integer-valued floats here (exact addition), pinning the re-associated
    schedule too."""
    mesh = _mesh(8)
    hier = topology.TopologyMap(groups=groups, source="env")
    assert hier.is_hierarchical
    flat = topology.flat_topology(8)
    rng = np.random.default_rng(11)
    x = rng.standard_normal((64, 5)).astype(np.float32)
    xi = rng.integers(-100, 100, size=(64, 5)).astype(np.float32)
    for op, data in (
        ("allgather_rows", x),
        ("gather_stack", x),
        ("psum_merge", x),
        ("psum", xi),
    ):
        a = _apply_collective(mesh, hier, op, data)
        b = _apply_collective(mesh, flat, op, data)
        np.testing.assert_array_equal(a, b, err_msg=f"{op} {groups}")


def test_hier_ring_shift_full_pass_is_identity():
    """n_dev applications of the hierarchical cycle return every block
    home (single n-cycle => permutation^n = identity), and on CONTIGUOUS
    groups the cycle degenerates to the flat +1 rotation, so even a single
    hop is bitwise-equal to flat."""
    mesh = _mesh(8)
    hier = topology.TopologyMap(
        groups=((0, 1, 2, 3), (4, 5, 6, 7)), source="env"
    )
    flat = topology.flat_topology(8)
    rng = np.random.default_rng(12)
    x = rng.standard_normal((64, 3)).astype(np.float32)

    def full_pass(topo, hops):
        def body(xs):
            sec = device_collective("topo_test.ring", topo)
            for _ in range(hops):
                xs = sec.ring_shift(xs)
            return xs

        f = shard_map(
            body, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=P(DATA_AXIS), check_vma=False,
        )
        return np.asarray(jax.jit(f)(x))

    np.testing.assert_array_equal(full_pass(hier, 8), x)
    np.testing.assert_array_equal(full_pass(hier, 1), full_pass(flat, 1))


def test_hier_counter_split_matches_byte_model():
    """The ici/dcn split counters follow the documented trace-time model,
    and the headline bound holds: hierarchical DCN bytes <= flat cross-
    host bytes / n_hosts (the flat schedule on a multi-group topology is
    all-DCN — it pins nothing to a link)."""
    mesh = _mesh(8)
    hier = topology.TopologyMap(
        groups=((0, 1, 2, 3), (4, 5, 6, 7)), source="env"
    )
    pinned = topology.TopologyMap(
        groups=hier.groups, source="env", pinned=True
    )
    x = np.ones((64, 4), np.float32)
    B = (64 // 8) * 4 * 4  # per-shard payload bytes
    for name in ("hsplit", "fsplit"):
        profiling.reset_counters(f"exchange.topo_test.{name}")
    profiling.reset_counters("exchange.topo_test.")
    _apply_collective(mesh, hier, "gather_stack", x)
    ctr = profiling.counters("exchange.topo_test.")
    G, g, n = 2, 4, 8
    assert ctr["exchange.topo_test.gather_stack.ici_bytes"] == (
        n * (g - 1) * B + G * (g - 1) * (n - g) * B
    )
    hier_dcn = ctr["exchange.topo_test.gather_stack.dcn_bytes"]
    assert hier_dcn == G * (G - 1) * g * B
    profiling.reset_counters("exchange.topo_test.")
    _apply_collective(mesh, pinned, "gather_stack", x)
    ctr = profiling.counters("exchange.topo_test.")
    flat_dcn = ctr["exchange.topo_test.gather_stack.dcn_bytes"]
    assert flat_dcn == n * (n - 1) * B
    assert "exchange.topo_test.gather_stack.ici_bytes" not in ctr
    # the acceptance headline, at the collective level
    assert hier_dcn <= flat_dcn / G * 1.1
    profiling.reset_counters("exchange.topo_test.")


# -- the kNN exchange kernels across simulated topologies ---------------------


def _knn_case(n_dev, route, topo_env, pin, monkeypatch, k=9):
    from spark_rapids_ml_tpu.ops.knn import (
        _exchange_geometry,
        _exchange_topology,
        knn_block_kernel_exchange,
        prepare_items,
    )

    monkeypatch.delenv(topology.TOPO_ENV, raising=False)
    monkeypatch.delenv(topology.EXCHANGE_TOPO_ENV, raising=False)
    if topo_env:
        monkeypatch.setenv(topology.TOPO_ENV, topo_env)
    if pin:
        monkeypatch.setenv(topology.EXCHANGE_TOPO_ENV, "flat")
    rng = np.random.default_rng(2)
    items = rng.standard_normal((1024, 16)).astype(np.float32)
    ids = np.arange(1024, dtype=np.int64)
    queries = rng.standard_normal((128, 16)).astype(np.float32)
    mesh = _mesh(n_dev)
    prepared = prepare_items(items, ids, mesh, shuffle=False)
    chunk, qt = _exchange_geometry(
        prepared.items.shape[0] // n_dev, 128, n_dev, route
    )
    topo = _exchange_topology(mesh)
    d, p = knn_block_kernel_exchange(
        prepared.items, prepared.norm, prepared.pos, prepared.valid,
        jnp.asarray(queries), mesh, k, route, chunk, qt, topo,
    )
    return np.asarray(d), np.asarray(p), topo


def test_knn_topology_parity_matrix_bitwise(monkeypatch):
    """The acceptance gate: hierarchical == flat-pinned == 1-device
    reference, BITWISE, for the ring and gather exchange kernels on
    1/2/8-device meshes under simulated topologies 1x8 / 2x4 / 4x2."""
    for route in ("ring", "gather"):
        ref_d, ref_p, _ = _knn_case(1, route, None, False, monkeypatch)
        for n_dev in (1, 2, 8):
            for topo_env in (None, "1:8", "2:4", "4:2"):
                for pin in (False, True):
                    d, p, topo = _knn_case(
                        n_dev, route, topo_env, pin, monkeypatch
                    )
                    tag = f"{route}/{n_dev}dev/{topo_env}/pin={pin}"
                    np.testing.assert_array_equal(d, ref_d, err_msg=tag)
                    np.testing.assert_array_equal(p, ref_p, err_msg=tag)


def test_knn_hier_dcn_bytes_bound_on_2x4(monkeypatch):
    """`exchange.knn.*.dcn_bytes` under the hierarchical route <= the
    flat route's cross-host bytes / n_hosts (+10% slack) on the 2x4 CI
    topology — the measurable O(n_dev) -> O(n_hosts) collapse."""
    def dcn(route, pin):
        profiling.reset_counters("exchange.knn.")
        # k=11 keeps these statics distinct from every other test's, so
        # the jit traces fresh here (sections count at TRACE time — a jit
        # cache hit records nothing)
        _knn_case(8, route, "2:4", pin, monkeypatch, k=11)
        ctr = profiling.counters("exchange.knn.")
        return sum(v for k, v in ctr.items() if k.endswith(".dcn_bytes"))

    for route in ("ring", "gather"):
        hier, flat = dcn(route, False), dcn(route, True)
        assert flat > 0
        assert hier <= flat / 2 * 1.1, (route, hier, flat)
    profiling.reset_counters("exchange.knn.")


def test_topology_is_a_cache_key_static(monkeypatch):
    """A topology change re-keys the AOT executable cache — same shapes,
    same route, different TopologyMap must NEVER reuse the same compiled
    schedule.  Equal maps (by value) key identically."""
    from spark_rapids_ml_tpu.ops.precompile import kernel_cache_key

    mesh = _mesh(8)
    args = (jax.ShapeDtypeStruct((128, 16), np.float32),)
    base = dict(k=9, route="ring", chunk=128, qt=16)
    k_flat = kernel_cache_key(
        "knn_ring", args, mesh,
        dict(base, topo=topology.flat_topology(8)),
    )
    hier = topology.TopologyMap(
        groups=((0, 1, 2, 3), (4, 5, 6, 7)), source="env"
    )
    k_hier = kernel_cache_key("knn_ring", args, mesh, dict(base, topo=hier))
    k_hier2 = kernel_cache_key(
        "knn_ring", args, mesh,
        dict(base, topo=topology.TopologyMap(
            groups=((0, 1, 2, 3), (4, 5, 6, 7)), source="env"
        )),
    )
    k_pin = kernel_cache_key(
        "knn_ring", args, mesh,
        dict(base, topo=topology.TopologyMap(
            groups=hier.groups, source="env", pinned=True
        )),
    )
    assert k_flat != k_hier != k_pin
    assert k_hier == k_hier2


def test_hier_route_zero_new_compiles_on_repeat_search(monkeypatch):
    """Repeat same-shape search under SRML_TOPO=2:4: the second search
    rides the AOT cache with ZERO new compilations — the steady-state
    contract holds on the hierarchical schedule too."""
    from spark_rapids_ml_tpu.ops.knn import (
        knn_search_prepared, prepare_items,
    )

    monkeypatch.setenv(topology.TOPO_ENV, "2:4")
    monkeypatch.setenv("SRML_KNN_EXCHANGE", "ring")
    rng = np.random.default_rng(4)
    items = rng.standard_normal((2048, 24)).astype(np.float32)
    queries = rng.standard_normal((256, 24)).astype(np.float32)
    mesh = _mesh(8)
    prepared = prepare_items(
        items, np.arange(2048, dtype=np.int64), mesh, shuffle=False
    )
    d1, i1 = knn_search_prepared(prepared, queries, 9, mesh)
    c0 = profiling.counters("precompile")
    d2, i2 = knn_search_prepared(prepared, queries, 9, mesh)
    c1 = profiling.counters("precompile")
    assert c1.get("precompile.compile", 0) == c0.get("precompile.compile", 0)
    assert c1.get("precompile.aot_hit", 0) > c0.get("precompile.aot_hit", 0)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(i1, i2)


# -- host-plane distributed ring cycle ----------------------------------------


def test_distributed_ring_topology_cycle_bitwise_vs_flat(monkeypatch):
    """distributed_kneighbors under SRML_TOPO=2:2 (4 thread-ranks, 2
    simulated hosts): identical bits to the flat run, and the host-ring
    hops are attributed to exchange.ring.ici_bytes / .dcn_bytes."""
    from test_knn_exchange import _distributed_case

    profiling.reset_counters("exchange.ring")
    res_flat, q_split, sk_d, sk_ids = _distributed_case("ring", monkeypatch)
    flat_ctr = profiling.counters("exchange.ring")
    assert "exchange.ring.ici_bytes" not in flat_ctr  # no grouping, no split
    monkeypatch.setenv(topology.TOPO_ENV, "2:2")
    profiling.reset_counters("exchange.ring")
    res_topo, _, _, _ = _distributed_case("ring", monkeypatch)
    topo_ctr = profiling.counters("exchange.ring")
    for rank in range(4):
        ((df, i_f),) = res_flat[rank]
        ((dt, i_t),) = res_topo[rank]
        np.testing.assert_array_equal(dt, df)
        np.testing.assert_array_equal(i_t, i_f)
        rows = q_split[rank]
        np.testing.assert_allclose(dt, sk_d[rows], rtol=1e-4, atol=1e-4)
    # 2:2 on 4 ranks: ranks 0/2 drive intra-host edges, 1/3 the gateways
    assert topo_ctr.get("exchange.ring.ici_bytes", 0) > 0
    assert topo_ctr.get("exchange.ring.dcn_bytes", 0) > 0
    profiling.reset_counters("exchange.ring")


# -- telemetry rollup ---------------------------------------------------------


def test_link_totals_and_prometheus_family(monkeypatch):
    """The per-link rollup reaches export_metrics()['gauges'] and renders
    as the srml_exchange_bytes{link=ici|dcn} Prometheus family."""
    mesh = _mesh(8)
    hier = topology.TopologyMap(
        groups=((0, 1, 2, 3), (4, 5, 6, 7)), source="env"
    )
    before = link_totals()
    _apply_collective(mesh, hier, "gather_stack", np.ones((64, 4), np.float32))
    after = link_totals()
    assert after["ici"] > before["ici"] and after["dcn"] > before["dcn"]
    gauges = profiling.export_metrics()["gauges"]
    assert gauges["exchange.link.ici_bytes"] == float(after["ici"])
    assert gauges["exchange.link.dcn_bytes"] == float(after["dcn"])
    prom = profiling.render_prometheus()
    assert "# TYPE srml_exchange_bytes gauge" in prom
    assert f'srml_exchange_bytes{{link="ici"}} {float(after["ici"])}' in prom
    assert f'srml_exchange_bytes{{link="dcn"}} {float(after["dcn"])}' in prom
    profiling.reset_counters("exchange.topo_test.")
