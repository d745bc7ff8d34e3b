#
# Distributed-runtime context tests — the analog of the reference's comms
# test (python/tests/test_ucx.py:35-99, which spins a barrier stage, builds a
# real CumlContext, and asserts the endpoint mesh came up).  Here the data
# plane is jax.distributed + mesh collectives: we check the coordinator
# handshake protocol over a fake control plane (the part the reference tests
# via BarrierTaskContext.allGather) and run a real psum/all_gather over the
# 8-device CPU mesh (the part test_ucx verifies by constructing comms).
#

import json
import os
import sys
from typing import List

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from spark_rapids_ml_tpu.parallel.context import (  # noqa: E402
    LocalControlPlane,
    TpuContext,
)
from spark_rapids_ml_tpu.parallel.netplane import (  # noqa: E402
    _free_port,
    _local_ip,
)
from spark_rapids_ml_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    data_sharding,
    get_mesh,
    shard_rows,
)
from spark_rapids_ml_tpu.parallel.partition import PartitionDescriptor  # noqa: E402


class FakeBarrierControlPlane:
    """Records every rank's allGather message like BarrierTaskContext would,
    releasing the gathered list once all ranks have posted."""

    def __init__(self, nranks: int):
        self.nranks = nranks
        self.messages: List[str] = []
        self.barriers = 0

    def allGather(self, message: str) -> List[str]:
        self.messages.append(message)
        assert len(self.messages) <= self.nranks
        return list(self.messages)

    def barrier(self) -> None:
        self.barriers += 1


class TestTpuContext:
    def test_single_rank_is_noop(self):
        with TpuContext(rank=0, nranks=1) as ctx:
            assert ctx.rank == 0 and ctx.nranks == 1
            assert not ctx._initialized_distributed  # no jax.distributed in-process

    def test_multi_rank_handshake(self, monkeypatch):
        calls, heartbeats = [], []

        def fake_initialize(
            coordinator_address, num_processes, process_id,
            heartbeat_timeout_seconds,
        ):
            calls.append((coordinator_address, num_processes, process_id))
            heartbeats.append(heartbeat_timeout_seconds)

        def fake_shutdown():
            calls.append("shutdown")

        monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
        monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)

        # rank 0 first (it mints the coordinator address, like the NCCL uid
        # in cuml_context.py:75-103), then rank 1 sees it via the gather
        cp = FakeBarrierControlPlane(nranks=2)
        with TpuContext(rank=0, nranks=2, control_plane=cp):
            pass
        addr0 = json.loads(cp.messages[0])["addr"]
        assert addr0 and ":" in addr0
        with TpuContext(rank=1, nranks=2, control_plane=cp):
            pass
        assert calls[0] == (addr0, 2, 0)
        assert calls[1] == "shutdown"
        assert calls[2] == (addr0, 2, 1)
        # the tightened coordination heartbeat reaches jax on every rank
        assert heartbeats == [10, 10]

    def test_heartbeat_timeout_env_is_forwarded(self, monkeypatch):
        captured = {}
        monkeypatch.setattr(
            jax.distributed, "initialize", lambda **kw: captured.update(kw)
        )
        monkeypatch.setenv("SRML_JAX_HEARTBEAT_TIMEOUT_S", "37")
        ctx = TpuContext(
            rank=0, nranks=2, control_plane=FakeBarrierControlPlane(nranks=2)
        )
        ctx.__enter__()
        ctx._initialized_distributed = False  # initialize was a stub
        ctx.__exit__(None, None, None)
        assert captured["heartbeat_timeout_seconds"] == 37
        assert set(captured) == {
            "coordinator_address", "num_processes", "process_id",
            "heartbeat_timeout_seconds",
        }

    def test_rank0_address_missing_raises(self, monkeypatch):
        monkeypatch.setattr(jax.distributed, "initialize", lambda **kw: None)

        class EmptyCp:
            def allGather(self, message):
                return [json.dumps({"rank": 7, "addr": ""})]

            def barrier(self):
                pass

        with pytest.raises(AssertionError):
            TpuContext(rank=1, nranks=2, control_plane=EmptyCp()).__enter__()

    def test_local_ip_and_port_helpers(self):
        ip = _local_ip()
        assert ip.count(".") == 3
        port = _free_port()
        assert 0 < port < 65536


class TestMeshCollectives:
    def test_mesh_spans_devices(self):
        mesh = get_mesh()
        assert mesh.devices.size == len(jax.devices())
        assert DATA_AXIS in mesh.shape

    def test_psum_over_mesh_matches_numpy(self):
        from jax import shard_map

        mesh = get_mesh()
        X_host = np.arange(64, dtype=np.float32).reshape(16, 4)
        Xs, _ = shard_rows(X_host, mesh)

        def local_sum(x):
            return jax.lax.psum(x.sum(axis=0), DATA_AXIS)

        total = shard_map(
            local_sum, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
            check_vma=False,
        )(Xs)
        np.testing.assert_allclose(np.asarray(total), X_host.sum(axis=0))

    def test_all_gather_roundtrip(self):
        from jax import shard_map

        mesh = get_mesh()
        n_dev = mesh.devices.size
        X_host = np.arange(n_dev * 3, dtype=np.float32).reshape(n_dev, 3)
        Xs = jax.device_put(X_host, data_sharding(mesh))

        def gather(x):
            return jax.lax.all_gather(x, DATA_AXIS).reshape(-1, x.shape[-1])

        out = shard_map(
            gather, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
            check_vma=False,
        )(Xs)
        np.testing.assert_array_equal(np.asarray(out), X_host)


class TestPartitionDescriptor:
    def test_build(self):
        pd_ = PartitionDescriptor.build([5, 0, 7], 3)
        assert pd_.m == 12 and pd_.n == 3
        assert pd_.parts_rank_size == [(0, 5), (1, 0), (2, 7)]
