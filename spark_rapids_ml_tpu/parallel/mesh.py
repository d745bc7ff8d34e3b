#
# Device-mesh construction and row-sharded ingest.
#
# TPU-native replacement for the reference's GPU binding + cuDF ingest
# (/root/reference/python/src/spark_rapids_ml/core.py:233-259 device binding,
# :558-632 Arrow->cupy ingest).  Instead of "1 Spark task = 1 GPU = 1 NCCL
# rank", the unit of parallelism is a jax.sharding.Mesh over all addressable
# devices: within one host the mesh rides ICI; across hosts jax.distributed +
# DCN extends the same mesh (see parallel/context.py).  Data parallelism is
# expressed by sharding the row axis with NamedSharding(P("data")) and letting
# GSPMD insert psum/all_gather collectives during compilation.
#

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"


def default_num_workers() -> int:
    """One logical worker per addressable device (chips on this host, or the
    whole pod under jax.distributed)."""
    return jax.device_count()


def get_mesh(num_workers: Optional[int] = None) -> Mesh:
    """1-D data-parallel mesh over the first `num_workers` devices (all of
    them when unset).  Asking for more workers than there are devices is an
    error, never a silently narrower mesh."""
    devices = jax.devices()
    n = num_workers or len(devices)
    if n > len(devices):
        raise ValueError(
            f"num_workers={n} exceeds the {len(devices)} "
            f"{devices[0].platform} device(s) jax sees"
        )
    return Mesh(np.array(devices[:n]), (DATA_AXIS,))


def get_2d_mesh(num_data: int, num_model: int) -> Mesh:
    """(data, model) mesh for feature-axis sharding of very wide problems
    (e.g. X^T X when n_cols is huge) — the GSPMD generalization noted in
    SURVEY.md §2.4."""
    devices = np.array(jax.devices()[: num_data * num_model]).reshape(
        num_data, num_model
    )
    return Mesh(devices, (DATA_AXIS, MODEL_AXIS))


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS))


def col_sharding(mesh: Mesh) -> NamedSharding:
    """Shard axis 1 (columns) over the data axis.  The UMAP layout engine
    keeps its edge arrays in transposed (P, n) component-sliced form (minor
    dimension = nodes, for full TPU lanes); sharding the NODE axis there
    means sharding columns, so each device owns a contiguous head block."""
    return NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))


def axis_sharding(mesh: Mesh, axis: int, ndim: int) -> NamedSharding:
    """Shard one axis of an `ndim`-rank array over the data axis (the
    generic form of data_sharding/col_sharding).  The forest engine shards
    its (T, N) routing state and (T, N, S) per-tree stats on the ROW axis
    (axis=1) so every per-shard histogram pass sees row-aligned slices of
    bins, stats and node ids."""
    spec = [None] * ndim
    spec[axis] = DATA_AXIS
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def slice_meshes(n_slices: int, devices=None) -> list:
    """Carve `n_slices` DISJOINT 1-D data meshes over the device list — the
    unit of replica parallelism for the serving router (serving/router.py)
    and the thread-mocked multicontroller ranks (ops/knn).

    Disjointness is load-bearing, not cosmetic: XLA:CPU's cross_module
    rendezvous deadlocks when two multi-device programs launched from
    different threads interleave their per-device enqueue order on SHARED
    devices, and on TPU hardware a shared slice would serialize the
    replicas on the same chips anyway.  With fewer devices than slices the
    surplus slices each get ONE device, round-robin — single-device
    programs have no cross-program rendezvous, so oversubscription degrades
    to compute contention instead of deadlock.

    The carve order is GROUP-MAJOR over the host topology
    (parallel/topology.py): devices sharing a host group come first,
    consecutively, so a contiguous slice never straddles a host group when
    the device count allows — a replica spanning DCN would pay the slow
    link on every dispatch.  On flat/unknown topologies this is the
    identity order."""
    if n_slices < 1:
        raise ValueError(f"n_slices must be >= 1, got {n_slices}")
    from . import topology

    devs = list(devices) if devices is not None else jax.devices()
    devs = topology.group_major_devices(devs)
    per = len(devs) // n_slices
    out = []
    for i in range(n_slices):
        if per >= 1:
            local = devs[i * per : (i + 1) * per]
        else:
            local = [devs[i % len(devs)]]
        out.append(Mesh(np.array(local), (DATA_AXIS,)))
    return out


def carve_device_slices(devices, slice_devices: int) -> list:
    """Carve the device list into as many DISJOINT `slice_devices`-sized
    device groups as it holds — the fixed-granularity counterpart of
    slice_meshes, and the ONE carve rule behind the serving slice pool
    (serving/slicepool.SlicePool).

    Group-aware, not merely group-major: when the host topology is known
    and a slice fits inside a host group (slice_devices <= group size),
    the carve runs PER GROUP, so no slice ever straddles a host group —
    a replica spanning DCN would pay the slow link on every dispatch.
    Devices left over inside a group (group size not a multiple of
    slice_devices) are stranded rather than glued across the boundary;
    the pool accounts for them explicitly.  A slice BIGGER than a host
    group must span DCN by construction, so the carve falls back to
    contiguous group-major runs (the whole-mesh n_slices=1 case).  On
    flat/unknown topologies this is a plain contiguous carve."""
    if slice_devices < 1:
        raise ValueError(f"slice_devices must be >= 1, got {slice_devices}")
    from . import topology

    devs = list(devices) if devices is not None else jax.devices()
    topo = topology.topology_map(devices=devs)
    out = []
    if topo.n_groups > 1 and slice_devices <= min(len(g) for g in topo.groups):
        for g in topo.groups:
            members = [devs[p] for p in g]
            for i in range(len(members) // slice_devices):
                out.append(members[i * slice_devices : (i + 1) * slice_devices])
        return out
    ordered = topology.group_major_devices(devs)
    for i in range(len(ordered) // slice_devices):
        out.append(ordered[i * slice_devices : (i + 1) * slice_devices])
    return out


def ring_permutation(n_dev: int, shift: int = 1):
    """The (source, destination) pairs of a +shift rotation along the
    1-D data mesh — the ONE definition of the mesh's ring order, used by
    parallel/exchange.DeviceSection.ring_shift (lax.ppermute fallback) so
    the XLA and remote-DMA paths agree on who "the +1 neighbor" is.

    get_mesh builds the data axis in jax.devices() order, which on a TPU
    slice enumerates chips along the physical ICI ring — so the +1 logical
    neighbor is (one hop of) the wired neighbor and a full ring pass never
    crosses the bisection.  A custom Mesh with a shuffled device order
    still computes CORRECT results (ppermute/remote-DMA route by logical
    index); it just pays longer physical paths per hop."""
    return [(i, (i + shift) % n_dev) for i in range(n_dev)]


# Row-pad multiple shared by sharded kernels whose RNG streams index GLOBAL
# padded positions (the UMAP layout's counter-based threefry draws): padding
# to lcm(64, n_shards) keeps the padded geometry — and therefore every
# counter-derived draw — IDENTICAL across all mesh sizes that DIVIDE 64
# (every power-of-two TPU mesh up to 64 devices), which is what makes
# "fixed seed => same embedding on any such mesh" testable.  A mesh size
# that does not divide 64 (e.g. 6) raises the lcm, changing the padded
# geometry: still deterministic for that shape, just not bit-identical to
# the power-of-two shapes.
ROW_PAD_LANES = 64


def padded_row_count(n: int, mesh: Optional[Mesh] = None) -> int:
    """Rows padded up to a multiple of lcm(ROW_PAD_LANES, data-axis size)."""
    import math

    mult = ROW_PAD_LANES
    if mesh is not None:
        mult = math.lcm(mult, mesh.shape[DATA_AXIS])
    return -(-max(n, 1) // mult) * mult


def shard_rows(
    arr: np.ndarray, mesh: Mesh, dtype: Optional[np.dtype] = None
) -> Tuple[jax.Array, int]:
    """Zero-pad rows to a multiple of the data-axis size and device_put with a
    row sharding.  Returns (sharded_array, n_valid_rows).  Padded rows must be
    masked by callers via the weight vector produced in core ingest."""
    from ..utils import pad_rows

    if dtype is not None:
        arr = np.asarray(arr, dtype=dtype)
    n_valid = arr.shape[0]
    n_shards = mesh.shape[DATA_AXIS]
    padded = pad_rows(arr, n_shards)
    sharded = jax.device_put(padded, data_sharding(mesh))
    return sharded, n_valid
