#
# IVF-Flat approximate nearest neighbors, pure jax, mesh-aware.
#
# TPU-native counterpart of cuML's ApproximateNearestNeighbors
# (algorithm='ivfflat', algoParams={nlist, nprobe}) and the FAISS IVF-Flat
# tier (Johnson et al., "Billion-scale similarity search with GPUs"):
#
#   build:  the EXISTING kmeans engine (ops/kmeans.lloyd_iterations +
#           scalable k-means|| init) trains the coarse quantizer on a
#           deterministic sample; list assignment is the fused
#           distance+argmin kernel (ops/pallas_tpu.min_dist_argmin — Pallas
#           on TPU, identical-math XLA elsewhere); the inverted lists are
#           laid out host-side as ONE dense (nlist_pad, L_pad, D) buffer —
#           L_pad is the pow2 bucket of the longest list, nlist_pad a
#           multiple of lcm(8, n_dev) — and row-sharded over DATA_AXIS on
#           the LIST axis, so each device owns a contiguous block of whole
#           lists.
#   search: queries are replicated; every shard picks the query's nprobe
#           nearest centroids (replicated math), gathers the probed lists
#           it OWNS from its resident shard, computes distances on the
#           gathered tile, and keeps a local top-k; ONE psum'd candidate
#           merge (parallel/exchange.psum_merge_parts) combines the
#           per-shard (Q, k) lists and a final selection yields the global
#           top-k.  Host orchestration reuses the kNN engine's block
#           pipeline (ops/knn._run_block_pipeline) over pow2-bucketed query
#           blocks, and every kernel dispatches through
#           ops/precompile.cached_kernel — repeat same-shape probed
#           searches perform ZERO new compilations.
#
# Mesh parity (the CI gate): every selection point orders candidates by the
# LEXICOGRAPHIC key (d2, global position) — jax.lax.sort with num_keys=2 —
# and positions are unique, so the selected set AND its order form a total
# order independent of how lists shard.  A candidate's d2 (the expanded
# ||q||^2 - 2 q.x + ||x||^2 form, same as the exact engine) reduces over
# the fixed-width feature axis of an identically shaped tile on every mesh
# size, so its bits are mesh-independent too: fixed seed =>
# bitwise-identical probed results on 1-device and 8-device
# meshes.  (Plain value-only top-k would break this: the pool
# concatenation order differs between the single-shard pool and the
# shard-merged pool, so value ties would resolve differently.)
#
# Exactness knob: probing all lists (nprobe >= nlist) visits every item
# exactly once, so the probed result EQUALS the exact kneighbors result up
# to f32 distance formulation differences — the recall harness
# (recall_at_k) gates probed results against ops/knn's exact path in tests.
#

from __future__ import annotations

import math
import os
from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import profiling
from ..parallel.mesh import (
    DATA_AXIS,
    axis_sharding,
    data_sharding,
    get_mesh,
    replicated_sharding,
)
from ..ops.precompile import cached_kernel, kernel_cache_key, shape_bucket

# The ONE lexicographic (d2, pos) tie contract: ops/knn.lex_topk (this
# engine's mesh-parity gate established it; PR 9 moved the implementation
# into the exact engine's exchange kernels).  Re-exported here — `_lex_topk`
# used to be a private wrapper around it, and the PQ tier (ann/pq.py)
# imports the same names — so every selection point in the ANN subsystem
# shares one total order and one sentinel.
from ..ops.knn import LEX_POS_SENTINEL, lex_topk as _lex_topk  # noqa: E402

# nlist padding unit: the packed layout pads the list count to a multiple of
# 8, and staging re-pads to lcm(8, n_dev) — every power-of-two mesh up to 8
# devices therefore sees the IDENTICAL padded geometry (the parity basis;
# larger meshes stay deterministic per shape, like mesh.padded_row_count)
_LIST_ALIGN = 8
# smallest per-list slot bucket (pow2 ladder floor, like the serving
# min-bucket rule)
_MIN_LIST_SLOTS = 8
# positions are int32 (list * L_pad + slot); the sentinel marks
# invalid/padded candidate slots and must exceed every real position —
# the SAME sentinel lex_topk pads unfillable slots with (one contract)
_POS_SENTINEL = LEX_POS_SENTINEL
# byte budget for the gathered (chunk, nprobe, L_pad, D) candidate tile —
# the probe kernel's only big intermediate; sized per query chunk so HBM
# use stays flat no matter the query block.  SRML_ANN_TILE_BUDGET overrides
# (tests shrink it to exercise the multi-chunk scan).
_PROBE_TILE_BUDGET = 64 << 20
# assignment row-block cap (pow2-bucketed, so repeat builds reuse kernels)
_ASSIGN_BLOCK = 65536
# quantizer training sample cap: IVF quantizers train on a sample (the
# FAISS convention); the cap bounds build time independent of index size
_TRAIN_CAP = 65536


def default_nlist(n_items: int) -> int:
    """sqrt(n) lists clamped to [8, 1024] — the standard IVF sizing rule
    (documented in docs/ann_engine.md with the measured recall table)."""
    return int(max(_LIST_ALIGN, min(1024, round(math.sqrt(max(n_items, 1))))))


def default_nprobe(n_lists: int) -> int:
    """A quarter of the lists, floor 8: recall ~0.95+ on clustered data at
    the docs/ann_engine.md operating points."""
    return int(max(8, n_lists // 4))


def _probe_tile_budget() -> int:
    try:
        return int(os.environ.get("SRML_ANN_TILE_BUDGET", _PROBE_TILE_BUDGET))
    except ValueError:
        return _PROBE_TILE_BUDGET


def _probe_chunk(block: int, nprobe: int, l_pad: int, dim: int) -> int:
    """Power-of-two query-chunk size whose gathered candidate tile fits the
    byte budget.  `block` is itself a pow2 bucket, so the chunk always
    divides it exactly — the kernel's scan needs no ragged tail."""
    per_row = max(nprobe * l_pad * dim * 4, 1)
    c = max(1, _probe_tile_budget() // per_row)
    c = 1 << (c.bit_length() - 1)
    return min(c, block)


def select_probes(
    q: jax.Array,       # (Q, D) replicated queries
    c: jax.Array,       # (nlist_pad, D) replicated centroids
    cn: jax.Array,      # (nlist_pad,) replicated ||c||^2, +inf pad rows
    nprobe: int,
    lps: int,           # lists per shard
    mesh: Mesh,
):
    """Replicated probe selection shared by the IVF-Flat and IVF-PQ probe
    kernels: expanded-form query->centroid distances, top-nprobe lists, and
    each shard's local-list mapping.  Identical on every shard and every
    mesh size (pad-list rows carry +inf norms so they lose to every genuine
    list; lax.top_k tie-break is lowest-index-first, also replicated).

    Returns (qn (Q,), d2c_probe (Q, nprobe) probed-centroid distances —
    the ADC base term the PQ kernel consumes, discarded by IVF-Flat —
    probes (Q, nprobe) int32, lp (Q, nprobe) clamped local list ids,
    is_local (Q, nprobe) ownership mask)."""
    qn = (q * q).sum(axis=1)
    cross = jnp.matmul(
        q, c.T,
        precision=jax.lax.Precision.HIGH,
        preferred_element_type=jnp.float32,
    )
    d2c = qn[:, None] - 2.0 * cross + cn[None, :]
    neg_d2, probes = jax.lax.top_k(-d2c, nprobe)  # (Q, nprobe)
    if mesh.shape[DATA_AXIS] > 1:
        off = jax.lax.axis_index(DATA_AXIS) * lps
    else:
        off = jnp.int32(0)
    local = probes - off
    is_local = (local >= 0) & (local < lps)
    lp = jnp.clip(local, 0, lps - 1)
    return qn, -neg_d2, probes, lp, is_local


def merge_shard_topk(
    best_d: jax.Array, best_p: jax.Array, mesh: Mesh, k: int
):
    """The ONE cross-shard candidate merge, shared VERBATIM by the IVF-Flat
    and IVF-PQ probe kernels (the 1-dev-vs-8-dev bitwise parity contract
    has a single implementation): per-shard (Q, k) candidates scattered
    into a (n_dev, Q, k) slab and psum'd (exact — each element is one
    shard's value plus zeros), then one final lexicographic (d2, pos)
    selection.  Typed exchange section: uniform exchange.ann.probe_merge.*
    counters."""
    if mesh.shape[DATA_AXIS] <= 1:
        return best_d, best_p
    from ..parallel.exchange import device_collective

    Q = best_d.shape[0]
    sec = device_collective("ann.probe_merge")
    all_d = sec.psum_merge(best_d, DATA_AXIS)
    all_p = sec.psum_merge(best_p, DATA_AXIS)
    cand_d = jnp.moveaxis(all_d, 0, 1).reshape(Q, -1)
    cand_p = jnp.moveaxis(all_p, 0, 1).reshape(Q, -1)
    return _lex_topk(cand_d, cand_p, k)


@partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "chunk"))
def ivf_probe_kernel(
    list_data: jax.Array,  # (nlist_pad, L_pad, D) list-sharded over DATA_AXIS
    list_norm: jax.Array,  # (nlist_pad, L_pad) list-sharded ||x||^2
    counts: jax.Array,     # (nlist_pad,) int32 list-sharded valid-slot counts
    centroids: jax.Array,  # (nlist_pad, D) replicated (pad rows zero)
    c_norm: jax.Array,     # (nlist_pad,) replicated ||c||^2, +inf in pad rows
    queries: jax.Array,    # (Q, D) replicated
    mesh: Mesh,
    k: int,
    nprobe: int,
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Probed IVF-Flat search: (euclidean distances (Q, k) ascending,
    positions (Q, k) into the padded list layout; unfillable slots carry
    inf distance and the _POS_SENTINEL position — the host maps them to the
    -1 id sentinel, same contract as the exact kNN kernels)."""
    nlist_pad, l_pad, _d = list_data.shape

    def per_shard(ld_loc, ln_loc, cnt_loc, c, cn, q):
        lps = ld_loc.shape[0]
        Q = q.shape[0]
        # probe selection on REPLICATED data (shared with the PQ kernel;
        # the probed-centroid distances it also returns are the ADC base
        # term — unused here, DCE'd by XLA)
        qn, _d2p, probes, lp, is_local = select_probes(
            q, c, cn, nprobe, lps, mesh
        )
        slot = jnp.arange(l_pad, dtype=jnp.int32)

        def chunk_body(carry, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
            qn_c = jax.lax.dynamic_slice_in_dim(qn, i * chunk, chunk)
            lp_c = jax.lax.dynamic_slice_in_dim(lp, i * chunk, chunk)
            loc_c = jax.lax.dynamic_slice_in_dim(is_local, i * chunk, chunk)
            pr_c = jax.lax.dynamic_slice_in_dim(probes, i * chunk, chunk)
            # gather the chunk's probed lists from the RESIDENT shard:
            # (chunk, nprobe, L_pad, D) — the budget-bounded tile
            tile = jnp.take(ld_loc, lp_c, axis=0)
            xn = jnp.take(ln_loc, lp_c, axis=0)
            # expanded-form distances (||q||^2 - 2 q.x + ||x||^2) — the
            # SAME formulation as the exact engine and the kmeans/UMAP
            # kernels, so probed distances agree with exact kneighbors to
            # shared-rounding precision (the UMAP graph calibration
            # consumes distances, not just ids).  Parity basis: the
            # contraction reduces over the fixed feature axis of an
            # identically shaped tile on every mesh size, so a candidate's
            # d2 bits are mesh-independent.
            cross = jnp.einsum(
                "qd,qpld->qpl", qs, tile,
                precision=jax.lax.Precision.HIGH,
                preferred_element_type=jnp.float32,
            )
            d2 = qn_c[:, None, None] - 2.0 * cross + xn  # (chunk, nprobe, L_pad)
            valid = loc_c[:, :, None] & (
                slot[None, None, :] < jnp.take(cnt_loc, lp_c, axis=0)[:, :, None]
            )
            d2 = jnp.where(valid, d2, jnp.inf)
            pos = pr_c[:, :, None] * l_pad + slot[None, None, :]
            pos = jnp.where(valid, pos, _POS_SENTINEL)
            bd, bp = _lex_topk(
                d2.reshape(chunk, -1), pos.reshape(chunk, -1), k
            )
            return carry, (bd, bp)

        n_chunks = Q // chunk
        _, (ds, ps) = jax.lax.scan(
            chunk_body, 0, jnp.arange(n_chunks, dtype=jnp.int32)
        )
        best_d, best_p = merge_shard_topk(
            ds.reshape(Q, k), ps.reshape(Q, k), mesh, k
        )
        return jnp.sqrt(jnp.maximum(best_d, 0.0)), best_p

    return shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(list_data, list_norm, counts, centroids, c_norm, queries)


@partial(jax.jit, static_argnames=("mesh", "nprobe"))
def ivf_select_kernel(
    centroids: jax.Array,  # (nlist_pad, D) replicated
    c_norm: jax.Array,     # (nlist_pad,) replicated, +inf pad rows
    queries: jax.Array,    # (Q, D) replicated
    mesh: Mesh,            # unused in the math — cache-key rider only, so
    #                        executables never cross mesh placements
    nprobe: int,
) -> jax.Array:
    """Probe selection ALONE, for the tiered pager (flat and PQ): the host
    needs each block's probed list ids BEFORE dispatch so cold lists can
    page in.  Op-for-op the select_probes math (expanded-form distances at
    HIGH matmul precision, lax.top_k over the same +inf-padded norms) on
    the same replicated arrays — the probe kernels re-select identically
    inside their shard_map, so the pager and the scan always agree on
    which lists a query touches."""
    qn = (queries * queries).sum(axis=1)
    cross = jnp.matmul(
        queries, centroids.T,
        precision=jax.lax.Precision.HIGH,
        preferred_element_type=jnp.float32,
    )
    d2c = qn[:, None] - 2.0 * cross + c_norm[None, :]
    _neg_d2, probes = jax.lax.top_k(-d2c, nprobe)
    return probes.astype(jnp.int32)


@partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "chunk"))
def ivf_probe_tiered_kernel(
    list_data: jax.Array,  # (n_dev * slots_per_shard, L_pad, D) slot pool
    list_norm: jax.Array,  # (n_dev * slots_per_shard, L_pad) slot pool
    list_slot: jax.Array,  # (nlist_pad,) int32 list->local-slot, 0 sentinel
    counts: jax.Array,     # (nlist_pad,) int32 list-sharded
    centroids: jax.Array,  # (nlist_pad, D) replicated (pad rows zero)
    c_norm: jax.Array,     # (nlist_pad,) replicated ||c||^2, +inf pad rows
    queries: jax.Array,    # (Q, D) replicated
    mesh: Mesh,
    k: int,
    nprobe: int,
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """ivf_probe_kernel's body plus ONE indirection: probed local list ids
    map through list_slot into the shard's HBM slot pool before the
    data/norm gathers (ann/tier.py pages the pool).  Positions stay GLOBAL
    (probe * L_pad + slot) so ids/refine are untouched, and the gathered
    tiles hold byte-for-byte the values the resident kernel gathers —
    the tiered-vs-resident bitwise parity argument.  A probed list whose
    slot is 0 reads the sentinel (+inf norms) and drops out: residency
    bugs degrade recall, never corrupt."""
    _rows, l_pad, _d = list_data.shape

    def per_shard(ld_loc, ln_loc, slot_loc, cnt_loc, c, cn, q):
        lps = cnt_loc.shape[0]
        Q = q.shape[0]
        qn, _d2p, probes, lp, is_local = select_probes(
            q, c, cn, nprobe, lps, mesh
        )
        slot = jnp.arange(l_pad, dtype=jnp.int32)

        def chunk_body(carry, i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
            qn_c = jax.lax.dynamic_slice_in_dim(qn, i * chunk, chunk)
            lp_c = jax.lax.dynamic_slice_in_dim(lp, i * chunk, chunk)
            loc_c = jax.lax.dynamic_slice_in_dim(is_local, i * chunk, chunk)
            pr_c = jax.lax.dynamic_slice_in_dim(probes, i * chunk, chunk)
            # THE tiered indirection: local list -> pool slot, then gather
            # from the slot pool instead of the full list plane
            ls_c = jnp.take(slot_loc, lp_c, axis=0)
            tile = jnp.take(ld_loc, ls_c, axis=0)
            xn = jnp.take(ln_loc, ls_c, axis=0)
            cross = jnp.einsum(
                "qd,qpld->qpl", qs, tile,
                precision=jax.lax.Precision.HIGH,
                preferred_element_type=jnp.float32,
            )
            d2 = qn_c[:, None, None] - 2.0 * cross + xn
            valid = loc_c[:, :, None] & (
                slot[None, None, :] < jnp.take(cnt_loc, lp_c, axis=0)[:, :, None]
            )
            d2 = jnp.where(valid, d2, jnp.inf)
            pos = pr_c[:, :, None] * l_pad + slot[None, None, :]
            pos = jnp.where(valid, pos, _POS_SENTINEL)
            bd, bp = _lex_topk(
                d2.reshape(chunk, -1), pos.reshape(chunk, -1), k
            )
            return carry, (bd, bp)

        n_chunks = Q // chunk
        _, (ds, ps) = jax.lax.scan(
            chunk_body, 0, jnp.arange(n_chunks, dtype=jnp.int32)
        )
        best_d, best_p = merge_shard_topk(
            ds.reshape(Q, k), ps.reshape(Q, k), mesh, k
        )
        return jnp.sqrt(jnp.maximum(best_d, 0.0)), best_p

    return shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
            P(), P(), P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(list_data, list_norm, list_slot, counts, centroids, c_norm, queries)


@jax.jit
def _assign_block_kernel(X: jax.Array, centroids: jax.Array) -> jax.Array:
    """Fused distance+argmin list assignment for one pow2 row block
    (ops/pallas_tpu.min_dist_argmin: the Pallas kernel on TPU in its
    profitable regime, identical-math XLA elsewhere).  Per-row math with no
    cross-row reduction — assignments are bitwise mesh-independent."""
    from ..ops.pallas_tpu import min_dist_argmin

    _, assign = min_dist_argmin(X, centroids)
    return assign


class PackedIVF:
    """Host-side, mesh-INDEPENDENT index payload: items sorted by list
    (stable), their ids, per-list counts, and the genuine (unpadded)
    centroids.  This is what the model persists (plain np arrays through
    the core npz path); index_from_packed expands it into the device
    layout for whatever mesh serves it."""

    __slots__ = ("items", "ids", "counts", "centroids", "n_lists", "n_items")

    def __init__(self, items, ids, counts, centroids, n_lists, n_items):
        self.items = items          # (N, D) f32, list-sorted
        self.ids = ids              # (N,) int64 user ids, list-sorted
        self.counts = counts        # (nlist_base,) int64 per-list counts
        self.centroids = centroids  # (n_lists, D) f32
        self.n_lists = int(n_lists)
        self.n_items = int(n_items)


class IVFFlatIndex:
    """Device-staged IVF-Flat index (one mesh's layout of a PackedIVF)."""

    __slots__ = (
        "list_data", "list_norm", "counts", "centroids", "c_norm",
        "ids", "n_items", "n_lists", "nlist_pad", "l_pad", "dim",
    )

    def __init__(
        self, list_data, list_norm, counts, centroids, c_norm, ids,
        n_items, n_lists, nlist_pad, l_pad, dim,
    ):
        self.list_data = list_data  # (nlist_pad, L_pad, D) sharded
        self.list_norm = list_norm  # (nlist_pad, L_pad) sharded ||x||^2
        self.counts = counts        # (nlist_pad,) int32 sharded
        self.centroids = centroids  # (nlist_pad, D) replicated
        self.c_norm = c_norm        # (nlist_pad,) replicated, inf pad rows
        self.ids = ids              # (nlist_pad * L_pad,) int64 HOST, -1 pads
        self.n_items = n_items
        self.n_lists = n_lists
        self.nlist_pad = nlist_pad
        self.l_pad = l_pad
        self.dim = dim

    def device_bytes(self) -> int:
        """Global device-resident footprint of the staged index (logical
        bytes across all shards; ids stay host-side) — the numerator of the
        benchmark's index_bytes_per_item column, where the flat-vs-PQ
        compression headline is measured."""
        return int(
            self.list_data.nbytes + self.list_norm.nbytes
            + self.counts.nbytes + self.centroids.nbytes + self.c_norm.nbytes
        )


def train_coarse_quantizer(
    items: np.ndarray,
    n_clusters: int,
    seed: int,
    max_train_rows: int = _TRAIN_CAP,
    max_iter: int = 25,
    tol: float = 1e-4,
    phase: str = "ann.train",
) -> np.ndarray:
    """Train an (n_clusters, D) quantizer with the EXISTING kmeans engine on
    a SINGLE-device submesh over a deterministic seed-keyed sample (the
    FAISS convention — IVF quantizers train on a sample anyway, and a
    multi-shard psum would tie the centroid bits to the mesh size).  The
    result is therefore mesh-independent data.  Shared by the IVF coarse
    quantizer and the PQ per-subspace codebooks (ann/pq.py)."""
    from ..ops.kmeans import lloyd_iterations, scalable_kmeans_pp_init

    items = np.ascontiguousarray(np.asarray(items), dtype=np.float32)
    n = items.shape[0]
    n_clusters = int(max(1, min(n_clusters, n)))
    seed = int(seed) & 0x7FFFFFFF
    with profiling.phase(phase):
        mesh1 = get_mesh(1)
        rng = np.random.default_rng(seed)
        if n > max_train_rows:
            sel = np.sort(rng.choice(n, size=max_train_rows, replace=False))
            train = items[sel]
        else:
            train = items
        Xd = jax.device_put(train, data_sharding(mesh1))
        wd = jax.device_put(
            np.ones(train.shape[0], np.float32), data_sharding(mesh1)
        )
        round_size = max(1, min(2 * n_clusters, train.shape[0]))
        centers0 = scalable_kmeans_pp_init(
            Xd, wd, n_clusters, seed, 2.0, rounds=4, round_size=round_size
        )
        centers, _, _ = lloyd_iterations(
            Xd, wd, centers0, mesh1, max_iter, float(tol),
            min(32768, train.shape[0]),
        )
        return np.asarray(jax.device_get(centers), np.float32)


def assign_nearest(
    items: np.ndarray,
    centroids: np.ndarray,
    phase: str = "ann.assign",
    counter: str = "ann.assign_blocks",
) -> np.ndarray:
    """Nearest-centroid id per row via the fused distance+argmin kernel in
    pow2 row blocks through the AOT executable cache, ONE batched fetch.
    Per-row math with no cross-row reduction — assignments are bitwise
    mesh-independent.  Shared by IVF list assignment and PQ subspace
    encoding (same executable when shapes agree)."""
    items = np.ascontiguousarray(np.asarray(items), dtype=np.float32)
    n, d = items.shape
    with profiling.phase(phase):
        cdev = jnp.asarray(centroids)
        block = shape_bucket(min(n, _ASSIGN_BLOCK), lo=256)
        handles = []
        for start in range(0, n, block):
            stop = min(start + block, n)
            xb = items[start:stop]
            if xb.shape[0] != block:
                xb = np.concatenate(
                    [xb, np.zeros((block - xb.shape[0], d), np.float32)]
                )
            handles.append(
                cached_kernel(
                    "ann_assign", _assign_block_kernel, jnp.asarray(xb), cdev
                )
            )
        # ONE batched fetch for every dispatched block (per-block asarray
        # would pay a host round-trip apiece)
        fetched = jax.device_get(handles)
        assign = np.concatenate([np.asarray(a) for a in fetched])[:n]
        profiling.incr_counter(counter, len(handles))
        return assign.astype(np.int64)


def build_ivfflat_packed(
    items,
    item_ids: np.ndarray,
    n_lists: int,
    seed: int = 0,
    max_train_rows: int = _TRAIN_CAP,
    max_iter: int = 25,
    tol: float = 1e-4,
) -> PackedIVF:
    """Train the coarse quantizer and pack the inverted lists.

    Every step is mesh-independent by construction: the kmeans engine runs
    on a single-device submesh over a deterministic sample
    (train_coarse_quantizer), assignment is per-row argmin with no
    cross-row reduction (assign_nearest), and the layout is a stable host
    sort.  The same PackedIVF therefore stages bitwise-identically on any
    mesh."""
    items = np.ascontiguousarray(np.asarray(items), dtype=np.float32)
    n, _d = items.shape
    if n == 0:
        raise ValueError("cannot build an IVF-Flat index over 0 items")
    n_lists = int(max(1, min(n_lists, n)))
    centroids = train_coarse_quantizer(
        items, n_lists, seed, max_train_rows, max_iter, tol
    )
    assign = assign_nearest(items, centroids)

    with profiling.phase("ann.layout"):
        nlist_base = -(-n_lists // _LIST_ALIGN) * _LIST_ALIGN
        counts = np.bincount(assign, minlength=nlist_base).astype(np.int64)
        order = np.argsort(assign, kind="stable")
    return PackedIVF(
        items[order],
        np.asarray(item_ids, np.int64)[order],
        counts,
        centroids,
        n_lists,
        n,
    )


def item_norms(data: np.ndarray) -> np.ndarray:
    """||x||^2 per padded row, host-computed in f64 and stored f32: the
    norms are index DATA (the same bits on every mesh — and across the
    live-mutation restages of ann/mutable.py), not per-search math."""
    return np.einsum(
        "nd,nd->n", data.astype(np.float64), data.astype(np.float64)
    ).astype(np.float32)


def padded_host_layout(packed: PackedIVF, mesh: Mesh, l_pad: int = None):
    """Expand a PackedIVF into the padded HOST layout this mesh stages:
    lists padded to the pow2 slot bucket of the LONGEST list (one static
    geometry for the whole index — rebuilds at nearby sizes reuse compiled
    kernels), the list axis padded to a multiple of lcm(8, n_dev) with
    empty lists.  Returns (data (nlist_pad*l_pad, D), x_norm, ids_pad,
    counts int64, cpad, c_norm, nlist_pad, l_pad).  `l_pad` may be forced
    UP (the mutable index's repack-with-headroom path); forcing it below
    the longest list raises.  Shared by index_from_packed and the live
    mutation tier (ann/mutable.py), so the two can never disagree on the
    geometry a probe kernel sees."""
    n_dev = mesh.shape[DATA_AXIS]
    mult = math.lcm(_LIST_ALIGN, n_dev)
    nlist_pad = -(-max(packed.n_lists, 1) // mult) * mult
    counts = np.zeros(nlist_pad, np.int64)
    counts[: packed.counts.shape[0]] = packed.counts
    l_need = shape_bucket(int(max(counts.max(), 1)), lo=_MIN_LIST_SLOTS)
    if l_pad is None:
        l_pad = l_need
    elif l_pad < l_need:
        raise ValueError(
            f"l_pad={l_pad} cannot hold the longest list ({counts.max()} "
            f"items needs {l_need} slots)"
        )
    if nlist_pad * l_pad > int(_POS_SENTINEL):
        raise ValueError(
            f"IVF layout overflows int32 positions: {nlist_pad} lists x "
            f"{l_pad} slots; raise nlist so lists shrink"
        )
    d = packed.items.shape[1]
    offs = np.zeros(nlist_pad + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    row_list = np.repeat(np.arange(nlist_pad, dtype=np.int64), counts)
    slot = np.arange(packed.items.shape[0], dtype=np.int64) - offs[row_list]
    flat = row_list * l_pad + slot
    data = np.zeros((nlist_pad * l_pad, d), np.float32)
    data[flat] = packed.items
    ids_pad = np.full(nlist_pad * l_pad, -1, np.int64)
    ids_pad[flat] = packed.ids
    cpad = np.zeros((nlist_pad, d), np.float32)
    cpad[: packed.n_lists] = packed.centroids
    c_norm = np.einsum(
        "nd,nd->n", cpad.astype(np.float64), cpad.astype(np.float64)
    ).astype(np.float32)
    c_norm[packed.n_lists :] = np.inf  # pad lists never win a probe slot
    x_norm = item_norms(data)
    return data, x_norm, ids_pad, counts, cpad, c_norm, nlist_pad, l_pad


def stage_padded_layout(
    data: np.ndarray,
    x_norm: np.ndarray,
    ids_pad: np.ndarray,
    counts: np.ndarray,
    cpad: np.ndarray,
    c_norm: np.ndarray,
    nlist_pad: int,
    l_pad: int,
    n_items: int,
    n_lists: int,
    mesh: Mesh,
) -> IVFFlatIndex:
    """device_put a padded host layout as this mesh's IVFFlatIndex (the
    staging half of index_from_packed, reused verbatim by every live
    mutation restage — a plain upload, never a compile)."""
    d = data.shape[1]
    with profiling.phase("ann.stage", bytes=int(data.nbytes)):
        index = IVFFlatIndex(
            list_data=jax.device_put(
                data.reshape(nlist_pad, l_pad, d), axis_sharding(mesh, 0, 3)
            ),
            list_norm=jax.device_put(
                x_norm.reshape(nlist_pad, l_pad), axis_sharding(mesh, 0, 2)
            ),
            counts=jax.device_put(counts.astype(np.int32), data_sharding(mesh)),
            centroids=jax.device_put(cpad, replicated_sharding(mesh)),
            c_norm=jax.device_put(c_norm, replicated_sharding(mesh)),
            ids=ids_pad,
            n_items=n_items,
            n_lists=n_lists,
            nlist_pad=nlist_pad,
            l_pad=l_pad,
            dim=d,
        )
    profiling.incr_counter("ann.stage_bytes", int(data.nbytes))
    return index


def index_from_packed(packed: PackedIVF, mesh: Mesh) -> IVFFlatIndex:
    """Expand a PackedIVF into this mesh's device layout (padded host
    layout + staging; user ids stay on the host in int64)."""
    data, x_norm, ids_pad, counts, cpad, c_norm, nlist_pad, l_pad = (
        padded_host_layout(packed, mesh)
    )
    return stage_padded_layout(
        data, x_norm, ids_pad, counts, cpad, c_norm, nlist_pad, l_pad,
        packed.n_items, packed.n_lists, mesh,
    )


class TieredIVFFlatIndex:
    """IVF-Flat index whose data/norm list planes live in a
    TieredListPlanes HBM pool (hot lists pinned, cold lists LRU-paged from
    the padded host layout).  Same search frame contract as IVFFlatIndex;
    paging is a residency change, never a math change.  The tier's host
    planes are VIEWS of the padded layout arrays, so a mutable holder that
    edits its mirrors in place only has to tier.refresh() the touched
    lists for resident copies to match (non-resident lists pick the edit
    up at their next page-in — the tombstone-interaction contract)."""

    __slots__ = (
        "tier", "counts", "centroids", "c_norm", "ids", "n_items",
        "n_lists", "nlist_pad", "l_pad", "dim", "hot_fraction",
    )

    def __init__(self, tier, counts, centroids, c_norm, ids, n_items,
                 n_lists, nlist_pad, l_pad, dim, hot_fraction):
        self.tier = tier            # TieredListPlanes over [data, norms]
        self.counts = counts
        self.centroids = centroids
        self.c_norm = c_norm
        self.ids = ids
        self.n_items = n_items
        self.n_lists = n_lists
        self.nlist_pad = nlist_pad
        self.l_pad = l_pad
        self.dim = dim
        self.hot_fraction = float(hot_fraction)

    def device_bytes(self) -> int:
        return int(
            self.tier.device_bytes() + self.counts.nbytes
            + self.centroids.nbytes + self.c_norm.nbytes
        )

    def host_bytes(self) -> int:
        return self.tier.host_bytes()


def tiered_stage_padded_layout(
    data: np.ndarray,
    x_norm: np.ndarray,
    ids_pad: np.ndarray,
    counts: np.ndarray,
    cpad: np.ndarray,
    c_norm: np.ndarray,
    nlist_pad: int,
    l_pad: int,
    n_items: int,
    n_lists: int,
    mesh: Mesh,
    hot_fraction: float,
    pool_slots: int = None,
) -> TieredIVFFlatIndex:
    """Stage a padded host layout with only `hot_fraction` of each shard's
    lists HBM-resident (stage_padded_layout's tiered twin).  The tier
    planes are reshaped VIEWS of `data`/`x_norm` — zero host copies, and
    in-place mutation of those arrays is visible to every later page-in."""
    from .tier import TieredListPlanes

    d = data.shape[1]
    tier = TieredListPlanes(
        planes=[
            data.reshape(nlist_pad, l_pad, d),
            x_norm.reshape(nlist_pad, l_pad),
        ],
        sentinels=[None, np.inf],
        counts=counts,
        mesh=mesh,
        hot_fraction=hot_fraction,
        pool_slots=pool_slots,
        name="ann.tier",
    )
    with profiling.phase("ann.stage", bytes=tier.device_bytes()):
        index = TieredIVFFlatIndex(
            tier=tier,
            counts=jax.device_put(counts.astype(np.int32), data_sharding(mesh)),
            centroids=jax.device_put(cpad, replicated_sharding(mesh)),
            c_norm=jax.device_put(c_norm, replicated_sharding(mesh)),
            ids=ids_pad,
            n_items=n_items,
            n_lists=n_lists,
            nlist_pad=nlist_pad,
            l_pad=l_pad,
            dim=d,
            hot_fraction=hot_fraction,
        )
    return index


def tiered_index_from_packed(
    packed: PackedIVF,
    mesh: Mesh,
    hot_fraction: float,
    pool_slots: int = None,
) -> TieredIVFFlatIndex:
    """index_from_packed's tiered twin: padded host layout + slot-pool
    staging at the given hot fraction."""
    data, x_norm, ids_pad, counts, cpad, c_norm, nlist_pad, l_pad = (
        padded_host_layout(packed, mesh)
    )
    return tiered_stage_padded_layout(
        data, x_norm, ids_pad, counts, cpad, c_norm, nlist_pad, l_pad,
        packed.n_items, packed.n_lists, mesh, hot_fraction, pool_slots,
    )


def _effective_nprobe(index: IVFFlatIndex, nprobe: int) -> int:
    return int(max(1, min(nprobe, index.nlist_pad)))


def _tiered_flat_probe_all(
    index: TieredIVFFlatIndex,
    q: np.ndarray,
    k: int,
    np_eff: int,
    mesh: Mesh,
    block: int,
    chunk: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Tiered flat probe sweep — the PQ pager's exact shape: selection
    kernel replays probe selection for the host, the planner splits each
    block into groups whose cold lists fit the pool, each group dispatches
    at the SAME block bucket with its queries at their ORIGINAL offsets
    (zeros elsewhere; every op is row-independent, so group rows carry
    bitwise the all-resident sweep's values).  One cached executable per
    shape — zero new compiles at steady state."""
    n = q.shape[0]
    out_d = np.empty((n, k), np.float32)
    out_p = np.empty((n, k), np.int32)
    # Pass 1: dispatch every block's selection kernel, then ONE batched
    # device_get — the planner needs host probes, but not one sync per block.
    blocks = []
    sel = []
    for start in range(0, n, block):
        n_q = min(block, n - start)
        qb = np.zeros((block, index.dim), np.float32)
        qb[:n_q] = q[start : start + n_q]
        blocks.append((start, n_q, qb))
        sel.append(
            cached_kernel(
                "ann_select", ivf_select_kernel,
                index.centroids, index.c_norm, jnp.asarray(qb),
                mesh=mesh, nprobe=np_eff,
            )
        )
    # Pass 2: plan/page/dispatch per group, deferring the result fetch to
    # ONE device_get — tier buffers are immutably replaced on slot writes,
    # so earlier results stay valid on their old buffers.
    spans = []
    parts = []
    for (start, n_q, qb), probes in zip(blocks, jax.device_get(sel)):
        for s, e in index.tier.plan_groups(probes[:n_q]):
            planes, slot_map = index.tier.acquire(probes[s:e].ravel())
            gq = np.zeros((block, index.dim), np.float32)
            gq[s:e] = qb[s:e]
            spans.append((start, s, e))
            parts.append(
                cached_kernel(
                    "ann_probe_tiered", ivf_probe_tiered_kernel,
                    planes[0], planes[1], slot_map, index.counts,
                    index.centroids, index.c_norm, jnp.asarray(gq),
                    mesh=mesh, k=k, nprobe=np_eff, chunk=chunk,
                )
            )
    for (start, s, e), (d_host, p_host) in zip(spans, jax.device_get(parts)):
        out_d[start + s : start + e] = d_host[s:e]
        out_p[start + s : start + e] = p_host[s:e]
    return out_d, out_p


def ivfflat_search_prepared(
    index: IVFFlatIndex,
    queries,
    k: int,
    nprobe: int,
    mesh: Mesh,
    query_block: int = 8192,
    dtype=np.float32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Probed search of `queries` against a staged index: returns
    (distances (Q, k_eff) ascending euclidean, ids (Q, k_eff) int64, -1 in
    unfillable slots), k_eff = min(k, n_items).  Query blocks are pow2
    buckets driven through the kNN engine's dispatch/collect pipeline;
    every kernel dispatch rides the AOT executable cache — a repeat search
    at a seen geometry performs zero new compilations."""
    from ..ops.knn import _PIPELINE_WINDOW, _query_block_bucket, _run_block_pipeline

    if isinstance(queries, jax.Array):
        q = queries if queries.dtype == dtype else queries.astype(dtype)
    else:
        q = np.asarray(queries, dtype=dtype)
    if q.ndim != 2 or q.shape[1] != index.dim:
        raise ValueError(
            f"queries must be (n, {index.dim}); got {q.shape}"
        )
    k_eff = min(k, index.n_items)
    if q.shape[0] == 0:
        return (
            np.zeros((0, k_eff), dtype=dtype),
            np.zeros((0, k_eff), dtype=np.int64),
        )
    np_eff = _effective_nprobe(index, nprobe)
    block = _query_block_bucket(q.shape[0], query_block)
    chunk = _probe_chunk(block, np_eff, index.l_pad, index.dim)
    if isinstance(index, TieredIVFFlatIndex):
        d_all, p_all = _tiered_flat_probe_all(
            index, np.asarray(q, dtype=dtype), k, np_eff, mesh, block, chunk
        )
        profiling.incr_counter("ann.searches")
        with profiling.phase("ann.merge"):
            ids_all = index.ids[np.minimum(p_all, index.ids.size - 1)]
            ids_all[np.isinf(d_all)] = -1
            return d_all[:, :k_eff], ids_all[:, :k_eff]
    starts = list(range(0, q.shape[0], block))
    pending: list = []
    out_d, out_i = [], []

    def _dispatch(bi):
        start = starts[bi]
        qb = q[start : start + block]
        n_q = qb.shape[0]
        if n_q != block:
            if isinstance(qb, jax.Array):
                qb = jnp.pad(qb, ((0, block - n_q), (0, 0)))
            else:
                qb = np.concatenate(
                    [qb, np.zeros((block - n_q, q.shape[1]), dtype=dtype)]
                )
        d, pos = cached_kernel(
            "ann_probe", ivf_probe_kernel,
            index.list_data, index.list_norm, index.counts,
            index.centroids, index.c_norm, jnp.asarray(qb),
            mesh=mesh, k=k, nprobe=np_eff, chunk=chunk,
        )
        for h in (d, pos):
            try:
                h.copy_to_host_async()
            except (AttributeError, RuntimeError):
                break
        pending.append((d, pos, n_q))

    def _collect(bi):
        d, pos, n_q = pending.pop(0)
        d_host, pos_host = jax.device_get((d, pos))
        d_host = d_host[:n_q]
        # sentinel positions index past the id table; clamp then overwrite
        # via the inf-distance mask (same -1 contract as the exact engine)
        ids_host = index.ids[
            np.minimum(pos_host[:n_q], index.ids.size - 1)
        ]
        ids_host[np.isinf(d_host)] = -1
        out_d.append(d_host)
        out_i.append(ids_host)

    _run_block_pipeline(
        len(starts), _dispatch, _collect, _PIPELINE_WINDOW,
        phase_prefix="ann",
    )
    profiling.incr_counter("ann.searches")
    with profiling.phase("ann.merge"):
        return (
            np.concatenate(out_d)[:, :k_eff],
            np.concatenate(out_i)[:, :k_eff],
        )


def warm_probe_kernels(
    index: IVFFlatIndex,
    k: int,
    nprobe: int,
    mesh: Mesh,
    n_queries: int = None,
    query_block: int = 8192,
    dtype=np.float32,
) -> list:
    """Submit the AOT compilation the next same-shape probed search will
    dispatch (key derived by the SAME kernel_cache_key the dispatch path
    uses, so the first dispatch lands on the warmed executable).  Returns
    the submitted keys — the serving entry's warm hook."""
    from ..ops.knn import _query_block_bucket
    from ..ops.precompile import aval, global_precompiler

    np_eff = _effective_nprobe(index, nprobe)
    block = _query_block_bucket(n_queries or query_block, query_block)
    chunk = _probe_chunk(block, np_eff, index.l_pad, index.dim)
    q_aval = aval((block, index.dim), dtype)
    statics = dict(k=k, nprobe=np_eff, chunk=chunk)
    if isinstance(index, TieredIVFFlatIndex):
        planes, slot_map = index.tier.snapshot()
        args = (
            planes[0], planes[1], slot_map, index.counts,
            index.centroids, index.c_norm, q_aval,
        )
        key = kernel_cache_key("ann_probe_tiered", args, mesh, statics)
        global_precompiler().submit(
            key, ivf_probe_tiered_kernel, *args, mesh=mesh, **statics
        )
        sel_args = (index.centroids, index.c_norm, q_aval)
        sel_statics = dict(nprobe=np_eff)
        sel_key = kernel_cache_key("ann_select", sel_args, mesh, sel_statics)
        global_precompiler().submit(
            sel_key, ivf_select_kernel, *sel_args, mesh=mesh, **sel_statics
        )
        return [key, sel_key]
    args = (
        index.list_data, index.list_norm, index.counts,
        index.centroids, index.c_norm, q_aval,
    )
    key = kernel_cache_key("ann_probe", args, mesh, statics)
    global_precompiler().submit(
        key, ivf_probe_kernel, *args, mesh=mesh, **statics
    )
    return [key]


def recall_at_k(approx_ids, exact_ids) -> float:
    """Mean fraction of each row's exact k-nearest ids recovered by the
    probed result — the gate every probed result set is scored with
    (tests/test_ann_engine.py, tests/test_pq_engine.py).  The -1
    unfillable sentinel never counts as a hit."""
    a = np.asarray(approx_ids)
    e = np.asarray(exact_ids)
    if a.shape[0] != e.shape[0]:
        raise ValueError(
            f"row mismatch: {a.shape[0]} approx vs {e.shape[0]} exact"
        )
    if e.size == 0:
        return 1.0
    hits = 0
    for ar, er in zip(a, e):
        hits += np.intersect1d(ar[ar >= 0], er).size
    return hits / float(e.shape[0] * e.shape[1])
