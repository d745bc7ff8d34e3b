#
# IVF-PQ: residual product quantization on top of the IVF machinery —
# the ~32x-compressed 100M+-item tier of the ANN subsystem.
#
# IVF-Flat (ivfflat.py) stores raw f32 vectors, so device memory caps the
# index around ~10M items at embedding dims.  This tier stores each item as
# m_sub one-byte codes plus one f32 correction scalar (FAISS IVFPQ, Jegou
# et al. "Product quantization for nearest neighbor search"; cuML
# algorithm='ivfpq'):
#
#   build:  the coarse quantizer and list assignment are the SHARED IVF
#           helpers (train_coarse_quantizer / assign_nearest — the kmeans
#           engine + the fused distance+argmin kernel).  Residuals
#           r = x - centroid[assign] are split into m_sub subspaces
#           (feature dim zero-padded to m_sub * dsub, dsub a pow2), each
#           subspace gets its own ksub=2^n_bits-centroid codebook trained
#           with the SAME kmeans engine (single-device submesh, FAISS
#           training-sample cap), and encoding is the SAME fused
#           distance+argmin kernel per subspace.  The packed payload
#           (codes + per-item ADC scalars + list layout) is
#           mesh-independent, exactly like PackedIVF.
#   search: asymmetric distance computation (ADC).  With r^ the item's
#           reconstructed residual (disjoint subspace codewords),
#
#             d2(q, item) = ||q - centroid_l - r^||^2
#                         = ||q - centroid_l||^2            (probe term)
#                         + sum_j  -2 q_j . cb[j, code_j]   (query table)
#                         + (||r^||^2 + 2 centroid_l . r^)  (item scalar)
#
#           The probe term falls out of probe selection (select_probes
#           already computes every query->centroid distance), the item
#           scalar is packed per item at build time, and the query table
#           T (m_sub, ksub) is computed ONCE per query block and stays
#           VMEM-resident while the int8 codes of the probed lists stream
#           through the LUT-accumulation kernel (ops/pallas_pq — MXU-free,
#           and the per-item HBM traffic is m_sub bytes instead of
#           IVF-Flat's 4*D: the scan is bandwidth-optimal by layout).
#           Selection and the cross-shard merge are REUSED VERBATIM from
#           the flat kernel (lexicographic (d2, pos) total order +
#           merge_shard_topk), so probed PQ results are bitwise identical
#           on 1-device and 8-device meshes, same contract, same gate.
#   refine: ADC distances are quantized approximations; recall is
#           recovered by probing top (k * refine_ratio) candidates and
#           re-scoring them against the f32 vectors the exactSearch
#           fallback already keeps HOST-side (the expanded-form f32
#           formulation the exact engine uses).  The device index stays
#           codes-only — compression is a device-memory claim; the f32
#           payload lives in host RAM with the model.
#

from __future__ import annotations

import math
import os
from functools import partial
from typing import List, Optional, Sequence, Tuple

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
    replicated_sharding,
)
from ..ops.pallas_pq import fastscan_lut_accumulate, lut_accumulate, pack_codes4
from ..ops.precompile import cached_kernel, kernel_cache_key, shape_bucket
from .tier import TieredListPlanes
from .ivfflat import (
    _LIST_ALIGN,
    _MIN_LIST_SLOTS,
    _POS_SENTINEL,
    _TRAIN_CAP,
    _lex_topk,
    _probe_tile_budget,
    assign_nearest,
    ivf_select_kernel,
    merge_shard_topk,
    select_probes,
    train_coarse_quantizer,
)

# ADC re-score chunk budget: bytes of gathered (q_chunk, R, D) f32
# candidates the host refine materializes at once
_REFINE_BUDGET = 256 << 20
# subspace-seed stride: each codebook trains with its own deterministic
# seed so subspaces do not share init draws
_SUBSPACE_SEED_STRIDE = 0x51F1_5EED
# OPQ training sample cap (FAISS-style) and alternation count: rotation
# quality saturates after a handful of assign/encode/Procrustes rounds
_OPQ_TRAIN_CAP = 65536
_OPQ_ITERS = 4
_OPQ_KMEANS_ITERS = 8

DEFAULT_N_BITS = 8
DEFAULT_REFINE_RATIO = 4


def pq_fastscan(n_bits: int, m_sub: int) -> bool:
    """ONE fast-scan route derivation shared by the build validator, the
    stager, dispatch, and warm (the knn _fused_epilogue_route discipline:
    the flag picks the staged code layout AND is a cache-key static, so
    every consumer must derive it identically — here they all read the
    staged index's `fastscan` attribute, which this function set).  n_bits=4
    packs two codes per byte and scans through the 16-lane LUT kernel;
    every other width stays on the one-byte-per-code scan.  An ODD m_sub
    cannot pack two codes per byte, so it stays on the unpacked route too
    (the ops-layer packer pack_codes4 raises the typed error on odd
    widths — this derivation keeps such payloads from ever reaching it).
    Escape hatch: SRML_PQ_FASTSCAN=0 keeps n_bits=4 on the unpacked route
    (read at STAGING, like the fused-epilogue escape)."""
    if int(n_bits) != 4 or int(m_sub) % 2:
        return False
    return os.environ.get("SRML_PQ_FASTSCAN", "1") != "0"


def default_m_sub(dim: int) -> int:
    """Subspace count: the largest power of two <= dim/8 clamped to
    [4, 64] (and never above dim) — ~8 feature dims per one-byte code,
    the 32x-compression operating point at embedding dims (documented
    with the measured recall table in docs/ann_engine.md)."""
    target = max(4, dim // 8)
    m = 1 << (target.bit_length() - 1)
    return int(max(1, min(64, m, dim)))


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pq_geometry(dim: int, m_sub: int) -> Tuple[int, int, int]:
    """(m_sub, dsub, d_pad): subspace width is the pow2 bucket of
    ceil(dim / m_sub) and the feature axis zero-pads to m_sub * dsub —
    pow2-padded subspaces keep every per-subspace kernel at one static
    lane-aligned geometry."""
    m_sub = int(max(1, min(m_sub, dim)))
    dsub = _pow2_ceil(-(-dim // m_sub))
    return m_sub, dsub, m_sub * dsub


def _pad_features(x: np.ndarray, d_pad: int) -> np.ndarray:
    if x.shape[1] == d_pad:
        return x
    out = np.zeros((x.shape[0], d_pad), np.float32)
    out[:, : x.shape[1]] = x
    return out


class PackedPQ:
    """Host-side, mesh-INDEPENDENT IVF-PQ payload: per-item codes + ADC
    scalars sorted by list (stable, the SAME layout rule as PackedIVF),
    per-list counts, the coarse centroids, and the subspace codebooks.
    This is what the model persists through the core npz path;
    index_from_packed_pq expands it per mesh."""

    __slots__ = (
        "codes", "scalars", "ids", "items", "counts", "centroids",
        "codebooks", "n_lists", "n_items", "dim", "m_sub", "n_bits",
        "rotation",
    )

    def __init__(
        self, codes, scalars, ids, items, counts, centroids, codebooks,
        n_lists, n_items, dim, m_sub, n_bits, rotation=None,
    ):
        self.codes = codes          # (N, m_sub) uint8, list-sorted
        self.scalars = scalars      # (N,) f32 ADC item scalars, list-sorted
        self.ids = ids              # (N,) int64 user ids, list-sorted
        self.items = items          # (N, dim) f32 list-sorted — HOST-side
        #                             refine/exactSearch payload, never staged
        self.counts = counts        # (nlist_base,) int64 per-list counts
        self.centroids = centroids  # (n_lists, dim) f32 coarse quantizer
        self.codebooks = codebooks  # (m_sub, ksub, dsub) f32
        self.n_lists = int(n_lists)
        self.n_items = int(n_items)
        self.dim = int(dim)
        self.m_sub = int(m_sub)
        self.n_bits = int(n_bits)
        # optional OPQ rotation (d_pad, d_pad) f32 orthogonal, applied to
        # RESIDUALS (r^ = r @ R.T); None = identity (wire back-compat: the
        # srml-pq payload simply omits the R entry)
        self.rotation = rotation


def reconstruct(packed: PackedPQ, rows: Optional[np.ndarray] = None) -> np.ndarray:
    """Decode rows back to (approximate) vectors: coarse centroid + the
    subspace codewords, truncated to the true feature dim.  The encode/
    decode round-trip oracle in tests/test_pq_engine.py rides this."""
    m_sub, dsub, d_pad = pq_geometry(packed.dim, packed.m_sub)
    if rows is None:
        rows = np.arange(packed.codes.shape[0])
    codes = packed.codes[rows].astype(np.int64)
    rec = np.zeros((codes.shape[0], d_pad), np.float32)
    for j in range(m_sub):
        rec[:, j * dsub : (j + 1) * dsub] = packed.codebooks[j][codes[:, j]]
    if packed.rotation is not None:
        # codewords live in ROTATED residual space: un-rotate (R orthogonal,
        # so the inverse of r @ R.T is r^ @ R), host f64 once-rounded
        rec = (
            rec.astype(np.float64) @ packed.rotation.astype(np.float64)
        ).astype(np.float32)
    row_list = np.repeat(
        np.arange(packed.counts.shape[0]), packed.counts
    )[rows]
    cpad = _pad_features(packed.centroids, d_pad)
    return (rec + cpad[row_list])[:, : packed.dim]


def _train_opq_rotation(
    res: np.ndarray,
    dsub: int,
    ksub: int,
    seed: int,
    max_train_rows: int = _OPQ_TRAIN_CAP,
    opq_iters: int = _OPQ_ITERS,
) -> np.ndarray:
    """Learn the OPQ rotation R (d_pad x d_pad, orthogonal) over the coarse
    residuals: alternate (train per-subspace codebooks on the rotated
    sample with the SAME kmeans engine) / (encode with the SAME fused
    assign kernel) / (orthogonal Procrustes update), Ge et al. 2014.

    Procrustes step: minimizing ||X R^T - X^||_F over orthogonal R is
    maximizing tr(R M) with M = X^T X^, so with the SVD M = U S V^T the
    optimum is R = V U^T — host float64, deterministic (fixed sample, fixed
    subspace seeds), mesh-independent like every other trained bit.  The
    returned R is the ONE f32 rounding every consumer shares."""
    n, d_pad = res.shape
    m_sub = d_pad // dsub
    seed = int(seed) & 0x7FFFFFFF
    if n > max_train_rows:
        # deterministic sorted sample — the coarse trainer's sampling rule
        rng = np.random.default_rng(seed)
        sel = np.sort(rng.choice(n, size=max_train_rows, replace=False))
        res = res[sel]
    X = res.astype(np.float64)
    R = np.eye(d_pad)
    for it in range(int(opq_iters)):
        Xr = (X @ R.T).astype(np.float32)
        rec = np.zeros_like(X)
        for j in range(m_sub):
            sl = slice(j * dsub, (j + 1) * dsub)
            cb = train_coarse_quantizer(
                Xr[:, sl],
                ksub,
                (seed + _SUBSPACE_SEED_STRIDE * (m_sub * it + j + 1))
                & 0x7FFFFFFF,
                max_train_rows,
                _OPQ_KMEANS_ITERS,
                1e-3,
                phase="ann.opq_codebook",
            )
            cj = assign_nearest(
                Xr[:, sl], cb,
                phase="ann.opq_encode_block",
                counter="ann.opq_encode_blocks",
            )
            rec[:, sl] = cb[cj]
        M = X.T @ rec
        U, _s, Vh = np.linalg.svd(M)
        R = Vh.T @ U.T
    return R.astype(np.float32)


def build_ivfpq_packed(
    items,
    item_ids: np.ndarray,
    n_lists: int,
    m_sub: int,
    n_bits: int = DEFAULT_N_BITS,
    seed: int = 0,
    max_train_rows: int = _TRAIN_CAP,
    max_iter: int = 25,
    tol: float = 1e-4,
    opq: bool = False,
) -> PackedPQ:
    """Train the coarse quantizer + per-subspace codebooks and pack the
    code lists.  Mesh-independent by the same construction as the flat
    build: every kmeans runs on a single-device submesh over a
    deterministic sample, encoding is per-row argmin, the ADC scalars are
    host float64 math rounded once to f32 (index DATA, like c_norm), and
    the layout is a stable host sort."""
    items = np.ascontiguousarray(np.asarray(items), dtype=np.float32)
    n, d = items.shape
    if n == 0:
        raise ValueError("cannot build an IVF-PQ index over 0 items")
    if not 1 <= int(n_bits) <= 8:
        raise ValueError(f"n_bits must be in [1, 8]; got {n_bits}")
    n_lists = int(max(1, min(n_lists, n)))
    m_sub, dsub, d_pad = pq_geometry(d, m_sub)
    ksub = 1 << int(n_bits)
    seed = int(seed) & 0x7FFFFFFF

    centroids = train_coarse_quantizer(
        items, n_lists, seed, max_train_rows, max_iter, tol
    )
    assign = assign_nearest(items, centroids)

    with profiling.phase("ann.pq_train"):
        # residuals on the padded feature axis; pad dims are exactly zero,
        # so codebook centroids stay exactly zero there (means of zeros)
        cpad = _pad_features(centroids, d_pad)
        res = _pad_features(items, d_pad) - cpad[assign]
        rotation = None
        if opq:
            with profiling.phase("ann.opq_train"):
                rotation = _train_opq_rotation(res, dsub, ksub, seed)
            # codebooks/codes/scalars all live in ROTATED residual space
            # from here on; the stager rotates centroids and the search
            # path rotates queries to match
            res = (
                res.astype(np.float64)
                @ rotation.astype(np.float64).T
            ).astype(np.float32)
        codebooks = np.stack(
            [
                train_coarse_quantizer(
                    res[:, j * dsub : (j + 1) * dsub],
                    ksub,
                    (seed + _SUBSPACE_SEED_STRIDE * (j + 1)) & 0x7FFFFFFF,
                    max_train_rows,
                    max_iter,
                    tol,
                    phase="ann.pq_codebook",
                )
                for j in range(m_sub)
            ]
        )  # (m_sub, ksub_eff, dsub); ksub_eff = min(ksub, n)

    with profiling.phase("ann.pq_encode"):
        codes = np.empty((n, m_sub), np.uint8)
        for j in range(m_sub):
            cj = assign_nearest(
                res[:, j * dsub : (j + 1) * dsub],
                codebooks[j],
                phase="ann.pq_encode_block",
                counter="ann.pq_encode_blocks",
            )
            codes[:, j] = cj.astype(np.uint8)

    with profiling.phase("ann.pq_scalars"):
        # s_item = ||r^||^2 + 2 centroid . r^  in float64, stored f32:
        # mesh-independent index DATA (the same once-rounded contract as
        # the staged c_norm/x_norm).  Under OPQ both factors live in
        # rotated space: r^ is the rotated-residual reconstruction and the
        # centroid term uses c~ = c @ R.T — exactly the centroids the
        # stager puts on device, so the kernel's three ADC terms stay one
        # consistent decomposition of ||q~ - c~ - r^||^2.
        rec = np.zeros((n, d_pad), np.float64)
        idx = codes.astype(np.int64)
        for j in range(m_sub):
            rec[:, j * dsub : (j + 1) * dsub] = codebooks[j][idx[:, j]]
        cass = cpad[assign].astype(np.float64)
        if rotation is not None:
            cass = cass @ rotation.astype(np.float64).T
        scalars = (
            np.einsum("nd,nd->n", rec, rec)
            + 2.0 * np.einsum("nd,nd->n", cass, rec)
        ).astype(np.float32)

    with profiling.phase("ann.layout"):
        nlist_base = -(-n_lists // _LIST_ALIGN) * _LIST_ALIGN
        counts = np.bincount(assign, minlength=nlist_base).astype(np.int64)
        order = np.argsort(assign, kind="stable")
    return PackedPQ(
        codes[order],
        scalars[order],
        np.asarray(item_ids, np.int64)[order],
        items[order],
        counts,
        centroids,
        codebooks.astype(np.float32),
        n_lists,
        n,
        d,
        m_sub,
        n_bits,
        rotation=rotation,
    )


class IVFPQIndex:
    """Device-staged IVF-PQ index (one mesh's layout of a PackedPQ).  The
    device-resident per-item cost is m_sub bytes of codes + 4 bytes of ADC
    scalar — the compression headline device_bytes() measures."""

    __slots__ = (
        "codes", "scalars", "counts", "centroids", "c_norm", "codebooks",
        "ids", "rows", "n_items", "n_lists", "nlist_pad", "l_pad",
        "dim", "d_pad", "m_sub", "dsub", "ksub", "n_bits", "fastscan",
        "rotation",
    )

    def __init__(
        self, codes, scalars, counts, centroids, c_norm, codebooks, ids,
        rows, n_items, n_lists, nlist_pad, l_pad, dim, d_pad, m_sub, dsub,
        ksub, n_bits, fastscan=False, rotation=None,
    ):
        self.codes = codes          # (nlist_pad, L_pad, m_bytes) u8 sharded
        #                             m_bytes = m_sub//2 packed (fast-scan)
        #                             or m_sub one-byte codes
        self.scalars = scalars      # (nlist_pad, L_pad) f32 sharded
        self.counts = counts        # (nlist_pad,) int32 sharded
        self.centroids = centroids  # (nlist_pad, d_pad) f32 replicated
        self.c_norm = c_norm        # (nlist_pad,) f32 replicated, inf pads
        self.codebooks = codebooks  # (m_sub, ksub, dsub) f32 replicated
        self.ids = ids              # (nlist_pad * L_pad,) int64 HOST, -1 pads
        self.rows = rows            # (nlist_pad * L_pad,) int64 HOST packed
        #                             row per slot, -1 pads (the refine map)
        self.n_items = n_items
        self.n_lists = n_lists
        self.nlist_pad = nlist_pad
        self.l_pad = l_pad
        self.dim = dim
        self.d_pad = d_pad
        self.m_sub = m_sub
        self.dsub = dsub
        self.ksub = ksub
        self.n_bits = n_bits
        self.fastscan = bool(fastscan)  # staged-layout route flag: the ONE
        #                                 derivation dispatch/warm read
        self.rotation = rotation        # HOST (d_pad, d_pad) f32 OPQ R or
        #                                 None; queries rotate host-side

    def device_bytes(self) -> int:
        """Global device-resident footprint (logical bytes across shards;
        ids/rows and the refine f32 payload stay host-side)."""
        return int(
            self.codes.nbytes + self.scalars.nbytes + self.counts.nbytes
            + self.centroids.nbytes + self.c_norm.nbytes
            + self.codebooks.nbytes
        )


def _pq_host_layout(packed: PackedPQ, mesh: Mesh) -> dict:
    """The mesh's padded HOST layout of a PackedPQ — the SAME pow2 bucket
    geometry as the flat index (L_pad = pow2 of the longest list, nlist_pad
    a multiple of lcm(8, n_dev), int32 position overflow guard) — shared by
    the all-resident and tiered stagers.  Fast-scan (n_bits=4) packs two
    codes per byte HERE, and OPQ rotates the coarse centroids HERE
    (c~ = c @ R.T, host f64 once-rounded): downstream of this layout the
    whole device side lives in rotated/packed space and the probe kernel's
    gathers/einsums never know the difference."""
    m_sub, dsub, d_pad = pq_geometry(packed.dim, packed.m_sub)
    ksub = packed.codebooks.shape[1]
    fastscan = pq_fastscan(packed.n_bits, m_sub)
    n_dev = mesh.shape[DATA_AXIS]
    mult = math.lcm(_LIST_ALIGN, n_dev)
    nlist_pad = -(-max(packed.n_lists, 1) // mult) * mult
    counts = np.zeros(nlist_pad, np.int64)
    counts[: packed.counts.shape[0]] = packed.counts
    l_pad = shape_bucket(int(max(counts.max(), 1)), lo=_MIN_LIST_SLOTS)
    if nlist_pad * l_pad > int(_POS_SENTINEL):
        raise ValueError(
            f"IVF-PQ layout overflows int32 positions: {nlist_pad} lists x "
            f"{l_pad} slots; raise nlist so lists shrink"
        )
    n = packed.codes.shape[0]
    offs = np.zeros(nlist_pad + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    row_list = np.repeat(np.arange(nlist_pad, dtype=np.int64), counts)
    slot = np.arange(n, dtype=np.int64) - offs[row_list]
    flat = row_list * l_pad + slot
    src = pack_codes4(packed.codes) if fastscan else packed.codes
    m_bytes = src.shape[1]
    codes = np.zeros((nlist_pad * l_pad, m_bytes), np.uint8)
    codes[flat] = src
    scal = np.zeros(nlist_pad * l_pad, np.float32)
    scal[flat] = packed.scalars
    ids_pad = np.full(nlist_pad * l_pad, -1, np.int64)
    ids_pad[flat] = packed.ids
    rows_pad = np.full(nlist_pad * l_pad, -1, np.int64)
    rows_pad[flat] = np.arange(n, dtype=np.int64)
    cpad = np.zeros((nlist_pad, d_pad), np.float32)
    cpad[: packed.n_lists] = _pad_features(packed.centroids, d_pad)
    if packed.rotation is not None:
        cpad = (
            cpad.astype(np.float64)
            @ packed.rotation.astype(np.float64).T
        ).astype(np.float32)
    c_norm = np.einsum(
        "nd,nd->n", cpad.astype(np.float64), cpad.astype(np.float64)
    ).astype(np.float32)
    c_norm[packed.n_lists :] = np.inf  # pad lists never win a probe slot
    return dict(
        codes=codes.reshape(nlist_pad, l_pad, m_bytes),
        scalars=scal.reshape(nlist_pad, l_pad),
        counts=counts,
        ids=ids_pad,
        rows=rows_pad,
        cpad=cpad,
        c_norm=c_norm,
        nlist_pad=nlist_pad,
        l_pad=l_pad,
        m_sub=m_sub,
        dsub=dsub,
        d_pad=d_pad,
        ksub=ksub,
        fastscan=fastscan,
    )


def index_from_packed_pq(packed: PackedPQ, mesh: Mesh) -> IVFPQIndex:
    """Expand a PackedPQ into this mesh's ALL-RESIDENT device layout:
    (nlist_pad, L_pad, m_bytes) uint8 codes + (nlist_pad, L_pad) f32 ADC
    scalars row-sharded on the LIST axis instead of f32 vectors."""
    lay = _pq_host_layout(packed, mesh)
    stage_bytes = int(lay["codes"].nbytes + lay["scalars"].nbytes)
    with profiling.phase("ann.stage", bytes=stage_bytes):
        index = IVFPQIndex(
            codes=jax.device_put(lay["codes"], axis_sharding(mesh, 0, 3)),
            scalars=jax.device_put(
                lay["scalars"], axis_sharding(mesh, 0, 2)
            ),
            counts=jax.device_put(
                lay["counts"].astype(np.int32), data_sharding(mesh)
            ),
            centroids=jax.device_put(lay["cpad"], replicated_sharding(mesh)),
            c_norm=jax.device_put(lay["c_norm"], replicated_sharding(mesh)),
            codebooks=jax.device_put(
                np.ascontiguousarray(packed.codebooks, np.float32),
                replicated_sharding(mesh),
            ),
            ids=lay["ids"],
            rows=lay["rows"],
            n_items=packed.n_items,
            n_lists=packed.n_lists,
            nlist_pad=lay["nlist_pad"],
            l_pad=lay["l_pad"],
            dim=packed.dim,
            d_pad=lay["d_pad"],
            m_sub=lay["m_sub"],
            dsub=lay["dsub"],
            ksub=lay["ksub"],
            n_bits=packed.n_bits,
            fastscan=lay["fastscan"],
            rotation=packed.rotation,
        )
    profiling.incr_counter("ann.stage_bytes", stage_bytes)
    return index


class TieredIVFPQIndex:
    """IVF-PQ index whose codes/scalars list planes live in a
    TieredListPlanes HBM pool (hot lists pinned, cold lists LRU-paged from
    host RAM) — the billion-scale capacity mode.  The small replicated
    planes (centroids, c_norm, codebooks) and the sharded counts stay fully
    resident; ids/rows/refine payload were host-side already.  Same search
    frame contract as IVFPQIndex; paging is a residency change, never a
    math change (the tiered-vs-resident bitwise gate)."""

    __slots__ = (
        "tier", "counts", "centroids", "c_norm", "codebooks", "ids",
        "rows", "n_items", "n_lists", "nlist_pad", "l_pad", "dim",
        "d_pad", "m_sub", "dsub", "ksub", "n_bits", "fastscan",
        "rotation", "hot_fraction",
    )

    def __init__(self, tier, counts, centroids, c_norm, codebooks, ids,
                 rows, n_items, n_lists, nlist_pad, l_pad, dim, d_pad,
                 m_sub, dsub, ksub, n_bits, fastscan, rotation,
                 hot_fraction):
        self.tier = tier            # TieredListPlanes over [codes, scalars]
        self.counts = counts
        self.centroids = centroids
        self.c_norm = c_norm
        self.codebooks = codebooks
        self.ids = ids
        self.rows = rows
        self.n_items = n_items
        self.n_lists = n_lists
        self.nlist_pad = nlist_pad
        self.l_pad = l_pad
        self.dim = dim
        self.d_pad = d_pad
        self.m_sub = m_sub
        self.dsub = dsub
        self.ksub = ksub
        self.n_bits = n_bits
        self.fastscan = bool(fastscan)
        self.rotation = rotation
        self.hot_fraction = float(hot_fraction)

    def device_bytes(self) -> int:
        return int(
            self.tier.device_bytes() + self.counts.nbytes
            + self.centroids.nbytes + self.c_norm.nbytes
            + self.codebooks.nbytes
        )

    def host_bytes(self) -> int:
        """Host-RAM side of the tier split (the warm list planes; the
        refine f32 payload stays accounted with the model, as before)."""
        return self.tier.host_bytes()


def tiered_index_from_packed_pq(
    packed: PackedPQ,
    mesh: Mesh,
    hot_fraction: float,
    pool_slots: Optional[int] = None,
) -> TieredIVFPQIndex:
    """Stage a PackedPQ with only `hot_fraction` of each shard's lists
    HBM-resident; the rest stay in the host padded layout and page in
    on probe.  Scalars carry the +inf sentinel (slot 0), so a probed
    list that somehow is not resident scores +inf and drops out instead
    of corrupting results."""
    lay = _pq_host_layout(packed, mesh)
    tier = TieredListPlanes(
        planes=[lay["codes"], lay["scalars"]],
        sentinels=[None, np.inf],
        counts=lay["counts"],
        mesh=mesh,
        hot_fraction=hot_fraction,
        pool_slots=pool_slots,
        name="ann.tier",
    )
    with profiling.phase("ann.stage", bytes=tier.device_bytes()):
        index = TieredIVFPQIndex(
            tier=tier,
            counts=jax.device_put(
                lay["counts"].astype(np.int32), data_sharding(mesh)
            ),
            centroids=jax.device_put(lay["cpad"], replicated_sharding(mesh)),
            c_norm=jax.device_put(lay["c_norm"], replicated_sharding(mesh)),
            codebooks=jax.device_put(
                np.ascontiguousarray(packed.codebooks, np.float32),
                replicated_sharding(mesh),
            ),
            ids=lay["ids"],
            rows=lay["rows"],
            n_items=packed.n_items,
            n_lists=packed.n_lists,
            nlist_pad=lay["nlist_pad"],
            l_pad=lay["l_pad"],
            dim=packed.dim,
            d_pad=lay["d_pad"],
            m_sub=lay["m_sub"],
            dsub=lay["dsub"],
            ksub=lay["ksub"],
            n_bits=packed.n_bits,
            fastscan=lay["fastscan"],
            rotation=packed.rotation,
            hot_fraction=hot_fraction,
        )
    return index


def _pq_probe_chunk(block: int, nprobe: int, l_pad: int, m_sub: int) -> int:
    """Power-of-two query-chunk size whose gathered code tile + the LUT
    gather intermediate fit the shared probe tile budget
    (SRML_ANN_TILE_BUDGET).  `block` is a pow2 bucket, so the chunk always
    divides it — the scan needs no ragged tail."""
    per_row = max(nprobe * l_pad * (4 * m_sub + 8), 1)
    c = max(1, _probe_tile_budget() // per_row)
    c = 1 << (c.bit_length() - 1)
    return min(c, block)


@partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "chunk", "fastscan"))
def ivfpq_probe_kernel(
    codes: jax.Array,      # (nlist_pad, L_pad, m_bytes) u8 list-sharded
    scalars: jax.Array,    # (nlist_pad, L_pad) f32 list-sharded ADC scalars
    counts: jax.Array,     # (nlist_pad,) int32 list-sharded
    centroids: jax.Array,  # (nlist_pad, d_pad) replicated
    c_norm: jax.Array,     # (nlist_pad,) replicated, +inf pad rows
    codebooks: jax.Array,  # (m_sub, ksub, dsub) replicated
    queries: jax.Array,    # (Q, d_pad) replicated
    mesh: Mesh,
    k: int,
    nprobe: int,
    chunk: int,
    fastscan: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Probed IVF-PQ ADC search: (euclidean ADC distances (Q, k) ascending,
    positions (Q, k) into the padded list layout — the flat kernel's exact
    output contract, -1/inf sentinel mapping included).  Selection and the
    cross-shard merge are the flat kernel's own helpers, so the bitwise
    1-dev-vs-8-dev parity argument carries over verbatim: ADC terms reduce
    over fixed-shape tiles (m_sub-wide LUT rows, dsub-wide table einsum)
    identical on every mesh size, and every selection orders by the total
    (d2, pos) key.

    `fastscan` (cache-key static, set from the staged index's route flag)
    switches the LUT scan to the packed two-codes-per-byte kernel — the
    code tile is (.., m_sub//2) bytes, everything else is unchanged."""
    _nlist_pad, l_pad, m_bytes = codes.shape
    m_sub = codebooks.shape[0]
    ksub = codebooks.shape[1]
    dsub = codebooks.shape[2]
    scan = fastscan_lut_accumulate if fastscan else lut_accumulate

    def per_shard(cd_loc, sc_loc, cnt_loc, c, cn, cb, q):
        lps = cd_loc.shape[0]
        Q = q.shape[0]
        _qn, d2p, probes, lp, is_local = select_probes(
            q, c, cn, nprobe, lps, mesh
        )
        # the per-query ADC table T[q, j, c] = -2 q_j . cb[j, c] — computed
        # once per block on REPLICATED data, resident across the list scan
        tables = -2.0 * jnp.einsum(
            "qjd,jcd->qjc",
            q.reshape(Q, m_sub, dsub),
            cb,
            precision=jax.lax.Precision.HIGH,
            preferred_element_type=jnp.float32,
        )  # (Q, m_sub, ksub)
        slot = jnp.arange(l_pad, dtype=jnp.int32)

        def chunk_body(carry, i):
            d2p_c = jax.lax.dynamic_slice_in_dim(d2p, i * chunk, chunk)
            lp_c = jax.lax.dynamic_slice_in_dim(lp, i * chunk, chunk)
            loc_c = jax.lax.dynamic_slice_in_dim(is_local, i * chunk, chunk)
            pr_c = jax.lax.dynamic_slice_in_dim(probes, i * chunk, chunk)
            t_c = jax.lax.dynamic_slice_in_dim(tables, i * chunk, chunk)
            # gather the chunk's probed CODE lists from the resident shard:
            # (chunk, nprobe, L_pad, m_bytes) uint8 — m_sub bytes/item
            # (8-bit) or m_sub/2 (fast-scan), the whole bandwidth story
            ctile = jnp.take(cd_loc, lp_c, axis=0)
            stile = jnp.take(sc_loc, lp_c, axis=0)  # (chunk, nprobe, L_pad)
            acc = scan(
                t_c, ctile.reshape(chunk, nprobe * l_pad, m_bytes)
            ).reshape(chunk, nprobe, l_pad)
            # ADC distance: probe term + query-table term + item scalar,
            # fixed association order (parity: same shapes on every mesh)
            d2 = d2p_c[:, :, None] + (acc + stile)
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
            P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(), P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(codes, scalars, counts, centroids, c_norm, codebooks, queries)


# the tiered PQ pager reuses the flat engine's selection-only kernel (ONE
# select_probes replica, stated once) under its own cache name
ivfpq_select_kernel = ivf_select_kernel


@partial(jax.jit, static_argnames=("mesh", "k", "nprobe", "chunk", "fastscan"))
def ivfpq_probe_tiered_kernel(
    codes: jax.Array,      # (n_dev * slots_per_shard, L_pad, m_bytes) u8
    scalars: jax.Array,    # (n_dev * slots_per_shard, L_pad) f32
    list_slot: jax.Array,  # (nlist_pad,) int32 list->local-slot, 0 sentinel
    counts: jax.Array,     # (nlist_pad,) int32 list-sharded
    centroids: jax.Array,  # (nlist_pad, d_pad) replicated
    c_norm: jax.Array,     # (nlist_pad,) replicated, +inf pad rows
    codebooks: jax.Array,  # (m_sub, ksub, dsub) replicated
    queries: jax.Array,    # (Q, d_pad) replicated
    mesh: Mesh,
    k: int,
    nprobe: int,
    chunk: int,
    fastscan: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """The resident probe kernel's body plus ONE indirection: probed local
    list ids map through list_slot into the shard's slot pool before the
    codes/scalars gathers.  Positions stay GLOBAL (probe * L_pad + slot) so
    ids/rows/refine are untouched, and the gathered tiles hold byte-for-
    byte the values the resident kernel gathers (paged copies of the same
    host rows, same shapes, same reduction order) — which is the whole
    tiered-vs-resident bitwise parity argument.  A probed list whose slot
    is 0 reads the sentinel (+inf scalars) and drops out: residency bugs
    degrade recall, never corrupt."""
    _rows, l_pad, m_bytes = codes.shape
    m_sub = codebooks.shape[0]
    dsub = codebooks.shape[2]
    scan = fastscan_lut_accumulate if fastscan else lut_accumulate

    def per_shard(cd_loc, sc_loc, slot_loc, cnt_loc, c, cn, cb, q):
        lps = cnt_loc.shape[0]
        Q = q.shape[0]
        _qn, d2p, probes, lp, is_local = select_probes(
            q, c, cn, nprobe, lps, mesh
        )
        tables = -2.0 * jnp.einsum(
            "qjd,jcd->qjc",
            q.reshape(Q, m_sub, dsub),
            cb,
            precision=jax.lax.Precision.HIGH,
            preferred_element_type=jnp.float32,
        )
        slot = jnp.arange(l_pad, dtype=jnp.int32)

        def chunk_body(carry, i):
            d2p_c = jax.lax.dynamic_slice_in_dim(d2p, i * chunk, chunk)
            lp_c = jax.lax.dynamic_slice_in_dim(lp, i * chunk, chunk)
            loc_c = jax.lax.dynamic_slice_in_dim(is_local, i * chunk, chunk)
            pr_c = jax.lax.dynamic_slice_in_dim(probes, i * chunk, chunk)
            t_c = jax.lax.dynamic_slice_in_dim(tables, i * chunk, chunk)
            # THE tiered indirection: local list -> pool slot, then gather
            # from the slot pool instead of the full list plane
            ls_c = jnp.take(slot_loc, lp_c, axis=0)
            ctile = jnp.take(cd_loc, ls_c, axis=0)
            stile = jnp.take(sc_loc, ls_c, axis=0)
            acc = scan(
                t_c, ctile.reshape(chunk, nprobe * l_pad, m_bytes)
            ).reshape(chunk, nprobe, l_pad)
            d2 = d2p_c[:, :, None] + (acc + stile)
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
            P(), P(), P(), P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(codes, scalars, list_slot, counts, centroids, c_norm, codebooks,
      queries)


def _effective_nprobe(index: IVFPQIndex, nprobe: int) -> int:
    return int(max(1, min(nprobe, index.nlist_pad)))


def _probe_k(k_eff: int, refine_ratio: int, n_items: int) -> int:
    """Candidate count the probe kernel selects: k itself without refine,
    k * refine_ratio (clamped to the item count) with it.  Static — part
    of the kernel cache key, derived identically by warm and dispatch."""
    if refine_ratio <= 1:
        return k_eff
    return int(max(k_eff, min(k_eff * int(refine_ratio), n_items)))


def ivfpq_search_prepared(
    index: IVFPQIndex,
    queries,
    k: int,
    nprobe: int,
    mesh: Mesh,
    query_block: int = 8192,
    refine_items: Optional[np.ndarray] = None,
    refine_ratio: int = DEFAULT_REFINE_RATIO,
) -> Tuple[np.ndarray, np.ndarray]:
    """Probed ADC search + optional f32 refine: returns (distances
    (Q, k_eff) ascending euclidean, ids (Q, k_eff) int64, -1 unfillable),
    k_eff = min(k, n_items) — the flat engine's exact frame contract.

    With `refine_items` (the model's list-sorted f32 payload, the same
    array the exactSearch route scores), the kernel selects the top
    k * refine_ratio ADC candidates and the host re-scores them against
    the true vectors (expanded-form f32, lexicographic (d2, pos) ties) —
    deterministic given the probed candidates, which are themselves
    bitwise mesh-independent, so refined results inherit mesh parity.

    Query blocks ride the kNN engine's dispatch/collect pipeline and every
    kernel dispatch rides the AOT executable cache: repeat same-shape
    searches perform zero new compilations (refine adds none — it is host
    numpy)."""
    from ..ops.knn import _query_block_bucket

    q = np.asarray(queries, dtype=np.float32)
    if q.ndim != 2 or q.shape[1] != index.dim:
        raise ValueError(f"queries must be (n, {index.dim}); got {q.shape}")
    k_eff = min(k, index.n_items)
    if q.shape[0] == 0:
        return (
            np.zeros((0, k_eff), dtype=np.float32),
            np.zeros((0, k_eff), dtype=np.int64),
        )
    refine = refine_items is not None and int(refine_ratio) > 1
    kp = _probe_k(k_eff, int(refine_ratio) if refine else 1, index.n_items)
    np_eff = _effective_nprobe(index, nprobe)
    qp = _pad_features(q, index.d_pad)
    if index.rotation is not None:
        # OPQ: the device side lives in rotated space (rotated centroids,
        # rotated-residual codebooks) — rotate queries to match, host f64
        # once-rounded so every mesh sees the same f32 queries
        qp = (
            qp.astype(np.float64) @ index.rotation.astype(np.float64).T
        ).astype(np.float32)
    block = _query_block_bucket(q.shape[0], query_block)
    chunk = _pq_probe_chunk(block, np_eff, index.l_pad, index.m_sub)
    if isinstance(index, TieredIVFPQIndex):
        d_all, p_all = _tiered_probe_all(
            index, qp, kp, np_eff, mesh, block, chunk
        )
    else:
        d_all, p_all = _resident_probe_all(
            index, qp, kp, np_eff, mesh, block, chunk
        )
    profiling.incr_counter("ann.searches")
    if refine:
        with profiling.phase("ann.refine"):
            return _refine_host(
                index, refine_items, q, d_all, p_all, k_eff
            )
    with profiling.phase("ann.merge"):
        ids = index.ids[np.minimum(p_all, index.ids.size - 1)]
        ids[np.isinf(d_all)] = -1
        return d_all[:, :k_eff], ids[:, :k_eff]


def _resident_probe_all(
    index: IVFPQIndex,
    qp: np.ndarray,
    kp: int,
    np_eff: int,
    mesh: Mesh,
    block: int,
    chunk: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """All-resident probe sweep: query blocks ride the kNN engine's
    dispatch/collect pipeline, every dispatch rides the AOT cache."""
    from ..ops.knn import _PIPELINE_WINDOW, _run_block_pipeline

    n = qp.shape[0]
    starts = list(range(0, n, block))
    pending: list = []
    out_d, out_p = [], []

    def _dispatch(bi):
        start = starts[bi]
        qb = qp[start : start + block]
        n_q = qb.shape[0]
        if n_q != block:
            qb = np.concatenate(
                [qb, np.zeros((block - n_q, index.d_pad), np.float32)]
            )
        d, pos = cached_kernel(
            "ann_pq_probe", ivfpq_probe_kernel,
            index.codes, index.scalars, index.counts,
            index.centroids, index.c_norm, index.codebooks, jnp.asarray(qb),
            mesh=mesh, k=kp, nprobe=np_eff, chunk=chunk,
            fastscan=index.fastscan,
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
        out_d.append(d_host[:n_q])
        out_p.append(pos_host[:n_q])
    _run_block_pipeline(
        len(starts), _dispatch, _collect, _PIPELINE_WINDOW,
        phase_prefix="ann",
    )
    return np.concatenate(out_d), np.concatenate(out_p)


def _tiered_probe_all(
    index: TieredIVFPQIndex,
    qp: np.ndarray,
    kp: int,
    np_eff: int,
    mesh: Mesh,
    block: int,
    chunk: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Tiered probe sweep: per block, (1) the selection kernel replays
    probe selection so the host learns which lists each query touches,
    (2) the planner splits the block into contiguous query groups whose
    distinct cold lists fit the slot pool, (3) each group pages in and
    dispatches the tiered kernel AT THE SAME BLOCK BUCKET with the group's
    queries at their ORIGINAL row offsets (zeros elsewhere).  Every ADC/
    selection op is row-independent, so a row's outputs are bitwise what
    the one-dispatch all-resident sweep produces for that row — slicing
    out the group rows is exact, and every dispatch reuses the same cached
    executables (zero new compiles at steady state)."""
    n = qp.shape[0]
    out_d = np.empty((n, kp), np.float32)
    out_p = np.empty((n, kp), np.int32)
    # Pass 1: dispatch every block's selection kernel, then ONE batched
    # device_get — the planner needs host probes, but not one sync per block.
    blocks = []
    sel = []
    for start in range(0, n, block):
        n_q = min(block, n - start)
        qb = np.zeros((block, index.d_pad), np.float32)
        qb[:n_q] = qp[start : start + n_q]
        blocks.append((start, n_q, qb))
        sel.append(
            cached_kernel(
                "ann_pq_select", ivfpq_select_kernel,
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
            gq = np.zeros((block, index.d_pad), np.float32)
            gq[s:e] = qb[s:e]
            spans.append((start, s, e))
            parts.append(
                cached_kernel(
                    "ann_pq_probe_tiered", ivfpq_probe_tiered_kernel,
                    planes[0], planes[1], slot_map, index.counts,
                    index.centroids, index.c_norm, index.codebooks,
                    jnp.asarray(gq),
                    mesh=mesh, k=kp, nprobe=np_eff, chunk=chunk,
                    fastscan=index.fastscan,
                )
            )
    for (start, s, e), (d_host, p_host) in zip(spans, jax.device_get(parts)):
        out_d[start + s : start + e] = d_host[s:e]
        out_p[start + s : start + e] = p_host[s:e]
    return out_d, out_p


def _refine_host(
    index: IVFPQIndex,
    items: np.ndarray,      # (N, dim) f32 list-sorted (the packed payload)
    q: np.ndarray,          # (Q, dim) f32 queries, true feature width
    d_probe: np.ndarray,    # (Q, R) ADC distances (inf = invalid)
    pos: np.ndarray,        # (Q, R) padded-layout positions
    k_eff: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Re-score the probed ADC candidates against the f32 vectors: the
    expanded-form distance the exact engine uses (||q||^2 - 2 q.x +
    ||x||^2, f32), lexicographic (d2, pos) selection — the ONE tie
    contract.  Chunked over queries so the gathered (chunk, R, D)
    candidate tile stays inside a fixed byte budget."""
    Q, R = d_probe.shape
    rows = index.rows[np.minimum(pos, index.rows.size - 1)]
    invalid = np.isinf(d_probe) | (rows < 0)
    rows = np.where(invalid, 0, rows)
    qn = np.einsum("qd,qd->q", q, q, dtype=np.float32)
    q_chunk = max(1, _REFINE_BUDGET // max(R * index.dim * 4, 1))
    out_d = np.empty((Q, k_eff), np.float32)
    out_i = np.empty((Q, k_eff), np.int64)
    for s in range(0, Q, q_chunk):
        e = min(s + q_chunk, Q)
        cand = items[rows[s:e]]                      # (c, R, D) f32
        xn = np.einsum("crd,crd->cr", cand, cand, dtype=np.float32)
        cross = np.einsum("cd,crd->cr", q[s:e], cand, dtype=np.float32)
        d2 = qn[s:e, None] - 2.0 * cross + xn
        d2 = np.where(invalid[s:e], np.inf, d2)
        order = np.lexsort((pos[s:e], d2), axis=-1)[:, :k_eff]
        rsel = np.take_along_axis(d2, order, axis=1)
        psel = np.take_along_axis(pos[s:e], order, axis=1)
        ids = index.ids[np.minimum(psel, index.ids.size - 1)]
        ids[np.isinf(rsel)] = -1
        out_d[s:e] = np.sqrt(np.maximum(rsel, 0.0))
        out_i[s:e] = ids
    profiling.incr_counter("ann.refined_queries", int(Q))
    return out_d, out_i


def warm_pq_probe_kernels(
    index: IVFPQIndex,
    k: int,
    nprobe: int,
    mesh: Mesh,
    n_queries: int = None,
    query_block: int = 8192,
    refine: bool = True,
    refine_ratio: int = DEFAULT_REFINE_RATIO,
) -> list:
    """Submit the AOT compilation the next same-shape probed PQ search will
    dispatch — key derived by the SAME kernel_cache_key/_probe_k/_pq_probe_chunk
    the dispatch path uses, so the first dispatch lands on the warmed
    executable (the serving entry's warm hook, flat-warm contract)."""
    from ..ops.knn import _query_block_bucket
    from ..ops.precompile import aval, global_precompiler

    k_eff = min(k, index.n_items)
    kp = _probe_k(k_eff, int(refine_ratio) if refine else 1, index.n_items)
    np_eff = _effective_nprobe(index, nprobe)
    block = _query_block_bucket(n_queries or query_block, query_block)
    chunk = _pq_probe_chunk(block, np_eff, index.l_pad, index.m_sub)
    q_aval = aval((block, index.d_pad), np.float32)
    statics = dict(k=kp, nprobe=np_eff, chunk=chunk, fastscan=index.fastscan)
    keys = []
    if isinstance(index, TieredIVFPQIndex):
        planes, slot_map = index.tier.snapshot()
        args = (
            planes[0], planes[1], slot_map, index.counts,
            index.centroids, index.c_norm, index.codebooks, q_aval,
        )
        key = kernel_cache_key("ann_pq_probe_tiered", args, mesh, statics)
        global_precompiler().submit(
            key, ivfpq_probe_tiered_kernel, *args, mesh=mesh, **statics
        )
        keys.append(key)
        sel_args = (index.centroids, index.c_norm, q_aval)
        sel_statics = dict(nprobe=np_eff)
        sel_key = kernel_cache_key(
            "ann_pq_select", sel_args, mesh, sel_statics
        )
        global_precompiler().submit(
            sel_key, ivfpq_select_kernel, *sel_args,
            mesh=mesh, **sel_statics,
        )
        keys.append(sel_key)
        return keys
    args = (
        index.codes, index.scalars, index.counts,
        index.centroids, index.c_norm, index.codebooks, q_aval,
    )
    key = kernel_cache_key("ann_pq_probe", args, mesh, statics)
    global_precompiler().submit(
        key, ivfpq_probe_kernel, *args, mesh=mesh, **statics
    )
    return [key]
