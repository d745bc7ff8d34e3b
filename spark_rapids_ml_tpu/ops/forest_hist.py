#
# MXU-native random-forest histograms (pallas).
#
# Replaces the scatter (segment_sum) histogram path of ops/forest.py for the
# performance-critical fits.  TPU scatter sustains only ~10-50M scalar
# updates/s, which made the reference's RF benchmarks (tree.py:292-397 via
# cuML's GPU shared-memory atomic histograms) unreachable; this module
# reformulates histogram building as dense MXU matmuls, which the hardware
# serves at tens of TFLOP/s:
#
#   H[f, slot, b] = sum_r LHS[slot, r] * OneHot(bin[f, r])[b]
#
# where a SLOT packs (tree, node, stat): LHS[slot, r] =
# stat_s(tree, r) * [node(tree, r) == c].  With <= 128 slots the product is
# a (128, Kt) @ (Kt, B) MXU tile per (feature, row-tile) — both operands
# built on the fly in VMEM from the binned features, node ids and stats, so
# no one-hot ever touches HBM.
#
# Random feature subsets are materialized by `gather_rows_matmul`: XLA's
# gather scalarizes on this backend (~30M elem/s measured), while a one-hot
# selection matrix against the feature-major bin matrix is a single MXU
# contraction (exact: bin values < 2^8 are representable in bfloat16).
#
# Slot packing doubles as shallow-level tree batching: at level l a tree
# needs 2^l * S slots, so 128 // (2^l * S) lock-step trees share one scan
# (and the SAME streamed one-hot operand).
#

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# fixed matmul geometry: M = slot axis (<= 128), N = bin axis (n_bins <= 128),
# K = row tile; F processed in blocks of _F_BLOCK consecutive subset rows
# (32 = the int8 sublane tile, letting the subset matrix stay one byte/cell)
M_SLOTS = 128
_ROW_TILE = 2048
_F_BLOCK = 32


@partial(jax.jit, static_argnames=("f_pad", "chunk"))
def gather_rows_matmul(
    bins_fm: jax.Array, feats: jax.Array, f_pad: int, chunk: int = 65536
) -> jax.Array:
    """Select rows `feats` of the (D, N) int8 bin matrix as (f_pad, N) int8
    via OneHot(feats) @ bins — MXU-fast where XLA's row gather scalarizes.
    Exact: all values are small integers, exactly representable in bf16."""
    D, N = bins_fm.shape
    sel = (
        feats[:, None] == jnp.arange(D, dtype=feats.dtype)[None, :]
    ).astype(jnp.bfloat16)
    sel = jnp.pad(sel, ((0, f_pad - feats.shape[0]), (0, 0)))

    def body(_, i):
        blk = jax.lax.dynamic_slice_in_dim(bins_fm, i * chunk, chunk, axis=1)
        out = jnp.dot(
            sel, blk.astype(jnp.bfloat16), preferred_element_type=jnp.float32
        )
        return 0, out.astype(jnp.int8)

    n_chunks = N // chunk
    assert n_chunks * chunk == N, "pad N to the gather chunk"
    _, cols = jax.lax.scan(body, 0, jnp.arange(n_chunks, dtype=jnp.int32))
    return jnp.moveaxis(cols, 0, 1).reshape(f_pad, N)


def _hist_kernel(
    bins_ref,       # (_F_BLOCK, Kt) int8 — subset feature rows tile
    node_ref,       # (T_pack, Kt) int32 node-in-level ids (>= nodes -> masked)
    stats_ref,      # (T_pack * S, Kt) f32 per-tree stat rows
    out_ref,        # (_F_BLOCK, M_SLOTS, B) f32
    *,
    t_pack: int,
    nodes: int,
    s_dim: int,
    n_bins: int,
    row_tile: int,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # LHS (M_SLOTS, Kt): slot (t, c, s) -> stat_s(t) masked to node c;
    # shared by every feature in the block
    parts = []
    for t in range(t_pack):
        node_t = node_ref[t, :]  # (Kt,)
        on = (
            node_t[None, :]
            == jax.lax.broadcasted_iota(jnp.int32, (nodes, row_tile), 0)
        )
        st = stats_ref[t * s_dim : (t + 1) * s_dim, :]  # (S, Kt)
        parts.append(
            (on[:, None, :].astype(jnp.float32) * st[None, :, :]).reshape(
                nodes * s_dim, row_tile
            )
        )
    lhs = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    used = t_pack * nodes * s_dim
    if used < M_SLOTS:
        lhs = jnp.pad(lhs, ((0, M_SLOTS - used), (0, 0)))
    lhs = lhs.astype(jnp.bfloat16)

    for j in range(_F_BLOCK):
        # RHS^T (B, Kt): one-hot of feature j's bins, built lane-aligned so
        # no transpose is needed (dot contracts both operands' lane axes)
        ohT = (
            bins_ref[j, :].astype(jnp.int32)[None, :]
            == jax.lax.broadcasted_iota(jnp.int32, (n_bins, row_tile), 0)
        ).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            lhs,
            ohT,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (M_SLOTS, B)
        out_ref[j, :, :] += acc


@partial(
    jax.jit,
    static_argnames=("t_pack", "nodes", "s_dim", "n_bins", "interpret"),
)
def node_histograms(
    bins_sub: jax.Array,  # (F_pad, N_pad) int8 subset rows (gather_rows_matmul)
    node_rel: jax.Array,  # (T_pack, N_pad) int32; >= nodes masks a row out
    stats_s: jax.Array,   # (T_pack * S, N_pad) f32 weighted stat rows
    t_pack: int,
    nodes: int,
    s_dim: int,
    n_bins: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-(feature, slot, bin) stat sums: (F_pad, M_SLOTS, B) f32 with
    slot = (t * nodes + c) * s_dim + s.  N_pad must be a multiple of
    _ROW_TILE (pad rows carry node_rel >= nodes); F_pad a multiple of
    _F_BLOCK."""
    f_pad, n_pad = bins_sub.shape
    assert n_pad % _ROW_TILE == 0, "pad rows to _ROW_TILE"
    assert f_pad % _F_BLOCK == 0, "pad features to _F_BLOCK"
    assert t_pack * nodes * s_dim <= M_SLOTS
    assert n_bins <= 128
    k_steps = n_pad // _ROW_TILE

    kernel = partial(
        _hist_kernel,
        t_pack=t_pack,
        nodes=nodes,
        s_dim=s_dim,
        n_bins=n_bins,
        row_tile=_ROW_TILE,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((f_pad, M_SLOTS, n_bins), jnp.float32),
        grid=(f_pad // _F_BLOCK, k_steps),
        in_specs=[
            pl.BlockSpec((_F_BLOCK, _ROW_TILE), lambda f, k: (f, k)),
            pl.BlockSpec((node_rel.shape[0], _ROW_TILE), lambda f, k: (0, k)),
            pl.BlockSpec((stats_s.shape[0], _ROW_TILE), lambda f, k: (0, k)),
        ],
        out_specs=pl.BlockSpec(
            (_F_BLOCK, M_SLOTS, n_bins), lambda f, k: (f, 0, 0)
        ),
        interpret=interpret,
    )(bins_sub, node_rel, stats_s)


# deep-phase row tile: buckets are padded to a multiple of this, so a finer
# tile keeps the padding overhead low (~6% at 1M rows / 128 buckets)
_ROW_TILE_DEEP = 512


def _hist_kernel_bucketed(
    bins_ref,       # (_F_BLOCK, Kt) int8 — subset rows tile (bucket-sorted)
    node_ref,       # (1, Kt) int32 bucket-LOCAL node ids (>= nodes -> masked)
    stats_ref,      # (S, Kt) f32 stat rows
    out_ref,        # (1, _F_BLOCK, slots_pad, B) f32
    *,
    nodes: int,
    s_dim: int,
    slots_pad: int,
    n_bins: int,
    row_tile: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    node = node_ref[0, :]
    on = (
        node[None, :]
        == jax.lax.broadcasted_iota(jnp.int32, (nodes, row_tile), 0)
    )
    st = stats_ref[:, :]
    lhs = (
        on[:, None, :].astype(jnp.float32) * st[None, :, :]
    ).reshape(nodes * s_dim, row_tile)
    if nodes * s_dim < slots_pad:
        lhs = jnp.pad(lhs, ((0, slots_pad - nodes * s_dim), (0, 0)))
    lhs = lhs.astype(jnp.bfloat16)

    for j in range(_F_BLOCK):
        ohT = (
            bins_ref[j, :].astype(jnp.int32)[None, :]
            == jax.lax.broadcasted_iota(jnp.int32, (n_bins, row_tile), 0)
        ).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            lhs,
            ohT,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out_ref[0, j, :, :] += acc


@partial(
    jax.jit,
    static_argnames=("n_buckets", "nodes", "s_dim", "n_bins", "interpret"),
)
def node_histograms_bucketed(
    bins_sub: jax.Array,  # (F_pad, n_buckets * cap) int8, bucket-sorted rows
    node_rel: jax.Array,  # (1, n_buckets * cap) int32 bucket-LOCAL node ids
    stats_s: jax.Array,   # (S, n_buckets * cap) f32
    n_buckets: int,
    nodes: int,           # local nodes per bucket at this level
    s_dim: int,
    n_bins: int,
    interpret: bool = False,
) -> jax.Array:
    """Deep-phase histograms: rows grouped into `n_buckets` equal-length
    contiguous buckets (one level-L_s subtree each); every bucket only pays
    for its own <= 128 (local node, stat) slots.  Returns
    (n_buckets, F_pad, slots_pad, B) f32."""
    f_pad, n_tot = bins_sub.shape
    assert n_tot % n_buckets == 0
    cap = n_tot // n_buckets
    assert cap % _ROW_TILE_DEEP == 0, "pad buckets to _ROW_TILE_DEEP"
    assert f_pad % _F_BLOCK == 0
    slots = nodes * s_dim
    assert slots <= M_SLOTS
    slots_pad = max(8, -(-slots // 8) * 8)
    cap_k = cap // _ROW_TILE_DEEP

    kernel = partial(
        _hist_kernel_bucketed,
        nodes=nodes,
        s_dim=s_dim,
        slots_pad=slots_pad,
        n_bins=n_bins,
        row_tile=_ROW_TILE_DEEP,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_buckets, f_pad, slots_pad, n_bins), jnp.float32
        ),
        grid=(n_buckets, f_pad // _F_BLOCK, cap_k),
        in_specs=[
            pl.BlockSpec(
                (_F_BLOCK, _ROW_TILE_DEEP),
                lambda b, f, k: (f, b * cap_k + k),
            ),
            pl.BlockSpec(
                (1, _ROW_TILE_DEEP), lambda b, f, k: (0, b * cap_k + k)
            ),
            pl.BlockSpec(
                (stats_s.shape[0], _ROW_TILE_DEEP),
                lambda b, f, k: (0, b * cap_k + k),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, _F_BLOCK, slots_pad, n_bins), lambda b, f, k: (b, f, 0, 0)
        ),
        interpret=interpret,
    )(bins_sub, node_rel, stats_s)


@partial(
    jax.jit,
    static_argnames=("mesh", "t_pack", "nodes", "s_dim", "n_bins", "interpret"),
)
def node_histograms_sharded(
    bins_sub: jax.Array,  # (F_pad, N_pad) int8 subset rows, row-sharded
    node_rel: jax.Array,  # (T_pack, N_pad) int32 node-in-level ids
    stats_s: jax.Array,   # (T_pack * S, N_pad) f32 weighted stat rows
    mesh,
    t_pack: int,
    nodes: int,
    s_dim: int,
    n_bins: int,
    interpret: bool = False,
) -> jax.Array:
    """The MXU one-hot histogram kernel's SHARDING RULE: shard the row axis
    over DATA_AXIS (shard_map), run node_histograms on each device's local
    row tile, and combine the per-shard partial histograms with ONE psum
    (parallel/exchange.psum_parts) — the same partial-sums-then-all-reduce
    shape the scatter engine (ops/forest._forest_block_kernel) uses, so a
    multi-chip fit can keep the MXU path instead of falling back.  Each
    shard's row count must stay a multiple of _ROW_TILE, i.e. N_pad must be
    a multiple of n_devices * _ROW_TILE.  Returns the REPLICATED
    (F_pad, M_SLOTS, B) histogram."""
    from jax import shard_map
    from ..parallel.exchange import psum_parts
    from ..parallel.mesh import DATA_AXIS
    from jax.sharding import PartitionSpec as PSpec

    n_dev = mesh.devices.size
    n_pad = bins_sub.shape[1]
    assert n_pad % (n_dev * _ROW_TILE) == 0, (
        "pad rows to n_devices * _ROW_TILE for the sharded histogram rule"
    )

    def body(b_loc, nr_loc, st_loc):
        H = node_histograms(
            b_loc, nr_loc, st_loc, t_pack=t_pack, nodes=nodes, s_dim=s_dim,
            n_bins=n_bins, interpret=interpret,
        )
        return psum_parts(H, DATA_AXIS, section="forest.hist_parts")

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            PSpec(None, DATA_AXIS),
            PSpec(None, DATA_AXIS),
            PSpec(None, DATA_AXIS),
        ),
        out_specs=PSpec(),
        check_vma=False,
    )(bins_sub, node_rel, stats_s)


def node_histograms_reference(
    bins_sub: np.ndarray,
    node_rel: np.ndarray,
    stats_s: np.ndarray,
    t_pack: int,
    nodes: int,
    s_dim: int,
    n_bins: int,
) -> np.ndarray:
    """Plain-numpy oracle for tests."""
    f_pad = bins_sub.shape[0]
    H = np.zeros((f_pad, M_SLOTS, n_bins), np.float32)
    n = bins_sub.shape[1]
    for fi in range(f_pad):
        row = np.asarray(bins_sub[fi])
        for t in range(t_pack):
            for r in range(n):
                c = int(node_rel[t, r])
                if c >= nodes:
                    continue
                b = int(row[r])
                for s in range(s_dim):
                    slot = (t * nodes + c) * s_dim + s
                    H[fi, slot, b] += float(stats_s[t * s_dim + s, r])
    return H
