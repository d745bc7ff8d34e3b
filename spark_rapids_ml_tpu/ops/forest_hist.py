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
        name="forest_hist_shallow",
    )(bins_sub, node_rel, stats_s)


# deep-phase row tile: segments are padded to a multiple of this, so a finer
# tile keeps the padding overhead low (~6% at 1M rows / 128 buckets)
_ROW_TILE_DEEP = 512


def _hist_kernel_segmented(
    t0_ref,         # (1,) int32 scalar prefetch: first tree of this dispatch
    seg_ref,        # (t_chunk * n_tiles,) int32 scalar prefetch: tile -> segment
    bins_ref,       # (_F_BLOCK, Kt) int8 — subset rows tile (segment-sorted)
    node_ref,       # (1, Kt) int32 segment-LOCAL node ids (>= nodes -> masked)
    stats_ref,      # (S, Kt) f32 stat rows
    out_ref,        # (_F_BLOCK, slots_pad, B) f32: the tile's segment
    *,
    nodes: int,
    s_dim: int,
    slots_pad: int,
    n_bins: int,
    row_tile: int,
    n_tiles: int,
):
    del t0_ref  # read by the index maps only
    t = pl.program_id(0)
    k = pl.program_id(2)
    at = t * n_tiles + k
    # a segment's tiles are contiguous: its output block stays resident
    # while they stream, and is zeroed at the first of them
    first = jnp.logical_or(
        k == 0, seg_ref[at] != seg_ref[jnp.maximum(at - 1, 0)]
    )

    @pl.when(first)
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
        out_ref[j, :, :] += acc


@partial(
    jax.jit,
    static_argnames=(
        "t_chunk", "n_segs", "nodes", "s_dim", "n_bins", "f_pad", "interpret",
    ),
)
def node_histograms_segmented(
    bins_s: jax.Array,    # (T, F_all, n2) int8, rows sorted by segment
    node_loc: jax.Array,  # (T, 1, n2) int32 segment-LOCAL node ids
    stats_s: jax.Array,   # (T, S, n2) f32
    tile_seg: jax.Array,  # (t_chunk * n_tiles,) int32 segment of each row tile
    t0: jax.Array,        # () int32 first tree of the t_chunk window
    t_chunk: int,
    n_segs: int,          # segments a tree (the last one takes stray tiles)
    nodes: int,           # local nodes per segment at this level
    s_dim: int,
    n_bins: int,
    f_pad: int,           # leading feature rows to scan (multiple of _F_BLOCK)
    interpret: bool = False,
) -> jax.Array:
    """Deep-phase histograms over a STATIC layout: every tree's rows lie
    sorted by segment (one level-L_s subtree each), each segment padded to
    whole _ROW_TILE_DEEP tiles, and `tile_seg` names each tile's segment.
    The kernel walks a tree's tiles in order and accumulates into the
    output block of the tile's segment, so segments of any length share one
    executable: its geometry follows from (T, n2, f_pad, nodes) alone.
    A segment that owns no tile is never written (garbage): the layout gives
    every kept segment at least one.  Returns
    (t_chunk * n_segs, f_pad, slots_pad, B) f32."""
    from jax.experimental.pallas import tpu as pltpu

    T, f_all, n2 = bins_s.shape
    assert n2 % _ROW_TILE_DEEP == 0, "pad rows to _ROW_TILE_DEEP"
    assert f_pad % _F_BLOCK == 0 and f_pad <= f_all
    n_tiles = n2 // _ROW_TILE_DEEP
    assert tile_seg.shape == (t_chunk * n_tiles,)
    slots = nodes * s_dim
    assert slots <= M_SLOTS
    slots_pad = max(8, -(-slots // 8) * 8)

    kernel = partial(
        _hist_kernel_segmented,
        nodes=nodes,
        s_dim=s_dim,
        slots_pad=slots_pad,
        n_bins=n_bins,
        row_tile=_ROW_TILE_DEEP,
        n_tiles=n_tiles,
    )
    rows = lambda t, f, k, t0_ref, seg_ref: (t0_ref[0] + t, 0, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t_chunk, f_pad // _F_BLOCK, n_tiles),
        in_specs=[
            pl.BlockSpec(
                (None, _F_BLOCK, _ROW_TILE_DEEP),
                lambda t, f, k, t0_ref, seg_ref: (t0_ref[0] + t, f, k),
            ),
            pl.BlockSpec((None, 1, _ROW_TILE_DEEP), rows),
            pl.BlockSpec((None, stats_s.shape[1], _ROW_TILE_DEEP), rows),
        ],
        out_specs=pl.BlockSpec(
            (None, _F_BLOCK, slots_pad, n_bins),
            lambda t, f, k, t0_ref, seg_ref: (
                t * n_segs + seg_ref[t * n_tiles + k], f, 0, 0
            ),
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (t_chunk * n_segs, f_pad, slots_pad, n_bins), jnp.float32
        ),
        interpret=interpret,
        name="forest_hist_deep",
    )(t0.reshape(1).astype(jnp.int32), tile_seg, bins_s, node_loc, stats_s)


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
