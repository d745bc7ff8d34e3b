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
# Random feature subsets are materialized by `gather_rows_matmul`, which
# copies the rows it selects and reads nothing else.  XLA keeps the (D, N)
# int8 bin matrix in tiles of 8 features x 128 rows, four features' bytes to
# a word, so no feature's row is a run of bytes: `jnp.take` on it scalarizes
# (4.4 ms for 54 rows of 3000 x 401,408 on a v5e), and the one-hot selection
# product this function was named for read the whole matrix a gather (3.4 ms
# at 54 rows, 17.7 ms at 1000).  `tile_feature_rows` lays the matrix out once
# a fit as (D, N / 4096, 32, 128), a feature a run of bytes (4.0 ms), and the
# gather is one Pallas kernel: 32 selected rows a block by scalar prefetch,
# laid in VMEM into the (32, rows) block the histogram kernels read (0.23 ms
# at 54 rows, 1.45 ms at 1000: 570 GB/s of reads and writes).  Measured
# beside it (PERF.md section 6, PR 33): `jnp.take` on the leading axis and
# XLA's copy back to row-interleaved tiles 0.52 / 5.5 ms, the product with
# int8 operands 2.5 / 10.1 ms.
#
# Slot packing doubles as shallow-level tree batching: at level l a tree
# needs 2^l * S slots, so 128 // (2^l * S) lock-step trees share one scan
# (and the SAME streamed one-hot operand).
#
# PRECISION.  The MXU takes bfloat16 operands: 8 significant bits.  One-hots
# and a classifier's stat rows (bootstrap counts, small whole numbers) are
# exact in it, and the float32 accumulation keeps their sums exact below 2^24.
# A regressor's stat row w*y is a continuous float32 and one bfloat16 product
# would keep 8 of its 24 bits, so a label crosses the MXU as THREE bfloat16
# pieces cut from its bits (label_stat_rows: hi + mid + lo is the float32 value
# exactly, each piece exact in bfloat16).  The kernels take `products` groups
# of s_dim stat rows a tree and run one product a group into an output plane
# of its own over the SAME slots and the same one-hot operand: (w, hi) ride the
# first product and (mid, lo) the second, so the slot geometry, the trees a
# scan packs and the scans a fit takes are a classifier's, at two products a
# feature for one.  fold_label_products adds the planes back into the (w, w*y)
# histogram in float32.
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


# rows of one feature in one (32, 128) int8 tile, and the most such tiles of a
# feature a gather block copies: 32 features x 16 tiles x 4 KB in and as much
# out, each buffered twice, is 8 MB of VMEM
_TILE_ROWS = 4096
_GATHER_TILES = 16


def _gather_blocks(n_pad: int):
    """(tiles of a feature in a gather block, blocks along the rows): the
    fewest blocks of at most _GATHER_TILES tiles, evened out."""
    tiles = -(-n_pad // _TILE_ROWS)
    blocks = -(-tiles // _GATHER_TILES)
    return -(-tiles // blocks), blocks


@jax.jit
def tile_feature_rows(bins_fm: jax.Array) -> jax.Array:
    """The (D, n_pad) int8 bin matrix as (D, tiles, 32, 128): a feature's
    rows in whole tiles of its own, 4096 consecutive rows each, so that a
    feature is a slice of the leading axis and a run of bytes.  Rows past
    n_pad (up to whole gather blocks) are zero bins."""
    D, n_pad = bins_fm.shape
    per, blocks = _gather_blocks(n_pad)
    rows = per * blocks * _TILE_ROWS
    out = jnp.pad(bins_fm, ((0, 0), (0, rows - n_pad)))
    return out.reshape(D, per * blocks, 32, 128)


def _gather_kernel(feats_ref, *refs, tiles: int, rows: int):
    """refs: _F_BLOCK blocks (tiles, 32, 128) of the tiled bin matrix, one a
    selected feature (the index maps read feats_ref), then the output block
    (_F_BLOCK, tiles * 4096): feature j's tiles side by side in row j."""
    del feats_ref  # read by the index maps only
    in_refs, out_ref = refs[:-1], refs[-1]
    live = None
    if rows % _F_BLOCK:  # the last block's rows past the subset are zero
        at = pl.program_id(0) * _F_BLOCK + jax.lax.broadcasted_iota(
            jnp.int32, (_F_BLOCK, 128), 0
        )
        live = at < rows

    def tile(q, carry):
        # (feature, sublane, lane) -> (sublane, feature, lane): sublane s of
        # every feature's tile is the output's tile s.  As int32, the width
        # at which Mosaic swaps a major axis with the sublanes
        x = jnp.stack([r[q].astype(jnp.int32) for r in in_refs])
        y = jnp.swapaxes(x, 0, 1)
        for s in range(32):
            rows_s = y[s] if live is None else jnp.where(live, y[s], 0)
            at = pl.multiple_of(q * _TILE_ROWS + s * 128, 128)
            out_ref[:, pl.ds(at, 128)] = rows_s.astype(jnp.int8)
        return carry

    # a loop, not `tiles` copies of its body: a process traces and lowers the
    # kernel again at every start, and the copies cost it a second
    jax.lax.fori_loop(0, tiles, tile, 0)


@partial(jax.jit, static_argnames=("f_pad", "n_pad", "interpret"))
def gather_rows_matmul(
    bins_rows: jax.Array,  # (D, tiles, 32, 128) int8 (tile_feature_rows)
    feats: jax.Array,      # (F,) int32 rows to select
    f_pad: int,
    n_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """Rows `feats` of the bin matrix as (f_pad, n_pad) int8, rows F and up
    zero: a copy of the rows selected (the header; no product since PR 33,
    the name is what the benchmark's configuration finds the module by)."""
    from jax.experimental.pallas import tpu as pltpu

    per, blocks = _gather_blocks(n_pad)
    assert bins_rows.shape[1:] == (per * blocks, 32, 128), (
        "bins_rows is not tile_feature_rows' of n_pad rows"
    )
    rows = feats.shape[0]
    assert f_pad % _F_BLOCK == 0 and rows <= f_pad and n_pad % _ROW_TILE == 0

    def pick(j):
        return lambda f, c, at: (at[f * _F_BLOCK + j], c, 0, 0)

    with jax.named_scope("forest.gather"):
        # the rows past the subset copy feature 0 and the kernel zeroes them
        at = jnp.pad(feats.astype(jnp.int32), (0, f_pad - rows))
        return pl.pallas_call(
            partial(_gather_kernel, tiles=per, rows=rows),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(f_pad // _F_BLOCK, blocks),
                in_specs=[
                    pl.BlockSpec((None, per, 32, 128), pick(j))
                    for j in range(_F_BLOCK)
                ],
                out_specs=pl.BlockSpec(
                    (_F_BLOCK, per * _TILE_ROWS), lambda f, c, at: (f, c)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct((f_pad, n_pad), jnp.int8),
            interpret=interpret,
            name="forest_gather",
        )(at, *([bins_rows] * _F_BLOCK))


# MXU products a label's stat row takes in a histogram: its three bfloat16
# pieces (the counter forest.label_pieces reads it)
LABEL_PIECES = 3


def _top_bits(x: jax.Array) -> jax.Array:
    """x with all but its sign, exponent and 7 leading fraction bits cleared:
    the float32 that IS a bfloat16, cut from x's own bits (a mask, so no
    rounding mode and no compiler's view of a float32 -> bfloat16 -> float32
    round trip enters)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def label_stat_rows(w: jax.Array, wy: jax.Array, axis: int) -> jax.Array:
    """A regressor's stat rows as the histogram kernels take them: (w, hi),
    (mid, lo) stacked along `axis`, two products of two rows.  hi, mid and lo
    are LABEL_PIECES pieces of 8 significant bits cut from the float32 w*y,
    each exact in bfloat16 and their sum w*y to the bit (a float32 has 24:
    both remainders are exact, and the last fits a piece).  w is a bootstrap
    count times a 0/1 mask, a small whole number, and needs one."""
    hi = _top_bits(wy)
    rest = wy - hi
    mid = _top_bits(rest)
    return jnp.stack([w, hi, mid, rest - mid], axis=axis)


def fold_label_products(H: jax.Array, slots: int) -> jax.Array:
    """The two output planes of a regressor's histogram, (2, ..., slots_pad, B)
    with slots (…, node, stat) innermost-stat, back into one (w, w*y) histogram
    (..., slots, B): w from the first plane's first stat, w*y = hi + (mid + lo)
    in float32."""
    a, b = (
        h[..., :slots, :].reshape(h.shape[:-2] + (slots // 2, 2, h.shape[-1]))
        for h in (H[0], H[1])
    )
    wy = a[..., 1, :] + (b[..., 0, :] + b[..., 1, :])
    out = jnp.stack([a[..., 0, :], wy], axis=-2)
    return out.reshape(out.shape[:-3] + (slots, out.shape[-1]))


def _slot_operands(node_rows, stats_ref, nodes: int, s_dim: int,
                   products: int, slots_pad: int, row_tile: int):
    """The products' LHS operands, each (slots_pad, Kt) bfloat16: slot
    (t, c, s) of product p holds tree t's stat row p * s_dim + s masked to
    node c.  node_rows: the trees' (Kt,) node ids; tree t's stat rows start at
    row t * products * s_dim of stats_ref."""
    parts = [[] for _ in range(products)]
    for t, node_t in enumerate(node_rows):
        on = (
            node_t[None, :]
            == jax.lax.broadcasted_iota(jnp.int32, (nodes, row_tile), 0)
        )
        for p in range(products):
            at = (t * products + p) * s_dim
            st = stats_ref[at : at + s_dim, :]  # (S, Kt)
            parts[p].append(
                (on[:, None, :].astype(jnp.float32) * st[None, :, :]).reshape(
                    nodes * s_dim, row_tile
                )
            )
    used = len(node_rows) * nodes * s_dim
    out = []
    for group in parts:
        lhs = jnp.concatenate(group, axis=0) if len(group) > 1 else group[0]
        if used < slots_pad:
            lhs = jnp.pad(lhs, ((0, slots_pad - used), (0, 0)))
        # exact for whole-number stat rows and for a label's pieces (header)
        out.append(lhs.astype(jnp.bfloat16))
    return out


def _accumulate(bins_ref, lhs, out_ref, n_bins: int, row_tile: int):
    """out[p, j] += lhs[p] @ OneHot(bins[j]) for the block's features j; one
    product (a 3-d out_ref) has no plane axis."""
    planes = len(lhs) > 1
    for j in range(_F_BLOCK):
        # RHS^T (B, Kt): one-hot of feature j's bins, built lane-aligned so
        # no transpose is needed (dot contracts both operands' lane axes)
        ohT = (
            bins_ref[j, :].astype(jnp.int32)[None, :]
            == jax.lax.broadcasted_iota(jnp.int32, (n_bins, row_tile), 0)
        ).astype(jnp.bfloat16)
        for p, lhs_p in enumerate(lhs):
            acc = jax.lax.dot_general(
                lhs_p,
                ohT,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (slots_pad, B)
            if planes:
                out_ref[p, j, :, :] += acc
            else:
                out_ref[j, :, :] += acc


def _hist_kernel(
    bins_ref,       # (_F_BLOCK, Kt) int8 — subset feature rows tile
    node_ref,       # (T_pack, Kt) int32 node-in-level ids (>= nodes -> masked)
    stats_ref,      # (T_pack * products * S, Kt) f32 per-tree stat rows
    out_ref,        # (_F_BLOCK, M_SLOTS, B) f32, or (products, ...) of them
    *,
    t_pack: int,
    nodes: int,
    s_dim: int,
    products: int,
    n_bins: int,
    row_tile: int,
):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # LHS (M_SLOTS, Kt) a product: shared by every feature in the block
    lhs = _slot_operands(
        [node_ref[t, :] for t in range(t_pack)], stats_ref, nodes, s_dim,
        products, M_SLOTS, row_tile,
    )
    _accumulate(bins_ref, lhs, out_ref, n_bins, row_tile)


@partial(
    jax.jit,
    static_argnames=(
        "t_pack", "nodes", "s_dim", "n_bins", "products", "interpret",
    ),
)
def node_histograms(
    bins_sub: jax.Array,  # (F_pad, N_pad) int8 subset rows (gather_rows_matmul)
    node_rel: jax.Array,  # (T_pack, N_pad) int32; >= nodes masks a row out
    stats_s: jax.Array,   # (T_pack * products * S, N_pad) f32 stat rows
    t_pack: int,
    nodes: int,
    s_dim: int,
    n_bins: int,
    products: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Per-(feature, slot, bin) stat sums: (F_pad, M_SLOTS, B) f32 with
    slot = (t * nodes + c) * s_dim + s.  N_pad must be a multiple of
    _ROW_TILE (pad rows carry node_rel >= nodes); F_pad a multiple of
    _F_BLOCK.  With `products` > 1 a tree brings that many groups of s_dim
    stat rows (tree-major, then product, then stat) and each group's sums
    come in a plane of their own: (products, F_pad, M_SLOTS, B)."""
    f_pad, n_pad = bins_sub.shape
    assert n_pad % _ROW_TILE == 0, "pad rows to _ROW_TILE"
    assert f_pad % _F_BLOCK == 0, "pad features to _F_BLOCK"
    assert t_pack * nodes * s_dim <= M_SLOTS
    assert stats_s.shape[0] == t_pack * products * s_dim
    assert n_bins <= 128
    k_steps = n_pad // _ROW_TILE
    planes = () if products == 1 else (products,)

    kernel = partial(
        _hist_kernel,
        t_pack=t_pack,
        nodes=nodes,
        s_dim=s_dim,
        products=products,
        n_bins=n_bins,
        row_tile=_ROW_TILE,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            planes + (f_pad, M_SLOTS, n_bins), jnp.float32
        ),
        grid=(f_pad // _F_BLOCK, k_steps),
        in_specs=[
            pl.BlockSpec((_F_BLOCK, _ROW_TILE), lambda f, k: (f, k)),
            pl.BlockSpec((node_rel.shape[0], _ROW_TILE), lambda f, k: (0, k)),
            pl.BlockSpec((stats_s.shape[0], _ROW_TILE), lambda f, k: (0, k)),
        ],
        out_specs=pl.BlockSpec(
            planes + (_F_BLOCK, M_SLOTS, n_bins),
            lambda f, k: (0,) * len(planes) + (f, 0, 0),
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
    last_ref,       # (t_chunk,) int32 scalar prefetch: a tree's last kept tile
    bins_ref,       # (_F_BLOCK, Kt) int8 — subset rows tile (segment-sorted)
    node_ref,       # (1, Kt) int32 segment-LOCAL node ids (>= nodes -> masked)
    stats_ref,      # (products * S, Kt) f32 stat rows
    out_ref,        # (_F_BLOCK, slots_pad, B) f32, or (products, ...): the tile's segment
    *,
    nodes: int,
    s_dim: int,
    products: int,
    slots_pad: int,
    n_bins: int,
    row_tile: int,
    n_tiles: int,
    n_segs: int,
):
    del t0_ref, last_ref  # read by the index maps only
    t = pl.program_id(0)
    k = pl.program_id(2)
    at = t * n_tiles + k

    # a stray tile (segment n_segs: rows that weigh nothing) is not streamed:
    # the index maps name the blocks already resident, and nothing here runs,
    # the zeroing least of all: the block resident at a tree's first stray
    # tile is its last kept segment's, not yet written back
    @pl.when(seg_ref[at] < n_segs)
    def _kept():
        # a segment's tiles are contiguous: its output block stays resident
        # while they stream, and is zeroed at the first of them
        first = jnp.logical_or(
            k == 0, seg_ref[at] != seg_ref[jnp.maximum(at - 1, 0)]
        )

        @pl.when(first)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        lhs = _slot_operands(
            [node_ref[0, :]], stats_ref, nodes, s_dim, products, slots_pad,
            row_tile,
        )
        _accumulate(bins_ref, lhs, out_ref, n_bins, row_tile)


@partial(
    jax.jit,
    static_argnames=(
        "t_chunk", "n_segs", "nodes", "s_dim", "n_bins", "f_pad", "products",
        "interpret",
    ),
)
def node_histograms_segmented(
    bins_s: jax.Array,    # (T, F_all, n2) int8, rows sorted by segment
    node_loc: jax.Array,  # (T, 1, n2) int32 segment-LOCAL node ids
    stats_s: jax.Array,   # (T, products * S, n2) f32
    tile_seg: jax.Array,  # (t_chunk * n_tiles,) int32 segment of each row tile
    t0: jax.Array,        # () int32 first tree of the t_chunk window
    t_chunk: int,
    n_segs: int,          # segments a tree; a tile of segment n_segs is stray
    nodes: int,           # local nodes per segment at this level
    s_dim: int,
    n_bins: int,
    f_pad: int,           # leading feature rows to scan (multiple of _F_BLOCK)
    products: int = 1,    # groups of s_dim stat rows, a plane of sums each
    interpret: bool = False,
) -> jax.Array:
    """Deep-phase histograms over a STATIC layout: every tree's rows lie
    sorted by segment (one level-L_s subtree each), each segment padded to
    whole _ROW_TILE_DEEP tiles, and `tile_seg` names each tile's segment.
    The kernel walks a tree's tiles in order and accumulates into the
    output block of the tile's segment, so segments of any length share one
    executable: its geometry follows from (T, n2, f_pad, nodes) alone.

    The STRAY tiles (`tile_seg` == n_segs) are a tree's LAST: the rows that
    weigh nothing in it and the filler no segment used sort behind its
    segments.  They are not streamed: past a tree's last kept tile the index
    maps name the blocks already resident (no DMA is issued for a block
    that does not change, and every index stays inside its array) and the
    body does nothing, so what those tiles hold never enters a sum.  A
    segment that owns no tile is never written (garbage): the layout gives
    every segment at least one.  Returns
    (t_chunk * n_segs, f_pad, slots_pad, B) f32, under a leading axis of
    `products` planes where there is more than one (node_histograms)."""
    from jax.experimental.pallas import tpu as pltpu

    T, f_all, n2 = bins_s.shape
    assert n2 % _ROW_TILE_DEEP == 0, "pad rows to _ROW_TILE_DEEP"
    assert f_pad % _F_BLOCK == 0 and f_pad <= f_all
    n_tiles = n2 // _ROW_TILE_DEEP
    assert tile_seg.shape == (t_chunk * n_tiles,)
    slots = nodes * s_dim
    assert slots <= M_SLOTS
    assert stats_s.shape[1] == products * s_dim
    slots_pad = max(8, -(-slots // 8) * 8)
    planes = () if products == 1 else (products,)
    # a tree's last kept tile, from the tile map itself: the kernel's skip
    # and its index maps cannot disagree
    kept = (tile_seg.reshape(t_chunk, n_tiles) < n_segs).sum(
        axis=1, dtype=jnp.int32
    )
    last = jnp.maximum(kept - 1, 0)

    kernel = partial(
        _hist_kernel_segmented,
        nodes=nodes,
        s_dim=s_dim,
        products=products,
        slots_pad=slots_pad,
        n_bins=n_bins,
        row_tile=_ROW_TILE_DEEP,
        n_tiles=n_tiles,
        n_segs=n_segs,
    )

    # step k of tree t names tile min(k, the tree's last kept tile)
    def bins(t, f, k, t0_ref, seg_ref, last_ref):
        return (t0_ref[0] + t, f, jnp.minimum(k, last_ref[t]))

    def rows(t, f, k, t0_ref, seg_ref, last_ref):
        return (t0_ref[0] + t, 0, jnp.minimum(k, last_ref[t]))

    def out_block(t, f, k, t0_ref, seg_ref, last_ref):
        seg = seg_ref[t * n_tiles + jnp.minimum(k, last_ref[t])]
        return (0,) * len(planes) + (
            t * n_segs + jnp.minimum(seg, n_segs - 1), f, 0, 0
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t_chunk, f_pad // _F_BLOCK, n_tiles),
        in_specs=[
            pl.BlockSpec((None, _F_BLOCK, _ROW_TILE_DEEP), bins),
            pl.BlockSpec((None, 1, _ROW_TILE_DEEP), rows),
            pl.BlockSpec((None, stats_s.shape[1], _ROW_TILE_DEEP), rows),
        ],
        out_specs=pl.BlockSpec(
            planes + (None, _F_BLOCK, slots_pad, n_bins), out_block
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            planes + (t_chunk * n_segs, f_pad, slots_pad, n_bins), jnp.float32
        ),
        interpret=interpret,
        name="forest_hist_deep",
    )(t0.reshape(1).astype(jnp.int32), tile_seg, last, bins_s, node_loc, stats_s)


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
