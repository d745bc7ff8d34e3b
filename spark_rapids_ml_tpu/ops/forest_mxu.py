#
# MXU forest builder: lock-step level-wise growth driven by the pallas
# histogram kernels (ops/forest_hist.py).
#
# Replaces the scatter-bound grow_forest path (ops/forest.py) on TPU for the
# depths where every level's (node, stat) slots fit one 128-slot matmul
# (2^level * s_dim <= 128), and twice that depth plus one through the
# segmented deep phase.  Design notes:
#
#   - Trees grow LOCK-STEP; at shallow levels several trees pack into one
#     128-slot scan and share the streamed one-hot operand.
#   - Feature subsets (featureSubsetStrategy) follow ONE OF TWO RULES, and the
#     table's width decides which (per_node_subsets: no switch, no setting).
#     WIDER THAN ONE FEATURE BLOCK (D > 32): a subset is sampled per
#     (tree-group, level) in the shallow phase — one subset shared by the <= 64
#     trees packed into a scan — and ONCE PER TREE for all of its deep levels
#     (the subset's rows ride the deep phase's payload sort).  cuML/Spark
#     sample per node; this keeps the de-correlation role (random-subspace
#     forests, Ho 1998) while letting histogram work ride a single MXU operand:
#     a scan builds one-hots for the subset's rows alone.
#     NO WIDER THAN ONE BLOCK (D <= 32, F < D): a scan builds one-hots for a
#     whole block of 32 feature rows whatever it searches, so every scan,
#     shallow and deep, runs over the WHOLE table (one copy of it a fit, the
#     deep payload carries ceil(D / 4) words once for all trees) and EACH NODE
#     draws its own subset of F columns, as upstream does: a mask on the gains
#     before the argmax (_split_from_hist).  At this width the shared rule
#     would hand all the roots of a scan one subset of 5 columns of 28.  The
#     draw is counter-based (node_subset_mask): a column's score is
#     mix(mix(mix(mix(seed) + tree) + node) + (column + 1) * 0x9E3779B9), mix
#     the murmur3 finalizer on 32 bits, `node` the dense layout's index; the
#     subset is the F columns of lowest (score, column).  numpy computes it
#     again from the four words a model keeps (`feature_subset_draw_`: rule,
#     seed, F, D) without this program (chipbench/references/forest_pernode.py
#     does).  Ties between gains go to the lowest column, then the lowest edge.
#   - Regression split search uses only (w, w*y) histograms: the w*y^2 term
#     cancels in the weighted variance gain (sum_c (wy_c)^2/w_c is monotone
#     in it), halving slot usage; node impurities come from a per-node
#     3-stat total.  A label is CONTINUOUS, so every sum it enters is taken at
#     float32's precision: w*y crosses the histogram kernels' bfloat16
#     operands as three exact pieces in two products over a classifier's slots
#     (forest_hist's header), and the per-node totals (w, w*y, w*y*y) are
#     products at Precision.HIGHEST (_node_totals, _seg_totals).
#   - Row routing is scatter- and gather-free: a node's split feature is
#     selected by a one-hot against the subset rows and compared against the
#     node's split bin under the node mask.
#
# EVERY EXECUTABLE FOLLOWS FROM THE FIT'S STATIC GEOMETRY
# (n_pad, D, T, max_depth, n_bins, max_features, s_dim, kind): no shape, no
# trip count and no dispatch depends on what the data did.  The deep phase
# sorts each tree's rows ONCE by their ancestor at the bucket level (one
# "segment" per (tree, bucket)), pads every segment to whole row tiles with
# weight-0 filler rows that ride the sort, and hands the histogram kernel a
# per-tile segment id (forest_hist.node_histograms_segmented): segments of
# any length accumulate inside one kernel, so there are no size classes, no
# per-class executables and no host round-trip for the segment lengths.  All
# of it — counts, filler keys, tile map — is computed on the device; the
# host's only read is ONE batched fetch of the finished forest.  What the
# data DOES decide is how much of a grid the deep kernel streams: a row that
# weighs nothing in a tree (36.8% of them under a Poisson(1) bootstrap) sorts
# behind the tree's segments with the other stray rows, and the kernel does
# nothing for the tiles there (a grid step that names the resident blocks and
# runs no body).  That follows from the weights alone: no setting.
#
# PREFIX SUMS ARE A TRIANGULAR PRODUCT.  A candidate split's left class
# counts are the histogram's prefix sums over the bins.  jnp.cumsum lowers to
# reduce-window on this backend, many passes over the histogram where a
# prefix sum is the work of one; the same sums as hist @ tri, tri[i, j] = 1
# where i <= j, made from two iotas, are one pass on the MXU, and the product
# writes the transposed layout the gains read, so one table-sized copy goes
# with the scan (the timings: PERF.md, PR 29).  The product is taken at
# Precision.HIGHEST and nothing lower: class histograms are whole numbers
# (bootstrap counts x one-hots), the benchmark's check holds node counts to
# the unit, and HIGHEST carries a float32 as three bfloat16 pieces of 8 bits
# against ones and zeros, so every whole number below 2^24 comes out to the
# bit.  How the compiler cuts the pieces is not documented: the chip's smoke
# (chip_smoke.py, kernel_forest) holds the product to the bit on rows built
# to break a cut that rounds, and tests/test_forest_mxu.py does off the chip.
# Precision.HIGH's three passes keep 16 bits of the 24 and DEFAULT's one
# keeps 8: a constant column puts 400,000 rows in one bin.  Regression's
# (w, w*y) sums are float32 sums of continuous labels, not whole numbers;
# there the product differs from a scan by float32 rounding, as the scan's own
# tree order did from a sequential sum.  One path for every kind and every B:
# at B = 32 the product is no slower than the scan.
#
# Level steps carry a TRACED tree offset with a clamped window, so the last
# (partial) tree group reuses the same executable, and every geometry is
# submitted to ops/precompile at entry and compiled in parallel.
#
# Split records are written by the level steps into one device buffer of
# (channel, tree, node) float32 (`tree_buf`); pack_forest turns its host
# copy into grow_forest's dense arrays, so models/random_forest.py consumes
# either builder interchangeably.
#
# Sharding: the histogram kernel's mesh rule lives in
# forest_hist.node_histograms_sharded (per-shard pallas pass + one psum);
# this BUILDER still drives a single chip end-to-end (the deep phase's
# payload sort is not sharded yet), so multi-device fits run the
# mesh-parallel scatter engine (ops/forest.grow_forest) instead.
#

from __future__ import annotations

from functools import lru_cache, partial
from itertools import groupby
from typing import Any, List, NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import profiling
from .forest_hist import (
    LABEL_PIECES,
    M_SLOTS,
    _F_BLOCK,
    _ROW_TILE,
    _ROW_TILE_DEEP,
    fold_label_products,
    gather_rows_matmul,
    label_stat_rows,
    node_histograms,
    node_histograms_segmented,
    tile_feature_rows,
)
from .precompile import aval, global_precompiler

# channels of tree_buf, the (channel, tree, node) float32 record the level
# steps write: local split feature, split bin, split taken, node weight,
# node impurity, then the node's V values
_CH_F, _CH_B, _CH_OK, _CH_W, _CH_IMP, _CH_VAL = range(6)

# the deep phase's histogram budget a dispatch: sets how many trees a deep
# level step takes (one at the last level of a 128-bucket, 128-bin fit)
_DEEP_HIST_BYTES = 768 << 20
# split-search intermediate (segments, S, local, f_pad, B) per lax.map step
_SPLIT_CHUNK_BYTES = 64 << 20


def _shallow_levels(s_dim: int) -> int:
    """Levels the single-scan phase can host: 2^l * s_dim <= M_SLOTS."""
    l = 0
    while (2 ** (l + 1)) * s_dim <= M_SLOTS:
        l += 1
    return l  # deepest supported level index


def mxu_depth_supported(max_depth: int, s_dim: int) -> bool:
    """Shallow phase hosts levels up to L_s; the segmented deep phase covers
    another L_s + 1 levels (one segment per level-(L_s+1) node, each again
    bounded by the slot budget)."""
    l_s = _shallow_levels(s_dim)
    return max_depth <= 2 * l_s + 1


@lru_cache(maxsize=1024)
def _offset(value: int) -> jax.Array:
    """A tree window's start as a device scalar, put there once a process: a
    host-to-device copy in the level loop would wait for the steps queued
    before it, and hold the host in step with the device."""
    return jnp.asarray(np.int32(value))


def _even_chunk(total: int, cap: int) -> int:
    """Trees a step takes: the fewest windows of at most `cap` trees, of
    even size, so the clamped last window overlaps as little as it can."""
    cap = max(1, min(cap, total))
    return -(-total // -(-total // cap))


def _deep_chunk(T: int, kind: str, nb: int, f_pad: int, slots: int, n_bins: int) -> int:
    """Trees a deep level step takes: as many as keep the step's histograms
    (nb segments a tree) UNDER _DEEP_HIST_BYTES (128 segments x 64 features x
    64 slot rows x 128 bins are a third of it to the byte: two trees a step,
    the windows every measurement was taken with), and at most 16: their
    tile map is the kernel's scalar-prefetch operand."""
    slots_pad = max(8, -(-slots // 8) * 8)
    per_tree = _hist_products(kind) * nb * f_pad * slots_pad * n_bins * 4
    return _even_chunk(T, min(16, max(1, (_DEEP_HIST_BYTES - 1) // per_tree)))


def _hist_products(kind: str) -> int:
    """Products a histogram kernel runs a feature: a regressor's (w, hi) and
    (mid, lo), the label's three pieces (forest_hist's header); one of whole
    numbers for a classifier."""
    return 2 if kind == "regression" else 1


# feature_subset_draw_[0]: the rule a model's per-node subsets follow
SUBSET_RULE = 1


def per_node_subsets(n_cols: int, max_features: int) -> bool:
    """Whether every node draws its own feature subset (the header's second
    rule): the table is no wider than one feature block, so a scan over all
    of it costs the histogram kernels what a scan over a subset does."""
    return max_features < n_cols <= _F_BLOCK


def _mix32(x):
    """murmur3's 32-bit finalizer, on uint32 arrays of jax.numpy or numpy."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def node_subset_mask(seed, trees, nodes, n_cols: int, max_features: int, xp=jnp):
    """(trees, nodes, n_cols) bool: the max_features columns node nodes[j]
    (the dense layout's index) of tree trees[i] may split on, by the header's
    counter-based rule.  xp=np computes it on the host, from a model's
    `feature_subset_draw_` (node_subsets)."""
    u32 = xp.uint32
    tree_key = _mix32(_mix32(xp.asarray(seed, u32).reshape(1)) + xp.asarray(trees, u32))
    key = _mix32(tree_key[:, None] + xp.asarray(nodes, u32)[None, :])
    cols = xp.arange(1, n_cols + 1, dtype=u32) * np.uint32(0x9E3779B9)
    score = _mix32(key[:, :, None] + cols[None, None, :])
    col = xp.arange(n_cols)
    mine, other = score[..., :, None], score[..., None, :]
    ahead = (other < mine) | ((other == mine) & (col[None, :] < col[:, None]))
    return ahead.sum(axis=-1) < max_features


def node_subsets(draw, trees, nodes) -> np.ndarray:
    """A model's per-node feature subsets, drawn again on the host from its
    `feature_subset_draw_` (rule, seed, F, D): (trees, nodes, D) bool."""
    rule, seed, F, D = (int(v) for v in np.asarray(draw))
    if rule != SUBSET_RULE:
        raise ValueError(f"feature_subset_draw_ names rule {rule}; this program knows {SUBSET_RULE}")
    return node_subset_mask(seed, np.asarray(trees), np.asarray(nodes), D, F, xp=np)


def _step_subsets(seed, t0, trees: int, node0: int, nodes: int, n_cols: int, max_features: int, f_pad: int):
    """(trees, nodes, f_pad) bool mask of a level step's nodes: trees t0..,
    dense nodes node0..; the padding feature rows masked."""
    with jax.named_scope("forest.subset"):
        mask = node_subset_mask(
            seed, t0 + jnp.arange(trees), node0 + jnp.arange(nodes),
            n_cols, max_features,
        )
        return jnp.pad(mask, ((0, 0), (0, 0), (0, f_pad - n_cols)))


@partial(jax.jit, static_argnames=("tpack", "s_dim", "kind"))
def _stats_rows(
    base_s: jax.Array, w_group: jax.Array, tpack: int, s_dim: int, kind: str
):
    """(tpack * products * S, N) stat rows = per-tree bootstrap weight x base
    stats, a regressor's label row in its pieces.  base_s: (S, N); w_group:
    (tpack, N)."""
    out = base_s[None, :, :] * w_group[:, None, :]
    if kind == "regression":
        out = label_stat_rows(out[:, 0], out[:, 1], axis=1)
    return out.reshape(-1, base_s.shape[1])


def _prefix_sums(hist: jax.Array) -> jax.Array:
    """Inclusive prefix sums over the last (bin) axis, float32: one product
    with the (B, B) upper triangle of ones (the header's note on prefix
    sums says why a product, and why at HIGHEST)."""
    B = hist.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    tri = (row <= col).astype(jnp.float32)
    return jax.lax.dot_general(
        hist, tri, (((hist.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@partial(jax.jit, static_argnames=("tpack", "nodes", "s_dim", "kind"))
def _split_from_hist(
    H: jax.Array,          # (F_pad, slots, B) slot-packed histogram
    node_tot: jax.Array,   # (tpack, nodes, 3) (w, wy, wy2); None for clf
    feat_valid: jax.Array, # (F_pad,) bool — padding features masked
    tpack: int,
    nodes: int,
    s_dim: int,
    kind: str,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    node_mask: jax.Array = None,  # (tpack, nodes, F_pad) bool — a node's own subset
):
    """Best split per (tree, node) from the slot-packed histogram.  The
    tpack axis is any batch of independent slot groups — lock-step trees in
    the shallow phase, buckets in the deep phase.  node_mask (the per-node
    rule of the header) leaves a node the gains of its own columns alone.

    Returns (best_f_local, best_bin, split_ok, node_w, node_imp, node_val)
    with leading (tpack, nodes) axes; node_val is (tpack, nodes, V)."""
    F_pad, _, B = H.shape
    used = tpack * nodes * s_dim
    hist = H[:, :used, :].reshape(F_pad, tpack, nodes, s_dim, B)
    hist = jnp.transpose(hist, (1, 3, 2, 0, 4))  # (tpack, S, nodes, F, B)
    left = _prefix_sums(hist)
    right = left[..., -1:] - left

    if kind == "regression":
        p_w = node_tot[:, :, 0]
        l_w, l_wy = left[:, 0], left[:, 1]
        r_w, r_wy = right[:, 0], right[:, 1]
        p_wy, p_wy2 = node_tot[:, :, 1], node_tot[:, :, 2]
        # weighted variance gain with the wy^2 terms cancelled:
        # gain = wy_l^2/w_l + wy_r^2/w_r - wy_p^2/w_p
        eps = 1e-12
        gain = (
            l_wy * l_wy / jnp.maximum(l_w, eps)
            + r_wy * r_wy / jnp.maximum(r_w, eps)
            - (p_wy * p_wy / jnp.maximum(p_w, eps))[:, :, None, None]
        )
        p_imp = jnp.maximum(
            p_wy2 / jnp.maximum(p_w, eps)
            - (p_wy / jnp.maximum(p_w, eps)) ** 2,
            0.0,
        )
        p_val = (p_wy / jnp.maximum(p_w, eps))[:, :, None]
    else:
        l_w = left.sum(axis=1)
        r_w = right.sum(axis=1)
        eps = 1e-12
        # parent class counts from the per-node class totals folded into H:
        # total over any feature == node class counts (feature 0's last
        # prefix sum: a sum over hist itself would keep a second, transposed
        # copy of the histogram alive beside the product)
        node_cls = jnp.moveaxis(left[:, :, :, 0, -1], 1, 2)  # (tpack, nodes, S)
        p_w = node_cls.sum(axis=2)
        pw_safe = jnp.maximum(p_w, eps)
        pp = node_cls / pw_safe[:, :, None]
        if kind == "entropy":
            pl_ = left / jnp.maximum(l_w, eps)[:, None]
            pr_ = right / jnp.maximum(r_w, eps)[:, None]
            l_wimp = -(pl_ * jnp.log2(jnp.maximum(pl_, eps))).sum(axis=1) * l_w
            r_wimp = -(pr_ * jnp.log2(jnp.maximum(pr_, eps))).sum(axis=1) * r_w
            p_imp = -(pp * jnp.log2(jnp.maximum(pp, eps))).sum(axis=2)
            p_wimp = p_imp * p_w
        else:  # gini
            # weight x gini as sum_k c_k (w - c_k) / w: EXACTLY 0 for a pure
            # node.  1 - sum p^2 is not, where the division is not exactly
            # rounded (this chip's x / x can read 0.99999994), and a pure
            # node would then "gain" rounding noise and split
            l_wimp = (left * (l_w[:, None] - left)).sum(axis=1) / jnp.maximum(l_w, eps)
            r_wimp = (right * (r_w[:, None] - right)).sum(axis=1) / jnp.maximum(r_w, eps)
            p_wimp = (node_cls * (p_w[:, :, None] - node_cls)).sum(axis=2) / pw_safe
            p_imp = p_wimp / pw_safe
        p_val = pp
        gain = p_wimp[:, :, None, None] - (l_wimp + r_wimp)

    ok_lr = (l_w >= min_samples_leaf) & (r_w >= min_samples_leaf)
    gain = jnp.where(ok_lr, gain, -jnp.inf)
    gain = gain.at[..., -1].set(-jnp.inf)  # last bin: empty right side
    gain = jnp.where(feat_valid[None, None, :, None], gain, -jnp.inf)
    if node_mask is not None:
        gain = jnp.where(node_mask[:, :, :, None], gain, -jnp.inf)
    flat = gain.reshape(tpack, nodes, -1)
    best = jnp.argmax(flat, axis=-1)
    best_gain = jnp.take_along_axis(flat, best[..., None], axis=-1)[..., 0]
    bf = (best // B).astype(jnp.int32)
    bb = (best % B).astype(jnp.int32)
    noise_floor = 1e-6 * p_imp * p_w + 1e-30
    split_ok = (
        jnp.isfinite(best_gain)
        & (p_imp > 0)
        & (best_gain > jnp.maximum(min_impurity_decrease * p_w, noise_floor))
        & (p_w >= 2 * min_samples_leaf)
    )
    return bf, bb, split_ok, p_w, p_imp, p_val


@partial(jax.jit, static_argnames=("nodes",))
def _node_totals(node_rel: jax.Array, stats3: jax.Array, nodes: int):
    """(tpack, nodes, S3) per-node stat sums via a tiny slot matmul:
    node_rel (tpack, N), stats3 (tpack, S3, N).  At Precision.HIGHEST: a
    regressor's rows (w, w*y, w*y*y) are continuous float32, a leaf's
    prediction and a node's variance are quotients of these sums, and the
    default precision is one bfloat16 pass on this backend (8 bits of a
    label); against the 0/1 node mask HIGHEST carries each value whole.
    Row tile by row tile, and the tiles' sums added after: one float32
    accumulator over 400,000 rows of w*y*y drifts by 1e-5 of the sum, two
    levels of some 2,000 and 200 terms by a tenth of that."""
    tpack, n = node_rel.shape
    assert n % _ROW_TILE == 0, "pad rows to _ROW_TILE"
    tiles = n // _ROW_TILE
    with jax.named_scope("forest.totals"):
        rel = node_rel.reshape(tpack, 1, tiles, n // tiles)
        on = (
            rel == jnp.arange(nodes, dtype=node_rel.dtype)[None, :, None, None]
        ).astype(stats3.dtype)  # (tpack, nodes, tiles, tile)
        per_tile = jnp.einsum(
            "tckl,tskl->tkcs", on, stats3.reshape(tpack, -1, tiles, n // tiles),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        # behind a barrier: XLA folds a sum over a product's batch axis (or a
        # second product with ones) back into the one long contraction
        return jax.lax.optimization_barrier(per_tile).sum(axis=1)


@jax.jit
def _route(
    sub: jax.Array,        # (F_pad, N) int32 this level's subset rows
    node_rel: jax.Array,   # (tpack, N)
    bf_local: jax.Array,   # (tpack, nodes) local feature index
    bb: jax.Array,         # (tpack, nodes)
    ok: jax.Array,         # (tpack, nodes) bool
):
    """Scatter-free routing: select each node's split-feature row with a
    one-hot matmul, then move rows to 2c / 2c+1 (sentinel 2*nodes when the
    node stopped)."""
    tpack, nodes = bf_local.shape
    F_pad = sub.shape[0]
    sel = (
        bf_local[:, :, None] == jnp.arange(F_pad, dtype=bf_local.dtype)[None, None, :]
    ).astype(jnp.float32)  # (tpack, nodes, F_pad)
    sel_bins = jnp.einsum(
        "tcf,fn->tcn", sel, sub.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # (tpack, nodes, N): node c's feature row
    on = (
        node_rel[:, None, :]
        == jnp.arange(nodes, dtype=node_rel.dtype)[None, :, None]
    )  # (tpack, nodes, N)
    go_right = (
        (sel_bins > bb[:, :, None]) & on & ok[:, :, None]
    ).any(axis=1)
    stays = (on & ok[:, :, None]).any(axis=1)
    new_rel = jnp.where(
        stays, 2 * node_rel + go_right.astype(jnp.int32), 2 * nodes
    )
    return new_rel


@partial(jax.jit, static_argnames=("f_pad",))
def _pack_rows(sub: jax.Array, f_pad: int) -> jax.Array:
    """(f_pad, N) int8 -> (f_pad//4, N) int32, 4 bin bytes per word, so the
    deep-phase payload sort moves 4 features per operand."""
    # a byte row is widened as it is read: widening `sub` whole first makes
    # XLA write the int32 form of every row (4.5 GB for a classifier's 50
    # subsets of 56 rows) before it packs them
    v = sub.reshape(f_pad // 4, 4, -1)
    b = [v[:, k].astype(jnp.int32) for k in range(4)]
    return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)



# ---------------------------------------------------------------------------
# Fused per-geometry steps.  Each is ONE jit: the level loops dispatch these
# (through the precompiler) and nothing else.  Tree offsets are TRACED with a
# clamped window: the last (partial) group shifts its window back in-bounds
# and blends the overlap back unchanged, so remainders reuse the executable.
# ---------------------------------------------------------------------------


def _record(buf, vals, s0, g0, base: int):
    """Write a window's (C, trees, nodes) split records into tree_buf at
    trees s0.., nodes base..; trees below g0 (the clamp overlap) keep theirs."""
    c, trees, nodes = vals.shape
    old = jax.lax.dynamic_slice(buf, (0, s0, base), (c, trees, nodes))
    fresh = (s0 + jnp.arange(trees)) >= g0
    merged = jnp.where(fresh[None, :, None], vals, old)
    return jax.lax.dynamic_update_slice(buf, merged, (0, s0, base))


def _split_channels(bf, bb, ok, p_w, p_imp, p_val):
    """(5 + V, trees, nodes) float32 from a split search's outputs."""
    f32 = jnp.float32
    return jnp.concatenate(
        [
            jnp.stack(
                [bf.astype(f32), bb.astype(f32), ok.astype(f32), p_w, p_imp]
            ),
            jnp.moveaxis(p_val, -1, 0),
        ]
    )


def _leaf_channels(tot, kind: str):
    """(5 + V, trees, nodes) float32 from leaf-level per-node totals:
    (trees, nodes, 3) regression (w, wy, wy2) or (trees, nodes, S) class
    counts."""
    if kind == "regression":
        w = tot[..., 0]
        w_n = jnp.maximum(w, 1e-12)
        mean = tot[..., 1] / w_n
        imp = jnp.maximum(tot[..., 2] / w_n - mean * mean, 0.0)
        val = mean[..., None]
    else:
        w = tot.sum(axis=-1)
        val = tot / jnp.maximum(w, 1e-12)[..., None]
        if kind == "entropy":
            imp = -(val * jnp.log2(jnp.maximum(val, 1e-12))).sum(axis=-1)
        else:  # as _split_from_hist: exactly 0 for a pure node
            w_n = jnp.maximum(w, 1e-12)
            imp = (tot * (w[..., None] - tot)).sum(axis=-1) / w_n / w_n
    zero = jnp.zeros_like(w)
    return jnp.concatenate(
        [jnp.stack([zero, zero, zero, w, imp]), jnp.moveaxis(val, -1, 0)]
    )


@partial(
    jax.jit,
    static_argnames=(
        "tpack", "nodes", "s_dim", "kind", "n_bins", "F", "msl", "mid",
        "interpret", "subset",
    ),
)
def _shallow_step(
    rel: jax.Array,        # (T, n_pad) int32 — full routing state
    buf: jax.Array,        # (C, T, M) f32 tree_buf
    w_trees: jax.Array,    # (T, n_pad)
    stat_rows: jax.Array,  # (3, n_pad) reg (1,y,y2)*mask | (S, n_pad) clf
    sub: jax.Array,        # (f_pad, n_pad) int8 this group's subset rows
    g0: jax.Array,         # () int32 traced group start
    subset_seed: jax.Array = None,  # () uint32, with `subset`
    *,
    tpack: int,
    nodes: int,
    s_dim: int,
    kind: str,
    n_bins: int,
    F: int,
    msl: float,
    mid: float,
    interpret: bool,
    subset: int = 0,
):
    """One shallow (level, tree-group) step: totals + histogram + split +
    route, updating rel and tree_buf.  Window trees below g0 (clamp overlap)
    keep their routing and their records.  subset > 0: `sub` is the whole
    table (F its columns) and each node searches its own `subset` of them."""
    T, n_pad = rel.shape
    f_pad = sub.shape[0]
    s0 = jnp.minimum(g0, T - tpack)
    rel_g = jax.lax.dynamic_slice(rel, (s0, 0), (tpack, n_pad))
    w_g = jax.lax.dynamic_slice(w_trees, (s0, 0), (tpack, n_pad))
    if kind == "regression":
        base = stat_rows[:2]
        tot = _node_totals(rel_g, stat_rows[None, :, :] * w_g[:, None, :], nodes)
    else:
        base = stat_rows
        tot = None
    with jax.named_scope("forest.hist"):
        stats_s = _stats_rows(base, w_g, tpack, s_dim, kind)
        H = node_histograms(
            sub, rel_g, stats_s, t_pack=tpack, nodes=nodes, s_dim=s_dim,
            n_bins=n_bins, products=_hist_products(kind), interpret=interpret,
        )
        if kind == "regression":
            H = fold_label_products(H, tpack * nodes * s_dim)
    with jax.named_scope("forest.split"):
        feat_valid = jnp.arange(f_pad) < F
        node_mask = _step_subsets(
            subset_seed, s0, tpack, nodes - 1, nodes, F, subset, f_pad
        ) if subset else None
        out = _split_from_hist(
            H, tot, feat_valid, tpack, nodes, s_dim, kind, msl, mid, node_mask
        )
    with jax.named_scope("forest.route"):
        new_rel = _route(sub, rel_g, out[0], out[1], out[2])
        fresh = (s0 + jnp.arange(tpack)) >= g0
        new_rel = jnp.where(fresh[:, None], new_rel, rel_g)
        rel = jax.lax.dynamic_update_slice(rel, new_rel, (s0, 0))
    return rel, _record(buf, _split_channels(*out), s0, g0, nodes - 1)


@partial(jax.jit, static_argnames=("tpack", "nodes", "kind"))
def _shallow_leaf(
    rel: jax.Array,
    buf: jax.Array,
    w_trees: jax.Array,
    stat_rows: jax.Array,
    g0: jax.Array,
    tpack: int,
    nodes: int,
    kind: str,
):
    """Leaf-level totals for one tree group, recorded in tree_buf."""
    T, n_pad = rel.shape
    s0 = jnp.minimum(g0, T - tpack)
    rel_g = jax.lax.dynamic_slice(rel, (s0, 0), (tpack, n_pad))
    w_g = jax.lax.dynamic_slice(w_trees, (s0, 0), (tpack, n_pad))
    tot = _node_totals(rel_g, stat_rows[None, :, :] * w_g[:, None, :], nodes)
    return _record(buf, _leaf_channels(tot, kind), s0, g0, nodes - 1)


@partial(jax.jit, static_argnames=("n_pad", "P", "interpret"))
def _pack_all(
    bins_rows: jax.Array, feats_all: jax.Array, n_pad: int, P: int,
    interpret: bool,
) -> Tuple[jax.Array, ...]:
    """P arrays (T, n_pad) int32: word p of the packed per-tree deep-subset
    rows (4 bins/word).  An array a word: a payload sort takes its words as
    they are, and a word's buffer goes when its sort is done (a slice of a
    (T, P, n_pad) stack is an output of its own, allocated when the host
    enqueues it: 14 of them stood in rf_clf_fit's in-flight set, 1.26 GB).
    Only ceil(F/4) words are packed — feature PADDING rows never ride the
    payload sort; _deep_state re-pads to f_pad after the unpack.  The T
    subsets are ONE gather of T * 4P rows."""
    T, F = feats_all.shape
    # a tree's rows past its subset copy feature 0: zeroed below
    at = jnp.pad(feats_all, ((0, 0), (0, 4 * P - F))).reshape(-1)
    sub = gather_rows_matmul(
        bins_rows, at, f_pad=_pack_rows_pad(T, P), n_pad=n_pad,
        interpret=interpret,
    )[: T * 4 * P].reshape(T, 4 * P, n_pad)
    sub = jnp.where((jnp.arange(4 * P) < F)[None, :, None], sub, 0)
    words = jax.vmap(partial(_pack_rows, f_pad=4))
    return tuple(words(sub[:, 4 * p : 4 * p + 4])[:, 0] for p in range(P))


def _pack_rows_pad(T: int, P: int) -> int:
    """Rows _pack_all's one gather writes: T * 4P up to whole feature blocks."""
    return -(-T * 4 * P // _F_BLOCK) * _F_BLOCK


@partial(jax.jit, static_argnames=("n_buckets", "n2"))
def _sort_part(
    keys: jax.Array,     # (T, n_pad) int32 the rows' segments (_deep_layout)
    dkeys: jax.Array,    # (T, n2 - n_pad) int32 filler keys (_deep_layout)
    payloads: Tuple[jax.Array, ...],  # each (T, n_pad) or (n_pad,), any dtype
    n_buckets: int,
    n2: int,
) -> Tuple[jax.Array, ...]:
    """One group's share of the deep phase's batched bucket sort: the
    payloads it is handed, each (T, n2), in the layout's order.

    Every part sorts by the same UNIQUE combined key (segment * n2 + column)
    of the SAME keys, the layout's own, so every part computes the identical
    permutation, the one the tile map was counted from.  The key's
    uniqueness over a tree's n2 positions is what licenses is_stable=False:
    a stable and an unstable sort then have exactly one correct output, and
    XLA:TPU makes a sort stable by appending an iota and comparing
    (key, iota): a third array moved for one payload under a comparator of
    four compares and a select, 1.52 times this form's time on the chip.
    Here the operand list is the key and the payloads, 1 + k arrays, under
    a one-compare comparator.  Uniqueness needs (n_buckets + 1) * n2 < 2^31
    — 16.6 M rows at 128 buckets, far beyond a single chip's forest
    capacity.

    How many payloads share a sort is _sort_groups' rule.  The compile grows
    with the operands (on the chip's host at the cells' shapes: 7 s at
    k = 1, 20-24 s at k = 4, 27-29 s at k = 5, 67 s for 9 payloads, 101 s
    for 16; the stable form it replaced took 14-17 s at k = 1).  n2 is a
    STATIC bound (_deep_width: n_pad + one tile of filler a bucket), so the
    parts lower at fit entry and compile, concurrently, while the shallow
    phase runs."""
    T, n_pad = keys.shape
    assert (n_buckets + 1) * n2 < 2**31, "combined sort key overflows int32"
    ck = jnp.concatenate([keys, dkeys], axis=1) * np.int32(n2) + jnp.arange(
        n2, dtype=jnp.int32
    )
    full = tuple(
        jnp.pad(jnp.broadcast_to(p, (T, n_pad)), ((0, 0), (0, n2 - n_pad)))
        for p in payloads
    )
    return tuple(
        jax.lax.sort((ck, *full), num_keys=1, dimension=1, is_stable=False)[1:]
    )


_SORT_GROUP = 5  # payloads a sort carries at most


def _sort_groups(n_payloads: int) -> List[Tuple[int, int]]:
    """[start, stop) of the payloads each _sort_part carries, in order: the
    fewest sorts of at most _SORT_GROUP payloads, evenly filled (9 payloads:
    4 + 5; 16: four of 4).  A sort of a key and k payloads moves k + 1
    arrays where k sorts move 2k, and on the chip its time is linear in
    what it moves: 112 + 87 k ms at (25, 2,816,000), 21 + 14.4 k ms at
    (50, 466,944) (PERF.md section 5 has the probe's table), so a payload
    costs 43-45% less at k = 5 than alone.  The bound is the compile's: 7 s
    at k = 1, 27-29 s at k = 5, 67-101 s for a fit's 9 or 16 payloads in one
    sort, on a cold fit's path; the executable's temporaries do not grow
    with k and the outputs are kept until _deep_state whatever the groups,
    so no bound on bytes is needed."""
    n_groups = -(-n_payloads // _SORT_GROUP)
    stops = [n_payloads * (g + 1) // n_groups for g in range(n_groups)]
    return list(zip([0] + stops[:-1], stops))


def _sort_name(group) -> str:
    """A sort's name in its dispatch key, from the payloads it carries:
    sort_part_ and a payload's dtype (i32 | f32), _1d where the trees share
    it, xN where N alike follow each other (sort_part_i32x4,
    sort_part_i32_1dx2_f32_f32_1d)."""
    kinds = (
        ("i32" if a.dtype == jnp.int32 else "f32") + ("_1d" if a.ndim == 1 else "")
        for a in group
    )
    runs = ((kind, len(list(alike))) for kind, alike in groupby(kinds))
    return "sort_part_" + "_".join(
        kind + (f"x{n}" if n > 1 else "") for kind, n in runs
    )


# ---------------------------------------------------------------------------
# The deep phase: levels past the 128-slot budget.
#
# 1. Rows are grouped ONCE per tree by their ancestor at the bucket level via
#    a batched payload sort (the only fast data-movement primitive on this
#    backend — XLA gather/scatter scalarize).  Weight-0 filler rows ride the
#    sort so every (tree, bucket) SEGMENT is a whole number of
#    _ROW_TILE_DEEP tiles and owns at least one.  A row that weighs nothing
#    in a tree (out of its bag, padding, a user weight of 0) adds 0 to every
#    sum, so it belongs to no segment there: it takes the STRAY key, the
#    largest, with the rows whose node stopped in the shallow phase.
# 2. The sorted width n2 = n_pad + n_buckets * _ROW_TILE_DEEP is a static
#    bound (a segment takes less than one tile of filler, an empty one a
#    whole tile); the stray rows and the filler the segments do not use lie
#    behind them, in whole tiles of the stray segment, which the histogram
#    kernel does not stream (forest_hist.node_histograms_segmented): a
#    bootstrapped tree keeps some 590 of its 912 tiles at 400,000 rows.
# 3. Segments never move again: routing keeps rows inside their subtree, so
#    the layout is built once and reused by every deeper level, which runs
#    ONE histogram / split / route step per tree window.
#
# The per-tree deep feature subset rides the sort as packed int32 payload
# (4 bins/word).
# ---------------------------------------------------------------------------


def _deep_width(n_pad: int, n_buckets: int) -> int:
    return n_pad + n_buckets * _ROW_TILE_DEEP


@partial(jax.jit, static_argnames=("n_buckets", "n2"))
def _deep_layout(rel: jax.Array, w_trees: jax.Array, n_buckets: int, n2: int):
    """From the routing state at the bucket level and the trees' row weights:
    the rows' sort keys (T, n_pad) — a row's segment, or n_buckets, the
    stray one, where its node stopped or it weighs nothing in the tree —
    the filler rows' keys (T, n2 - n_pad), each row tile's segment
    (T, n2 / tile) and the tiles a tree keeps (T,): those of its segments,
    the stray ones being its last.  Computed ONCE: the payload sorts take
    these keys (_sort_part), so the tile map and every payload's order
    cannot disagree.  All on the device: the segments' lengths never reach
    the host."""
    T, n_pad = rel.shape
    tile = _ROW_TILE_DEEP
    keys = jnp.where(w_trees > 0, jnp.minimum(rel, n_buckets), n_buckets)
    ids = jnp.arange(n_buckets, dtype=keys.dtype)
    counts = (keys[:, :, None] == ids[None, None, :]).sum(
        axis=1, dtype=jnp.int32
    )  # (T, n_buckets)
    aligned = -(-jnp.maximum(counts, 1) // tile) * tile
    seg_end = jnp.cumsum(aligned, axis=1)
    fill_end = jnp.cumsum(aligned - counts, axis=1)
    at = jnp.arange(n2 - n_pad, dtype=jnp.int32)
    dkeys = (fill_end[:, None, :] <= at[None, :, None]).sum(
        axis=-1, dtype=jnp.int32
    )
    tile0 = jnp.arange(n2 // tile, dtype=jnp.int32) * tile
    tile_seg = (seg_end[:, None, :] <= tile0[None, :, None]).sum(
        axis=-1, dtype=jnp.int32
    )
    return keys, dkeys, tile_seg, seg_end[:, -1] // tile


@partial(jax.jit, static_argnames=("f_pad", "s_dim", "kind"))
def _deep_state(packed_sorted, w_sorted, y_sorted, f_pad: int, s_dim: int, kind: str):
    """The sorted payloads as the level steps read them: subset rows
    (T, f_pad, n2) int8, the histogram's stat rows (T, products * S, n2; a
    regressor's label row in its pieces), the 3-stat rows of regression's
    node totals (T, 3, n2; classification: the stat rows again), and
    segment-local node ids (T, 1, n2), all zero: every row starts at its
    segment's root; filler rows weigh nothing."""
    words = jnp.stack(packed_sorted, axis=1)                 # (T, P, n2)
    T, P, n2 = words.shape
    parts = jnp.stack(
        [(words >> (8 * i)) & 0xFF for i in range(4)], axis=2
    )  # (T, P, 4, n2)
    bins_s = parts.reshape(T, 4 * P, n2).astype(jnp.int8)
    bins_s = jnp.pad(bins_s, ((0, 0), (0, f_pad - 4 * P), (0, 0)))
    if kind == "regression":
        wy = w_sorted * y_sorted
        stats_s = label_stat_rows(w_sorted, wy, axis=1)
        stats3 = jnp.stack([w_sorted, wy, wy * y_sorted], axis=1)
    else:
        cls = jnp.arange(s_dim, dtype=jnp.float32)
        stats_s = w_sorted[:, None, :] * (
            y_sorted[:, None, :] == cls[None, :, None]
        ).astype(jnp.float32)
        stats3 = stats_s
    return bins_s, stats_s, stats3, jnp.zeros((T, 1, n2), jnp.int32)


def _seg_totals(rl, st, seg_oh, local: int):
    """(trees, n_buckets, local, S) per-node stat sums of a tree window.
    rl (trees, tiles, tile) local node ids, st (trees, S, tiles, tile),
    seg_oh (trees, tiles, n_buckets) one-hot of each tile's segment.  Tile
    by tile (a tile lies in one segment), one tree at a time so the node
    one-hot stays a tile-sized temporary.  Both products at
    Precision.HIGHEST, for _node_totals' reason: float32 sums of whatever
    the rows hold (a classifier's whole numbers come out exact)."""
    hi = jax.lax.Precision.HIGHEST

    def one(args):
        rl_t, st_t, oh_t = args
        on = (
            rl_t[:, None, :] == jnp.arange(local, dtype=rl_t.dtype)[None, :, None]
        ).astype(jnp.float32)  # (tiles, local, tile)
        per_tile = jnp.einsum("klc,skc->kls", on, st_t, precision=hi)
        return jnp.einsum("kb,kls->bls", oh_t, per_tile, precision=hi)

    return jax.lax.map(one, (rl, st, seg_oh))


@partial(
    jax.jit,
    static_argnames=(
        "t_chunk", "level", "bucket_level", "s_dim", "kind", "n_bins", "F",
        "msl", "mid", "interpret", "subset",
    ),
)
def _deep_step(
    bins_s: jax.Array,    # (T, f_pad, n2) int8
    rel_loc: jax.Array,   # (T, 1, n2) int32 segment-local node ids
    stats_s: jax.Array,   # (T, products * S, n2)
    stats3: jax.Array,    # (T, 3, n2) regression totals' rows
    tile_seg: jax.Array,  # (T, n_tiles) int32
    buf: jax.Array,       # (C, T, M) tree_buf
    t0: jax.Array,        # () int32 traced window start
    subset_seed: jax.Array = None,  # () uint32, with `subset`
    *,
    t_chunk: int,
    level: int,
    bucket_level: int,
    s_dim: int,
    kind: str,
    n_bins: int,
    F: int,
    msl: float,
    mid: float,
    interpret: bool,
    subset: int = 0,
):
    """One deep (level, tree window) step: segmented histogram + split +
    route, updating rel_loc and tree_buf.  subset > 0: as _shallow_step."""
    T, f_pad, n2 = bins_s.shape
    tile = _ROW_TILE_DEEP
    n_tiles = n2 // tile
    nb = 2**bucket_level
    local = 2 ** (level - bucket_level)
    s0 = jnp.minimum(t0, T - t_chunk)
    seg = jax.lax.dynamic_slice(tile_seg, (s0, 0), (t_chunk, n_tiles))
    rl = jax.lax.dynamic_slice(rel_loc, (s0, 0, 0), (t_chunk, 1, n2))
    rl = rl.reshape(t_chunk, n_tiles, tile)
    seg_oh = (
        seg[:, :, None] == jnp.arange(nb, dtype=seg.dtype)[None, None, :]
    ).astype(jnp.float32)  # stray tiles: all zero

    with jax.named_scope("forest.hist"):
        H = node_histograms_segmented(
            bins_s, rel_loc, stats_s, seg.reshape(-1), s0,
            t_chunk=t_chunk, n_segs=nb, nodes=local, s_dim=s_dim,
            n_bins=n_bins, f_pad=f_pad, products=_hist_products(kind),
            interpret=interpret,
        )  # (t_chunk * nb, f_pad, slots_pad, B): no block for the stray tiles
        slots = local * s_dim
        if kind == "regression":
            H = fold_label_products(H, slots)
        H = H[:, :, :slots, :]

    with jax.named_scope("forest.split"):
        n_seg = t_chunk * nb
        per = max(1, min(n_seg, _SPLIT_CHUNK_BYTES // (slots * f_pad * n_bins * 4)))
        while n_seg % per:
            per -= 1
        Hc = H.reshape(n_seg // per, per, f_pad, slots, n_bins)
        if kind == "regression":
            st3 = jax.lax.dynamic_slice(
                stats3, (s0, 0, 0), (t_chunk, 3, n2)
            ).reshape(t_chunk, 3, n_tiles, tile)
            tot = _seg_totals(rl, st3, seg_oh, local).reshape(
                n_seg // per, per, local, 3
            )
        else:
            tot = jnp.zeros((n_seg // per, 0), jnp.float32)  # unused
        feat_valid = jnp.arange(f_pad) < F
        # a segment's nodes lie side by side in the dense layout's level
        masks = _step_subsets(
            subset_seed, s0, t_chunk, 2**level - 1, nb * local, F, subset, f_pad
        ).reshape(n_seg // per, per, local, f_pad) if subset else None

        def search(args):
            h, t, m = args
            hf = jnp.transpose(h, (1, 0, 2, 3)).reshape(f_pad, per * slots, n_bins)
            return _split_from_hist(
                hf, t if kind == "regression" else None, feat_valid,
                per, local, s_dim, kind, msl, mid, m,
            )

        out = jax.lax.map(search, (Hc, tot, masks))
        bf, bb, ok, p_w, p_imp, p_val = (
            a.reshape((t_chunk, nb, local) + a.shape[3:]) for a in out
        )

    with jax.named_scope("forest.route"):
        # a tile's split table, by a one-hot over its tree's segments
        tab = jnp.stack(
            [bf.astype(jnp.float32), bb.astype(jnp.float32), ok.astype(jnp.float32)]
        )  # (3, t_chunk, nb, local)
        tile_tab = jnp.einsum(
            "tkb,ctbl->ctkl", seg_oh, tab, precision=jax.lax.Precision.HIGHEST
        )  # (3, t_chunk, n_tiles, local)
        on = rl[:, :, None, :] == jnp.arange(local, dtype=rl.dtype)[None, None, :, None]
        # a row's own node's (feature, bin, taken): one pass over the node one-hot
        mine = jnp.where(on[None], tile_tab[..., None], 0.0).sum(axis=3)
        f_row = mine[0].astype(jnp.int32).reshape(t_chunk, 1, n2)
        b_row = mine[1].astype(jnp.int32).reshape(t_chunk, n2)
        stays = (mine[2] > 0.5).reshape(t_chunk, n2)
        bins_c = jax.lax.dynamic_slice(bins_s, (s0, 0, 0), (t_chunk, f_pad, n2))
        own = f_row == jnp.arange(f_pad, dtype=jnp.int32)[None, :, None]
        v_row = jnp.where(own, bins_c.astype(jnp.int32), 0).sum(axis=1)
        old = rl.reshape(t_chunk, n2)
        new = jnp.where(stays, 2 * old + (v_row > b_row).astype(jnp.int32), 2 * local)
        fresh = (s0 + jnp.arange(t_chunk)) >= t0
        new = jnp.where(fresh[:, None], new, old)
        rel_loc = jax.lax.dynamic_update_slice(
            rel_loc, new.reshape(t_chunk, 1, n2), (s0, 0, 0)
        )

    vals = _split_channels(bf, bb, ok, p_w, p_imp, p_val)
    vals = vals.reshape(vals.shape[0], t_chunk, nb * local)
    return rel_loc, _record(buf, vals, s0, t0, 2**level - 1)


@partial(jax.jit, static_argnames=("level", "bucket_level", "kind"))
def _deep_leaf(rel_loc, stats3, tile_seg, buf, level: int, bucket_level: int, kind: str):
    """Leaf-level per-node totals of every tree, recorded in tree_buf."""
    T, _, n2 = rel_loc.shape
    tile = _ROW_TILE_DEEP
    n_tiles = n2 // tile
    nb = 2**bucket_level
    local = 2 ** (level - bucket_level)
    seg_oh = (
        tile_seg[:, :, None] == jnp.arange(nb, dtype=tile_seg.dtype)[None, None, :]
    ).astype(jnp.float32)
    tot = _seg_totals(
        rel_loc.reshape(T, n_tiles, tile),
        stats3.reshape(T, stats3.shape[1], n_tiles, tile),
        seg_oh, local,
    )  # (T, nb, local, S)
    vals = _leaf_channels(tot.reshape(T, nb * local, tot.shape[-1]), kind)
    zero = jnp.zeros((), jnp.int32)
    return _record(buf, vals, zero, zero, 2**level - 1)


def tree_state_bytes(
    n_pad: int, n_cols: int, max_features: int, max_depth: int, s_dim: int, kind: str
) -> int:
    """Device bytes ONE tree holds where this builder holds most (every tree
    is in flight at once: the T axis of w_trees, rel and the deep state), from
    the fit's static geometry.  Without a deep phase: its weights and its
    routing state.  With one, at _deep_state's dispatch: its weights and sort
    keys (n_pad words each), the sorted payloads going in (P packed words, w
    and y) and the deep state coming out (f_pad subset rows of a byte, the
    stat rows, regression's three total rows, the local node ids), n2 wide.
    A LOWER bound on a tree's share of the fit's peak (the sorts' and the
    steps' own temporaries come on top): a forest this bound refuses could not
    have been grown; one it admits may still not fit."""
    if max_depth <= _shallow_levels(s_dim):
        return 8 * n_pad
    F = n_cols if per_node_subsets(n_cols, max_features) else min(max_features, n_cols)
    n2 = _deep_width(n_pad, 2 ** (_shallow_levels(s_dim) + 1))
    f_pad = -(-max(F, 4) // _F_BLOCK) * _F_BLOCK
    words = -(-F // 4) + 2 + _hist_products(kind) * s_dim + 1
    if kind == "regression":
        words += 3
    return 8 * n_pad + n2 * (4 * words + f_pad)


class ForestPlan(NamedTuple):
    """What pack_forest needs beside tree_buf's host copy: the fit's static
    geometry and the feature subsets the host drew (the device records a
    split's feature by its index in the subset that was searched)."""

    max_depth: int
    n_values: int
    max_features: int
    shallow: List[Tuple[int, int, int, np.ndarray]]  # (level, g0, g1, feats)
    deep_level: int                                  # first deep level, or -1
    deep_feats: Any                                  # (T, F) int32 or None
    # (rule, seed, F, D) int64 where every node drew its own subset (the
    # model's feature_subset_draw_; max_features is then the table's width)
    subset_draw: Any = None


class _Dispatcher:
    """The fit's dispatches, counted: every executable goes through the
    process's precompiler under a key of its static geometry."""

    def __init__(self):
        self.pc = global_precompiler()
        self.keys: set = set()
        self.n = 0

    def submit(self, key, fn, *avals, **statics):
        self.pc.submit(key, fn, *avals, **statics)

    def call(self, key, fn, *args, **statics):
        self.n += 1
        self.keys.add(key)
        return self.pc.call(key, fn, *args, **statics)


def grow_forest_mxu_device(
    bins_rows: jax.Array,   # (D, tiles, 32, 128) int8 binned features, a feature
                            # a slice (forest_hist.tile_feature_rows of (D, N_pad))
    base_stats: jax.Array,  # (S, N_pad) f32 unweighted stat rows (see below)
    w_trees: jax.Array,     # (T, N_pad) f32 per-tree bootstrap*mask weights
    stats3: jax.Array,      # (3, N_pad) f32 (1, y, y^2)*mask rows (reg) or None
    max_depth: int,
    n_bins: int,
    kind: str,              # "gini" | "entropy" | "regression"
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    seed: int,
    y_vals: jax.Array = None,
    n_rows: int = None,
    interpret: bool = False,
) -> Tuple[Tuple[jax.Array, Any], ForestPlan]:
    """Grow T trees on the device; returns ((tree_buf, kept), plan) with
    nothing read back: the caller fetches the pair in its one batched fetch
    (core.fetch_fit_result) and hands the host copy to pack_forest.  kept:
    the tiles of the deep layout each tree's segments own, (T,) int32 (the
    rest are not streamed: forest.deep_tiles_kept); None without a deep phase.

    base_stats rows: regression -> (1*mask, y*mask); classification ->
    per-class one-hot rows (S = n_classes).  stats3 supplies the per-node
    impurity stats for regression (ignored for classification).  y_vals
    (raw target / class index per row) is required when max_depth exceeds
    the shallow slot budget — the deep phase rebuilds stats from it after
    the segment sort.  n_rows (the frame's rows, without padding) only feeds
    the forest.hist_rows_needed counter."""
    T, n_pad = w_trees.shape
    D = bins_rows.shape[0]
    S = base_stats.shape[0]
    V = 1 if kind == "regression" else S
    assert n_pad % _ROW_TILE == 0
    assert mxu_depth_supported(max_depth, S), "depth exceeds MXU slot budget"
    l_s = _shallow_levels(S)
    shallow_top = min(max_depth, l_s)
    deep = max_depth > l_s
    if deep:
        assert y_vals is not None, "deep growth needs y_vals"
    n_rows = n_pad if n_rows is None else int(n_rows)

    M = 2 ** (max_depth + 1) - 1
    C = 5 + V
    rng = np.random.default_rng(seed)
    # the header's two rules: where every node draws its own subset of
    # max_features columns, F, the width of every scan, is the table's
    per_node = per_node_subsets(D, int(max_features))
    subset = int(max_features) if per_node else 0
    F = D if per_node else int(max_features)
    whole = F >= D  # every scan reads the table's own rows: one copy a fit
    f_pad = -(-max(F, 1) // _F_BLOCK) * _F_BLOCK
    msl = float(min_samples_leaf)
    mid = float(min_impurity_decrease)
    stat_rows = stats3 if kind == "regression" else base_stats
    s_rows = int(stat_rows.shape[0])
    run = _Dispatcher()
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    a_rel, a_buf = aval((T, n_pad), i32), aval((C, T, M), f32)
    a_w, a_t0 = aval((T, n_pad), f32), aval((), i32)
    a_table = aval(bins_rows.shape, i8)

    def tpack_at(level: int) -> int:
        return _even_chunk(T, M_SLOTS // (2**level * S))

    def draw_subset() -> np.ndarray:
        # every feature: in the table's order, so ties go to the lowest feature
        if whole:
            return np.arange(D, dtype=np.int32)
        return rng.choice(D, F, replace=False).astype(np.int32)

    # what the per-node rule adds to a step: its seed (a runtime word) after
    # the offset, its width in the statics and in the key
    seed_arg = (jnp.asarray(np.uint32(seed & 0xFFFFFFFF)),) if per_node else ()
    seed_aval = tuple(aval((), a.dtype) for a in seed_arg)
    subset_static = {"subset": subset} if per_node else {}
    subset_key = tuple(subset_static.items())

    # --- every geometry of the fit, submitted for parallel compilation ----
    k_gather = ("gather_rows", D, n_pad, min(F, D), f_pad, interpret)
    run.submit(
        k_gather, gather_rows_matmul, a_table, aval((min(F, D),), i32),
        f_pad=f_pad, n_pad=n_pad, interpret=interpret,
    )
    shallow_keys = {}
    sort_groups: List[Tuple[int, int]] = []  # none without a deep phase
    for level in range(shallow_top + 1):
        nodes, tpack = 2**level, tpack_at(level)
        if level == max_depth:
            key = ("shallow_leaf", T, n_pad, M, s_rows, tpack, nodes, kind)
            run.submit(
                key, _shallow_leaf, a_rel, a_buf, a_w,
                aval((s_rows, n_pad), f32), a_t0,
                tpack=tpack, nodes=nodes, kind=kind,
            )
        else:
            key = ("shallow_step", T, n_pad, M, s_rows, f_pad, tpack, nodes, S,
                   kind, n_bins, F, msl, mid, interpret) + subset_key
            run.submit(
                key, _shallow_step, a_rel, a_buf, a_w,
                aval((s_rows, n_pad), f32), aval((f_pad, n_pad), i8), a_t0,
                *seed_aval,
                tpack=tpack, nodes=nodes, s_dim=S, kind=kind, n_bins=n_bins,
                F=F, msl=msl, mid=mid, interpret=interpret, **subset_static,
            )
        shallow_keys[level] = key
    if deep:
        bucket_level = l_s + 1
        nb = 2**bucket_level
        P = -(-F // 4)
        f_pad_d = -(-max(F, 4) // _F_BLOCK) * _F_BLOCK
        n2 = _deep_width(n_pad, nb)
        n_tiles = n2 // _ROW_TILE_DEEP
        a_keys = aval((T, n2 - n_pad), i32)
        a_bins = aval((T, f_pad_d, n2), i8)
        a_loc = aval((T, 1, n2), i32)
        a_st = aval((T, _hist_products(kind) * S, n2), f32)
        a_st3 = aval((T, 3 if kind == "regression" else S, n2), f32)
        a_seg = aval((T, n_tiles), i32)
        k_layout = ("deep_layout", T, n_pad, nb, n2)
        run.submit(k_layout, _deep_layout, a_rel, a_w, n_buckets=nb, n2=n2)
        # the whole table's words are the same for every tree: packed once,
        # and a sort hands each tree its copy (as it does the labels')
        t_packed = 1 if whole else T
        k_pack = ("pack_all", D, n_pad, t_packed, min(F, D), P, interpret)
        run.submit(
            k_pack, _pack_all, a_table, aval((t_packed, min(F, D)), i32),
            n_pad=n_pad, P=P, interpret=interpret,
        )
        # the payloads in _deep_state's order: the packed words, w, y
        a_pays = [aval((n_pad,) if whole else (T, n_pad), i32)] * P + [
            a_w, aval((n_pad,), f32),
        ]
        sort_groups = _sort_groups(len(a_pays))
        k_sort = []
        for g0, g1 in sort_groups:
            a_group = tuple(a_pays[g0:g1])
            k_sort.append((_sort_name(a_group), T, n_pad, nb, n2))
            run.submit(
                k_sort[-1], _sort_part, a_rel, a_keys, a_group,
                n_buckets=nb, n2=n2,
            )
        k_state = ("deep_state", T, n2, P, f_pad_d, S, kind)
        run.submit(
            k_state, _deep_state,
            tuple(aval((T, n2), i32) for _ in range(P)),
            aval((T, n2), f32), aval((T, n2), f32),
            f_pad=f_pad_d, s_dim=S, kind=kind,
        )
        deep_keys, deep_chunk = {}, {}
        for level in range(bucket_level, max_depth):
            tc = _deep_chunk(
                T, kind, nb, f_pad_d, 2 ** (level - bucket_level) * S, n_bins
            )
            key = ("deep_step", T, n2, M, f_pad_d, tc, level, bucket_level, S,
                   kind, n_bins, F, msl, mid, interpret) + subset_key
            run.submit(
                key, _deep_step, a_bins, a_loc, a_st, a_st3, a_seg, a_buf, a_t0,
                *seed_aval,
                t_chunk=tc, level=level, bucket_level=bucket_level, s_dim=S,
                kind=kind, n_bins=n_bins, F=F, msl=msl, mid=mid,
                interpret=interpret, **subset_static,
            )
            deep_keys[level], deep_chunk[level] = key, tc
        k_leaf = ("deep_leaf", T, n2, M, max_depth, bucket_level, S, kind)
        run.submit(
            k_leaf, _deep_leaf, a_loc, a_st3, a_seg, a_buf,
            level=max_depth, bucket_level=bucket_level, kind=kind,
        )

    # every subset of the fit, drawn and put on the device before the first
    # dispatch (the device idles then anyway): the level loops below enqueue
    # executables only, so the host runs ahead of the device and a late host
    # costs the device nothing
    shallow: List[Tuple[int, int, int, np.ndarray]] = [
        (level, g0, min(g0 + tpack_at(level), T), draw_subset())
        for level in range(min(shallow_top + 1, max_depth))
        for g0 in range(0, T, tpack_at(level))
    ]
    # one deep subset per tree, shared by its levels >= bucket_level
    deep_feats = np.stack([draw_subset() for _ in range(T)]) if deep else None
    if whole:  # the table's own rows, once for the shallow scans, once for the deep
        staged = jax.device_put([shallow[0][3]] + ([deep_feats[:1]] if deep else []))
    else:
        staged = jax.device_put([f for _lv, _g0, _g1, f in shallow] + ([deep_feats] if deep else []))
    subsets = iter(staged)

    rel = jnp.zeros((T, n_pad), jnp.int32)
    buf = jnp.zeros((C, T, M), jnp.float32)
    hist_rows = hist_needed = gathers = searches = 0
    kept = sub = None

    with profiling.span("forest.shallow"):
        for level in range(shallow_top + 1):
            nodes, tpack = 2**level, tpack_at(level)
            with profiling.span("forest.level", level=level) as sp:
                before = run.n
                for g0 in range(0, T, tpack):
                    g1 = min(g0 + tpack, T)
                    g0_dev = _offset(g0)
                    if level == max_depth:
                        buf = run.call(
                            shallow_keys[level], _shallow_leaf, rel, buf,
                            w_trees, stat_rows, g0_dev,
                            tpack=tpack, nodes=nodes, kind=kind,
                        )
                        continue
                    if sub is None or not whole:
                        sub = run.call(
                            k_gather, gather_rows_matmul, bins_rows, next(subsets),
                            f_pad=f_pad, n_pad=n_pad, interpret=interpret,
                        )
                        gathers += 1
                    rel, buf = run.call(
                        shallow_keys[level], _shallow_step, rel, buf, w_trees,
                        stat_rows, sub, g0_dev, *seed_arg,
                        tpack=tpack, nodes=nodes, s_dim=S, kind=kind,
                        n_bins=n_bins, F=F, msl=msl, mid=mid,
                        interpret=interpret, **subset_static,
                    )
                    hist_rows += tpack * n_pad
                    hist_needed += (g1 - g0) * n_rows
                    searches += (g1 - g0) * nodes
                sp.set(dispatches=run.n - before)
    del sub

    if deep:
        with profiling.span("forest.sort") as sp:
            before = run.n
            keys, dkeys, tile_seg, kept = run.call(
                k_layout, _deep_layout, rel, w_trees, n_buckets=nb, n2=n2
            )
            del rel
            packed = run.call(
                k_pack, _pack_all, bins_rows, next(subsets),
                n_pad=n_pad, P=P, interpret=interpret,
            )
            # the payloads in _deep_state's order; the words packed once
            # for all trees are (n_pad,) payloads
            payloads = [word[0] if whole else word for word in packed]
            payloads += [w_trees, y_vals]
            del packed
            done: List[jax.Array] = []
            for key, (g0, g1) in zip(k_sort, sort_groups):
                done += run.call(
                    key, _sort_part, keys, dkeys, tuple(payloads[g0:g1]),
                    n_buckets=nb, n2=n2,
                )
                payloads[g0:g1] = [None] * (g1 - g0)  # a word goes with its sort
            *packed_sorted, w_sorted, y_sorted = done
            packed_sorted = tuple(packed_sorted)
            del done, payloads
            bins_s, stats_s, st3, rel_loc = run.call(
                k_state, _deep_state, packed_sorted, w_sorted, y_sorted,
                f_pad=f_pad_d, s_dim=S, kind=kind,
            )
            del packed_sorted, w_sorted, y_sorted, keys
            sp.set(dispatches=run.n - before)
        with profiling.span("forest.deep"):
            for level in range(bucket_level, max_depth):
                tc = deep_chunk[level]
                with profiling.span("forest.level", level=level) as sp:
                    before = run.n
                    for t0 in range(0, T, tc):
                        rel_loc, buf = run.call(
                            deep_keys[level], _deep_step, bins_s, rel_loc,
                            stats_s, st3, tile_seg, buf, _offset(t0), *seed_arg,
                            t_chunk=tc, level=level, bucket_level=bucket_level,
                            s_dim=S, kind=kind, n_bins=n_bins, F=F, msl=msl,
                            mid=mid, interpret=interpret, **subset_static,
                        )
                        hist_rows += tc * n2
                        hist_needed += (min(t0 + tc, T) - t0) * n_rows
                        searches += (min(t0 + tc, T) - t0) * 2**level
                    sp.set(dispatches=run.n - before)
            with profiling.span("forest.level", level=max_depth, dispatches=1):
                buf = run.call(
                    k_leaf, _deep_leaf, rel_loc, st3, tile_seg, buf,
                    level=max_depth, bucket_level=bucket_level, kind=kind,
                )

    profiling.incr_counter("forest.levels", max_depth + 1)
    profiling.incr_counter("forest.dispatches", run.n)
    profiling.incr_counter("forest.geometries", len(run.keys))
    profiling.incr_counter("forest.hist_rows", hist_rows)
    profiling.incr_counter("forest.hist_rows_needed", hist_needed)
    # the row tiles the deep kernel's grids cover (0 without a deep phase: the
    # counter exists wherever this builder ran); what of them it streams is on
    # the device until the fetch (pack_forest: forest.deep_tiles_kept)
    profiling.incr_counter(
        "forest.deep_tiles",
        T * n_tiles * (max_depth - bucket_level) if deep else 0,
    )
    # the deep phase's payload sorts, static at dispatch: the fits that ran
    # them, those whose sorts carried no stability operand (every one: the
    # key is unique, _sort_part), the sorts and the arrays they carry, keys
    # counted (0 without a deep phase)
    profiling.incr_counter("forest.sort_fits", int(deep))
    profiling.incr_counter("forest.unique_key_sort_fits", int(deep))
    profiling.incr_counter("forest.sort_dispatches", len(sort_groups))
    profiling.incr_counter(
        "forest.sort_operands", sum(1 + g1 - g0 for g0, g1 in sort_groups)
    )
    profiling.incr_counter(
        "forest.gather_bytes",
        gathers * f_pad * n_pad
        + (_pack_rows_pad(t_packed, P) * n_pad if deep else 0),
    )
    # the nodes that searched a split, and what a search was handed: the
    # feature rows the histogram kernels built one-hots for (whole blocks,
    # both phases' f_pad is F's) against the columns a node could split on
    profiling.incr_counter("forest.node_subsets", searches if per_node else 0)
    profiling.incr_counter("forest.hist_feature_rows", searches * f_pad)
    profiling.incr_counter(
        "forest.searched_features", searches * (subset if per_node else min(F, D))
    )
    if kind == "regression":
        profiling.incr_counter("forest.label_pieces", LABEL_PIECES)
    plan = ForestPlan(
        max_depth, V, F, shallow, bucket_level if deep else -1, deep_feats,
        np.array([SUBSET_RULE, seed & 0xFFFFFFFF, subset, D], np.int64) if per_node else None,
    )
    return (buf, kept), plan


def pack_forest(
    grown: Tuple[np.ndarray, Any], plan: ForestPlan, edges: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The host copy of grow_forest_mxu_device's (tree_buf, kept) ->
    grow_forest's dense arrays: (features (T, M), thresholds, leaf_values
    (T, M, V), n_samples, impurities).  A split's feature was recorded by its
    index in the subset searched; the subset is the host's, and the threshold
    is that feature's edge at the split bin.  kept feeds a counter alone."""
    buf, kept = grown
    if kept is not None:
        profiling.incr_counter(
            "forest.deep_tiles_kept",
            int(np.sum(kept, dtype=np.int64)) * (plan.max_depth - plan.deep_level),
        )
    buf = np.asarray(buf)
    T, M = buf.shape[1:]
    edges = np.asarray(edges)
    # only the nodes that split have a feature and a threshold to look up
    ok = buf[_CH_OK] > 0.5
    at_t, at_n = np.nonzero(ok)
    local_f = np.minimum(buf[_CH_F][at_t, at_n].astype(np.int32), plan.max_features - 1)
    bb = np.minimum(buf[_CH_B][at_t, at_n].astype(np.int32), edges.shape[1] - 1)
    gf = np.empty(at_t.shape, np.int32)
    level = np.floor(np.log2(at_n + 1)).astype(np.int32)
    deep = level >= plan.deep_level if plan.deep_level >= 0 else np.zeros(at_t.shape, bool)
    if deep.any():
        gf[deep] = plan.deep_feats[at_t[deep], local_f[deep]]
    # shallow: the subset of the node's (level, tree group), from a table of them
    if plan.shallow:
        subset_of = np.full((plan.max_depth + 1, T), -1, np.int32)
        for k, (lv, g0, g1, _feats) in enumerate(plan.shallow):
            subset_of[lv, g0:g1] = k
        table = np.stack([f for _lv, _g0, _g1, f in plan.shallow])
        sh = ~deep
        gf[sh] = table[subset_of[level[sh], at_t[sh]], local_f[sh]]
    feature = np.full((T, M), -1, np.int32)
    feature[at_t, at_n] = gf
    threshold = np.zeros((T, M), np.float32)
    threshold[at_t, at_n] = edges[gf, bb]
    leaf_value = np.ascontiguousarray(np.moveaxis(buf[_CH_VAL:], 0, -1))
    profiling.incr_counter("forest.nodes", int(2 * at_t.size + T))
    return feature, threshold, leaf_value, buf[_CH_W].copy(), buf[_CH_IMP].copy()


def grow_forest_mxu(
    bins_fm: jax.Array,     # (D, N_pad) int8 feature-major binned features
    base_stats: jax.Array,
    w_trees: jax.Array,
    stats3: jax.Array,
    edges: np.ndarray,      # (D, B-1) raw-space bin edges
    **kwargs,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """tile_feature_rows + grow_forest_mxu_device + a plain fetch +
    pack_forest: grow_forest's host-array contract in one call (tests; the
    estimator fetches through core.fetch_fit_result between the two)."""
    grown, plan = grow_forest_mxu_device(
        tile_feature_rows(bins_fm), base_stats, w_trees, stats3, **kwargs
    )
    return pack_forest(jax.device_get(grown), plan, np.asarray(edges))
