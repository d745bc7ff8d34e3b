#
# Histogram-based random-forest builder (binned, level-wise), pure jax.
#
# TPU-native replacement for cuML's RandomForest{Classifier,Regressor}
# (used by the reference at tree.py:292-397).  cuML's node-batched GPU tree
# building has no XLA analog, so the builder is reformulated the way
# XGBoost-style systems map to accelerators (SURVEY.md §7 "hard parts"):
#
#   - features are quantile-binned once (maxBins = n_bins, as the reference's
#     cuml n_bins) -> all split search runs on integer bins
#   - trees grow LEVEL-WISE with static shapes: at level L there are 2^L
#     dense node slots; per-level histograms are segment-sums keyed by
#     (node, bin), vmapped over features; split selection is a pure argmax
#   - rows carry an int32 node id; routing is a gather + compare per level
#   - bootstrap = per-tree Poisson(1) row weights; featureSubsetStrategy =
#     per-node Gumbel top-k feature masks
#
# One stat layout serves both tasks: regression rows carry [w, w*y, w*y^2]
# (variance impurity), classification rows carry w*onehot(y) (gini/entropy).
#
# A dense complete binary tree of size 2^(max_depth+1)-1 holds
# (feature, threshold, leaf flag, leaf value); prediction is max_depth
# gather/compare steps vmapped over trees.  Node histograms at a level are
# chunked (node_batch) so deep levels stay within HBM for wide features.
#
# Since the device-resident engine rework, forest growth (grow_forest) runs
# as a MESH-PARALLEL, SCAN-BATCHED pipeline (see docs/forest_engine.md):
#
#   - MESH-PARALLEL HISTOGRAMS: the binned row matrix, per-tree stats and
#     routing state are row-sharded over DATA_AXIS via shard_map; each
#     device builds per-(tree, node, feature, bin) sums over its local
#     shard and ONE psum per level chunk (parallel/exchange.psum_parts)
#     yields the global histograms replicated everywhere.  Split selection
#     runs replicated; routing stays local to each shard's rows.
#   - SCAN-BATCHED LEVEL GROWTH: SRML_FOREST_LEVEL_BLOCK levels run per
#     jitted dispatch (lax.scan inside the shard_map body); split results
#     scatter into dense (T, M) device tree buffers INSIDE the kernel, so
#     the host loop only checks a per-block any-split flag (on-device early
#     stop mask) and the whole forest crosses the link in ONE device_get at
#     the end.  forest.levels.dispatches / forest.level_syncs /
#     forest.d2h_transfers counters make the collapse observable.
#   - COLD-COMPILE ELIMINATION: every block kernel dispatches through the
#     process-wide AOT executable cache (ops/precompile) keyed on
#     power-of-two node/feat-chunk geometry; all of a fit's block
#     geometries are submitted for parallel compilation at entry, and
#     warm_forest_kernels stages them even earlier (during binning), so a
#     repeat same-shape fit performs ZERO new compilations.
#
# The per-tree grow_tree path below is kept as the sequential REFERENCE
# implementation (exercised by tests); estimator fits always batch trees
# through the engine.
#

from __future__ import annotations

import os
from functools import partial
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec

from .. import profiling
from ..parallel.mesh import (
    DATA_AXIS,
    axis_sharding,
    get_mesh,
    replicated_sharding,
)


class TreeArrays(NamedTuple):
    feature: jax.Array     # (M,) int32, -1 => leaf/unused
    threshold: jax.Array   # (M,) float32 raw-space threshold (go left if x <= t)
    leaf_value: jax.Array  # (M, V) float32
    n_samples: jax.Array   # (M,) float32 weighted sample count (for export)
    impurity: jax.Array    # (M,) float32 node impurity (for export)


def compute_bin_edges(X: np.ndarray, n_bins: int, max_sample: int = 100_000, seed: int = 0) -> np.ndarray:
    """Per-feature quantile bin edges, (D, n_bins-1).  Host-side, computed
    once per fit on a row subsample (the binning role of cuml's n_bins)."""
    n = X.shape[0]
    if n > max_sample:
        idx = np.random.default_rng(seed).choice(n, max_sample, replace=False)
        sample = X[idx]
    else:
        sample = X
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    # one explicit sort + linear interpolation (the np.quantile formula):
    # np.quantile re-partitions per quantile vector internally and took
    # 1.4 s on the benchmark's (2778, 3000) sample where the sort form
    # runs in ~0.15 s — this sits inside every RandomForest fit
    # graftlint: disable=R5 (host-side binning: f64 interpolation on a host subsample, never device math)
    s = np.sort(np.asarray(sample, dtype=np.float64), axis=0)
    pos = qs * (s.shape[0] - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.ceil(pos).astype(np.int64)
    frac = (pos - lo)[:, None]
    edges = (s[lo] * (1.0 - frac) + s[hi] * frac).T.astype(np.float32)
    # strictly increasing edges make searchsorted/thresholds deterministic
    return edges


@partial(jax.jit, static_argnames=("n_bins", "n_cols"))
def _bin_edges_device_kernel(sample: jax.Array, n_bins: int, n_cols: int):
    """Device-side per-feature quantile edges over a (S, D) sample: the
    same sort + linear-interpolation formula as compute_bin_edges, run in
    f32 on device so only the (D, B-1) edge matrix crosses the host link
    (the bf16 sample fetch + host sort it replaces was ~0.5-1.4 s per fit
    at the 400k x 3000 bench shape).  Column-CHUNKED sort under lax.scan:
    one monolithic sort over (S, 3000) is an XLA compile pathology on this
    backend (20+ min), 256-column blocks compile in seconds."""
    S, D = sample.shape
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    pos = qs * (S - 1)
    lo = jnp.asarray(np.floor(pos).astype(np.int32))
    hi = jnp.asarray(np.ceil(pos).astype(np.int32))
    frac = jnp.asarray((pos - np.floor(pos)).astype(np.float32))[:, None]
    C = 256
    d_pad = -(-D // C) * C
    sp = jnp.pad(sample.astype(jnp.float32), ((0, 0), (0, d_pad - D)))

    def body(c, i):
        blk = jax.lax.dynamic_slice(sp, (0, i * C), (S, C))
        srt = jnp.sort(blk, axis=0)
        return c, srt[lo] * (1.0 - frac) + srt[hi] * frac  # (B-1, C)

    _, es = jax.lax.scan(body, 0, jnp.arange(d_pad // C))
    return jnp.transpose(es, (1, 0, 2)).reshape(n_bins - 1, d_pad)[:, :n_cols].T


def compute_bin_edges_device(sample_dev: jax.Array, n_bins: int) -> np.ndarray:
    """Edges (D, n_bins-1) float32 from a DEVICE-resident sample; one
    1.5 MB fetch.  f32 interpolation instead of the host path's float64 —
    a <=1 ulp delta on edge positions, orders of magnitude below the
    sampling error of the ~2.8k-row sample, and used consistently for
    training and prediction thresholds (no train/serve skew)."""
    return np.asarray(
        _bin_edges_device_kernel(
            sample_dev, n_bins=n_bins, n_cols=sample_dev.shape[1]
        )
    )


def bootstrap_chunks(draw) -> List[Tuple[int, int, jax.Array]]:
    """[(first tree, trees, key)]: the key chain of a fit's bootstrap draw, from
    the model's `bootstrap_draw_` = (seed, n_trees, n_rows, tree_chunk).
    tree_chunk 0 is the MXU builder's single draw of all trees; otherwise one
    split of the fit's key for every chunk of trees the mesh engine grows
    together (the chunking shapes the stream, so the model keeps it)."""
    seed, n_trees, _n_rows, tree_chunk = (int(v) for v in np.asarray(draw))
    if tree_chunk == 0:
        return [(0, n_trees, jax.random.PRNGKey((seed + 104729) & 0x7FFFFFFF))]
    key, chunks = jax.random.PRNGKey(seed), []
    for t0 in range(0, n_trees, tree_chunk):
        key, kt = jax.random.split(key)
        chunks.append((t0, min(tree_chunk, n_trees - t0), kt))
    return chunks


def bootstrap_counts(key: jax.Array, n_trees: int, n_rows: int) -> jax.Array:
    """(n_trees, n_rows) float32 Poisson(1) counts of one key."""
    return jax.random.poisson(key, 1.0, (n_trees, n_rows)).astype(jnp.float32)


def bootstrap_weights(draw) -> jax.Array:
    """(n_trees, n_rows) float32 Poisson(1) bootstrap counts of a forest fit,
    drawn again from the model's `bootstrap_draw_`.  bootstrap_chunks and
    bootstrap_counts are the ONE rule of the draw: both builders draw through
    them (the mesh engine chunk by chunk, sharded inside its jit), and so does
    a check.  n_rows counts the padding rows too; their mask is the caller's."""
    n_rows = int(np.asarray(draw)[2])
    return jnp.concatenate([bootstrap_counts(k, tc, n_rows) for _t0, tc, k in bootstrap_chunks(draw)])


@jax.jit
def bin_features(X: jax.Array, edges: jax.Array) -> jax.Array:
    """bin = number of edges strictly below x, in [0, B-1]; x <= edges[b]
    iff bin <= b, so thresholds in raw space are exactly edge values.

    Computed as a compare-accumulate over the B-1 edges (bin = sum_b
    (x > edge_b), identical to searchsorted side='left' on sorted edges)
    instead of searchsorted: binary search lowers to per-element gather
    chains that scalarize on TPU (~minutes for 400k x 3000), while the
    compare-sum is B-1 fused VPU passes over X (~seconds, HBM-bound).

    Bins <= 128 (the common case, and everything the MXU route accepts)
    emit int8 — the full-size int32 bin matrix was a 4.8 GB intermediate
    at the 400k x 3000 benchmark shape, 4x the int8 footprint."""
    # max bin value == number of edges; int8 holds up to 127
    dt = jnp.int8 if edges.shape[1] <= 127 else jnp.int32

    def body(b, acc):
        return acc + (X > edges[:, b][None, :]).astype(dt)

    return jax.lax.fori_loop(
        0, edges.shape[1], body, jnp.zeros(X.shape, dt)
    )


@partial(jax.jit, static_argnames=())
def _bin_chunk_t(X_chunk: jax.Array, edges: jax.Array) -> jax.Array:
    """(C, D) chunk -> (D, C) int8 bins; same compare-accumulate as
    bin_features (see there for why not searchsorted), on the transposed
    chunk so the output is feature-major."""
    Xt = X_chunk.T  # (D, C)

    def body(b, acc):
        return acc + (Xt > edges[:, b][:, None]).astype(jnp.int8)

    return jax.lax.fori_loop(
        0, edges.shape[1], body, jnp.zeros(Xt.shape, jnp.int8)
    )


def bin_features_feature_major(
    X: jax.Array, edges: jax.Array, chunk: int = 65536,
    n_pad: Optional[int] = None, tiled: bool = False,
) -> jax.Array:
    """(N, D) f32 -> (D, n_pad) int8 binned, row-chunked so peak temp memory
    is one (chunk, D) tile instead of a full int32 (N, D) copy (which OOMs
    at the 3000-column benchmark shape).  Requires n_bins <= 128 (int8).
    Trailing columns up to `n_pad` are zero bins (callers mask padded rows
    through weights).  With `tiled` the table comes as the MXU builder's
    subset gather reads it, a feature a slice of whole tiles
    (forest_hist.tile_feature_rows); the fused route lays it out in the
    binning executable itself, so the table is held once."""
    n = X.shape[0]
    if bin_route(X, edges.shape[1]) == "pallas":
        from .pallas_tpu import bin_features_fm_pallas

        return bin_features_fm_pallas(
            jnp.asarray(X), jnp.asarray(edges), n_pad if n_pad else n,
            tiled=tiled,
        )
    out = _bin_features_fm_xla(X, edges, chunk, n_pad)
    if tiled:
        from .forest_hist import tile_feature_rows

        out = tile_feature_rows(out)
    return out


def bin_route(X, n_edges: int) -> str:
    """'pallas' or 'xla': the route bin_features_feature_major takes.  The
    fused VMEM-resident kernel reads X from HBM once instead of once per
    edge (2.9 s -> ~0.2 s at the 400k x 3000 128-bin benchmark shape).
    Multi-device operands keep the XLA path: jit-of-pallas under a
    multi-device NamedSharding lowers through the partitioner, the failure
    mode documented at bin_features_fm_pallas."""
    from .pallas_tpu import pallas_enabled

    single_device = not (
        isinstance(X, jax.Array) and len(X.sharding.device_set) > 1
    )
    fused = pallas_enabled() and n_edges <= 127 and single_device
    return "pallas" if fused else "xla"


def _bin_features_fm_xla(
    X: jax.Array, edges: jax.Array, chunk: int, n_pad: Optional[int]
) -> jax.Array:
    """The XLA compare-accumulate route of bin_features_feature_major: a
    host-level chunk loop over _bin_chunk_t."""
    n, d = X.shape
    chunk = min(chunk, n)
    parts = []
    for i in range(0, n, chunk):
        c = min(chunk, n - i)
        parts.append(
            _bin_chunk_t(jax.lax.dynamic_slice_in_dim(X, i, c), edges)
        )
    if n_pad is not None and n_pad > n:
        parts.append(jnp.zeros((d, n_pad - n), jnp.int8))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _chunk_histogram(Xb, stats, rel_node, lo, node_batch, n_bins):
    """Per-(node, feature, bin) stat sums for nodes [lo, lo+node_batch):
    (S, node_batch, D, n_bins) — S-LEADING, scalar scatters per stat (see
    _impurity_s0: a trailing S axis lane-pads every scatter buffer 40-60x).
    Rows outside the chunk are masked; only one chunk's histogram is live."""
    S = stats.shape[1]
    in_chunk = (rel_node >= lo) & (rel_node < lo + node_batch)
    local = jnp.where(in_chunk, rel_node - lo, node_batch)
    seg = local * n_bins  # (N,)
    stats_s = jnp.where(in_chunk[None, :], stats.T, 0.0)  # (S, N)

    def per_feature(bins_col):
        ids = jnp.where(in_chunk, seg + bins_col, node_batch * n_bins)
        return jnp.stack(
            [
                jax.ops.segment_sum(
                    stats_s[s], ids, num_segments=node_batch * n_bins + 1
                )[:-1]
                for s in range(S)
            ]
        )  # (S, nb*B)

    out = jax.vmap(per_feature, in_axes=1, out_axes=0)(Xb)  # (D, S, nb*B)
    D = Xb.shape[1]
    out = jnp.moveaxis(out, 0, 1).reshape(S, D, node_batch, n_bins)
    return jnp.transpose(out, (0, 2, 1, 3))  # (S, nb, D, B)


def _impurity_s0(stats, kind: str):
    """S-LEADING variant: stats (S, ...) -> (impurity, count).

    Histogram buffers keep the stat axis FIRST because TPU tiles pad the
    last dimension to 128 lanes — an (…, S=2..3) trailing axis inflates
    every scatter buffer and intermediate 40-60x (observed as a 43 GB
    allocation for a 1 GB logical histogram)."""
    if kind == "regression":
        w = stats[0]
        mean = stats[1] / jnp.maximum(w, 1e-12)
        var = stats[2] / jnp.maximum(w, 1e-12) - mean**2
        return jnp.maximum(var, 0.0), w
    w = stats.sum(axis=0)
    p = stats / jnp.maximum(w, 1e-12)[None]
    if kind == "entropy":
        imp = -(p * jnp.log2(jnp.maximum(p, 1e-12))).sum(axis=0)
    else:  # gini
        imp = 1.0 - (p * p).sum(axis=0)
    return imp, w


def _node_value_s0(node_stats, kind: str):
    """node_stats (S, nb) -> value (nb, V); tiny, so the S-axis transpose
    here is free."""
    if kind == "regression":
        return (node_stats[1] / jnp.maximum(node_stats[0], 1e-12))[:, None]
    w = node_stats.sum(axis=0)
    return (node_stats / jnp.maximum(w, 1e-12)[None]).T


def _split_ok(bg, p_w, p_imp, min_samples_leaf, min_impurity_decrease):
    """Shared split gate.  The float-noise guard scales with the parent's
    weighted impurity so tiny label magnitudes still split (an absolute
    floor would not); pure parents (p_imp == 0) are gated explicitly because
    any positive gain there is float32 noise."""
    noise_floor = 1e-6 * p_imp * p_w + 1e-30
    return (
        jnp.isfinite(bg)
        & (p_imp > 0)
        & (bg > jnp.maximum(min_impurity_decrease * p_w, noise_floor))
        & (p_w >= 2 * min_samples_leaf)
    )


def _best_split_from_hist(hist, kind, min_samples_leaf):
    """hist (S, nb, Dc, B) S-LEADING (see _impurity_s0) ->
    (gain (nb, Dc, B), p_w (nb,), p_imp (nb,), p_val (nb, V)) with the
    Spark/cuml weighted-impurity-decrease gain semantics; the empty-right
    last bin and min_samples_leaf gating applied."""
    left = jnp.cumsum(hist, axis=-1)
    total = left[..., -1:]
    right = total - left
    l_imp, l_w = _impurity_s0(left, kind)
    r_imp, r_w = _impurity_s0(right, kind)
    node_stats = total[:, :, 0, 0]  # (S, nb); identical across features
    p_imp, p_w = _impurity_s0(node_stats, kind)
    p_val = _node_value_s0(node_stats, kind)
    gain = p_imp[:, None, None] * p_w[:, None, None] - (l_imp * l_w + r_imp * r_w)
    ok = (l_w >= min_samples_leaf) & (r_w >= min_samples_leaf)
    gain = jnp.where(ok, gain, -jnp.inf)
    gain = gain.at[:, :, -1].set(-jnp.inf)  # last bin = empty right side
    return gain, p_w, p_imp, p_val


def _wide_split_search(
    Xb,
    stats_s,     # (S, tile*N) masked scalar stat rows (S-leading)
    base_ids,    # (tile*N,) combined-node*B base segment ids
    tile,        # how many times each bin column repeats (trees in lock-step)
    combined,    # total (tree, node) slots at this level
    key,
    n_bins,
    feat_batch,
    kind,
    max_features,
    min_samples_leaf,
    min_impurity_decrease,
    combine_hist=None,
):
    """Shared body of the wide (pass-per-level) split search: ONE segment_sum
    pass over the rows per feature (ids = combined_node * n_bins + bin),
    chunked over FEATURES to bound the histogram buffer.  Used by
    level_split_kernel_wide (tile=1) and the mesh-parallel level-block
    engine (tile=T), which passes `combine_hist` = a psum over DATA_AXIS so
    per-shard partial histograms become global sums (one collective per
    feature chunk — one per level when the chunk covers all features)
    before any gain math runs.

    Returns flat (bf, bb, split_ok, p_w, p_imp, p_val) over the combined
    node axis."""
    D = Xb.shape[1]
    S = stats_s.shape[0]
    B = n_bins
    n_chunks = -(-D // feat_batch)

    if max_features < D:
        # per-node exact-size random feature subset: threshold at the
        # max_features-th largest of per-(node, feature) uniform scores.
        # Drawn f32 EXPLICITLY: the default float dtype flips to f64 under
        # an x64 fit, and AOT executables lowered on the precompile worker
        # threads (outside the fit's enable_x64 scope) would then draw
        # different subsets than an inline jit trace — the draw must not
        # depend on precision scope or warm path
        scores = jax.random.uniform(key, (combined, D), dtype=jnp.float32)
        kth = jax.lax.top_k(scores, max_features)[0][:, -1]
        fmask_full = scores >= kth[:, None]  # (combined, D)

    def one_chunk(c):
        # clamped start keeps the slice in-bounds when feat_batch does not
        # divide D; overlapped features are merely evaluated twice (same
        # gain, same index), which cannot change the argmax result
        start = jnp.minimum(c * feat_batch, D - feat_batch)
        cols = jax.lax.dynamic_slice_in_dim(Xb, start, feat_batch, axis=1)

        # scan (not vmap) over the chunk's features: vmap would broadcast
        # the (S, rows) stat operand per feature
        def step(carry, bcol):
            ids = base_ids + (jnp.tile(bcol, tile) if tile > 1 else bcol)
            h = jnp.stack(
                [
                    jax.ops.segment_sum(stats_s[s], ids, num_segments=combined * B)
                    for s in range(S)
                ]
            )
            return carry, h

        _, hist = jax.lax.scan(step, 0, cols.T)  # (fc, S, combined*B)
        if combine_hist is not None:
            hist = combine_hist(hist)  # shard partials -> global sums
        hist = jnp.transpose(
            jnp.moveaxis(hist, 0, 1).reshape(S, feat_batch, combined, B),
            (0, 2, 1, 3),
        )  # (S, combined, fc, B)
        gain, p_w, p_imp, p_val = _best_split_from_hist(
            hist, kind, min_samples_leaf
        )
        if max_features < D:
            fmask = jax.lax.dynamic_slice_in_dim(fmask_full, start, feat_batch, axis=1)
            gain = jnp.where(fmask[:, :, None], gain, -jnp.inf)
        flat = gain.reshape(combined, -1)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        bf = (start + best // B).astype(jnp.int32)
        bb = (best % B).astype(jnp.int32)
        return bf, bb, best_gain, p_w, p_imp, p_val

    def combine(carry, c):
        bf, bb, bg, p_w, p_imp, p_val = one_chunk(c)
        cbf, cbb, cbg = carry
        better = bg > cbg
        return (
            (jnp.where(better, bf, cbf), jnp.where(better, bb, cbb), jnp.maximum(bg, cbg)),
            (p_w, p_imp, p_val),
        )

    init = (
        jnp.zeros(combined, jnp.int32),
        jnp.zeros(combined, jnp.int32),
        jnp.full(combined, -jnp.inf),
    )
    (bf, bb, bg), aux = jax.lax.scan(
        combine, init, jnp.arange(n_chunks, dtype=jnp.int32)
    )
    p_w, p_imp, p_val = (a[0] for a in aux)  # identical across chunks
    split_ok = _split_ok(bg, p_w, p_imp, min_samples_leaf, min_impurity_decrease)
    return bf, bb, split_ok, p_w, p_imp, p_val


@partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "feat_batch", "kind", "max_features"),
)
def level_split_kernel_wide(
    Xb: jax.Array,
    stats: jax.Array,
    rel_node: jax.Array,
    key: jax.Array,
    n_nodes: int,
    n_bins: int,
    feat_batch: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
):
    """Deep-level growth for one tree: the pass-per-level formulation that
    makes depth-13 forests tractable (the node-chunked kernel below rescans
    all rows once per node chunk — 32+ full passes at 2^13 nodes).

    Same return contract as level_split_kernel."""
    active = rel_node < n_nodes
    stats_s = jnp.where(active[None, :], stats.T, 0.0)  # (S, N)
    base_ids = jnp.where(active, rel_node, 0) * n_bins
    return _wide_split_search(
        Xb, stats_s, base_ids, 1, n_nodes, key, n_bins, feat_batch, kind,
        max_features, min_samples_leaf, min_impurity_decrease,
    )


@partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "node_batch", "kind", "max_features"),
)
def level_split_kernel(
    Xb: jax.Array,
    stats: jax.Array,
    rel_node: jax.Array,
    key: jax.Array,
    n_nodes: int,
    n_bins: int,
    node_batch: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
):
    """One level of growth: chunked histograms -> best (feature, bin) per
    node.  Only one (node_batch, D, B, S) histogram is live at a time; per
    node only scalars + the value vector escape the chunk loop.

    Returns (best_feature (n,), best_bin (n,), split_ok (n,), node_count (n,),
    node_impurity (n,), node_value (n, V)).
    """
    D = Xb.shape[1]
    n_chunks = -(-n_nodes // node_batch)

    def one_chunk(c):
        lo = c * node_batch
        hist = _chunk_histogram(Xb, stats, rel_node, lo, node_batch, n_bins)
        gain, p_w, p_imp, p_val = _best_split_from_hist(
            hist, kind, min_samples_leaf
        )
        if max_features < D:
            # per-node random feature subset (featureSubsetStrategy)
            scores = jax.random.uniform(
                jax.random.fold_in(key, c), (node_batch, D)
            )
            kth = -jnp.sort(-scores, axis=1)[:, max_features - 1]
            fmask = scores >= kth[:, None]
            gain = jnp.where(fmask[:, :, None], gain, -jnp.inf)
        flat = gain.reshape(node_batch, -1)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        return (
            (best // n_bins).astype(jnp.int32),
            (best % n_bins).astype(jnp.int32),
            best_gain,
            p_w,
            p_imp,
            p_val,
        )

    bf, bb, bg, p_w, p_imp, p_val = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    bf = bf.reshape(-1)[:n_nodes]
    bb = bb.reshape(-1)[:n_nodes]
    bg = bg.reshape(-1)[:n_nodes]
    p_w = p_w.reshape(-1)[:n_nodes]
    p_imp = p_imp.reshape(-1)[:n_nodes]
    p_val = p_val.reshape(n_chunks * node_batch, -1)[:n_nodes]
    split_ok = _split_ok(bg, p_w, p_imp, min_samples_leaf, min_impurity_decrease)
    return bf, bb, split_ok, p_w, p_imp, p_val


@jax.jit
def route_rows_kernel(Xb, rel_node, abs_node, best_feature, best_bin, split_ok):
    """Send each active row to its child; rows on leaf nodes become inactive.

    rel_node: index within level (sentinel n_nodes for inactive);
    abs_node: dense-tree absolute index.  Returns (new_rel, new_abs)."""
    n_nodes = best_feature.shape[0]
    active = rel_node < n_nodes
    safe_rel = jnp.minimum(rel_node, n_nodes - 1)
    f = best_feature[safe_rel]
    b = best_bin[safe_rel]
    ok = split_ok[safe_rel] & active
    row_bin = jnp.take_along_axis(Xb, f[:, None].astype(jnp.int32), axis=1)[:, 0]
    go_right = (row_bin > b).astype(jnp.int32)
    new_rel = jnp.where(ok, 2 * rel_node + go_right, 2 * n_nodes)
    new_abs = jnp.where(ok, 2 * abs_node + 1 + go_right, abs_node)
    return new_rel, new_abs


@partial(jax.jit, static_argnames=("max_depth",))
def forest_predict_kernel(
    X: jax.Array,
    feature: jax.Array,    # (T, M) int32
    threshold: jax.Array,  # (T, M) float32
    leaf_value: jax.Array, # (T, M, V)
    max_depth: int,
) -> jax.Array:
    """Average of per-tree leaf values, (N, V).  max_depth gather/compare
    steps; vmapped over trees."""

    def one_tree(feat, thr, values):
        def step(_, node):
            f = feat[node]
            is_leaf = f < 0
            x = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
            child = 2 * node + 1 + (x > thr[node]).astype(jnp.int32)
            return jnp.where(is_leaf, node, child)

        node = jax.lax.fori_loop(
            0, max_depth, step, jnp.zeros(X.shape[0], jnp.int32)
        )
        return values[node]

    per_tree = jax.vmap(one_tree)(feature, threshold, leaf_value)  # (T, N, V)
    return per_tree.mean(axis=0)


def forest_predict_cached(
    X: jax.Array,
    feature: jax.Array,
    threshold: jax.Array,
    leaf_value: jax.Array,
    max_depth: int,
) -> jax.Array:
    """forest_predict_kernel through the process-wide AOT executable cache,
    with the row count padded to the shared power-of-two bucket — repeat
    transforms at ANY partition size land on a handful of cached
    executables instead of one compile per distinct batch length."""
    from .precompile import cached_kernel, shape_bucket

    n = X.shape[0]
    b = shape_bucket(n)
    Xp = jnp.pad(X, ((0, b - n), (0, 0))) if b != n else X
    out = cached_kernel(
        "forest_predict", forest_predict_kernel, Xp, feature, threshold,
        leaf_value, max_depth=max_depth,
    )
    return out[:n]


# ---------------------------------------------------------------------------
# Device-resident mesh-parallel engine (the estimator growth path).
# ---------------------------------------------------------------------------

# inactive-row node id: far above any dense level's node range (depth <= 16
# -> rel < 2^16) and never doubled (retired rows are WRITTEN the sentinel,
# not routed), so it cannot overflow or collide across level blocks
_SENTINEL = np.int32(1 << 20)


def _p2floor(x: int) -> int:
    """Largest power of two <= x (>= 1): node paddings and feature chunks
    draw from this bucketed universe so kernel-geometry cache keys repeat
    across levels, fits and datasets."""
    return 1 << (max(1, int(x)).bit_length() - 1)


def _level_block() -> int:
    """Levels fused per engine dispatch (lax.scan)."""
    return max(1, int(os.environ.get("SRML_FOREST_LEVEL_BLOCK", "4")))


# per-chunk histogram buffer budget of the feature-chunked split search
_HIST_BUDGET_BYTES = 256 << 20


def _feat_chunk(n_cols: int, combined: int, n_bins: int, s_dim: int) -> int:
    """Power-of-two feature-chunk width keeping one (fc, S, combined*B)
    histogram under the budget — bucketed (like the node counts) so the
    executable-cache key universe stays small."""
    fc = max(1, _HIST_BUDGET_BYTES // max(1, combined * n_bins * s_dim * 4))
    return max(1, min(_p2floor(fc), _p2floor(n_cols)))


def _forest_block_body(
    Xb: jax.Array,       # (N_loc, D) binned rows (this shard's)
    stats_t: jax.Array,  # (T, N_loc, S) bootstrap-weighted stats
    rel: jax.Array,      # (T, N_loc) node-in-level ids; _SENTINEL = retired
    key: jax.Array,
    *,
    l0: int,
    block: int,
    n_nodes_pad: int,
    max_depth: int,
    n_bins: int,
    feat_batch: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    axis_name: Optional[str] = None,
):
    """`block` growth levels over (a shard of) the rows: per level one
    feature-chunked histogram pass — psum-combined across shards when
    `axis_name` binds a mesh axis — then replicated split selection and
    local row routing, under ONE lax.scan.  Every level in the block runs
    at the block's padded node count n_nodes_pad = 2^(top level); node
    slots a shallower level does not populate carry zero stats and gate
    themselves off through _split_ok, so their outputs are the dense
    layout's leaf defaults."""
    T, n_loc = rel.shape
    S = stats_t.shape[2]
    combined = T * n_nodes_pad
    stats_flat = stats_t.reshape(T * n_loc, S).T  # (S, T*N_loc), S-leading
    tree_base = (jnp.arange(T, dtype=jnp.int32) * n_nodes_pad)[:, None]
    combine = None
    if axis_name is not None:
        from ..parallel.exchange import psum_parts

        # typed section name: uniform exchange.forest.hist_parts.* counters
        combine = lambda h: psum_parts(  # noqa: E731
            h, axis_name, section="forest.hist_parts"
        )

    def level_step(rel_l, li):
        active = rel_l < _SENTINEL
        rel_c = jnp.where(active, rel_l + tree_base, combined).reshape(-1)
        stats_m = jnp.where(active.reshape(-1)[None, :], stats_flat, 0.0)
        base_ids = jnp.where(rel_c < combined, rel_c, 0) * n_bins
        kl = jax.random.fold_in(key, li)
        bf, bb, ok, p_w, p_imp, p_val = _wide_split_search(
            Xb, stats_m, base_ids, T, combined, kl, n_bins, feat_batch,
            kind, max_features, min_samples_leaf, min_impurity_decrease,
            combine_hist=combine,
        )
        rs = lambda x: x.reshape(T, n_nodes_pad, *x.shape[1:])  # noqa: E731
        bf_t, bb_t, pw_t, pi_t, pv_t = rs(bf), rs(bb), rs(p_w), rs(p_imp), rs(p_val)
        # the forest's last level never splits (its nodes are the leaves)
        ok_t = rs(ok) & (li < max_depth)
        # route local rows; rows on leaf (or depth-capped) nodes retire
        safe = jnp.where(active, rel_l, 0)
        f_r = jnp.take_along_axis(bf_t, safe, axis=1)
        b_r = jnp.take_along_axis(bb_t, safe, axis=1)
        ok_r = jnp.take_along_axis(ok_t, safe, axis=1) & active
        row_bin = jax.vmap(
            lambda f: jnp.take_along_axis(
                Xb, f[:, None].astype(jnp.int32), axis=1
            )[:, 0]
        )(f_r)
        go = (row_bin > b_r).astype(jnp.int32)
        new_rel = jnp.where(ok_r, 2 * rel_l + go, _SENTINEL)
        return new_rel, (bf_t, bb_t, ok_t, pw_t, pi_t, pv_t, ok_t.any())

    return jax.lax.scan(
        level_step, rel, l0 + jnp.arange(block, dtype=jnp.int32)
    )


@partial(
    jax.jit,
    static_argnames=(
        "l0", "block", "n_nodes_pad", "max_depth", "n_bins", "feat_batch",
        "kind", "max_features", "min_samples_leaf", "min_impurity_decrease",
        "mesh",
    ),
)
def _forest_block_kernel(
    Xb: jax.Array,
    stats_t: jax.Array,
    rel: jax.Array,
    feature: jax.Array,     # (T, M) int32 dense tree buffers (device)
    threshold: jax.Array,   # (T, M) f32
    leaf_value: jax.Array,  # (T, M, V) f32
    counts: jax.Array,      # (T, M) f32 weighted sample counts
    impurity: jax.Array,    # (T, M) f32
    edges_dev: jax.Array,   # (D, B-1) f32 raw-space bin edges
    key: jax.Array,
    l0: int,
    block: int,
    n_nodes_pad: int,
    max_depth: int,
    n_bins: int,
    feat_batch: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    mesh=None,
):
    """One engine dispatch: `block` scan-batched levels (mesh-parallel via
    shard_map when `mesh` is given, plain GSPMD otherwise) PLUS the dense
    tree-buffer writes — split features, raw-space thresholds (the on-device
    edges gather that used to be a per-level host write), leaf values and
    export stats all land in the (T, M) device buffers, so the host only
    ever reads the per-level any-split flags until the final single fetch."""
    body = partial(
        _forest_block_body,
        l0=l0, block=block, n_nodes_pad=n_nodes_pad, max_depth=max_depth,
        n_bins=n_bins, feat_batch=feat_batch, kind=kind,
        max_features=max_features, min_samples_leaf=min_samples_leaf,
        min_impurity_decrease=min_impurity_decrease,
    )
    if mesh is not None:
        from jax import shard_map

        rel, outs = shard_map(
            partial(body, axis_name=DATA_AXIS),
            mesh=mesh,
            in_specs=(
                PSpec(DATA_AXIS, None),        # Xb rows
                PSpec(None, DATA_AXIS, None),  # stats rows
                PSpec(None, DATA_AXIS),        # routing state rows
                PSpec(),                       # key (replicated)
            ),
            out_specs=(PSpec(None, DATA_AXIS), PSpec()),
            check_vma=False,
        )(Xb, stats_t, rel, key)
    else:
        rel, outs = body(Xb, stats_t, rel, key)
    bf_s, bb_s, ok_s, pw_s, pi_s, pv_s, flags = outs
    D = Xb.shape[1]
    e_cols = edges_dev.shape[1]
    for i, level in enumerate(range(l0, l0 + block)):
        n_nodes = 2**level
        sl = slice(n_nodes - 1, 2 * n_nodes - 1)
        bf_i = bf_s[i, :, :n_nodes]
        bb_i = bb_s[i, :, :n_nodes]
        ok_i = ok_s[i, :, :n_nodes]
        feature = feature.at[:, sl].set(jnp.where(ok_i, bf_i, -1))
        thr = jnp.where(
            ok_i,
            edges_dev[jnp.clip(bf_i, 0, D - 1), jnp.clip(bb_i, 0, e_cols - 1)],
            0.0,
        )
        threshold = threshold.at[:, sl].set(thr.astype(threshold.dtype))
        leaf_value = leaf_value.at[:, sl].set(
            pv_s[i, :, :n_nodes].astype(leaf_value.dtype)
        )
        counts = counts.at[:, sl].set(
            pw_s[i, :, :n_nodes].astype(counts.dtype)
        )
        impurity = impurity.at[:, sl].set(
            pi_s[i, :, :n_nodes].astype(impurity.dtype)
        )
    return feature, threshold, leaf_value, counts, impurity, rel, flags


@partial(jax.jit, static_argnames=("T", "N", "mesh"))
def _init_rel(T: int, N: int, mesh=None):
    """Root routing state, created ON DEVICE (an (T, N) host upload per fit
    would ride the congested link) with the engine's canonical row sharding
    so AOT executables lowered from warmed avals accept it."""
    z = jnp.zeros((T, N), jnp.int32)
    if mesh is not None:
        z = jax.lax.with_sharding_constraint(z, axis_sharding(mesh, 1, 2))
    return z


@partial(jax.jit, static_argnames=("T", "M", "V", "mesh"))
def _init_tree_buffers(T: int, M: int, V: int, mesh=None):
    """Dense (T, M) device tree buffers at their leaf defaults, replicated
    across the mesh (split selection is replicated, so every device writes
    the same values)."""
    bufs = (
        jnp.full((T, M), -1, jnp.int32),
        jnp.zeros((T, M), jnp.float32),
        jnp.zeros((T, M, V), jnp.float32),
        jnp.zeros((T, M), jnp.float32),
        jnp.zeros((T, M), jnp.float32),
    )
    if mesh is not None:
        rep = replicated_sharding(mesh)
        bufs = tuple(jax.lax.with_sharding_constraint(b, rep) for b in bufs)
    return bufs


def _engine_blocks(max_depth: int):
    """(l0, block, n_nodes_pad) per engine dispatch: levels grouped in
    SRML_FOREST_LEVEL_BLOCK runs, each padded to its top level's node
    count (power of two by construction)."""
    lb = _level_block()
    out = []
    for l0 in range(0, max_depth + 1, lb):
        l1 = min(l0 + lb, max_depth + 1)
        out.append((l0, l1 - l0, 2 ** (l1 - 1)))
    return out


def warm_forest_kernels(
    n_rows: int,
    n_cols: int,
    n_trees: int,
    s_dim: int,
    *,
    max_depth: int,
    n_bins: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    mesh=None,
    dtype=np.float32,
) -> list:
    """Submit ahead-of-time compilations for every level-block kernel a
    grow_forest at this geometry will dispatch, so XLA compiles on the
    precompile worker pool WHILE the caller bins features and builds
    per-tree stats (rf_clf's 50 s cold start was almost entirely serial
    level-kernel compiles).  Keys and statics are derived exactly like the
    dispatch path's, and the avals carry the engine's canonical shardings,
    so the first dispatch lands on the warmed executables.  Returns the
    submitted keys (empty when warming is unsound, e.g. multi-process fits
    or rows not padded to the mesh)."""
    from .precompile import global_precompiler, kernel_cache_key

    if jax.process_count() > 1:
        return []
    mesh = mesh or get_mesh(1)
    if int(n_rows) % max(1, mesh.devices.size):
        return []
    T, N, S, D = int(n_trees), int(n_rows), int(s_dim), int(n_cols)
    V = 1 if kind == "regression" else S
    M = 2 ** (max_depth + 1) - 1
    bins_dt = jnp.int8 if n_bins - 1 <= 127 else jnp.int32
    rep = replicated_sharding(mesh)
    sds = jax.ShapeDtypeStruct
    avals = (
        sds((N, D), bins_dt, sharding=axis_sharding(mesh, 0, 2)),
        sds((T, N, S), jnp.dtype(dtype), sharding=axis_sharding(mesh, 1, 3)),
        sds((T, N), jnp.int32, sharding=axis_sharding(mesh, 1, 2)),
        sds((T, M), jnp.int32, sharding=rep),
        sds((T, M), jnp.float32, sharding=rep),
        sds((T, M, V), jnp.float32, sharding=rep),
        sds((T, M), jnp.float32, sharding=rep),
        sds((T, M), jnp.float32, sharding=rep),
        sds((D, n_bins - 1), jnp.float32, sharding=rep),
        sds((2,), jnp.uint32, sharding=rep),
    )
    pc = global_precompiler()
    keys = []
    for l0, block, npad in _engine_blocks(max_depth):
        statics = dict(
            l0=l0, block=block, n_nodes_pad=npad, max_depth=max_depth,
            n_bins=n_bins, feat_batch=_feat_chunk(D, T * npad, n_bins, S),
            kind=kind, max_features=int(max_features),
            min_samples_leaf=float(min_samples_leaf),
            min_impurity_decrease=float(min_impurity_decrease),
        )
        ck = kernel_cache_key("forest_level_block", avals, mesh, statics)
        pc.submit(ck, _forest_block_kernel, *avals, mesh=mesh, **statics)
        keys.append(ck)
    return keys


def grow_forest(
    Xb: jax.Array,
    stats_t: jax.Array,   # (T, N, S) per-tree (bootstrap-weighted) stats
    edges: np.ndarray,
    max_depth: int,
    n_bins: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    seed: int,
    mesh=None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Grow ALL trees as a device-resident, mesh-parallel, scan-batched
    engine: ceil((max_depth+1) / SRML_FOREST_LEVEL_BLOCK) level-block
    dispatches (forest.levels.dispatches), each through the AOT executable
    cache; per block the host reads ONLY the (block,)-bool any-split flags
    (forest.level_syncs — the on-device early-stop mask), and the fitted
    forest crosses the host link in ONE device_get at the end
    (forest.d2h_transfers).  Returns stacked host arrays (features (T, M),
    thresholds, leaf_values (T, M, V), n_samples, impurities) in the same
    dense-tree layout as grow_tree.

    `mesh` shards the histogram work: rows of Xb/stats_t/rel ride
    DATA_AXIS, each device accumulates its shard's histograms and one psum
    per level chunk combines them (parallel/exchange.psum_parts).
    Multi-process fits (jax.process_count() > 1) run the identical math
    through plain GSPMD lowering instead of explicit shard_map — see
    docs/forest_engine.md for the determinism contract."""
    from .precompile import global_precompiler, kernel_cache_key

    T, N, S = stats_t.shape
    D = Xb.shape[1]
    V = 1 if kind == "regression" else S
    M = 2 ** (max_depth + 1) - 1
    # the fixed retired-row sentinel must stay above every live node id
    # (rel < 2^(depth+1) after the deepest routing step) or deep rows would
    # silently read as retired — refuse loudly instead (the estimator's
    # _MAX_SUPPORTED_DEPTH = 16 gate keeps real fits far below this)
    assert 2 ** (max_depth + 1) < int(_SENTINEL), (
        f"max_depth={max_depth} exceeds the engine's sentinel headroom"
    )
    single_ctrl = jax.process_count() == 1
    if mesh is None and single_ctrl:
        mesh = get_mesh(1)
    smesh = mesh if single_ctrl else None
    if smesh is not None:
        assert N % max(1, smesh.devices.size) == 0, (
            "rows must be padded to a multiple of the mesh size"
        )
        # canonical input shardings: repeat fits and warmed avals must
        # present the block kernels identical placements (no-op device_put
        # when the arrays already arrive row-sharded from binning)
        Xb = jax.device_put(Xb, axis_sharding(smesh, 0, 2))
        stats_t = jax.device_put(stats_t, axis_sharding(smesh, 1, 3))
        rep = replicated_sharding(smesh)
        edges_dev = jax.device_put(np.asarray(edges, np.float32), rep)
        key = jax.device_put(jax.random.PRNGKey(seed), rep)
    else:
        edges_dev = jnp.asarray(np.asarray(edges, np.float32))
        key = jax.random.PRNGKey(seed)
    rel = _init_rel(T=T, N=N, mesh=smesh)
    bufs = _init_tree_buffers(T=T, M=M, V=V, mesh=smesh)
    args = [Xb, stats_t, rel, *bufs, edges_dev, key]
    blocks = _engine_blocks(max_depth)
    pc = global_precompiler()
    plan = []
    for l0, block, npad in blocks:
        statics = dict(
            l0=l0, block=block, n_nodes_pad=npad, max_depth=max_depth,
            n_bins=n_bins, feat_batch=_feat_chunk(D, T * npad, n_bins, S),
            kind=kind, max_features=int(max_features),
            min_samples_leaf=float(min_samples_leaf),
            min_impurity_decrease=float(min_impurity_decrease),
        )
        ck = kernel_cache_key(
            "forest_level_block", tuple(args), smesh, statics
        )
        plan.append((ck, statics))
        # parallel AOT compilation of every block from fit entry (sum of
        # compiles -> max); dedups against warm_forest_kernels' submits
        avals = tuple(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
            for a in args
        )
        pc.submit(ck, _forest_block_kernel, *avals, mesh=smesh, **statics)

    top = max_depth
    for (ck, statics), (l0, block, npad) in zip(plan, blocks):
        with profiling.phase("forest.hist", l0=l0, levels=block):
            out = pc.cached_call(
                ck, _forest_block_kernel, *args, mesh=smesh, **statics
            )
        args[2:8] = [out[5], *out[:5]]
        flags = out[6]
        profiling.incr_counter("forest.levels.dispatches")
        profiling.record_event("forest.level_block", l0=l0, block=block)
        with profiling.phase("forest.route"):
            # graftlint: disable=R1 (one tiny early-stop flag read per level BLOCK — the collapsed remnant of the old per-level six-array sync)
            flags_h = np.asarray(jax.device_get(flags)).tolist()
        profiling.incr_counter("forest.level_syncs")
        stopped = False
        for i, any_split in enumerate(flags_h):
            if not any_split:
                top = l0 + i
                stopped = True
                break
        if stopped:
            break

    feature_d, threshold_d, leaf_d, nsamp_d, imp_d = args[3:8]
    M_used = 2 ** (top + 1) - 1
    with profiling.phase("forest.split"):
        # ONE transfer for the whole forest (sliced to the levels actually
        # grown), in place of one device_get round-trip per level
        f_h, t_h, v_h, n_h, i_h = jax.device_get(
            (
                feature_d[:, :M_used],
                threshold_d[:, :M_used],
                leaf_d[:, :M_used],
                nsamp_d[:, :M_used],
                imp_d[:, :M_used],
            )
        )
    profiling.incr_counter("forest.d2h_transfers")
    if M_used == M:
        return f_h, t_h, v_h, n_h, i_h
    feature = np.full((T, M), -1, np.int32)
    threshold = np.zeros((T, M), np.float32)
    leaf_value = np.zeros((T, M, V), np.float32)
    n_samples = np.zeros((T, M), np.float32)
    impurity = np.zeros((T, M), np.float32)
    feature[:, :M_used] = f_h
    threshold[:, :M_used] = t_h
    leaf_value[:, :M_used] = v_h
    n_samples[:, :M_used] = n_h
    impurity[:, :M_used] = i_h
    return feature, threshold, leaf_value, n_samples, impurity


def grow_tree(
    Xb: jax.Array,
    stats: jax.Array,
    edges: np.ndarray,
    max_depth: int,
    n_bins: int,
    kind: str,
    max_features: int,
    min_samples_leaf: float,
    min_impurity_decrease: float,
    seed: int,
    node_batch: int = 256,
) -> TreeArrays:
    """Grow one tree level-by-level (host loop over <= max_depth jitted
    levels; each level kernel is compiled once per shape and cached)."""
    N, D = Xb.shape
    V = 1 if kind == "regression" else stats.shape[1]
    S = stats.shape[1]
    # Cap the node chunk so one (nb, D, B, S) histogram stays ~128 MB: the
    # split-search stack (cumsum/right/gains) holds ~6 copies, and an
    # unbounded nb at wide D (256 x 3000 x 128 -> 786 MB x 6) OOM-crashed
    # the TPU worker at depth 13.  Power-of-two nb keeps the per-level
    # kernel shapes reusable across levels and trees.
    nb_cap = max(8, (128 << 20) // max(D * n_bins * S * 4, 1))
    nb_cap = 1 << (nb_cap.bit_length() - 1)  # round DOWN to a power of two
    node_batch = min(node_batch, nb_cap)
    M = 2 ** (max_depth + 1) - 1
    feature = np.full(M, -1, np.int32)
    threshold = np.zeros(M, np.float32)
    leaf_value = np.zeros((M, V), np.float32)
    n_samples = np.zeros(M, np.float32)
    impurity = np.zeros(M, np.float32)

    rel = jnp.zeros(N, jnp.int32)
    abs_node = jnp.zeros(N, jnp.int32)
    key = jax.random.PRNGKey(seed)
    for level in range(max_depth + 1):
        n_nodes = 2**level
        key, kl = jax.random.split(key)
        if n_nodes > node_batch:
            # deep level: one histogram pass over the rows, feature-chunked
            # (node-chunking would rescan all rows once per chunk)
            fc = max(1, (256 << 20) // (n_nodes * n_bins * S * 4))
            fc = min(D, 1 << (fc.bit_length() - 1))
            bf, bb, ok, cnt, imp, val = level_split_kernel_wide(
                Xb, stats, rel, kl,
                n_nodes=n_nodes, n_bins=n_bins, feat_batch=fc, kind=kind,
                max_features=max_features, min_samples_leaf=min_samples_leaf,
                min_impurity_decrease=min_impurity_decrease,
            )
        else:
            bf, bb, ok, cnt, imp, val = level_split_kernel(
                Xb, stats, rel, kl,
                n_nodes=n_nodes, n_bins=n_bins, node_batch=n_nodes, kind=kind,
                max_features=max_features, min_samples_leaf=min_samples_leaf,
                min_impurity_decrease=min_impurity_decrease,
            )
        if level == max_depth:
            ok = jnp.zeros_like(ok)
        # ONE batched device_get per level: six sequential np.asarray calls
        # each pay a host-link round trip, which dominates steady-state
        # grow time in the host level loop
        # graftlint: disable=R1 (per-LEVEL batched fetch: the host tree builder consumes each level before growing the next)
        bf_h, bb_h, ok_h, cnt_h, imp_h, val_h = jax.device_get(
            (bf, bb, ok, cnt, imp, val)
        )
        base = 2**level - 1  # absolute index of first node in this level
        sl = slice(base, base + n_nodes)
        n_samples[sl] = cnt_h
        impurity[sl] = imp_h
        # every node records its value; internal nodes keep it for export,
        # rows that stop here read it as the leaf value
        leaf_value[sl] = val_h
        feature[sl] = np.where(ok_h, bf_h, -1)
        threshold[sl] = np.where(
            ok_h, edges[np.minimum(bf_h, D - 1), np.minimum(bb_h, edges.shape[1] - 1)], 0.0
        )
        if not ok_h.any() or level == max_depth:
            break
        rel, abs_node = route_rows_kernel(Xb, rel, abs_node, bf, bb, ok)
    return TreeArrays(
        feature=jnp.asarray(feature),
        threshold=jnp.asarray(threshold),
        leaf_value=jnp.asarray(leaf_value),
        n_samples=jnp.asarray(n_samples),
        impurity=jnp.asarray(impurity),
    )
