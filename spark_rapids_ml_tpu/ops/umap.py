#
# UMAP primitives: fuzzy simplicial set construction + SGD layout, pure jax.
#
# TPU-native replacement for cuML's UMAP fit/transform (used by the reference
# at umap.py:926 and :1159).  The algorithm follows the published UMAP
# formulation (McInnes et al.); the implementation is shaped for XLA and,
# since the sharded-engine rework, for the DEVICE MESH:
#
#   - kNN graph from ops/knn.py (exact, mesh-distributed)
#   - smooth-kNN calibration (rho/sigma) as a vectorized fixed-iteration
#     bisection over all points at once
#   - ON-DEVICE GRAPH ASSEMBLY: symmetrize/dedupe/pad runs as jnp sort +
#     searchsorted + gather kernels, so the fuzzy graph never round-trips
#     through the host (the only host sync is one scalar — the P98 degree
#     that fixes the static pad width)
#   - MESH-PARALLEL LAYOUT: the padded head layout is sharded over
#     DATA_AXIS (each device owns a contiguous head block, the embedding is
#     replicated, per-epoch updates are combined with one tiled all-gather
#     through parallel/exchange.allgather_rows); edge firing draws come
#     from counter-based threefry keyed on GLOBAL padded positions, so a
#     fixed seed produces the same embedding on any mesh shape
#   - SCAN-BATCHED EPOCHS: SRML_UMAP_EPOCH_BLOCK epochs run per jitted step
#     via lax.scan, and every step dispatches through the process-wide AOT
#     executable cache (ops/precompile.cached_kernel) — repeat same-shape
#     fits perform zero new compilations
#   - init: "random", or "spectral" = normalized-Laplacian eigenmap of the
#     fuzzy graph via deflated subspace iteration (as cuml/umap-learn)
#
# Phase timers mirror the knn.* set: umap.graph / umap.init / umap.layout /
# umap.transform; process counters: umap.h2d_transfers / umap.h2d_bytes
# (host->device uploads — the graph must ride the link ONCE) and
# umap.layout.dispatches / umap.transform.dispatches (epoch-step launches).
#

from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.extend.random import threefry_2x32

from .. import profiling
from ..parallel.mesh import (
    DATA_AXIS,
    Mesh,
    col_sharding,
    get_mesh,
    padded_row_count,
    replicated_sharding,
)
from jax.sharding import PartitionSpec as PSpec


def find_ab_params(spread: float, min_dist: float) -> Tuple[float, float]:
    """Fit the (a, b) curve 1/(1+a*x^(2b)) to the fuzzy membership target
    (standard UMAP curve fit)."""
    from scipy.optimize import curve_fit

    def curve(x, a, b):
        return 1.0 / (1.0 + a * x ** (2 * b))

    xv = np.linspace(0, spread * 3, 300)
    yv = np.zeros(xv.shape)
    yv[xv < min_dist] = 1.0
    yv[xv >= min_dist] = np.exp(-(xv[xv >= min_dist] - min_dist) / spread)
    params, _ = curve_fit(curve, xv, yv)
    return float(params[0]), float(params[1])


@partial(jax.jit, static_argnames=("n_iters",))
def smooth_knn_calibration(
    knn_dists: jax.Array,  # (n, k) ascending, col 0 may be self (0.0)
    local_connectivity: float = 1.0,
    n_iters: int = 64,
    bandwidth: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Vectorized rho/sigma search: rho = distance to the local_connectivity-th
    nearest nonzero neighbor; sigma solves sum_j exp(-(d_ij - rho)/sigma) =
    log2(k) by bisection (fixed iterations, all points in parallel)."""
    n, k = knn_dists.shape
    target = jnp.log2(k) * bandwidth
    nonzero = knn_dists > 0.0
    # rho: local_connectivity-th smallest nonzero distance (interpolated)
    idx = jnp.int32(jnp.floor(local_connectivity)) - 1
    frac = local_connectivity - jnp.floor(local_connectivity)
    big = jnp.where(nonzero, knn_dists, jnp.inf)
    sorted_nz = jnp.sort(big, axis=1)
    lo_val = sorted_nz[:, jnp.maximum(idx, 0)]
    hi_val = sorted_nz[:, jnp.minimum(idx + 1, k - 1)]
    rho = jnp.where(
        jnp.isfinite(lo_val), lo_val + frac * jnp.where(jnp.isfinite(hi_val), hi_val - lo_val, 0.0), 0.0
    )

    def psum_of(sigma):
        val = jnp.exp(-jnp.maximum(knn_dists - rho[:, None], 0.0) / sigma[:, None])
        return jnp.where(nonzero, val, 1.0).sum(axis=1)

    def body(_, state):
        lo, hi, sigma = state
        cur = psum_of(sigma)
        too_high = cur > target
        hi = jnp.where(too_high, sigma, hi)
        lo = jnp.where(too_high, lo, sigma)
        sigma = jnp.where(jnp.isinf(hi), sigma * 2.0, (lo + hi) / 2.0)
        return lo, hi, sigma

    lo0 = jnp.zeros(n, knn_dists.dtype)
    hi0 = jnp.full(n, jnp.inf, knn_dists.dtype)
    sigma0 = jnp.ones(n, knn_dists.dtype)
    _, _, sigma = jax.lax.fori_loop(0, n_iters, body, (lo0, hi0, sigma0))
    # floor from the mean NONZERO distance (sum/count, not mean over all
    # slots): all-zero padding rows added by callers' power-of-two query
    # bucketing must not dilute the floor, else a query's membership weights
    # would depend on how many rows its partition happened to hold
    nz_count = jnp.maximum(nonzero.sum(), 1)
    mean_d = jnp.where(nonzero, knn_dists, 0.0).sum() / nz_count
    sigma = jnp.maximum(sigma, 1e-3 * mean_d)
    return rho, sigma


@jax.jit
def fuzzy_simplicial_set(
    knn_ids: jax.Array,    # (n, k) int32
    knn_dists: jax.Array,  # (n, k)
    rho: jax.Array,
    sigma: jax.Array,
    set_op_mix_ratio: float = 1.0,
) -> jax.Array:
    """Directed membership strengths (n, k), symmetrized via the fuzzy set
    union/intersection mix: w_sym = mix*(w + wT - w*wT) + (1-mix)*w*wT.
    The transpose lookup stays dense: for each edge (i -> j) we search i in
    j's neighbor list."""
    n, k = knn_ids.shape
    w = jnp.exp(-jnp.maximum(knn_dists - rho[:, None], 0.0) / sigma[:, None])
    w = jnp.where(knn_dists > 0.0, w, jnp.where(knn_ids == jnp.arange(n)[:, None], 0.0, 1.0))
    # w_T[i, j_slot] = weight of edge (j -> i) if present else 0
    rows = jnp.repeat(jnp.arange(n)[:, None], k, axis=1)  # (n, k) source i
    neigh_of_j = knn_ids[knn_ids]          # (n, k, k): neighbors of each j
    w_of_j = w[knn_ids]                    # (n, k, k)
    match = neigh_of_j == rows[:, :, None]
    wT = jnp.where(match, w_of_j, 0.0).max(axis=2)
    return set_op_mix_ratio * (w + wT - w * wT) + (1.0 - set_op_mix_ratio) * (w * wT)


@jax.jit
def categorical_simplicial_set_intersection(
    W: jax.Array,        # (n, k) membership strengths
    knn_ids: jax.Array,  # (n, k) int32
    labels: jax.Array,   # (n,) categorical labels; < 0 means unknown
    far_dist: float = 5.0,
    unknown_dist: float = 1.0,
) -> jax.Array:
    """Supervised UMAP: intersect the data-driven fuzzy set with the label
    partition (umap-learn ``categorical_simplicial_set_intersection``; the
    path cuML takes when the reference passes y= at umap.py:939-945).
    Edges between differently-labeled points are downweighted by
    exp(-far_dist); edges touching an unknown label by exp(-unknown_dist).
    Local connectivity is then reset by renormalizing each row to max 1
    (a dense approximation of umap-learn's reset_local_connectivity)."""
    yi = labels[:, None]
    yj = labels[knn_ids]
    unknown = (yi < 0) | (yj < 0)
    differ = yi != yj
    scale = jnp.where(
        unknown, jnp.exp(-unknown_dist), jnp.where(differ, jnp.exp(-far_dist), 1.0)
    )
    W2 = W * scale
    return W2 / jnp.maximum(W2.max(axis=1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("c", "n_iter"))
def _laplacian_eigenmap_kernel(
    tails_pad: jax.Array,  # (n, P) int32 head-grouped directed neighbors
    w_pad: jax.Array,      # (n, P) symmetric weights (0 = padding)
    key: jax.Array,
    valid_count: jax.Array,  # () rows beyond this are padding (zeroed in x0)
    c: int,
    n_iter: int = 50,
) -> jax.Array:
    """Top non-trivial eigenvectors of the normalized adjacency
    A_hat = D^-1/2 W D^-1/2 by deflated subspace iteration (equivalently the
    bottom eigenvectors of the normalized Laplacian — the spectral embedding
    umap-learn/cuml use for init).  SpMV runs in the padded head-grouped
    layout (gather + axis sum) — the edge-list scatter-add formulation this
    replaces cost ~120M scalar scatter updates for a 50k x 15 graph at 50
    iterations, the single slowest phase of the round-2 UMAP fit.  The
    trivial eigenvector D^1/2*1 is projected out each iteration.

    Padding rows (>= valid_count; zero-degree self-loops by construction)
    are zeroed in the random start and stay exactly zero through every
    SpMV, so they never perturb the subspace the real graph converges to."""
    n, P = tails_pad.shape
    deg = w_pad.sum(axis=1)
    dinv = 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12))
    wn = w_pad * dinv[:, None] * dinv[tails_pad]
    # trivial top eigenvector of A_hat (unit-normalized)
    v0 = jnp.sqrt(jnp.maximum(deg, 0.0))
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-12)

    # Component-sliced SpMV in (P, n) layout: the natural (n, P, c) form
    # puts c (= 2-3 components) in the minor dimension, which TPU tiles pad
    # to 128 lanes — a 64x waste that made this loop ~25 ms/iteration.
    # With n minor every array packs full lanes.  The neighbor values come
    # from ONE flat row-gather with slice width c (hardware-measured: the
    # per-component x[:, j][tails] form scalarizes into c single-element
    # gather chains — 2.6 s for the 50-iteration loop at 50k x 15 where
    # the row-gather form runs it in ~0.5 s; same lesson as the SGD layout
    # epochs).
    tails_T = tails_pad.T  # (P, n)
    wn_T = wn.T
    P_, n_ = tails_T.shape
    flat_tails_T = tails_T.reshape(-1)

    def spmv(x):  # (n, c)
        xt = x[flat_tails_T].T.reshape(c, P_, n_)  # one row-gather
        cols = [(wn_T * xt[j]).sum(axis=0) for j in range(c)]
        return jnp.stack(cols, axis=1)

    def orthonormalize(y):
        y = y - v0[:, None] * (v0 @ y)[None, :]
        g = y.T @ y + 1e-12 * jnp.eye(c)
        r = jnp.linalg.cholesky(g)
        return jax.lax.linalg.triangular_solve(
            r, y, left_side=False, lower=True, transpose_a=True
        )

    row_valid = jnp.arange(n) < valid_count
    x0 = orthonormalize(jax.random.normal(key, (n, c)) * row_valid[:, None])

    def cond(state):
        i, _x, res = state
        # subspace-rotation residual: ||y - x (x^T y)||_F per component.
        # kNN-graph spectra usually converge in 20-35 iterations; the init
        # only needs a good low-frequency embedding, so 3e-3 is plenty
        return (i < n_iter) & (res > 3e-3)

    def body(state):
        i, x, _ = state
        # shift by +1 so the most-positive eigenvalues of A_hat dominate
        # (A_hat spectrum lies in [-1, 1])
        y = orthonormalize(spmv(x) + x)
        res = jnp.linalg.norm(y - x @ (x.T @ y)) / jnp.sqrt(c * 1.0)
        return i + 1, y, res

    _, x, _ = jax.lax.while_loop(cond, body, (0, x0, jnp.inf))
    return x


@jax.jit
def _spectral_scale_noise(emb: jax.Array, key: jax.Array) -> jax.Array:
    """10-box rescale + tiny symmetry-breaking jitter, on device (umap-learn
    scales its spectral init the same way)."""
    scale = jnp.maximum(jnp.abs(emb).max(), 1e-12)
    noise = 1e-4 * jax.random.normal(key, emb.shape)
    return (emb / scale * 10.0 + noise).astype(jnp.float32)


def dedupe_undirected(
    knn_ids: np.ndarray, W: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Directed (n, k) adjacency -> undirected (ii, jj, ww) edge list with
    each pair kept once (host-side REFERENCE implementation; the fit path
    assembles the same layout on device — see build_head_layout_device).
    umap-learn operates on the deduped symmetric COO graph; keeping both
    directed copies of a mutual edge would give it two head-grouped slots
    PER ENDPOINT and so double its SGD firing rate (and double its spectral
    weight)."""
    n, k = knn_ids.shape
    heads = np.repeat(np.arange(n, dtype=np.int64), k)
    tails = knn_ids.astype(np.int64).reshape(-1)
    w = np.asarray(W, dtype=np.float32).reshape(-1)
    keep = (w > 0) & (heads != tails)
    heads, tails, w = heads[keep], tails[keep], w[keep]
    lo = np.minimum(heads, tails)
    hi = np.maximum(heads, tails)
    key_ = lo * n + hi
    # per-pair MAX of the two directed weights: the symmetrized fuzzy set
    # is symmetric (either direction works), but the supervised label
    # intersection row-renormalizes and breaks symmetry — dropping an
    # arbitrary direction there loses the stronger label-informed weight
    order = np.argsort(key_, kind="stable")
    k_s, w_s = key_[order], w[order]
    firsts = np.r_[True, k_s[1:] != k_s[:-1]]
    group = np.cumsum(firsts) - 1
    ww = np.zeros(int(group[-1]) + 1 if group.size else 0, np.float32)
    np.maximum.at(ww, group, w_s)
    sel = order[firsts]
    return lo[sel].astype(np.int32), hi[sel].astype(np.int32), ww


def spectral_from_layout(
    tails_pad,
    w_pad,
    n_components: int,
    seed: int,
) -> np.ndarray:
    """Spectral embedding from an already-built padded head-grouped layout
    (host or device arrays).  Returns (n, c) scaled to the same 10-box
    umap-learn uses."""
    tails_dev = _h2d(tails_pad, np.int32)
    w_dev = _h2d(w_pad, np.float32)
    key = jax.random.PRNGKey(seed)
    emb = _laplacian_eigenmap_kernel(
        tails_dev,
        w_dev,
        key,
        jnp.int32(tails_dev.shape[0]),
        c=int(n_components),
    )
    return np.asarray(_spectral_scale_noise(emb, jax.random.fold_in(key, 0x5CA1E)))


def spectral_init(
    knn_ids: np.ndarray, W: np.ndarray, n_components: int, seed: int
) -> np.ndarray:
    """Spectral embedding of the fuzzy graph (standalone host entry: dedupe +
    layout + subspace iteration)."""
    ii, jj, ww = dedupe_undirected(knn_ids, W)
    n = knn_ids.shape[0]
    tails_pad, w_pad = padded_head_layout(ii, jj, ww, n)
    return spectral_from_layout(tails_pad, w_pad, n_components, seed)


# engine tunables (env-overridable):
#   SRML_UMAP_DEGREE_CAP / SRML_UMAP_DEGREE_QUANTILE — layout truncation:
#     hub-heavy graphs (e.g. scale-free neighborhoods) can raise the cap or
#     the quantile to keep more hub edges at the cost of a wider per-epoch
#     gather; the defaults hold trustworthiness on i.i.d. AND power-law
#     degree graphs (test_umap.test_hub_heavy_graph_layout_quality)
#   SRML_UMAP_EPOCH_BLOCK — epochs fused per jitted layout step (lax.scan);
#     the epoch loop issues ceil(n_epochs / block) dispatches total
def _layout_cap() -> int:
    return int(os.environ.get("SRML_UMAP_DEGREE_CAP", 36))


def _layout_quantile() -> float:
    return float(os.environ.get("SRML_UMAP_DEGREE_QUANTILE", 0.98))


def _epoch_block() -> int:
    return max(1, int(os.environ.get("SRML_UMAP_EPOCH_BLOCK", 50)))


# negative-sample table size per epoch, where `table_size=` is left at 0
_NEG_TABLE = 256


def padded_head_layout(
    heads: np.ndarray,
    tails: np.ndarray,
    weights: np.ndarray,
    n: int,
    cap: int = 0,  # 0 = SRML_UMAP_DEGREE_CAP (default 36)
):
    """Static scatter-free edge layout for the SGD epochs (host-side
    REFERENCE implementation; the fit path builds the same layout on device
    — see build_head_layout_device): every undirected edge becomes two
    directed edges, grouped by head and padded to a fixed per-node degree
    `cap` (padding slots point at the node itself with weight 0, so they
    fire never and their diff is zero).  Hub nodes beyond `cap` keep their
    strongest edges — the truncation umap-learn's epochs_per_sample
    schedule approximates anyway (weak edges of high-degree nodes fire
    rarely).

    Returns (tails_pad (n, P) int32, w_pad (n, P) f32)."""
    h2 = np.concatenate([heads, tails]).astype(np.int64)
    t2 = np.concatenate([tails, heads]).astype(np.int64)
    w2 = np.concatenate([weights, weights]).astype(np.float32)
    keep = w2 > 0
    h2, t2, w2 = h2[keep], t2[keep], w2[keep]
    # weight-descending within each head group so truncation drops the
    # weakest edges.  One argsort of a packed int64 key instead of a
    # two-key lexsort (~2x on the 1.5M-edge benchmark graph): weights are
    # strictly positive f32, whose IEEE bit patterns order identically to
    # their values, so (head << 32) | ~bits(w) is head-major,
    # weight-descending.
    wbits = w2.view(np.uint32).astype(np.int64)
    order = np.argsort((h2 << 32) | (0xFFFFFFFF - wbits), kind="stable")
    h2, t2, w2 = h2[order], t2[order], w2[order]
    counts = np.bincount(h2, minlength=n)
    # pad width from the 98th-percentile degree, not the max: kNN graphs
    # have hub nodes whose degree sets a P that is mostly padding for
    # everyone else, and the per-epoch edge gather is O(P * n) regardless
    # of how many slots are real.  Nodes above the quantile lose only
    # their weakest edges (the weight-descending order below), the same
    # truncation the cap already applied to extreme hubs.
    cap = cap or _layout_cap()
    nz = counts[counts > 0]
    p98 = int(np.quantile(nz, _layout_quantile())) if nz.size else 1
    P = int(min(cap, max(8, p98, 1)))
    starts = np.cumsum(counts) - counts
    pos = np.arange(h2.size) - np.repeat(starts, counts)
    sel = pos < P
    tails_pad = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, P))
    w_pad = np.zeros((n, P), np.float32)
    tails_pad[h2[sel], pos[sel]] = t2[sel].astype(np.int32)
    w_pad[h2[sel], pos[sel]] = w2[sel]
    return tails_pad, w_pad


# -- on-device graph assembly --------------------------------------------------
# The host pipeline this replaces (dedupe_undirected + padded_head_layout,
# both kept above as the reference implementation) fetched the (n, k) fuzzy
# graph to the host, symmetrized/deduped/padded it in numpy, and re-uploaded
# the ~(n, P) layout — a full round-trip of the graph through the host link
# per fit.  Here the same three steps run as jnp kernels on the device the
# calibration already produced W on: edge expansion with the dense transpose
# lookup, ONE lexsort to head-major weight-descending order, and a gather
# (not scatter) into the padded layout.  The single host sync is the P98
# degree scalar that fixes the static pad width P.


@jax.jit
def _graph_edges(knn_ids: jax.Array, W: jax.Array):
    """Directed (n, k) adjacency -> flat directed edge list covering BOTH
    directions of every undirected pair exactly once per endpoint, with the
    per-pair MAX weight (the dedupe_undirected contract).  A pair present in
    both rows (mutual) would emit each direction twice — once forward from
    its own row, once reversed from the partner's — so reversed copies of
    mutual edges are dropped.

    Returns (heads, tails, w, valid, wmax), each flat of size 2nk."""
    n, k = knn_ids.shape
    rows = jnp.broadcast_to(jnp.arange(n, dtype=knn_ids.dtype)[:, None], (n, k))
    # transpose lookup, dense: does i appear in j's neighbor list, and with
    # what weight (same trick as fuzzy_simplicial_set)
    neigh_of_j = knn_ids[knn_ids]          # (n, k, k)
    w_of_j = W[knn_ids]                    # (n, k, k)
    match = neigh_of_j == rows[:, :, None]
    wT = jnp.where(match, w_of_j, 0.0).max(axis=2)
    mutual = match.any(axis=2)
    ws = jnp.maximum(W, wT)                # symmetric per-pair weight
    self_e = knn_ids == rows
    valid_f = (ws > 0.0) & ~self_e
    valid_r = valid_f & ~mutual
    heads = jnp.concatenate([rows.reshape(-1), knn_ids.reshape(-1)])
    tails = jnp.concatenate([knn_ids.reshape(-1), rows.reshape(-1)])
    w2 = jnp.concatenate([ws.reshape(-1), ws.reshape(-1)])
    valid = jnp.concatenate([valid_f.reshape(-1), valid_r.reshape(-1)])
    return heads, tails, w2, valid, W.max()


@partial(jax.jit, static_argnames=("n_pad",))
def _edge_order(heads, tails, w2, valid, wmax, epochs_total, quantile, n_pad):
    """Head-major weight-descending edge order + per-head group geometry.

    Also applies the epoch-schedule prune (edges with w < wmax/n_epochs can
    never fire; dropping them here keeps them out of the pad-width budget)
    and computes the degree quantile that fixes the static pad width P —
    the ONE scalar the host needs before the gather kernel can be shaped."""
    keep = valid & (w2 * epochs_total >= wmax)
    hkey = jnp.where(keep, heads, n_pad).astype(jnp.int32)  # dropped -> end
    order = jnp.lexsort((-w2, hkey))
    sh = hkey[order]
    st = tails[order].astype(jnp.int32)
    sw = w2[order]
    node_ids = jnp.arange(n_pad, dtype=sh.dtype)
    starts = jnp.searchsorted(sh, node_ids)
    ends = jnp.searchsorted(sh, node_ids, side="right")
    deg = (ends - starts).astype(jnp.int32)
    # linear-interpolated quantile of the NONZERO degrees (np.quantile
    # semantics): ascending degree sort puts the zero-degree rows first
    degs = jnp.sort(deg)
    nz = (deg > 0).sum()
    pos = (n_pad - nz).astype(jnp.float32) + quantile * jnp.maximum(
        nz - 1, 0
    ).astype(jnp.float32)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_pad - 1)
    hi = jnp.clip(lo + 1, 0, n_pad - 1)
    frac = pos - lo.astype(jnp.float32)
    qval = degs[lo].astype(jnp.float32) * (1.0 - frac) + degs[hi].astype(
        jnp.float32
    ) * frac
    qval = jnp.where(nz > 0, qval, 1.0)
    return st, sw, starts.astype(jnp.int32), deg, qval


@partial(jax.jit, static_argnames=("P",))
def _gather_layout(st, sw, starts, deg, wmax, P):
    """Sorted edge list -> padded head-grouped (n_pad, P) layout by GATHER
    (slot p of head h reads sorted position starts[h]+p), truncating each
    head to its P strongest edges.  Empty slots self-point with weight 0 so
    they never fire.  Weights come out normalized by wmax — the epoch
    schedule's firing probability."""
    n_pad = starts.shape[0]
    slot = jnp.arange(P, dtype=jnp.int32)[None, :]
    in_group = slot < jnp.minimum(deg, P)[:, None]
    idx = jnp.clip(starts[:, None] + slot, 0, st.shape[0] - 1)
    self_col = jnp.broadcast_to(
        jnp.arange(n_pad, dtype=jnp.int32)[:, None], (n_pad, P)
    )
    tails_pad = jnp.where(in_group, st[idx], self_col)
    w_pad = jnp.where(in_group, sw[idx] / jnp.maximum(wmax, 1e-12), 0.0)
    return tails_pad, w_pad.astype(jnp.float32)


def build_head_layout_device(
    knn_ids_dev: jax.Array,  # (n, k) int32, on device
    W: jax.Array,            # (n, k) f32 membership strengths, on device
    n_pad: int,
    n_epochs: int,
) -> Tuple[jax.Array, jax.Array]:
    """On-device symmetrize + dedupe + pad: (n, k) fuzzy graph ->
    (n_pad, P) head-grouped layout (wmax-normalized weights), rows >= n
    padded with 0-weight self-loops.  All three kernels dispatch through
    the AOT executable cache; the only host sync is the P98-degree scalar
    that fixes the static pad width."""
    from .precompile import cached_kernel

    heads, tails, w2, valid, wmax = cached_kernel(
        "umap_graph_edges", _graph_edges, knn_ids_dev, W
    )
    st, sw, starts, deg, qval = cached_kernel(
        "umap_edge_order",
        _edge_order,
        heads,
        tails,
        w2,
        valid,
        wmax,
        jnp.float32(max(n_epochs, 1)),
        jnp.float32(_layout_quantile()),
        n_pad=n_pad,
    )
    # ONE intentional scalar sync: the pad width must be a static shape, and
    # it depends on the realized degree distribution.
    # graftlint: disable=R1 (P is a static kernel shape; a 4-byte scalar fetch replaces the full-graph host round-trip this assembly removed)
    p98 = int(np.asarray(qval))
    P = int(min(_layout_cap(), max(8, p98, 1)))
    tails_pad, w_pad = cached_kernel(
        "umap_layout_gather", _gather_layout, st, sw, starts, deg, wmax, P=P
    )
    return tails_pad, w_pad


# -- mesh-parallel scan-batched layout ----------------------------------------


def _counter_uniform(key: jax.Array, counters: jax.Array) -> jax.Array:
    """Uniforms in [0, 1) from counter-mode threefry: element e's draw is a
    pure function of (key, counters[e]).  The layout engine feeds GLOBAL
    padded grid positions as counters, so a device owning any column block
    draws exactly the values a single device owning the whole grid would —
    the mechanism behind "fixed seed => same embedding on every mesh size
    sharing the padded geometry" (see mesh.padded_row_count).

    threefry_2x32 splits its count array in HALF and hashes pairs
    (count[i], count[i+half]) — element i's bits would depend on the array
    SIZE, exactly the shard-shape dependence this function must not have.
    Feeding each counter as both lanes (count ++ count) makes lane 0 of
    element i a function of (key, counters[i]) alone."""
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(2)
    flat = counters.reshape(-1)
    bits = threefry_2x32(kd, jnp.concatenate([flat, flat]))[: flat.size]
    bits = bits.reshape(counters.shape)
    return (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


@partial(jax.jit, static_argnames=("mesh", "block", "table_size"))
def _layout_step_sharded(
    emb: jax.Array,        # (n_pad, c) f32, replicated
    tails_T: jax.Array,    # (P, n_pad) int32, column-sharded head blocks
    w_T: jax.Array,        # (P, n_pad) f32 in [0, 1], column-sharded
    e0: jax.Array,         # () i32 first epoch of this block
    epochs_total: jax.Array,   # () f32 whole-fit epoch count (alpha schedule)
    valid_count: jax.Array,    # () i32 real rows (negative-sample range)
    a: jax.Array,
    b: jax.Array,
    lr: jax.Array,
    gamma: jax.Array,          # repulsion strength
    neg_rate: jax.Array,       # negative_sample_rate as f32
    seed: jax.Array,           # () i32
    mesh: Mesh,
    block: int,
    table_size: int,
) -> jax.Array:
    """`block` SGD epochs in ONE dispatch: lax.scan over epochs inside a
    shard_map over DATA_AXIS.  Each device owns a contiguous column block of
    the transposed head layout (its head nodes), computes those nodes' new
    embedding rows against the replicated embedding, and one tiled
    all-gather per epoch rebuilds the full embedding everywhere.

    Scatter-free as before (head updates reduce over the P axis; the
    symmetric tail update is the head update of the reversed directed edge;
    repulsion uses one shared negative table per epoch), and component-
    sliced in (P, n) layout for full TPU lanes.  The 2x attraction constant
    matches umap-learn's both-directions + move_other firing accounting
    (see the reference layout's history).  Edge firing draws are counter-
    based threefry over GLOBAL grid positions — mesh-shape independent."""
    from ..parallel.exchange import device_collective

    _layout_sec = device_collective("umap.layout_rows")

    n_pad, c = emb.shape
    M = table_size

    def per_device(emb, tails_loc, w_loc, e0, epochs_total, valid_count,
                   a, b, lr, gamma, neg_rate, seed):
        Pw, n_loc = tails_loc.shape
        col0 = jax.lax.axis_index(DATA_AXIS) * n_loc
        flat_tails = tails_loc.reshape(-1)
        # global flat position of every local (p, col) slot — the threefry
        # counter grid.  uint32 bounds the addressable grid at P * n_pad <
        # 2^32 (~119M rows at P=36; optimize_layout_sharded rejects more).
        counters = (
            jnp.arange(Pw, dtype=jnp.uint32)[:, None] * jnp.uint32(n_pad)
            + jnp.uint32(col0)
            + jnp.arange(n_loc, dtype=jnp.uint32)[None, :]
        )
        key0 = jax.random.PRNGKey(seed)

        def epoch(emb, e):
            key = jax.random.fold_in(key0, e)
            k1, k2 = jax.random.split(key)
            alpha = lr * (1.0 - e.astype(jnp.float32) / epochs_total)
            comps = jax.lax.dynamic_slice(emb, (col0, 0), (n_loc, c)).T
            tT = emb[flat_tails].T.reshape(c, Pw, n_loc)
            diffs = [comps[j][None, :] - tT[j] for j in range(c)]
            d2 = diffs[0] * diffs[0]
            for dj in diffs[1:]:
                d2 = d2 + dj * dj
            fire = _counter_uniform(k1, counters) < w_loc
            att = (-4.0 * a * b * d2 ** (b - 1.0)) / (1.0 + a * d2**b)
            att = jnp.where(d2 > 0, att, 0.0) * fire

            # shared negative table: replicated draw (same key, same shape
            # on every device), scaled by each node's expected negative
            # count — same expectation as per-edge sampling, dense compute
            neg = jax.random.randint(
                k2, (M,), 0, jnp.maximum(valid_count, 1)
            )
            tblT = emb[neg].T                            # (c, M) tiny
            diffs_n = [comps[j][None, :] - tblT[j][:, None] for j in range(c)]
            d2n = diffs_n[0] * diffs_n[0]                # (M, n_loc)
            for dj in diffs_n[1:]:
                d2n = d2n + dj * dj
            rep = (2.0 * gamma * b) / ((0.001 + d2n) * (1.0 + a * d2n**b))
            scale = neg_rate * fire.sum(axis=0).astype(emb.dtype) / M
            new_cols = []
            for cj, dj, dnj in zip(comps, diffs, diffs_n):
                upd = jnp.clip(att * dj, -4.0, 4.0).sum(axis=0)
                g_rep = jnp.clip(rep * dnj, -4.0, 4.0).sum(axis=0)
                new_cols.append(cj + alpha * (upd + scale * g_rep))
            new_loc = jnp.stack(new_cols, axis=1)        # (n_loc, c)
            # typed exchange section: uniform exchange.umap.layout_rows.*
            # counters (the per-epoch embedding rebuild collective)
            return _layout_sec.allgather_rows(new_loc), None

        emb_out, _ = jax.lax.scan(epoch, emb, e0 + jnp.arange(block))
        return emb_out

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            PSpec(),
            PSpec(None, DATA_AXIS),
            PSpec(None, DATA_AXIS),
        ) + (PSpec(),) * 9,
        out_specs=PSpec(),
        check_vma=False,
    )(emb, tails_T, w_T, e0, epochs_total, valid_count,
      a, b, lr, gamma, neg_rate, seed)


def optimize_layout_sharded(
    emb: jax.Array,        # (n_pad, c) f32 initial embedding (device)
    tails_pad: jax.Array,  # (n_pad, P) int32 head-grouped layout (device)
    w_pad: jax.Array,      # (n_pad, P) f32 normalized weights (device)
    valid_count: int,
    mesh: Mesh,
    a: float,
    b: float,
    n_epochs: int,
    learning_rate: float,
    repulsion_strength: float,
    negative_sample_rate: int,
    seed: int,
    table_size: int = 0,   # 0 = _NEG_TABLE (256)
) -> jax.Array:
    """Mesh-parallel SGD layout driver: reshard the layout into column-
    sharded head blocks, replicate the embedding, then launch
    ceil(n_epochs / SRML_UMAP_EPOCH_BLOCK) scan-batched steps through the
    AOT executable cache (at most two geometries: full block + remainder).
    Each dispatch bumps the umap.layout.dispatches counter and logs an
    ordered umap.layout.step event."""
    from .precompile import cached_kernel

    n_pad, P = tails_pad.shape
    # the counter-based firing draws address the (P, n_pad) grid in uint32;
    # past 2^32 counters would silently alias and correlate distinct edges'
    # draws every epoch — refuse loudly instead
    if P * n_pad >= 1 << 32:
        raise ValueError(
            f"layout grid P*n_pad = {P}*{n_pad} exceeds the uint32 counter "
            "space of the seed-deterministic firing draws; lower "
            "SRML_UMAP_DEGREE_CAP or shard the fit"
        )
    tails_T = jax.device_put(jnp.transpose(tails_pad), col_sharding(mesh))
    w_T = jax.device_put(jnp.transpose(w_pad), col_sharding(mesh))
    emb = jax.device_put(emb, replicated_sharding(mesh))
    M = table_size or _NEG_TABLE
    block = _epoch_block()
    epochs_total = jnp.float32(max(n_epochs, 1))
    scal = (
        jnp.int32(valid_count),
        jnp.float32(a),
        jnp.float32(b),
        jnp.float32(learning_rate),
        jnp.float32(repulsion_strength),
        jnp.float32(negative_sample_rate),
        jnp.int32(np.int64(seed) & 0x7FFFFFFF),
    )
    for e0 in range(0, n_epochs, block):
        blk = min(block, n_epochs - e0)
        emb = cached_kernel(
            "umap_layout_step",
            _layout_step_sharded,
            emb,
            tails_T,
            w_T,
            jnp.int32(e0),
            epochs_total,
            *scal,
            mesh=mesh,
            block=blk,
            table_size=M,
        )
        profiling.incr_counter("umap.layout.dispatches")
        profiling.record_event("umap.layout.step", e0=e0, block=blk)
    return emb


@partial(
    jax.jit,
    static_argnames=("n_epochs", "negative_sample_rate", "table_size"),
    donate_argnums=(0,),
)
def optimize_layout_padded(
    embedding: jax.Array,   # (n, c) initial
    tails_pad: jax.Array,   # (n, P) int32 head-grouped directed edges
    w_pad: jax.Array,       # (n, P) f32 membership strengths (0 = padding)
    a: float,
    b: float,
    n_epochs: int,
    learning_rate: float,
    repulsion_strength: float,
    negative_sample_rate: int,
    seed: int,
    table_size: int = 256,
) -> jax.Array:
    """Single-device REFERENCE layout (the pre-sharding implementation,
    kept as the quality baseline optimize_layout_sharded is tested
    against).  Scatter-free SGD: attraction in the padded head-grouped
    layout, one shared negative table per epoch, component-sliced (P, n)
    compute; the whole epoch loop is one fori in one jit."""
    n, c = embedding.shape
    P = tails_pad.shape[1]
    M = table_size
    key0 = jax.random.PRNGKey(seed)
    # P-major flat tails: ONE row-gather with slice width c (block slices
    # stay fast where c separate single-element gathers scalarize), whose
    # result transposes straight into (c, P, n) component planes
    flat_tails_T = tails_pad.T.reshape(-1)
    w_T = w_pad.T

    def epoch(e, emb):
        key = jax.random.fold_in(key0, e)
        k1, k2 = jax.random.split(key)
        alpha = learning_rate * (1.0 - e / n_epochs)
        comps = emb.T                                    # (c, n)
        tT = emb[flat_tails_T].T.reshape(c, P, n)
        diffs = [comps[j][None, :] - tT[j] for j in range(c)]  # c x (P, n)
        d2 = diffs[0] * diffs[0]
        for dj in diffs[1:]:
            d2 = d2 + dj * dj
        fire = jax.random.uniform(k1, (P, n)) < w_T
        # 2x attraction: umap-learn's symmetric COO carries BOTH directed
        # entries of every pair, and each firing entry moves head AND tail
        # (move_other) — per endpoint that is 2 attraction updates per pair
        # cycle.  The deduped head-grouped layout fires each endpoint's one
        # slot once, so the attraction term doubles to match expectation;
        # negatives stay 1x (umap-learn samples them only for the head of
        # the firing entry — S per endpoint per cycle, same as here).
        att = (-4.0 * a * b * d2 ** (b - 1.0)) / (1.0 + a * d2**b)
        att = jnp.where(d2 > 0, att, 0.0) * fire

        neg = jax.random.randint(k2, (M,), 0, n)
        tblT = emb[neg].T                                # (c, M) tiny
        diffs_n = [comps[j][None, :] - tblT[j][:, None] for j in range(c)]
        d2n = diffs_n[0] * diffs_n[0]                    # (M, n)
        for dj in diffs_n[1:]:
            d2n = d2n + dj * dj
        rep = (2.0 * repulsion_strength * b) / (
            (0.001 + d2n) * (1.0 + a * d2n**b)
        )
        scale = negative_sample_rate * fire.sum(axis=0).astype(emb.dtype) / M
        new_comps = []
        for cj, dj, dnj in zip(comps, diffs, diffs_n):
            upd = jnp.clip(att * dj, -4.0, 4.0).sum(axis=0)
            g_rep = jnp.clip(rep * dnj, -4.0, 4.0).sum(axis=0)
            new_comps.append(cj + alpha * (upd + scale * g_rep))
        return jnp.stack(new_comps, axis=1)

    return jax.lax.fori_loop(0, n_epochs, epoch, embedding)


@partial(jax.jit, static_argnames=("local_connectivity", "set_op_mix_ratio"))
def _calibrated_weights(
    knn_ids: jax.Array,
    knn_dists: jax.Array,
    local_connectivity: float,
    set_op_mix_ratio: float,
) -> jax.Array:
    """Calibration + fuzzy union in ONE dispatch: the fit previously paid a
    host sync between the two (rho/sigma round-tripped through the host
    for no reason — only W is ever consumed)."""
    rho, sigma = smooth_knn_calibration(
        knn_dists, local_connectivity=local_connectivity
    )
    return fuzzy_simplicial_set(knn_ids, knn_dists, rho, sigma, set_op_mix_ratio)


def _h2d(arr, dtype) -> jax.Array:
    """Counted host->device upload: already-device arrays pass through (a
    dtype cast stays on device); host arrays bump umap.h2d_transfers /
    umap.h2d_bytes.  The counters make the single-upload contract testable
    — a fit must move the (n, k) graph over the link at most once."""
    if isinstance(arr, jax.Array):
        return arr.astype(dtype) if arr.dtype != dtype else arr
    host = np.asarray(arr, dtype)
    profiling.incr_counter("umap.h2d_transfers")
    profiling.incr_counter("umap.h2d_bytes", host.nbytes)
    return jnp.asarray(host)


@partial(jax.jit, static_argnames=("n_pad", "c"))
def _random_init(seed, n_pad, c):
    """Uniform [-10, 10] start, drawn on device at the padded shape (the
    draw depends only on seed and n_pad, both mesh-shape independent)."""
    return jax.random.uniform(
        jax.random.PRNGKey(seed), (n_pad, c), jnp.float32, -10.0, 10.0
    )


def umap_fit_embedding(
    knn_ids,
    knn_dists,
    n_components: int,
    a: float,
    b: float,
    n_epochs: Optional[int],
    learning_rate: float,
    init: str,
    set_op_mix_ratio: float,
    local_connectivity: float,
    repulsion_strength: float,
    negative_sample_rate: int,
    seed: int,
    y: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
) -> np.ndarray:
    """Host orchestration of the fit pipeline (graph + init + layout),
    device-resident end to end: the (n, k) kNN graph is uploaded ONCE
    (counted), calibration/symmetrization/dedupe/pad all run as device
    kernels, the spectral or random init is drawn on device, and the SGD
    epochs run mesh-parallel in scan-batched AOT-cached steps.  One d2h
    fetch at the end returns the (n, c) embedding.

    When ``y`` is given, runs the supervised path: the fuzzy set is
    intersected with the label partition before layout (the reference's
    y= branch, umap.py:939-945).

    Determinism contract: with a fixed seed the returned embedding is
    identical across all mesh sizes that divide ROW_PAD_LANES (= 64 —
    every power-of-two TPU mesh up to 64 devices): those shapes share one
    padded geometry, so init draws and per-edge firing draws are functions
    of (seed, n) only.  Other mesh sizes are deterministic for their own
    shape (docs/umap_engine.md)."""
    n = knn_ids.shape[0]
    if mesh is None:
        mesh = get_mesh()
    with profiling.phase("umap.graph"):
        ids_dev = _h2d(knn_ids, np.int32)
        dists_dev = _h2d(knn_dists, np.float32)
        W = _calibrated_weights(
            ids_dev,
            dists_dev,
            float(local_connectivity),
            float(set_op_mix_ratio),
        )
        if y is not None:
            codes = np.full(n, -1, dtype=np.int32)
            # graftlint: disable=R5 (host-side label-finiteness check; f64 holds any label dtype exactly)
            finite = np.isfinite(np.asarray(y, dtype=np.float64))
            _, inv = np.unique(np.asarray(y)[finite], return_inverse=True)
            codes[finite] = inv.astype(np.int32)
            W = categorical_simplicial_set_intersection(
                W, ids_dev, _h2d(codes, np.int32)
            )
        if n_epochs is None:
            n_epochs = 500 if n <= 10_000 else 200
        n_pad = padded_row_count(n, mesh)
        tails_pad, w_pad = build_head_layout_device(
            ids_dev, W, n_pad, int(n_epochs)
        )
    with profiling.phase("umap.init"):
        if init == "random":
            emb = _random_init(
                jnp.int32(np.int64(seed) & 0x7FFFFFFF),
                n_pad=n_pad,
                c=int(n_components),
            )
        else:
            # "spectral": normalized-Laplacian eigenmap of the fuzzy graph,
            # as umap-learn/cuml (plain jits — jax's own cache covers them)
            key = jax.random.PRNGKey(int(np.int64(seed) & 0x7FFFFFFF))
            emb = _spectral_scale_noise(
                _laplacian_eigenmap_kernel(
                    tails_pad, w_pad, key, jnp.int32(n), c=int(n_components)
                ),
                jax.random.fold_in(key, 0x5CA1E),
            )
    with profiling.phase("umap.layout"):
        out = optimize_layout_sharded(
            emb,
            tails_pad,
            w_pad,
            n,
            mesh,
            a,
            b,
            int(n_epochs),
            float(learning_rate),
            float(repulsion_strength),
            int(negative_sample_rate),
            int(seed),
        )
        return np.asarray(out)[:n]


# -- transform -----------------------------------------------------------------


@jax.jit
def _transform_prepare(ids_p, dists_p, train_emb, valid_count,
                       local_connectivity):
    """Device-resident transform staging in ONE dispatch: smooth-kNN
    calibration, membership weights, the weighted-neighbor-mean init, and
    the wmax-normalized firing weights (padding rows zeroed so they never
    fire).  Replaces a host round-trip of the (bucket, k) weight matrix."""
    bucket = ids_p.shape[0]
    rho, sigma = smooth_knn_calibration(
        dists_p, local_connectivity=local_connectivity
    )
    w = jnp.exp(-jnp.maximum(dists_p - rho[:, None], 0.0) / sigma[:, None])
    row_valid = (jnp.arange(bucket) < valid_count)[:, None]
    w = jnp.where(row_valid, w, 0.0)
    wn = w / jnp.maximum(w.sum(axis=1, keepdims=True), 1e-12)
    init = jnp.einsum("nk,nkc->nc", wn, train_emb[ids_p]).astype(jnp.float32)
    weights = (w / jnp.maximum(w.max(), 1e-12)).astype(jnp.float32)
    return init, weights


@partial(jax.jit, static_argnames=("block", "negative_sample_rate"))
def _transform_step(
    emb_q: jax.Array,      # (bucket, c) query embedding (updated)
    ref_emb: jax.Array,    # (nr, c) training embedding (FIXED)
    tails: jax.Array,      # (bucket, k) int32 reference neighbor indices
    weights: jax.Array,    # (bucket, k) firing weights in [0, 1]
    e0: jax.Array,         # () i32 first epoch of this block
    epochs_total: jax.Array,
    a: jax.Array,
    b: jax.Array,
    lr: jax.Array,
    gamma: jax.Array,
    seed: jax.Array,
    block: int,
    negative_sample_rate: int,
) -> jax.Array:
    """`block` refinement epochs of cuml/umap-learn transform in one
    dispatch (lax.scan): the query points run the same attract/repel SGD as
    fit, but only against the frozen training embedding, and only the query
    side moves.  Each query's edge set IS its k-neighbor row, so gradients
    reduce onto their query with a plain axis-1 sum — scatter-free, like
    the padded fit layout."""
    nr = ref_emb.shape[0]
    nq, k = tails.shape
    S = negative_sample_rate
    key0 = jax.random.PRNGKey(seed)

    def epoch(emb, e):
        key = jax.random.fold_in(key0, e)
        k1, k2 = jax.random.split(key)
        alpha = lr * (1.0 - e.astype(jnp.float32) / epochs_total)
        fire = jax.random.uniform(k1, (nq, k)) < weights
        diff = emb[:, None, :] - ref_emb[tails]      # (nq, k, c)
        d2 = (diff * diff).sum(axis=2)
        att = (-2.0 * a * b * d2 ** (b - 1.0)) / (1.0 + a * d2**b)
        att = jnp.where(d2 > 0, att, 0.0) * fire
        upd = jnp.clip(att[:, :, None] * diff, -4.0, 4.0).sum(axis=1)

        neg = jax.random.randint(k2, (nq, k, S), 0, nr)
        diff_n = emb[:, None, None, :] - ref_emb[neg]  # (nq, k, S, c)
        d2n = (diff_n * diff_n).sum(axis=3)
        rep = (2.0 * gamma * b) / ((0.001 + d2n) * (1.0 + a * d2n**b))
        rep = rep * fire[:, :, None]
        g_rep = jnp.clip(rep[:, :, :, None] * diff_n, -4.0, 4.0)
        return emb + alpha * (upd + g_rep.sum(axis=(1, 2))), None

    emb_out, _ = jax.lax.scan(epoch, emb_q, e0 + jnp.arange(block))
    return emb_out


def umap_transform_embedding(
    query_knn_ids: np.ndarray,
    query_knn_dists: np.ndarray,
    train_embedding: np.ndarray,
    local_connectivity: float,
    a: Optional[float] = None,
    b: Optional[float] = None,
    n_epochs: Optional[int] = None,
    learning_rate: float = 1.0,
    repulsion_strength: float = 1.0,
    negative_sample_rate: int = 5,
    seed: int = 42,
    train_embedding_dev: Optional[jax.Array] = None,
) -> np.ndarray:
    """Embed new points: membership-weighted mean of training neighbors'
    embeddings, then (when a/b are given) the SGD refinement epochs that
    cuml/umap-learn transform runs — n_epochs//3, or 100/30 by data size,
    against the frozen training embedding.  The whole path is device-
    resident: one counted upload of the query (bucket, k) graph, staging
    and refinement as AOT-cached kernels, one d2h fetch of the result.

    The query count is padded to a power-of-two bucket (>=64) so the jitted
    kernels compile a bounded number of shapes across partitions of varying
    size; pass ``train_embedding_dev`` (uploaded once by the caller, e.g.
    alongside knn_search_prepared staging) so query kNN + layout share one
    device-resident dataset instead of re-transferring per partition."""
    from .precompile import cached_kernel, shape_bucket

    nq, k = query_knn_ids.shape
    if nq == 0:
        return np.zeros((0, train_embedding.shape[1]), np.float32)
    with profiling.phase("umap.transform"):
        bucket = shape_bucket(nq, lo=64)
        pad = bucket - nq
        ids_dev = _h2d(np.pad(query_knn_ids, ((0, pad), (0, 0))), np.int32)
        dists_dev = _h2d(
            np.pad(query_knn_dists, ((0, pad), (0, 0))), np.float32
        )
        if train_embedding_dev is None:
            train_embedding_dev = _h2d(train_embedding, np.float32)
        emb_q, weights = cached_kernel(
            "umap_transform_prepare",
            _transform_prepare,
            ids_dev,
            dists_dev,
            train_embedding_dev,
            jnp.int32(nq),
            jnp.float32(local_connectivity),
        )
        if a is None or b is None:
            return np.asarray(emb_q)[:nq]
        if n_epochs is None:
            n_epochs = 100 if train_embedding.shape[0] <= 10_000 else 30
        else:
            n_epochs = max(int(n_epochs) // 3, 1)
        epochs_total = jnp.float32(max(n_epochs, 1))
        scal = (
            jnp.float32(a),
            jnp.float32(b),
            jnp.float32(learning_rate),
            jnp.float32(repulsion_strength),
            jnp.int32(np.int64(seed) & 0x7FFFFFFF),
        )
        block = _epoch_block()
        for e0 in range(0, n_epochs, block):
            blk = min(block, n_epochs - e0)
            emb_q = cached_kernel(
                "umap_transform_step",
                _transform_step,
                emb_q,
                train_embedding_dev,
                ids_dev,
                weights,
                jnp.int32(e0),
                epochs_total,
                *scal,
                block=blk,
                negative_sample_rate=int(negative_sample_rate),
            )
            profiling.incr_counter("umap.transform.dispatches")
            profiling.record_event("umap.transform.step", e0=e0, block=blk)
        return np.asarray(emb_q)[:nq]
