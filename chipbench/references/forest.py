"""The plain reference of the forest family: the labelled table from the seed, a
level-wise builder in numpy (float32 data, float64 sums, no kernels, no packing,
no size classes), and the pieces of the comparison that holds a forest to one
exact split search of its own nodes.

It imports nothing of the program but `bootstrap_weights`, the one function
that turns the few numbers a model keeps (`bootstrap_draw_`) back into the
(tree, row) bootstrap counts: those are random DATA, not mathematics, and the
reference checks that what it is handed looks like Poisson(1) draws.

Departures from upstream (spark-rapids-ml's cuML forest), each the program's and
stated in the configuration's `guarantees`:
- bootstrap: Poisson(1) counts per (tree, row) over ALL rows; cuML draws with
  replacement on a worker's shard;
- feature subsets: one draw per (tree, level) here; the program shares a draw
  among the trees it packs into one scan and among a tree's deep levels;
  upstream draws per node.  The comparison needs no knowledge of the draw;
- minInfoGain 0 means "more than 1e-6 of the node's weighted impurity" (the
  program's float32 noise floor), where upstream means "more than 0".
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.forest import bootstrap_weights  # noqa: F401  (the one import; see above)

from .common import block_rows, sharded_rows

HIGHEST = jax.lax.Precision.HIGHEST
GAIN_FLOOR = 1e-6          # of the node's weighted impurity
BINNING_ROWS = 16_384      # the strided sample's budget: rows, and bytes below
BINNING_BYTES = 32 << 20


# --------------------------------------------------------------------------- data


def _geometry(shared, classes: int, informative: int, redundant: int, class_sep: float):
    """make_classification's class geometry, from the seed alone: hypercube-vertex
    centroids (the first coordinate alternates with the class, so no two classes
    share a vertex), a random mixing of the informative columns, and the
    redundant columns' combinations."""
    kv, kr, km = jax.random.split(shared, 3)
    signs = jax.random.rademacher(kv, (classes, informative)).astype(jnp.float32)
    signs = signs.at[:, 0].set(1.0 - 2.0 * (jnp.arange(classes) % 2))
    rotate = jax.random.normal(kr, (informative, informative), jnp.float32)
    mix = jax.random.normal(km, (informative, redundant), jnp.float32)
    return signs * class_sep, rotate, mix


def _rows(key, shared, block: int, cols: int, classes: int, informative: int, redundant: int, class_sep: float):
    centroids, rotate, mix = _geometry(shared, classes, informative, redundant, class_sep)
    ky, kz, kn = jax.random.split(key, 3)
    y = jax.random.randint(ky, (block,), 0, classes)
    z = centroids[y] + jax.random.normal(kz, (block, informative), jnp.float32)
    inf = jnp.matmul(z, rotate, precision=HIGHEST)
    red = jnp.matmul(inf, mix, precision=HIGHEST)
    noise = jax.random.normal(kn, (block, cols - informative - redundant), jnp.float32)
    # as gen_data.py lays them out: informative, redundant, noise; not shuffled
    return jnp.concatenate([inf, red, noise], axis=1), y.astype(jnp.float32)


def table_fn(mesh, axis: str, rows_per_device: int, cols: int, classes: int, informative: int, redundant: int, class_sep: float):
    """jit(words) -> (X (rows, cols) float32, y (rows,) float32 class index), made
    on the device(s) block by block."""
    gen = lambda key, shared, block: _rows(key, shared, block, cols, classes, informative, redundant, class_sep)
    return sharded_rows(mesh, axis, rows_per_device, gen, 2)


def fresh_fn(rows: int, cols: int, classes: int, informative: int, redundant: int, class_sep: float):
    """jit(words) -> `rows` rows of the same problem that the table does not hold
    (the table folds its key with a device's position, then a block's; this
    folds it with a number no position reaches)."""
    block = block_rows(rows, 4000)

    def make(words):
        shared, mine = jax.random.split(jax.random.wrap_key_data(words))
        mine = jax.random.fold_in(mine, 1 << 20)
        X, y = jax.lax.map(
            lambda i: _rows(jax.random.fold_in(mine, i), shared, block, cols, classes, informative, redundant, class_sep),
            jnp.arange(rows // block),
        )
        return X.reshape(rows, cols), y.reshape(rows)

    return jax.jit(make)


# --------------------------------------------------------------------------- edges


def sample_rows(n_rows: int, cols: int) -> np.ndarray:
    """The rows whose quantiles are the bin edges: valid rows, strided down to
    the budget with a ceiling stride so they span the whole table."""
    quota = max(2048, min(BINNING_ROWS, BINNING_BYTES // (cols * 4)))
    idx = np.arange(n_rows)
    return idx[:: -(-n_rows // quota)] if n_rows > quota else idx


def quantile_edges(sample: np.ndarray, n_bins: int) -> np.ndarray:
    """(cols, n_bins - 1) float32: linear-interpolated quantiles k / n_bins of each
    column of the sample, in float64."""
    s = np.sort(np.asarray(sample, np.float64), axis=0)
    pos = np.linspace(0, 1, n_bins + 1)[1:-1] * (s.shape[0] - 1)
    lo, hi = np.floor(pos).astype(np.int64), np.ceil(pos).astype(np.int64)
    frac = (pos - lo)[:, None]
    return (s[lo] * (1.0 - frac) + s[hi] * frac).T.astype(np.float32)


@jax.jit
def _bin(x: jax.Array, edges: jax.Array) -> jax.Array:
    """A value's bin is its count of edges strictly below it (searchsorted, side
    left): x (n, f), edges (f, e) -> (n, f), int8 where 127 edges or fewer."""
    bins = (x[:, :, None] > edges[None, :, :]).sum(axis=-1, dtype=jnp.int32)
    return bins.astype(jnp.int8 if edges.shape[1] <= 127 else jnp.int32)


def columns_fn(X: jax.Array, edges: np.ndarray):
    """feats -> the binned columns (n, len(feats)) on the host, binned on
    the device the table lies on (one column gather, one pass of compares)."""

    def take(feats: np.ndarray) -> np.ndarray:
        feats = np.asarray(feats, np.int32)
        return np.asarray(_bin(jnp.take(X, jnp.asarray(feats), axis=1), jnp.asarray(edges[feats])))

    return take


# --------------------------------------------------------------------------- gini


def gini(counts: np.ndarray) -> np.ndarray:
    """1 - sum p^2 over the last axis; 0 for an empty node."""
    w = counts.sum(axis=-1)
    p = counts / np.maximum(w, 1e-300)[..., None]
    return np.where(w > 0, 1.0 - (p * p).sum(axis=-1), 0.0)


def edge_gains(hist: np.ndarray, min_leaf: float = 1.0) -> np.ndarray:
    """hist (..., B, C) weighted class counts by bin -> (..., B - 1) gini gain
    (weighted impurity removed) of a split after each edge; -inf where a side
    would hold less than min_leaf."""
    left = np.cumsum(hist, axis=-2)[..., :-1, :]
    total = hist.sum(axis=-2)[..., None, :]
    right = total - left
    lw, rw = left.sum(-1), right.sum(-1)
    gain = gini(total[..., 0, :])[..., None] * total.sum(-1) - (gini(left) * lw + gini(right) * rw)
    return np.where((lw >= min_leaf) & (rw >= min_leaf), gain, -np.inf)


def in_bf16(a: np.ndarray) -> np.ndarray:
    """a, rounded to bfloat16 and back to float32."""
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))


def _held(a: np.ndarray, sums: str) -> np.ndarray:
    """Histogram sums as the builder holds them: float64, or, for the control,
    rounded to bfloat16 (which loses integer counts above 256)."""
    if sums == "float64":
        return a
    if sums == "bf16":
        return in_bf16(a).astype(np.float64)
    raise ValueError(f"sums {sums!r}: float64 or bf16")


# --------------------------------------------------------------------------- builder

FAULTS = ("block", "swap", "half", "wide")
DRAW_FAULTS = ("draw_shared", "draw_clipped", "draw_ones")


def faulty_draw(w: np.ndarray, fault: Optional[str]) -> np.ndarray:
    """(T, n) bootstrap counts with a degenerate draw planted in the sound one's
    place, for poisson_like to fail: "draw_shared" gives every tree the first
    tree's counts, "draw_clipped" counts a row once however often it was drawn
    (a subsample without replacement), "draw_ones" is no bootstrap at all."""
    if fault == "draw_shared":
        return np.broadcast_to(w[:1], w.shape)
    if fault == "draw_clipped":
        return np.minimum(w, 1.0)
    if fault == "draw_ones":
        return np.ones_like(w)
    return w


def build_tree(
    binned: Callable[[np.ndarray], np.ndarray],
    edges: np.ndarray,
    y: np.ndarray,
    w: np.ndarray,
    classes: int,
    max_depth: int,
    max_features: int,
    rng: np.random.Generator,
    sums: str = "float64",
    fault: Optional[str] = None,
) -> Dict[str, np.ndarray]:
    """One tree, level by level, in the dense layout the program's models use
    (node i's children are 2i + 1, where x <= threshold, and 2i + 2).  binned(feats)
    gives those columns' bins for every row; y is the class index; w the row's
    weight in this tree.  Returns features (M,), thresholds, counts (M, C),
    impurities.  `fault` plants one, for the comparison to fail: "block" never
    searches the first block of 32 features of a subset (22 of 54 remain), or of a
    subset of 32 or fewer searches only the first half, rounded down;
    "swap" sends level 2's rows to the wrong child; "half" leaves the second half
    of the rows out of every histogram from level 3 on; "wide" searches four
    times max_features columns."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    n, cols = y.shape[0], edges.shape[0]
    M = 2 ** (max_depth + 1) - 1
    feature = np.full(M, -1, np.int32)
    threshold = np.zeros(M, np.float32)
    counts = np.zeros((M, classes))
    yi = y.astype(np.int64)
    node = np.zeros(n, np.int64)        # level-local node of each row
    live = w > 0
    B = edges.shape[1] + 1
    for level in range(max_depth + 1):
        nodes, base = 2**level, 2**level - 1
        seen = live if not (fault == "half" and level >= 3) else live & (np.arange(n) < n // 2)
        cc = np.bincount(node[live] * classes + yi[live], weights=w[live], minlength=nodes * classes)
        counts[base:base + nodes] = _held(cc.reshape(nodes, classes), sums)
        if level == max_depth:
            break
        # one subset a (tree, level); every feature when the subset is the table's width
        width = 4 * max_features if fault == "wide" else max_features
        feats = np.sort(rng.choice(cols, width, replace=False)) if width < cols else np.arange(cols)
        bins = binned(feats)
        if fault == "block":      # the columns left to search come first
            keep = np.arange(32, len(feats)) if len(feats) > 32 else np.arange(max(1, len(feats) // 2))
            feats, bins = feats[keep], bins[:, keep]
        searched = len(feats)
        flat = (node[seen, None] * B + bins[seen].astype(np.int64)) * classes + yi[seen, None]
        flat += np.arange(searched)[None, :] * (nodes * B * classes)
        hist = np.bincount(flat.ravel(), weights=np.repeat(w[seen], searched), minlength=searched * nodes * B * classes)
        hist = _held(hist.reshape(searched, nodes, B, classes), sums)
        gain = np.moveaxis(edge_gains(hist), 0, 1).reshape(nodes, -1)       # (nodes, searched * (B - 1))
        best = gain.argmax(axis=1)                                          # ties: first (feature, edge)
        g = gain[np.arange(nodes), best]
        here = counts[base:base + nodes]
        ok = np.isfinite(g) & (g > GAIN_FLOOR * gini(here) * here.sum(-1))
        f_at, e_at = best // (B - 1), best % (B - 1)
        feature[base:base + nodes] = np.where(ok, feats[f_at], -1)
        threshold[base:base + nodes] = np.where(ok, edges[feats[f_at], e_at], 0.0)
        right = bins[np.arange(n), f_at[node]] > e_at[node]
        if fault == "swap" and level == 2:
            right = ~right
        live = live & ok[node]
        node = np.where(live, 2 * node + right, 0)
    return {"features": feature, "thresholds": threshold, "counts": counts, "impurities": gini(counts)}


def build_forest(binned, edges, y, weights, classes, max_depth, max_features, seed, sums="float64", fault=None):
    """weights (T, n): a tree's row weights.  The model's arrays, stacked over trees."""
    rng = np.random.default_rng(seed)
    trees = [build_tree(binned, edges, y, np.asarray(w, np.float64), classes, max_depth, max_features, rng, sums, fault) for w in weights]
    counts = np.stack([t["counts"] for t in trees])
    total = counts.sum(-1)
    return {
        "features_": np.stack([t["features"] for t in trees]),
        "thresholds_": np.stack([t["thresholds"] for t in trees]),
        "leaf_values_": (counts / np.maximum(total, 1e-12)[..., None]).astype(np.float32),
        "node_counts_": total.astype(np.float32),
        "impurities_": np.stack([t["impurities"] for t in trees]).astype(np.float32),
    }


# --------------------------------------------------------------------------- the walk


def walk_fn(max_depth: int):
    """jit(X (n, d), features (T, M), thresholds (T, M)) -> (max_depth + 1, T, n) int32:
    the node every row stands at after each level of every tree, by the raw values
    and the stored thresholds (a row stays at a leaf)."""

    def walk(X, features, thresholds):
        def one(f, t):
            node = jnp.zeros(X.shape[0], jnp.int32)
            path = [node]
            for _ in range(max_depth):
                fn = f[node]
                x = jnp.take_along_axis(X, jnp.maximum(fn, 0)[:, None], axis=1)[:, 0]
                node = jnp.where(fn >= 0, 2 * node + 1 + (x > t[node]).astype(jnp.int32), node)
                path.append(node)
            return jnp.stack(path)

        return jnp.moveaxis(jax.vmap(one)(features, thresholds), 0, 1)

    return jax.jit(walk)


def walk_host(X: np.ndarray, features: np.ndarray, thresholds: np.ndarray, max_depth: int) -> np.ndarray:
    """The same walk on the host, to the leaves alone: X (n, d) -> (T, n) leaf node
    of each row in each tree."""
    rows = np.arange(X.shape[0])
    out = np.zeros((features.shape[0], X.shape[0]), np.int64)
    for t, (f, th) in enumerate(zip(features, thresholds)):
        node = out[t]
        for _ in range(max_depth):
            fn = f[node]
            node = np.where(fn >= 0, 2 * node + 1 + (X[rows, np.maximum(fn, 0)] > th[node]), node)
        out[t] = node
    return out


def node_counts(path: np.ndarray, y: np.ndarray, w: np.ndarray, classes: int, max_depth: int) -> np.ndarray:
    """(M, C) float64 weighted class counts of every node of one tree from its
    rows' path (levels + 1, n): a row counts at each node it passed through."""
    M = 2 ** (max_depth + 1) - 1
    out = np.zeros(M * classes)
    yi, prev = y.astype(np.int64), None
    for level in range(max_depth + 1):
        at = path[level].astype(np.int64)
        moved = np.ones(at.shape, bool) if prev is None else at != prev
        prev = at
        out += np.bincount(at[moved] * classes + yi[moved], weights=w[moved], minlength=M * classes)
    return out.reshape(M, classes)


def poisson_like(weights: np.ndarray) -> Dict[str, float]:
    """How far (T', n) bootstrap counts are from independent Poisson(1) draws:
    the worst tree's |mean - 1| and |variance - 1|, the worst |correlation| of
    two trees, and the share of values that are not whole numbers >= 0."""
    w = np.asarray(weights, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):     # a constant draw correlates with anything
        c = np.nan_to_num(np.corrcoef(w), nan=1.0) if w.shape[0] > 1 else np.zeros((1, 1))
    return {
        "mean_gap": float(np.abs(w.mean(axis=1) - 1.0).max()),
        "var_gap": float(np.abs(w.var(axis=1) - 1.0).max()),
        "cross_corr": float(np.abs(c - np.diag(np.diag(c))).max()),
        "not_counts": float(((w < 0) | (w != np.round(w))).mean()),
    }


def split_search(bins: np.ndarray, y: np.ndarray, w: np.ndarray, classes: int, n_bins: int) -> Tuple[np.ndarray, float]:
    """The exact split search of one node over the given columns: bins (k, f) of
    the k weighted rows that reach it -> (gains (f, n_bins - 1), the node's
    weighted impurity)."""
    k, f = bins.shape
    flat = (np.arange(f)[None, :] * n_bins + bins.astype(np.int64)) * classes + y.astype(np.int64)[:, None]
    hist = np.bincount(flat.ravel(), weights=np.repeat(w, f), minlength=f * n_bins * classes).reshape(f, n_bins, classes)
    total = hist[0].sum(axis=0)
    return edge_gains(hist), float(gini(total) * total.sum())


def predict(paths_last: np.ndarray, leaf_values: np.ndarray) -> np.ndarray:
    """Class by the mean of the trees' leaf distributions: paths_last (T, n) leaf
    node of each row, leaf_values (T, M, C) -> (n,) class index."""
    T = paths_last.shape[0]
    probs = sum(leaf_values[t][paths_last[t]] for t in range(T)) / T
    return probs.argmax(axis=1)
