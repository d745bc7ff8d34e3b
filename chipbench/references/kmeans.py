"""Plain reference for the KMeans configurations: the table from the seed, Lloyd's
iterations and the exact inertia.

Semantics held to (configs/kmeans-*.json states them): full-batch Lloyd from k
distinct seeded rows; squared Euclidean distance; ties to the lowest index; a
centre that loses all its rows keeps its place; exactly `iters` updates."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import block_rows, local_shards, mm, sharded_rows


# -- data -------------------------------------------------------------------

def table_fn(mesh, axis: str, rows_per_device: int, cols: int, k_true: int,
             ridges: int, ridge_share: float, ridge_scale: float):
    """jit(words) -> (X,): unit-variance rows.  Most are drawn evenly from k_true
    clusters around unit-scale centres, so far apart in `cols` dimensions that
    they never touch (upstream gen_data's blobs; bench.py:153-166).  A
    `ridge_share` of the rows lie along `ridges` straight ridges, each from one
    centre towards another and `ridge_scale` times as long as their distance:
    density that is continuous, as real tables have, and long enough that the
    centres on it are many noise widths apart, on which Lloyd's boundaries creep
    for far more than 30 iterations while every other centre has long stopped."""

    def gen_block(key, shared, block):
        ka, kn, kr, kt = jax.random.split(key, 4)
        centres = jax.random.normal(shared, (k_true, cols), jnp.float32)
        assign = jax.random.randint(ka, (block,), 0, k_true)
        ridge = jax.random.randint(kr, (block,), 0, ridges)
        t = jax.random.uniform(kt, (block, 1), jnp.float32)
        on_ridge = jax.random.uniform(jax.random.fold_in(kr, 1), (block, 1)) < ridge_share
        a, b = centres[2 * ridge], centres[2 * ridge + 1]
        base = jnp.where(on_ridge, a + (ridge_scale * t) * (b - a), centres[assign])
        return (base + jax.random.normal(kn, (block, cols), jnp.float32),)

    return sharded_rows(mesh, axis, rows_per_device, gen_block, 1)


@partial(jax.jit, static_argnames=("k",))
def seeded_row_ids(words: jax.Array, n_rows: int, k: int) -> jax.Array:
    """k distinct row numbers below n_rows, from the seed (n_rows is traced)."""
    key = jax.random.fold_in(jax.random.wrap_key_data(words), 7)
    ids = jax.random.randint(key, (4 * k,), 0, n_rows)
    # distinct: first occurrences of the sorted draw, in sorted order
    s = jnp.sort(ids)
    first = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]])
    return jnp.sort(jnp.where(first, s, n_rows + jnp.arange(4 * k)))[:k]


# -- Lloyd ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block", "prec", "exact_cost"))
def _shard_stats(X_loc, centres, block: int, prec: str, exact_cost: bool):
    """One device's rows: per-centre sums and counts (exact_cost False), or the
    sum of squared distances to the nearest centre in the difference form."""
    n, d = X_loc.shape
    k = centres.shape[0]
    c_norm = (centres * centres).sum(axis=1)

    def body(carry, xb):
        sums, counts, cost = carry
        d2 = (xb * xb).sum(axis=1)[:, None] - 2.0 * mm(xb, centres.T, prec) + c_norm[None, :]
        a = jnp.argmin(d2, axis=1)
        if exact_cost:
            diff = xb - centres[a]
            return (sums, counts, cost + (diff * diff).sum()), None
        onehot = jax.nn.one_hot(a, k, dtype=jnp.float32)
        return (sums + mm(onehot.T, xb, prec), counts + onehot.sum(axis=0), cost), None

    init = (jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.float32), jnp.zeros((), jnp.float32))
    (sums, counts, cost), _ = jax.lax.scan(body, init, X_loc.reshape(n // block, block, d))
    return sums, counts, cost


def _all_stats(X, centres: np.ndarray, prec: str, exact_cost: bool = False):
    parts = []
    for shard in local_shards(X):     # every chip's pass is enqueued before any is fetched
        c = jax.device_put(centres, list(shard.devices())[0])
        parts.append(_shard_stats(shard, c, block_rows(shard.shape[0]), prec, exact_cost))
    sums = counts = cost = 0.0
    for s, n, i in parts:
        sums, counts, cost = sums + np.asarray(s, np.float64), counts + np.asarray(n, np.float64), cost + float(i)
    return sums, counts, cost


def lloyd(X: jax.Array, centres0: np.ndarray, iters: int, prec: str = "highest") -> np.ndarray:
    """`iters` full-batch Lloyd updates of centres0 over the row-sharded X."""
    centres = np.asarray(centres0, np.float32)
    for _ in range(iters):
        sums, counts, _ = _all_stats(X, centres, prec)
        new = sums / np.maximum(counts, 1.0)[:, None]
        centres = np.where(counts[:, None] > 0, new, centres).astype(np.float32)
    return centres


def inertia(X: jax.Array, centres: np.ndarray) -> float:
    """Sum over rows of the squared distance to the nearest centre, difference form."""
    return _all_stats(X, np.asarray(centres, np.float32), "highest", exact_cost=True)[2]


# -- the comparison -------------------------------------------------------------

def fixed_point_gaps(centres: np.ndarray, stepped: np.ndarray) -> np.ndarray:
    """Per centre, how far it lies from where one exact Lloyd update of the whole
    set puts it, as a share of its norm.  A centre whose members did not change in
    the fit's last update is the mean of exactly the rows the reference assigns
    to it, so its gap is the rounding of the fit's own sums and nothing else."""
    stepped = np.asarray(stepped, np.float64)
    return np.linalg.norm(np.asarray(centres, np.float64) - stepped, axis=1) / np.linalg.norm(stepped, axis=1)
