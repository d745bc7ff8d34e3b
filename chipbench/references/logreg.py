"""Plain reference for the logistic-regression configurations: the labelled table
from the seed, the objective and its gradient, and L-BFGS.

Semantics held to (configs/logreg-*.json states them): binary labels; objective
mean log-loss + regParam/2 * |w|^2, the intercept not regularised; L-BFGS from
zero with 10 pairs of history, an Armijo (1e-4) backtracking search that halves
from 1 (from 1/max(|g|, 1) on the first step), exactly `iters` iterations."""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import block_rows, local_shards, mm, sharded_rows

HISTORY, ARMIJO, MAX_HALVINGS = 10, 1e-4, 20


# -- data -------------------------------------------------------------------

def table_fn(mesh, axis: str, rows_per_device: int, cols: int, scale_decades: float, flip_share: float):
    """jit(words) -> (X, y).  Columns are unit normals times a seeded scale spread
    log-uniformly over `scale_decades` decades, as unstandardised tabular columns
    are; labels come from a seeded linear model in which every column carries
    the same weight, and `flip_share` of them are flipped so the optimum is finite."""

    def gen_block(key, shared, block):
        kx, kn, kf = jax.random.split(key, 3)
        ks, kc = jax.random.split(shared)
        scale = 10.0 ** (-scale_decades * jax.random.uniform(ks, (cols,), jnp.float32))
        coef = jax.random.normal(kc, (cols,), jnp.float32) / (scale * np.sqrt(cols))
        X = jax.random.normal(kx, (block, cols), jnp.float32) * scale[None, :]
        z = (X * coef[None, :]).sum(axis=1) + 0.1 * jax.random.normal(kn, (block,), jnp.float32)
        flip = jax.random.uniform(kf, (block,)) < flip_share
        return X, jnp.where(flip, z <= 0, z > 0).astype(jnp.float32)

    return sharded_rows(mesh, axis, rows_per_device, gen_block, 2)


# -- objective --------------------------------------------------------------

@partial(jax.jit, static_argnames=("block", "prec"))
def _shard_loss_grad(X_loc, y_loc, w, b, block: int, prec: str):
    n, d = X_loc.shape

    def body(carry, xy):
        loss, gw, gb = carry
        xb, yb = xy
        z = mm(xb, w[:, None], prec)[:, 0] + b
        r = jax.nn.sigmoid(z) - yb
        return (
            loss + (jnp.logaddexp(0.0, z) - yb * z).sum(),
            gw + mm(r[None, :], xb, prec)[0],
            gb + r.sum(),
        ), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    (loss, gw, gb), _ = jax.lax.scan(
        body, init, (X_loc.reshape(n // block, block, d), y_loc.reshape(n // block, block))
    )
    return loss, gw, gb


def objective(X, y, reg: float, prec: str = "highest") -> Callable[[np.ndarray], Tuple[float, np.ndarray]]:
    """theta = (w..., b) -> (f, grad) over the row-sharded (X, y), in float64 on the host."""
    shards = list(zip(local_shards(X), local_shards(y)))
    n = sum(s.shape[0] for s, _ in shards)

    def value_and_grad(theta: np.ndarray):
        w, b = theta[:-1].astype(np.float32), np.float32(theta[-1])
        loss, gw, gb = 0.0, 0.0, 0.0
        for xs, ys in shards:
            dev = list(xs.devices())[0]
            l, g, h = _shard_loss_grad(xs, ys, jax.device_put(w, dev), jax.device_put(b, dev), block_rows(xs.shape[0]), prec)
            loss, gw, gb = loss + float(l), gw + np.asarray(g, np.float64), gb + float(h)
        f = loss / n + 0.5 * reg * float(theta[:-1] @ theta[:-1])
        return f, np.concatenate([gw / n + reg * theta[:-1], [gb / n]])

    return value_and_grad


# -- L-BFGS -------------------------------------------------------------------

def _two_loop(g, pairs):
    q = g.copy()
    alphas = []
    for s, yv, rho in reversed(pairs):
        a = rho * (s @ q)
        q -= a * yv
        alphas.append(a)
    if pairs:
        s, yv, _ = pairs[-1]
        q *= (s @ yv) / (yv @ yv)
    for (s, yv, rho), a in zip(pairs, reversed(alphas)):
        q += (a - rho * (yv @ q)) * s
    return q


def lbfgs(value_and_grad, n_params: int, iters: int) -> Tuple[np.ndarray, int]:
    """`iters` iterations from zero; returns (theta, iterations run).  Stops early
    only where the search finds no decrease or the objective stops changing, as
    any float32 L-BFGS must."""
    x = np.zeros(n_params)
    f, g = value_and_grad(x)
    pairs = []
    it = 0
    while it < iters:
        d = -_two_loop(g, pairs)
        deriv = g @ d
        if deriv >= 0:
            d, deriv = -g, -(g @ g)
        t = 1.0 if pairs else 1.0 / max(np.linalg.norm(g), 1.0)
        for _ in range(MAX_HALVINGS):
            x_new = x + t * d
            f_new, g_new = value_and_grad(x_new)
            ok = f_new <= f + ARMIJO * t * deriv
            if ok:
                break
            t *= 0.5
        it += 1
        if not ok:
            break
        s, yv = x_new - x, g_new - g
        if s @ yv > 1e-10:
            pairs = (pairs + [(s, yv, 1.0 / (s @ yv))])[-HISTORY:]
        stalled = f_new == f
        x, f, g = x_new, f_new, g_new
        if stalled:
            break
    return x, it


# -- the comparison -------------------------------------------------------------

@jax.jit
def scores(X_rows: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(X_rows, w, precision=jax.lax.Precision.HIGHEST) + b


def score_gap(X_rows: jax.Array, theta: np.ndarray, theta_ref: np.ndarray) -> float:
    """Relative gap of the decision scores on a sample of rows."""
    z, zr = (
        np.asarray(scores(X_rows, jnp.asarray(t[:-1], jnp.float32), jnp.float32(t[-1])), np.float64)
        for t in (theta, theta_ref)
    )
    return float(np.linalg.norm(z - zr) / np.linalg.norm(zr))
