"""Plain reference for CrossValidator over the logistic-regression arm: the split
law, a fit of ONE (fold, candidate), the held-out log-loss of given coefficients on
given rows, the mean over folds and the choice.  One model at a time, no lanes, no
mask inside a kernel: a fold is a vector of row weights handed to a plain objective.

Semantics held to (configs/cv-logreg-*.json states them):
- the split: rows are permuted by numpy's default generator seeded with the
  validator's seed, and the permutation is cut at the integer bounds
  int(cumsum(1/k) * n); a row's fold is the segment its place falls in.  Every row
  is in exactly one fold;
- fold f's models see weight 0 on fold f's rows and 1 on all others; the objective
  is references/logreg.py's with the mean taken over the weights
  (sum w * logloss / sum w + regParam/2 * |w|^2, intercept not regularised), solved
  by its `lbfgs`;
- a model's held-out metric is the mean over its fold's rows of
  -log max(P(true class), eps), eps 1e-15 (Spark's logLoss);
- avgMetrics is the mean over folds, stdMetrics the population standard deviation,
  the best candidate the one of least mean; it is fitted again on all rows."""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import block_rows, local_shards, mm
from .logreg import lbfgs, score_gap, table_fn  # noqa: F401  (the arm's table, solver and score comparison)

EPS = 1e-15


# -- the split ----------------------------------------------------------------

def fold_ids(n_rows: int, n_folds: int, seed: int) -> np.ndarray:
    """(n_rows,) int32: the fold each row is held out of."""
    perm = np.random.default_rng(seed).permutation(n_rows)
    bounds = np.cumsum([1.0 / n_folds] * n_folds)[:-1]
    cuts = (bounds * n_rows).astype(int)
    out = np.empty(n_rows, np.int32)
    for fold, rows in enumerate(np.split(perm, cuts)):
        out[rows] = fold
    return out


def train_weights(folds: np.ndarray, fold: int) -> np.ndarray:
    """Row weights of fold `fold`'s training set: 0 on its own rows, 1 elsewhere."""
    return (folds != fold).astype(np.float32)


# -- objective over weighted rows -----------------------------------------------

@partial(jax.jit, static_argnames=("block", "prec"))
def _shard_loss_grad(X_loc, y_loc, w_loc, coef, b, block: int, prec: str):
    n, d = X_loc.shape

    def body(carry, xyw):
        loss, gw, gb = carry
        xb, yb, wb = xyw
        z = mm(xb, coef[:, None], prec)[:, 0] + b
        r = (jax.nn.sigmoid(z) - yb) * wb
        return (
            loss + ((jnp.logaddexp(0.0, z) - yb * z) * wb).sum(),
            gw + mm(r[None, :], xb, prec)[0],
            gb + r.sum(),
        ), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    rows = lambda a: a.reshape((n // block, block) + a.shape[1:])
    (loss, gw, gb), _ = jax.lax.scan(body, init, (rows(X_loc), rows(y_loc), rows(w_loc)))
    return loss, gw, gb


def _placed(X, host_rows: np.ndarray):
    """A host vector of X's rows, cut and placed as X's row shards are."""
    shards = local_shards(X)
    out, at = [], 0
    for xs in shards:
        n = xs.shape[0]
        out.append(jax.device_put(np.asarray(host_rows[at:at + n], np.float32), list(xs.devices())[0]))
        at += n
    return shards, out


def objective(X, y, w: np.ndarray, reg: float, prec: str = "highest") -> Callable[[np.ndarray], Tuple[float, np.ndarray]]:
    """theta = (coef..., b) -> (f, grad) of the weighted objective over the rows of
    the row-sharded (X, y), in float64 on the host; `w` is a host vector."""
    shards, ws = _placed(X, w)
    ys = local_shards(y)
    wsum = float(np.asarray(w, np.float64).sum())

    def value_and_grad(theta: np.ndarray):
        coef, b = theta[:-1].astype(np.float32), np.float32(theta[-1])
        loss, gw, gb = 0.0, 0.0, 0.0
        for xs, yl, wl in zip(shards, ys, ws):
            dev = list(xs.devices())[0]
            l, g, h = _shard_loss_grad(xs, yl, wl, jax.device_put(coef, dev), jax.device_put(b, dev), block_rows(xs.shape[0]), prec)
            loss, gw, gb = loss + float(l), gw + np.asarray(g, np.float64), gb + float(h)
        f = loss / wsum + 0.5 * reg * float(theta[:-1] @ theta[:-1])
        return f, np.concatenate([gw / wsum + reg * theta[:-1], [gb / wsum]])

    return value_and_grad


def fit(X, y, w: np.ndarray, reg: float, iters: int, prec: str = "highest") -> Tuple[np.ndarray, int]:
    """One model: `iters` L-BFGS iterations from zero on the rows `w` weighs."""
    return lbfgs(objective(X, y, w, reg, prec), X.shape[1] + 1, iters)


# -- the held-out metric ----------------------------------------------------------

@partial(jax.jit, static_argnames=("block", "prec"))
def _shard_log_loss(X_loc, y_loc, m_loc, coef, b, block: int, prec: str):
    n, d = X_loc.shape

    def body(_, xym):
        xb, yb, mb = xym
        z = mm(xb, coef[:, None], prec)[:, 0] + b
        ll = jnp.minimum(jnp.logaddexp(0.0, z) - yb * z, -np.log(EPS))
        return None, (ll * mb).sum()

    rows = lambda a: a.reshape((n // block, block) + a.shape[1:])
    _, sums = jax.lax.scan(body, None, (rows(X_loc), rows(y_loc), rows(m_loc)))
    return sums


def held_out_log_loss(X, y, held: np.ndarray, theta: np.ndarray, prec: str = "highest") -> float:
    """Mean of -log max(P(true class), EPS) under `theta` over the rows where `held`
    is 1, in blocks; a block's sum in float32, the blocks' in float64."""
    shards, ms = _placed(X, held)
    coef, b = theta[:-1].astype(np.float32), np.float32(theta[-1])
    total = 0.0
    for xs, yl, ml in zip(shards, local_shards(y), ms):
        dev = list(xs.devices())[0]
        sums = _shard_log_loss(xs, yl, ml, jax.device_put(coef, dev), jax.device_put(b, dev), block_rows(xs.shape[0]), prec)
        total += float(np.asarray(sums, np.float64).sum())
    return total / float(np.asarray(held, np.float64).sum())


# -- the choice -------------------------------------------------------------------

def select(metrics: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
    """metrics (folds, candidates) -> (mean over folds, population standard
    deviation over folds, index of the least mean)."""
    m = np.asarray(metrics, np.float64)
    avg = m.mean(axis=0)
    return avg, m.std(axis=0), int(np.argmin(avg))
