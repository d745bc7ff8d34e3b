"""Plain reference for the linear-regression configurations: the labelled table
from the seed, cyclic coordinate descent in its RESIDUAL form (the one cuML's
CD solver runs), and the exact objective.

Semantics held to (configs/linreg-*.json states them): objective
(1/2n)|y - Xb - b0|^2 + a(l1r|b|_1 + (1 - l1r)/2 |b|_2^2), the intercept not
penalised; coordinates in cyclic order 0..d-1 from zero, exactly `sweeps` sweeps;
float32, every product exact.  No Gram matrix is formed: a step reads one column
of the table and the residual, so it shares no arithmetic with a
covariance-update solver beyond the algebra."""
from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import block_rows, mm, sharded_rows

FAULTS = ("sweeps9", "block", "no_intercept")      # planted in cd(); a subject may plant more of its own
BLOCK = 32                                          # columns fault "block" never updates, from cols // 2 on


# -- data -------------------------------------------------------------------

def table_fn(mesh, axis: str, rows_per_device: int, cols: int, informative: int, coef_scale: float,
             noise: float, bias: float):
    """jit(words) -> (X, y): benchmark/gen_data.py's `regression` (sklearn's
    make_regression): unit normal columns, the first `informative` of them with
    coefficients coef_scale * U(0, 1), the rest with none, labels X @ coef + bias
    + noise * N(0, 1)."""

    def gen_block(key, shared, block):
        kx, kn = jax.random.split(key)
        coef = coef_scale * jax.random.uniform(shared, (informative,), jnp.float32)
        X = jax.random.normal(kx, (block, cols), jnp.float32)
        y = (X[:, :informative] * coef[None, :]).sum(axis=1) + bias + noise * jax.random.normal(kn, (block,), jnp.float32)
        return X, y

    return sharded_rows(mesh, axis, rows_per_device, gen_block, 2)


# -- coordinate descent, residual form ------------------------------------------

def _dot(a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    return mm(a[None, :], b[:, None], prec)[0, 0]


@partial(jax.jit, static_argnames=("fit_intercept", "prec"))
def _cd(X, y, alpha, l1r, sweeps, frozen, fit_intercept: bool, prec: str):
    n, d = X.shape
    # a product's operands as the precision holds them: the update of r is a product too
    lo = (lambda v: v) if prec == "highest" else (lambda v: v.astype(jnp.bfloat16).astype(jnp.float32))
    Xt = X.T
    ones = jnp.ones((n,), X.dtype)
    xbar = mm(Xt, ones[:, None], "highest")[:, 0] / n if fit_intercept else jnp.zeros((d,), X.dtype)
    ybar = y.sum() / n if fit_intercept else jnp.zeros((), X.dtype)

    def coord(j, state):
        b, r = state
        xc = jax.lax.dynamic_index_in_dim(Xt, j, keepdims=False) - xbar[j]
        sq = _dot(xc, xc, prec) / n
        rho = _dot(xc, r, prec) / n + sq * b[j]
        new = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - alpha * l1r, 0.0) / (sq + alpha * (1.0 - l1r))
        new = jnp.where((j >= frozen[0]) & (j < frozen[1]), b[j], new)
        return b.at[j].set(new), r - lo(xc) * lo(new - b[j])

    def sweep(_, state):
        return jax.lax.fori_loop(0, d, coord, state)

    b, _ = jax.lax.fori_loop(0, sweeps, sweep, (jnp.zeros((d,), X.dtype), y - ybar))
    return b, ybar - _dot(xbar, b, prec)


def cd(X, y, alpha: float, l1r: float, sweeps: int, fit_intercept: bool = True, prec: str = "highest",
       fault: str = None) -> Tuple[np.ndarray, float, int]:
    """(coefficients, intercept, sweeps run) of `sweeps` sweeps from zero.  r = y -
    ybar; for each column j in order: rho = x_j.r / n + (x_j.x_j / n) b_j on the
    centred column, b_j = soft(rho, a l1r) / (x_j.x_j / n + a (1 - l1r)), r -=
    x_j (b_j_new - b_j); b0 = ybar - xbar.b.  `prec` "bf16" holds every
    product's operands in bfloat16 (the control).  A planted `fault`: "sweeps9"
    one sweep too few, "block" BLOCK columns from d // 2 on never updated,
    "no_intercept" nothing centred and no intercept."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    d = X.shape[1]
    run = sweeps - 1 if fault == "sweeps9" else sweeps
    frozen = (d // 2, min(d, d // 2 + BLOCK)) if fault == "block" else (0, 0)
    b, b0 = _cd(X, y, jnp.float32(alpha), jnp.float32(l1r), jnp.int32(run), jnp.asarray(frozen, jnp.int32),
                fit_intercept and fault != "no_intercept", prec)
    return np.asarray(b, np.float64), float(b0), run


# -- the exact objective -----------------------------------------------------------

@partial(jax.jit, static_argnames=("block",))
def _squared_residuals(X, y, b, b0, block: int):
    """Per block of rows, the sum of (y - Xb - b0)^2: the products at HIGHEST."""

    def one(i):
        xb = jax.lax.dynamic_slice_in_dim(X, i * block, block)
        r = jax.lax.dynamic_slice_in_dim(y, i * block, block) - mm(xb, b[:, None], "highest")[:, 0] - b0
        return (r * r).sum()

    return jax.lax.map(one, jnp.arange(X.shape[0] // block))


def objective(X, y, alpha: float, l1r: float) -> Callable[[np.ndarray, float], float]:
    """(b, b0) -> (1/2n)|y - Xb - b0|^2 + a(l1r|b|_1 + (1 - l1r)/2 |b|_2^2) over all
    rows, block by block; the blocks' sums and the penalty in float64 on the host."""
    n = X.shape[0]
    block = block_rows(n)

    def value(b: np.ndarray, b0: float) -> float:
        b = np.asarray(b, np.float64)
        parts = _squared_residuals(X, y, jnp.asarray(b, jnp.float32), jnp.float32(b0), block)
        penalty = alpha * (l1r * np.abs(b).sum() + 0.5 * (1.0 - l1r) * float(b @ b))
        return float(np.asarray(parts, np.float64).sum()) / (2.0 * n) + penalty

    return value
