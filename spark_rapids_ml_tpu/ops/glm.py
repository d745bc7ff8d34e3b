#
# Linear-model solvers (OLS / Ridge closed form, ElasticNet coordinate
# descent), pure jax, mesh-aware.
#
# TPU-native replacement for cuML's LinearRegressionMG / RidgeMG / CDMG
# (dispatched by the reference at regression.py:499-556).  The design is
# sufficient-statistics-first: one fused pass over the row-sharded data
# computes (XtWX, XtWy, means) with GSPMD psums; every subsequent solve —
# including all extra param maps of a single-pass fitMultiple — runs on the
# small replicated (D, D) system with zero additional data passes.  That is
# the TPU-shaped formulation of cuML's "eig" algorithm and of its
# covariance-update coordinate descent.
#
# Spark-parity notes (mirrored behaviors, not code):
#   - Ridge: Spark normalizes the sample term of the objective by n but cuML
#     does not, so the reference scales alpha by the row count
#     (regression.py:528-534); the closed form below solves
#     (Xc'WXc + alpha*n*I) b = Xc'Wy.
#   - ElasticNet: both Spark and cuML CD normalize by n, so alpha is used
#     as-is (regression.py:536-543): obj = (1/2n)||y-Xb||^2 +
#     alpha*(l1r*|b|_1 + (1-l1r)/2*|b|_2^2).
#   - standardization maps to solver-side feature scaling with coefficient
#     unscaling, matching cuML's `normalize`.
#

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import cd_sweep
from .linalg import exact_matmul


class LinregStats(NamedTuple):
    wsum: jax.Array     # scalar: total weight (== row count without weightCol)
    x_mean: jax.Array   # (D,)
    y_mean: jax.Array   # scalar
    G: jax.Array        # (D, D) = X'WX (uncentered)
    c: jax.Array        # (D,)   = X'Wy (uncentered)
    y2: jax.Array       # scalar = sum w y^2


@partial(jax.jit, static_argnames=("mesh", "chunk"))
def linreg_sufficient_stats(
    X: jax.Array, y: jax.Array, w: jax.Array, mesh=None, chunk: int = 32768
) -> LinregStats:
    """One fused pass over row-sharded (X, y, w); outputs replicated.

    With a mesh, the pass is a per-shard dynamic-slice scan over `chunk`-row
    blocks + one psum: XLA's compile time on the monolithic (D, N) @ (N, D)
    contraction was seen to grow pathologically with N on a TPU backend,
    while the chunked scan compiles quickly at identical throughput.
    mesh=None keeps the one-shot GSPMD contraction."""
    if mesh is None:
        wsum = w.sum()
        Xw = X * w[:, None]
        x_mean = Xw.sum(axis=0) / wsum
        y_mean = (y * w).sum() / wsum
        G = exact_matmul(Xw.T, X)
        c = exact_matmul(Xw.T, y)
        y2 = (y * y * w).sum()
        return LinregStats(wsum, x_mean, y_mean, G, c, y2)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS
    from .linalg import _local_moments

    def per_device(X_loc, y_loc, w_loc):
        # shared chunked-moment accumulator (ops/linalg.py) with the y-terms
        with jax.named_scope("linreg.gram"):
            local = _local_moments(X_loc, w_loc, chunk, y_loc=y_loc)
        return tuple(jax.lax.psum(v, DATA_AXIS) for v in local)

    wsum, xwsum, G, ywsum, c, y2 = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(),) * 6,
        check_vma=False,
    )(X, y, w)
    return LinregStats(wsum, xwsum / wsum, ywsum / wsum, G, c, y2)


def _centered_system(stats: LinregStats, fit_intercept: bool):
    """Center G/c around the weighted means when fitting an intercept."""
    if fit_intercept:
        Gc = stats.G - stats.wsum * jnp.outer(stats.x_mean, stats.x_mean)
        cc = stats.c - stats.wsum * stats.x_mean * stats.y_mean
    else:
        Gc, cc = stats.G, stats.c
    return Gc, cc


def _feature_scales(Gc: jax.Array, wsum: jax.Array, normalize: bool):
    if not normalize:
        return jnp.ones(Gc.shape[0], Gc.dtype)
    var = jnp.maximum(jnp.diag(Gc) / wsum, 0.0)
    return jnp.where(var > 0, jnp.sqrt(var), 1.0)


@partial(jax.jit, static_argnames=("fit_intercept", "normalize"))
def solve_linear(
    stats: LinregStats,
    alpha: float,
    fit_intercept: bool = True,
    normalize: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Closed-form OLS (alpha == 0) / Spark-parity Ridge (alpha > 0):
    (Xc'WXc + alpha*n*I) b = Xc'Wy, intercept = ym - xm.b."""
    Gc, cc = _centered_system(stats, fit_intercept)
    s = _feature_scales(Gc, stats.wsum, normalize)
    Gs = Gc / jnp.outer(s, s)
    cs = cc / s
    d = Gs.shape[0]
    reg = alpha * stats.wsum
    A = Gs + reg * jnp.eye(d, dtype=Gs.dtype)
    # Cholesky when PD; tiny-jitter retry keeps rank-deficient OLS stable
    jitter = jnp.finfo(Gs.dtype).eps * jnp.trace(Gs) / d
    b = jnp.linalg.solve(A + jitter * jnp.eye(d, dtype=Gs.dtype), cs)
    b = b / s
    intercept = jnp.where(
        fit_intercept, stats.y_mean - stats.x_mean @ b, jnp.zeros((), b.dtype)
    )
    return b, intercept


@partial(jax.jit, static_argnames=("fit_intercept", "normalize", "max_iter", "mesh"))
def solve_elasticnet_cd(
    stats: LinregStats,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    normalize: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-3,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Covariance-update cyclic coordinate descent on the replicated Gram
    system; data already reduced to sufficient statistics.

    obj = (1/2n)||y - Xb||^2 + alpha*(l1r*|b|_1 + (1-l1r)/2*|b|_2^2)

    update: rho_j = (c_j - G_j.b + G_jj b_j)/n
            b_j   = soft(rho_j, alpha*l1r) / (G_jj/n + alpha*(1-l1r))
    Converges when the largest coefficient change in a sweep <= tol.
    Returns (coef, intercept, n_sweeps).  `mesh` is the mesh the statistics
    are replicated on, None for statistics on one device: on the chip the
    sweep kernel runs per device under its shard_map (ops/cd_sweep.py).
    """
    Gc, cc = _centered_system(stats, fit_intercept)
    s = _feature_scales(Gc, stats.wsum, normalize)
    G = Gc / jnp.outer(s, s)
    c = cc / s
    n = stats.wsum
    d = G.shape[0]
    diag = jnp.diag(G)
    denom = diag / n + alpha * (1.0 - l1_ratio)
    denom = jnp.where(denom > 0, denom, 1.0)
    thresh = alpha * l1_ratio

    if cd_sweep.takes(G):
        # on the chip a sweep is one kernel (ops/cd_sweep.py): the system
        # padded once a solve, the coefficients carried as a lane vector
        pad = cd_sweep.padded(d) - d
        Gp = jnp.pad(G, ((0, pad), (0, pad)))
        cp = jnp.pad(c, (0, pad))
        diagp = jnp.pad(diag, (0, pad))
        denomp = jnp.pad(denom, (0, pad), constant_values=1.0)
        b0 = jnp.zeros((1, d + pad), G.dtype)

        def one_sweep(b):
            return cd_sweep.sweep(Gp, cp, diagp, denomp, b, n, thresh, mesh)

    else:
        b0 = jnp.zeros((d,), G.dtype)

        def coord(j, state):
            b, max_delta = state
            gj = G[j] @ b
            rho = (c[j] - gj + G[j, j] * b[j]) / n
            bj = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - thresh, 0.0) / denom[j]
            max_delta = jnp.maximum(max_delta, jnp.abs(bj - b[j]))
            return b.at[j].set(bj), max_delta

        def one_sweep(b):
            return jax.lax.fori_loop(0, d, coord, (b, jnp.zeros((), b.dtype)))

    def sweep(carry):
        b, _, it = carry
        with jax.named_scope("cd.sweep"):
            b, max_delta = one_sweep(b)
        return b, max_delta, it + 1

    def cond(carry):
        _, max_delta, it = carry
        return (it < max_iter) & (max_delta > tol)

    b, _, n_iter = jax.lax.while_loop(
        cond, sweep, (b0, jnp.array(jnp.inf, G.dtype), jnp.array(0, jnp.int32))
    )
    b = b.reshape(-1)[:d]
    b = b / s
    intercept = jnp.where(
        fit_intercept, stats.y_mean - stats.x_mean @ b, jnp.zeros((), b.dtype)
    )
    return b, intercept, n_iter


# -- batched hyperparameter sweep (srml-sweep; docs/tuning_engine.md) --------
# The sufficient-statistics design already makes extra param maps free
# WITHIN a fold; these kernels extend that across folds and candidates so a
# CrossValidator sweep of m (alpha, l1_ratio) candidates x k folds is a
# handful of compiled dispatches over ONE staged dataset: the fold axis is
# expressed as weight masks from a per-row fold id (zero re-staging), and
# the candidate/fold solves run as stacked lanes inside one program.
#
# Lane driving is lax.map, NOT vmap, on purpose: lax.map inlines the exact
# per-solve HLO of solve_linear / solve_elasticnet_cd per lane, so each
# lane is bit-identical to the sequential path's solve on the same stats
# (gated in tests/test_tuning.py), while a vmapped jnp.linalg.solve factors
# the lanes through a batched LU whose low bits drift from the single-lane
# factorization.  The lanes are (D, D) systems — tiny next to the data
# scan — so serializing them inside the program costs nothing measurable.


@partial(jax.jit, static_argnames=("k", "mesh", "chunk"))
def sweep_linreg_fold_stats(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    fold_id: jax.Array,
    k: int = 2,
    mesh=None,
    chunk: int = 32768,
) -> LinregStats:
    """Per-fold TRAIN sufficient statistics from fold-id masks, leading
    (k,) axis on every LinregStats field — one program over the one staged
    dataset instead of k re-staged subset passes.

    fold_id is int32, row-aligned with X (padded rows carry -1, and their
    zero weight masks them out of every fold's train stats anyway).  Fold
    f's train weights are ``w * (fold_id != f)``."""
    if mesh is None:
        per_fold = []
        for f in range(k):
            wf = w * (fold_id != f).astype(w.dtype)
            wsum = wf.sum()
            Xw = X * wf[:, None]
            per_fold.append(
                (
                    wsum,
                    Xw.sum(axis=0),
                    exact_matmul(Xw.T, X),
                    (y * wf).sum(),
                    exact_matmul(Xw.T, y),
                    (y * y * wf).sum(),
                )
            )
    else:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS
        from .linalg import _local_moments

        def per_device(X_loc, y_loc, w_loc, fid_loc):
            outs = []
            for f in range(k):
                wf = w_loc * (fid_loc != f).astype(w_loc.dtype)
                outs.append(_local_moments(X_loc, wf, chunk, y_loc=y_loc))
            stacked = tuple(
                jnp.stack([o[i] for o in outs]) for i in range(6)
            )
            return tuple(jax.lax.psum(s, DATA_AXIS) for s in stacked)

        wsum, xwsum, G, ywsum, c, y2 = shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(DATA_AXIS),) * 4,
            out_specs=(P(),) * 6,
            check_vma=False,
        )(X, y, w, fold_id)
        return LinregStats(
            wsum, xwsum / wsum[:, None], ywsum / wsum, G, c, y2
        )
    wsum, xwsum, G, ywsum, c, y2 = (
        jnp.stack([pf[i] for pf in per_fold]) for i in range(6)
    )
    return LinregStats(wsum, xwsum / wsum[:, None], ywsum / wsum, G, c, y2)


@partial(jax.jit, static_argnames=("fit_intercept", "normalize", "mesh"))
def sweep_solve_linear(
    stats: LinregStats,
    alphas: jax.Array,
    fit_intercept: bool = True,
    normalize: bool = False,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """All (fold, candidate) closed-form OLS/Ridge solves in one dispatch:
    stats carry a leading (k,) fold axis, alphas are the (m,) candidate
    lanes; returns (coef (k, m, D), intercept (k, m)).  `mesh` only keys
    the AOT executable cache (the solves run replicated)."""

    def per_fold(st):
        return jax.lax.map(
            lambda a: solve_linear(
                st, a, fit_intercept=fit_intercept, normalize=normalize
            ),
            alphas,
        )

    return jax.lax.map(per_fold, stats)


@partial(
    jax.jit, static_argnames=("fit_intercept", "normalize", "max_iter", "mesh")
)
def sweep_solve_elasticnet_cd(
    stats: LinregStats,
    alphas: jax.Array,
    l1_ratios: jax.Array,
    tol: jax.Array,
    fit_intercept: bool = True,
    normalize: bool = False,
    max_iter: int = 1000,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All (fold, candidate) coordinate-descent solves in one dispatch;
    each lane runs its OWN while_loop to its own convergence (lax.map), so
    a lane's sweep count is exactly the sequential path's.  Returns
    (coef (k, m, D), intercept (k, m), n_sweeps (k, m))."""

    def per_fold(st):
        return jax.lax.map(
            lambda al: solve_elasticnet_cd(
                st,
                al[0],
                al[1],
                fit_intercept=fit_intercept,
                normalize=normalize,
                max_iter=max_iter,
                tol=tol,
                mesh=mesh,
            ),
            (alphas, l1_ratios),
        )

    return jax.lax.map(per_fold, stats)


@jax.jit
def stream_linreg_chunk_kernel(
    X: jax.Array, y: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One streamed chunk's UNREDUCED linear-regression sufficient
    statistics (wsum, xwsum, G, ywsum, c, y2) — the srml-stream update
    kernel.  Raw weighted sums, not means: the streaming accumulator folds
    chunk partials additively (the same algebra linreg_sufficient_stats
    psums across shards) and derives means once at finalize."""
    xw = X * w[:, None]
    return (
        w.sum(),
        xw.sum(axis=0),
        exact_matmul(xw.T, X),
        (y * w).sum(),
        exact_matmul(xw.T, y),
        (y * y * w).sum(),
    )


@jax.jit
def linear_predict_kernel(X: jax.Array, coef: jax.Array, intercept: jax.Array) -> jax.Array:
    from .sparse import EllMatrix, ell_matvec

    if isinstance(X, EllMatrix):
        return ell_matvec(X, coef) + intercept
    return exact_matmul(X, coef) + intercept


@jax.jit
def multi_linear_predict_kernel(
    X: jax.Array, coefs: jax.Array, intercepts: jax.Array
) -> jax.Array:
    """(N, D) x (M, D) -> (M, N): one pass predicting for M combined models."""
    return exact_matmul(coefs, X.T) + intercepts[:, None]


@jax.jit
def lane_linear_predict_kernel(
    X: jax.Array, lanes: jax.Array, coefs: jax.Array, intercepts: jax.Array
) -> jax.Array:
    """Multiplexed linear_predict_kernel (srml-lanes): coefs (L, D) and
    intercepts (L,) are lane-stacked variant parameters, and row r predicts
    with lane lanes[r] — one kernel per micro-batch across K served model
    variants.  Lane VALUES (and the lane ids) are traced, so paging a new
    variant into a lane is zero new compiles; the per-row dot is the exact
    contraction of the dedicated kernel (SOLVER_PRECISION), so on
    integer-exact data the two are bitwise equal."""
    from .linalg import exact_gather_matmul

    preds = exact_gather_matmul(X, coefs[:, None, :], lanes)[:, 0]
    return preds + jnp.take(intercepts, lanes, axis=0)
