#
# Logistic-regression objective + fit/predict kernels (binary sigmoid and
# multinomial softmax), pure jax, mesh-aware.
#
# TPU-native replacement for cuML's LogisticRegressionMG qn solver as driven
# by the reference (classification.py:915-1001).  Objective matches Spark /
# cuml-with-penalty_normalized=False semantics (classification.py:960):
#
#   f(W, b) = (1/sum w) * sum_i w_i * logloss_i
#           + reg * ( l1r * |W|_1  +  (1 - l1r)/2 * |W|_2^2 )
#
# with reg = regParam (C = 1/reg in the param surface), intercepts never
# regularized.  L1 is handled by OWL-QN in ops/lbfgs.py.
#
# The data term and its gradient, over the row-sharded (X, y, w):
#   - dense binary: closed form (ops/logistic_pass.py), no autodiff.  On the
#     TPU the Pallas call `logistic_pass` reads each row ONCE an evaluation
#     (one_pass_objective says when); on a mesh every device walks its own
#     rows under shard_map and one psum of (g, gb, loss) joins them.  Anywhere
#     else the same sums in plain jnp, whose reductions GSPMD turns into psums.
#   - ELL sparse X, multinomial: closed form too where the Pallas call
#     `softmax_ell_pass` takes the fit (ops/softmax_ell_pass.py: a float32 table
#     of at most 128 columns, on the TPU; ell_pass_objective says when): one
#     read of the table an evaluation, per device under shard_map, one psum.
#   - every other fit (dense multinomial, binary ELL, wider or float64 ELL
#     tables, ELL off the TPU): jax.value_and_grad of the loss, whose
#     reductions become psums the same way (two reads of X an evaluation).
#

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..parallel.mesh import DATA_AXIS
from .lbfgs import minimize_lbfgs, minimize_lbfgs_batched
from .linalg import SOLVER_PRECISION, exact_matmul
from .logistic_pass import binary_block_sums, one_pass_sums, takes_table
from .pallas_tpu import pallas_enabled
from .softmax_ell_pass import ell_pass_sums, takes as ell_pass_takes
from .sparse import EllMatrix, ell_matmat


def _unpack(theta: jax.Array, k: int, d: int, fit_intercept: bool):
    W = theta[: k * d].reshape(k, d)
    b = theta[k * d :] if fit_intercept else jnp.zeros((k,), theta.dtype)
    return W, b


def _model_scores(X, W, b):
    """X @ W.T + b for dense (N, D) or ELL sparse X (ops/sparse.py): the
    sparse form is a W-row gather whose jax.grad transpose is the
    scatter-add X.T @ r — one code path for both L-BFGS objectives, no
    densification of sparse inputs (reference sparse qn fit,
    classification.py:1206-1218)."""
    if isinstance(X, EllMatrix):
        return ell_matmat(X, W.T) + b
    return X @ W.T + b


def _binary_data_loss(theta, X, y01, w, d, fit_intercept):
    W, b = _unpack(theta, 1, d, fit_intercept)
    z = _model_scores(X, W, b)[:, 0]
    # logloss via logaddexp for stability: y in {0,1}
    ll = jnp.logaddexp(0.0, z) - y01 * z
    return (ll * w).sum() / w.sum()


def _softmax_data_loss(theta, X, yidx, w, k, d, fit_intercept):
    W, b = _unpack(theta, k, d, fit_intercept)
    z = _model_scores(X, W, b)  # (N, K)
    logp = z - jax.scipy.special.logsumexp(z, axis=1, keepdims=True)
    ll = -jnp.take_along_axis(logp, yidx[:, None], axis=1)[:, 0]
    return (ll * w).sum() / w.sum()


def one_pass_objective(X, k: int, mesh=None) -> bool:
    """Whether a fit of (X, k classes) takes the one-pass data term: a dense
    float32 table, binary, where Pallas kernels run, of more rows a device
    than XLA keeps in VMEM (ops/logistic_pass.takes_table).  Static at
    dispatch, so the host knows it too (the lbfgs.one_pass_fits counter)."""
    if isinstance(X, EllMatrix) or k != 1 or X.dtype != jnp.float32:
        return False
    shards = 1 if mesh is None else mesh.shape[DATA_AXIS]
    return pallas_enabled() and takes_table(X.shape[0] // shards, X.shape[1])


def ell_pass_objective(X, k: int) -> bool:
    """Whether a fit of (X, k classes) takes the one-pass SPARSE data term: an
    ELL table the kernel takes (ops/softmax_ell_pass.takes: float32, at most
    128 columns, k >= 2), where Pallas kernels run.  Static at dispatch, as
    one_pass_objective is (the lbfgs.ell_pass_fits counter)."""
    return ell_pass_takes(X, k) and pallas_enabled()


def _closed_form_value_and_grad(sums, w, k, d, fit_intercept):
    """theta -> (the data loss, its gradient) from `sums`: (W (k, d), b (k,))
    -> (sum of weighted log-loss, its gradient by W, by b), all unnormalised.
    Built once a solve, outside the optimizer's loop; so is 1 / sum w, which
    the sums are multiplied by (autodiff's gradient was: a division in the
    loop costs a streamed chunk's fit 1%, v5e, 8192 x 256)."""
    inv_wsum = 1.0 / w.sum()

    def value_and_grad(theta):
        W, b = _unpack(theta, k, d, fit_intercept)
        loss, g, gb = sums(W, b)
        g = g.reshape(-1)
        if fit_intercept:
            g = jnp.concatenate([g, gb.reshape(-1)])
        return loss * inv_wsum, g * inv_wsum

    return value_and_grad


def _binary_value_and_grad(X, y01, w, d, fit_intercept, mesh):
    """theta -> (_binary_data_loss, its gradient) of a dense X in closed
    form: the one-pass kernel where it takes the table, else the same sums in
    plain jnp."""
    if one_pass_objective(X, 1, mesh):
        sums = one_pass_sums(X, y01, w, mesh)
    else:
        def sums(W, b):
            return binary_block_sums(X, y01, w, W, b)

    return _closed_form_value_and_grad(
        lambda W, b: sums(W[0], b[0]), w, 1, d, fit_intercept
    )


def _softmax_ell_value_and_grad(X, yidx, w, k, d, fit_intercept, mesh):
    """theta -> (_softmax_data_loss, its gradient) of an ELL X from one read
    of it an evaluation (ops/softmax_ell_pass.py)."""
    sums = ell_pass_sums(X, yidx, w, mesh)

    def scoped(W, b):
        with jax.named_scope("lbfgs.ell_pass"):
            return sums(W, b)

    return _closed_form_value_and_grad(scoped, w, k, d, fit_intercept)


def _solve_from(
    X, y_enc, w, theta0, k, reg, l1_ratio, fit_intercept, max_iter, tol,
    use_owlqn, mesh=None,
):
    """Shared L-BFGS/OWL-QN solve from an explicit starting point — the ONE
    objective construction behind the batch kernel (zero init) and the
    streaming warm-start kernel (srml-stream partial_fit resumes from the
    running coefficients)."""
    d = X.shape[1]
    n_params = k * d + (k if fit_intercept else 0)
    dtype = X.dtype
    l2 = reg * (1.0 - l1_ratio)
    l1 = reg * l1_ratio
    reg_mask = jnp.concatenate(
        [jnp.ones(k * d, dtype), jnp.zeros(n_params - k * d, dtype)]
    )

    def penalty(t):
        return 0.5 * l2 * ((t * reg_mask) ** 2).sum()

    data_value_and_grad = None
    if k == 1 and not isinstance(X, EllMatrix):
        data_value_and_grad = _binary_value_and_grad(
            X, y_enc.astype(dtype), w, d, fit_intercept, mesh
        )
    elif ell_pass_objective(X, k):
        data_value_and_grad = _softmax_ell_value_and_grad(
            X, y_enc.astype(jnp.int32), w, k, d, fit_intercept, mesh
        )
    if data_value_and_grad is not None:

        def value_and_grad(theta):
            f, g = data_value_and_grad(theta)
            return f + penalty(theta), g + l2 * reg_mask * theta

    else:

        def value_and_grad(theta):
            def smooth(t):
                if k == 1:
                    data = _binary_data_loss(
                        t, X, y_enc.astype(dtype), w, d, fit_intercept
                    )
                else:
                    data = _softmax_data_loss(
                        t, X, y_enc.astype(jnp.int32), w, k, d, fit_intercept
                    )
                return data + penalty(t)

            return jax.value_and_grad(smooth)(theta)

    result = minimize_lbfgs(
        value_and_grad,
        theta0,
        l1_weight=l1 * reg_mask,
        max_iter=max_iter,
        tol=tol,
        history=10,
        use_owlqn=use_owlqn,
    )
    W, b = _unpack(result.x, k, d, fit_intercept)
    return W, b, result.n_iter, result.converged, result.n_evals


@partial(
    jax.jit,
    static_argnames=("k", "fit_intercept", "max_iter", "use_owlqn", "mesh"),
)
def logistic_fit_kernel(
    X: jax.Array,
    y_enc: jax.Array,
    w: jax.Array,
    k: int,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    max_iter: int,
    tol: float,
    use_owlqn: bool,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fit one logistic model; k == 1 -> binary sigmoid (y_enc in {0,1}),
    k >= 2 -> multinomial softmax (y_enc = class index).  Returns
    (W (k, D), b (k,), n_iter, converged, n_evals): n_evals counts the
    objective's evaluations (ops/lbfgs.LbfgsResult).  `mesh` is the mesh a
    row-sharded X lies on: the one-pass data term runs per device under its
    shard_map (a Pallas call is no program GSPMD can partition); None for a
    table on one device."""
    d = X.shape[1]
    n_params = k * d + (k if fit_intercept else 0)
    return _solve_from(
        X, y_enc, w, jnp.zeros((n_params,), X.dtype), k, reg, l1_ratio,
        fit_intercept, max_iter, tol, use_owlqn, mesh,
    )


@partial(
    jax.jit,
    static_argnames=("k", "fit_intercept", "max_iter", "use_owlqn"),
)
def logistic_warm_fit_kernel(
    X: jax.Array,
    y_enc: jax.Array,
    w: jax.Array,
    W0: jax.Array,
    b0: jax.Array,
    reg: jax.Array,
    l1_ratio: jax.Array,
    tol: jax.Array,
    k: int,
    fit_intercept: bool,
    max_iter: int,
    use_owlqn: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """logistic_fit_kernel warm-started from (W0 (k, D), b0 (k,)) — the
    srml-stream partial_fit kernel: each device-staged chunk resumes the
    solve from the running streamed coefficients instead of zeros, so a
    steady stream converges per chunk in a handful of iterations.  The
    objective (and therefore the fixed point) is identical to the batch
    kernel's — only the starting point differs.  reg/l1_ratio/tol are
    TRACED scalars (positional, after the arrays) so the one cached
    executable serves every regularization setting at a geometry."""
    theta0 = W0.reshape(-1).astype(X.dtype)
    if fit_intercept:
        theta0 = jnp.concatenate([theta0, b0.astype(X.dtype)])
    return _solve_from(
        X, y_enc, w, theta0, k, reg, l1_ratio, fit_intercept, max_iter, tol,
        use_owlqn,
    )


# -- batched hyperparameter sweep (srml-sweep; docs/tuning_engine.md) --------


def _line_search_trials(columns: int) -> int:
    """How many steps of the halving sequence a sweep's line search tries in
    one evaluation (ops/lbfgs.minimize_lbfgs_batched): as many, of 4, 2 or 1,
    as keep the lanes' score columns within ONE 128-wide MXU tile, where the
    product hides under the read of X and the extra trial points cost nothing.
    24 lanes halve somewhere in a quarter of their iterations, and each
    halving was one more scan of the table for all of them (258 scans a job
    for 200 iterations at 400,000 x 3000; PERF.md section 6, PR 39)."""
    return 4 if 4 * columns <= 128 else 2 if 2 * columns <= 128 else 1


@partial(
    jax.jit,
    static_argnames=(
        "k_folds", "kcls", "fit_intercept", "max_iter", "use_owlqn", "mesh"
    ),
)
def sweep_logistic_fit_kernel(
    X: jax.Array,
    y_enc: jax.Array,
    w: jax.Array,
    fold_id: jax.Array,
    regs: jax.Array,
    l1_ratios: jax.Array,
    tol: jax.Array,
    k_folds: int = 2,
    kcls: int = 1,
    fit_intercept: bool = True,
    max_iter: int = 100,
    use_owlqn: bool = False,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fit a whole regularization sweep — m candidates x k folds — as ONE
    jitted L-BFGS/OWL-QN run over the one staged dataset.

    Folds are weight masks from the per-row fold id (fold f trains on
    ``w * (fold_id != f)``; padded rows carry -1 and zero weight), so no
    fold is ever re-staged; candidates ride a lane axis whose (m,)
    reg/l1_ratio vectors are TRACED values — a different grid at the same
    shapes reuses the compiled executable.  Each optimizer iteration
    evaluates every lane's smooth objective through one fused contraction
    (the (N, D) x (D, k*m*kcls) product XLA builds from the lane einsum, and
    its transpose under autodiff: two reads of X an evaluation), both at
    SOLVER_PRECISION: float32 products, as the single fit's data term has
    (ops/logistic_pass.py), so a sub-model is the model its own fit would
    give and not that fit's bfloat16 shadow.  Per-lane convergence masks in
    minimize_lbfgs_batched freeze finished lanes.  Returns (W (k, m, kcls,
    D), b (k, m, kcls), n_iter (k, m), converged (k, m), n_evals (k, m),
    n_scans ()): n_scans counts the evaluations the lanes shared
    (ops/lbfgs.LbfgsResult).  `mesh` only keys the AOT executable cache:
    the row-sharded reductions compile to psums via GSPMD exactly like the
    single-fit kernel's."""
    n, d = X.shape
    mb = regs.shape[0]
    lanes = k_folds * mb
    n_params = kcls * d + (kcls if fit_intercept else 0)
    dtype = X.dtype
    fold_axis = jnp.arange(k_folds, dtype=fold_id.dtype)
    w_folds = w[None, :] * (fold_id[None, :] != fold_axis[:, None]).astype(
        dtype
    )  # (k, N) train-mask weights
    wsum_f = w_folds.sum(axis=1)
    l2 = (regs * (1.0 - l1_ratios)).astype(dtype)
    l1 = (regs * l1_ratios).astype(dtype)
    reg_mask = jnp.concatenate(
        [jnp.ones(kcls * d, dtype), jnp.zeros(n_params - kcls * d, dtype)]
    )
    y01 = y_enc.astype(dtype)
    yidx = y_enc.astype(jnp.int32)

    def value_and_grad(theta):  # (T * lanes, P) -> ((T * lanes,), (T * lanes, P))
        def smooth(t):
            # T trial points a lane (the line search's steps, outermost): all
            # of them ride the one contraction, so the one scan of X
            tf = t.reshape(-1, k_folds, mb, n_params)
            W = tf[..., : kcls * d].reshape(-1, k_folds, mb, kcls, d)
            z = jnp.einsum("nd,tfmkd->tfmnk", X, W, precision=SOLVER_PRECISION)
            if fit_intercept:
                z = z + tf[..., kcls * d :][:, :, :, None, :]
            if kcls == 1:
                zz = z[..., 0]  # (T, k, m, N)
                ll = jnp.logaddexp(0.0, zz) - y01 * zz
            else:
                logp = z - jax.scipy.special.logsumexp(
                    z, axis=-1, keepdims=True
                )
                idx = jnp.broadcast_to(
                    yidx[None, None, None, :, None], z.shape[:-1] + (1,)
                )
                ll = -jnp.take_along_axis(logp, idx, axis=-1)[..., 0]
            data = (ll * w_folds[None, :, None, :]).sum(axis=-1) / wsum_f[None, :, None]
            reg_term = 0.5 * l2 * ((tf * reg_mask) ** 2).sum(axis=-1)
            per_lane = (data + reg_term).reshape(-1)
            # lanes are independent in theta, so the grad of the SUM is the
            # stack of per-lane grads — one backward pass for the sweep
            return per_lane.sum(), per_lane
        (_, per_lane), g = jax.value_and_grad(smooth, has_aux=True)(theta)
        return per_lane, g

    l1w = jnp.broadcast_to(
        l1[None, :, None] * reg_mask[None, None, :],
        (k_folds, mb, n_params),
    ).reshape(lanes, n_params)
    result = minimize_lbfgs_batched(
        value_and_grad,
        jnp.zeros((lanes, n_params), dtype),
        l1_weight=l1w,
        max_iter=max_iter,
        tol=tol,
        history=10,
        use_owlqn=use_owlqn,
        trials=_line_search_trials(lanes * kcls),
    )
    W = result.x[:, : kcls * d].reshape(k_folds, mb, kcls, d)
    if fit_intercept:
        b = result.x[:, kcls * d :].reshape(k_folds, mb, kcls)
    else:
        b = jnp.zeros((k_folds, mb, kcls), dtype)
    return (
        W,
        b,
        result.n_iter.reshape(k_folds, mb),
        result.converged.reshape(k_folds, mb),
        result.n_evals.reshape(k_folds, mb),
        result.n_scans,
    )


@partial(jax.jit, static_argnames=("num_classes", "mesh"))
def sweep_logistic_score_kernel(
    X: jax.Array,
    y_enc: jax.Array,
    fold_id: jax.Array,
    W: jax.Array,
    b: jax.Array,
    eps: jax.Array,
    num_classes: int = 2,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Held-out statistics of every (fold, candidate) model of a sweep, from
    ONE read of the staged table: the scoring half of a CrossValidator whose
    rows live on the device (tuning.py).

    W (k, m, kcls, D) and b (k, m, kcls) are sweep_logistic_fit_kernel's, still
    on the device; row r is held out of fold fold_id[r] and of no other, so it
    is scored by that fold's m models alone (padded rows carry -1: no fold).
    One (N, D) x (D, k*m*kcls) product at SOLVER_PRECISION gives every lane's
    scores, as the host route's batched product does
    (LogisticRegressionModel._get_eval_predict_func); each row keeps its own
    fold's.  Returns what metrics.MulticlassMetrics merges, per lane:
    (confusion counts (k, m, C, C) int32, [true class, predicted class];
    sum of -log max(P(true class), eps) (k, m)).  `mesh` only keys the
    executable cache: the row reductions become psums via GSPMD."""
    k_folds, mb, kcls, _ = W.shape
    C = num_classes
    z_all = jnp.einsum("nd,fmkd->nfmk", X, W, precision=SOLVER_PRECISION) + b[None]
    own = fold_id[:, None] == jnp.arange(k_folds, dtype=fold_id.dtype)[None, :]
    z = jnp.where(own[:, :, None, None], z_all, 0.0).sum(axis=1)  # (N, m, kcls)
    yidx = y_enc.astype(jnp.int32)
    if kcls == 1:
        zz = z[..., 0]
        ll = jnp.logaddexp(0.0, zz) - yidx[:, None].astype(zz.dtype) * zz
        pred = (zz > 0).astype(jnp.int32)
    else:
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        ll = -jnp.take_along_axis(
            logp, jnp.broadcast_to(yidx[:, None, None], (z.shape[0], mb, 1)), axis=-1
        )[..., 0]
        pred = jnp.argmax(z, axis=-1).astype(jnp.int32)
    ll = jnp.minimum(ll, -jnp.log(eps).astype(ll.dtype))  # P clamped at eps
    loss = jnp.where(own[:, :, None], ll[:, None, :], 0.0).sum(axis=0)  # (k, m)
    # a row's cell of its fold's confusion matrix, counted in whole numbers
    cell = fold_id[:, None] * (C * C) + yidx[:, None] * C + pred  # (N, m)
    cell = jnp.where(fold_id[:, None] >= 0, cell, -1)
    cells = jnp.arange(k_folds * C * C, dtype=cell.dtype)
    conf = (cell[:, :, None] == cells[None, None, :]).sum(axis=0, dtype=jnp.int32)
    return conf.reshape(mb, k_folds, C, C).transpose(1, 0, 2, 3), loss


@jax.jit
def logistic_decision_kernel(X: jax.Array, W: jax.Array, b: jax.Array) -> jax.Array:
    """

    Raw decision scores (N, k): k == 1 column for binary, k columns for
    multinomial (matches cuML decision_function semantics used by the
    reference transform, classification.py:1236-1262).  Accepts dense or
    ELL sparse feature blocks."""
    if isinstance(X, EllMatrix):
        return _model_scores(X, W, b)
    return exact_matmul(X, W.T) + b


def scores_to_probs(scores: jnp.ndarray, num_classes: int) -> jnp.ndarray:
    """Sigmoid for binary single-column scores, stable softmax otherwise
    (behavior of classification.py:1236-1249)."""
    if num_classes == 2 and scores.shape[1] == 1:
        p1 = jax.nn.sigmoid(scores[:, 0])
        return jnp.stack([1.0 - p1, p1], axis=1)
    return jax.nn.softmax(scores, axis=1)


def scores_to_labels(scores: jnp.ndarray, num_classes: int) -> jnp.ndarray:
    if num_classes == 2 and scores.shape[1] == 1:
        return (scores[:, 0] > 0).astype(jnp.float32)
    return jnp.argmax(scores, axis=1).astype(jnp.float32)


@partial(jax.jit, static_argnames=("num_classes",))
def lane_logistic_predict_kernel(
    X: jax.Array, lanes: jax.Array, Ws: jax.Array, bs: jax.Array, *, num_classes: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Multiplexed fused serve kernel (srml-lanes): Ws (L, k, D) and
    bs (L, k) are lane-stacked variant parameters, row r scores against
    lane lanes[r], and decision scores, probabilities and label indices
    come out of ONE dispatch — the lane-batched form of the per-model
    _serve_kernel.  The per-row contraction is exact_gather_matmul
    (SOLVER_PRECISION), so lane-batched scores are bitwise-equal to the
    dedicated path on integer-exact data, and sigmoid/softmax/argmax on
    bitwise-identical scores are bitwise-identical outputs."""
    from .linalg import exact_gather_matmul

    scores = exact_gather_matmul(X, Ws, lanes) + jnp.take(bs, lanes, axis=0)
    return (
        scores,
        scores_to_probs(scores, num_classes),
        scores_to_labels(scores, num_classes),
    )
