#
# L-BFGS and OWL-QN, fully jitted (lax.while_loop, static history buffers).
#
# TPU-native replacement for the "qn" solver family behind cuML's
# LogisticRegressionMG (the reference configures it at
# classification.py:955-961: lbfgs_memory=10, penalty_normalized=False).
# The smooth objective's value+grad closure is evaluated over row-sharded
# arrays: its reductions compile to psums, or it brings one psum of its own
# (the one-pass logistic data term) — every optimizer iteration is one fused
# device program with one all-reduce an evaluation, no host round trips.
#
# OWL-QN (Andrew & Gao 2007) handles the L1 term: pseudo-gradient at the
# current orthant, direction aligned against the pseudo-gradient, orthant
# projection inside the backtracking line search.  l1_weight is a
# per-coordinate vector so intercepts stay unregularized (Spark semantics).
#
# jax.named_scope marks the two parts an iteration's device time divides
# into, lbfgs.eval (value and gradient) and lbfgs.direction (two-loop
# recursion): metadata on the operations, read in xprof's trace viewer and op
# profile, free at run time.
#

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class LbfgsResult(NamedTuple):
    x: jax.Array
    f: jax.Array
    n_iter: jax.Array
    converged: jax.Array
    # evaluations of the objective (value and gradient): the one at x0 and
    # every line-search trial.  An evaluation is what a fit's time is made
    # of (in the logistic kernels one pass over X for a dense binary fit on
    # the TPU, ops/logistic_pass.py, and two wherever the gradient comes
    # from autodiff), and the line search takes another number of them on
    # every dataset; n_iter does not say.
    n_evals: jax.Array
    # minimize_lbfgs_batched alone: the evaluations its lanes SHARED, a scalar.
    # Every trip of a line search evaluates all lanes at once, so one trip is one
    # scan of the data however many lanes are still searching: what a sweep's time
    # is made of, where the lanes' n_evals add up to what the lanes would have
    # taken alone.  None from minimize_lbfgs (there n_evals is that number).
    n_scans: Optional[jax.Array] = None


def _pseudo_gradient(x, g, l1w):
    """OWL-QN pseudo-gradient: subgradient choice that is steepest descent."""
    right = g + l1w
    left = g - l1w
    pg_zero = jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))
    return jnp.where(x != 0, g + l1w * jnp.sign(x), pg_zero)


def _two_loop(g, S, Y, rho, count, history):
    """Standard two-loop recursion over the circular (history, P) buffers."""
    idxs = jnp.arange(history)

    def bwd(i, carry):
        q, alphas = carry
        # iterate newest -> oldest: j = count-1-i (mod history)
        j = jnp.mod(count - 1 - i, history)
        valid = i < jnp.minimum(count, history)
        a = jnp.where(valid, rho[j] * (S[j] @ q), 0.0)
        q = q - a * Y[j] * valid
        return q, alphas.at[j].set(a)

    q, alphas = jax.lax.fori_loop(0, history, bwd, (g, jnp.zeros((history,), g.dtype)))
    last = jnp.mod(count - 1, history)
    sy = S[last] @ Y[last]
    yy = Y[last] @ Y[last]
    gamma = jnp.where((count > 0) & (yy > 0), sy / yy, 1.0)
    q = q * gamma

    def fwd(i, q):
        j = jnp.mod(count - jnp.minimum(count, history) + i, history)
        valid = i < jnp.minimum(count, history)
        b = jnp.where(valid, rho[j] * (Y[j] @ q), 0.0)
        return q + (alphas[j] - b) * S[j] * valid

    q = jax.lax.fori_loop(0, history, fwd, q)
    return q


@partial(
    jax.jit,
    static_argnames=(
        "value_and_grad", "max_iter", "history", "use_owlqn", "max_ls", "trials"
    ),
)
def minimize_lbfgs_batched(
    value_and_grad: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    x0: jax.Array,
    l1_weight: jax.Array,
    max_iter: int = 100,
    tol: float = 1e-6,
    history: int = 10,
    use_owlqn: bool = False,
    max_ls: int = 20,
    trials: int = 1,
) -> LbfgsResult:
    """Lane-batched minimize_lbfgs for hyperparameter sweeps (srml-sweep).

    x0/l1_weight are (L, P) — one lane per (fold, candidate) — and
    value_and_grad maps (L, P) -> ((L,), (L, P)), evaluated for ALL lanes
    each step so the data term is one fused contraction per iteration
    instead of L separate fits.  The outer while_loop runs until every lane
    converges; lanes that finished (their own convergence test, their own
    iteration budget) take masked no-op updates — state, memory buffers and
    iteration counters freeze exactly where the lane's solo run would have
    stopped.  The line search is the same masked construction: each lane
    halves its own step until its own Armijo test passes, frozen lanes ride
    along untouched.  Per-lane semantics mirror minimize_lbfgs; per-lane
    NUMBERS can differ from a solo run in the last bits because the fused
    contraction reduces across a different geometry (docs/tuning_engine.md
    documents the equality contract this leaves).

    `trials` steps of the halving sequence are tried in ONE evaluation: a trip
    of the line search evaluates t, t/2, ... t/2^(trials-1) for every lane
    (value_and_grad then sees (trials * L, P), the trials outermost) and a
    lane takes the first that passes, which is the step its own halving
    search would have stopped at, so its iterates and its n_evals are the
    solo run's whatever `trials` is.  What changes is how many trips the
    lanes share: with many lanes some lane halves in every few iterations,
    and every such halving was one more scan of the data for all of them.
    A caller whose evaluation is bound by reading the data, not by the
    lanes' columns, gets the extra trials for nothing."""
    L, P = x0.shape
    T = trials
    dtype = x0.dtype
    l1w = l1_weight.astype(dtype)

    def full_objective(x):
        with jax.named_scope("lbfgs.eval"):
            f, g = value_and_grad(x)
        if use_owlqn:  # x may stack several trial points a lane
            f = f + (l1w * jnp.abs(x.reshape(-1, L, P))).sum(axis=-1).reshape(-1)
        return f, g

    f0, g0 = full_objective(x0)
    halvings = 0.5 ** jnp.arange(T, dtype=dtype)
    state = (
        x0,
        f0,
        g0,
        jnp.zeros((L, history, P), dtype),  # S
        jnp.zeros((L, history, P), dtype),  # Y
        jnp.zeros((L, history), dtype),     # rho
        jnp.zeros((L,), jnp.int32),         # memory count
        jnp.zeros((L,), jnp.int32),         # per-lane iteration
        jnp.zeros((L,), bool),              # converged
        jnp.ones((L,), jnp.int32),          # per-lane evaluations (f0 is one)
        jnp.array(1, jnp.int32),            # shared evaluations (f0 is one)
    )
    two_loop_lanes = jax.vmap(_two_loop, in_axes=(0, 0, 0, 0, 0, None))

    def cond(state):
        _, _, _, _, _, _, _, it, converged, _, _ = state
        return jnp.any((it < max_iter) & (~converged))

    def body(state):
        x, f, g, S, Y, rho, count, it, converged, n_evals, n_scans = state
        active = (it < max_iter) & (~converged)
        pg = _pseudo_gradient(x, g, l1w) if use_owlqn else g
        with jax.named_scope("lbfgs.direction"):
            d = -two_loop_lanes(pg, S, Y, rho, count, history)
        if use_owlqn:
            d = jnp.where(d * -pg > 0, d, 0.0)
        xi = jnp.sign(x)
        xi = jnp.where(x == 0, jnp.sign(-pg), xi) if use_owlqn else xi
        deriv = (pg * d).sum(axis=-1)
        bad_dir = deriv >= 0
        d = jnp.where(bad_dir[:, None], -pg, d)
        deriv = jnp.where(bad_dir, -(pg * pg).sum(axis=-1), deriv)
        t0 = jnp.where(
            count == 0,
            1.0 / jnp.maximum(jnp.linalg.norm(pg, axis=-1), 1.0),
            1.0,
        ).astype(dtype)

        def ls_body(ls_state):
            t, xn, fn, gn, n_ls, ok, trips = ls_state
            live = active & (~ok) & (n_ls < max_ls)
            steps = halvings[:, None] * t[None, :]  # (T, L)
            x_try = x[None] + steps[:, :, None] * d[None]
            if use_owlqn:
                x_try = jnp.where(jnp.sign(x_try) == xi[None], x_try, 0.0)
            f_try, g_try = full_objective(x_try.reshape(T * L, P))
            f_try = f_try.reshape(T, L)
            # a lane takes the first of its steps that passes, among those
            # its own search would still have tried
            may = (n_ls[None, :] + jnp.arange(T)[:, None]) < max_ls
            ok_try = (f_try <= f[None] + 1e-4 * steps * deriv[None]) & may
            took = jnp.argmax(ok_try, axis=0)  # 0 where none passes
            passed = ok_try.any(axis=0)
            tried = jnp.where(passed, took + 1, jnp.minimum(T, max_ls - n_ls))
            pick = took[None, :]
            lv = live[:, None]
            return (
                jnp.where(live, t * 0.5**T, t),
                jnp.where(
                    lv, jnp.take_along_axis(x_try, pick[:, :, None], 0)[0], xn
                ),
                jnp.where(live, jnp.take_along_axis(f_try, pick, 0)[0], fn),
                jnp.where(
                    lv,
                    jnp.take_along_axis(
                        g_try.reshape(T, L, P), pick[:, :, None], 0
                    )[0],
                    gn,
                ),
                jnp.where(live, n_ls + tried.astype(jnp.int32), n_ls),
                jnp.where(live, passed, ok),
                trips + 1,
            )

        def ls_cond(ls_state):
            _, _, _, _, n_ls, ok, _ = ls_state
            return jnp.any(active & (~ok) & (n_ls < max_ls))

        # n_ls counts a lane's own trials (a frozen lane rides along
        # uncounted), so a lane's n_evals is its solo run's; trips counts
        # the evaluations all lanes shared
        _, x_new, f_new, g_new, n_ls, ls_ok, trips = jax.lax.while_loop(
            ls_cond,
            ls_body,
            (
                t0, x, f, g, jnp.zeros((L,), jnp.int32), jnp.zeros((L,), bool),
                jnp.array(0, jnp.int32),
            ),
        )
        # per-lane: on line-search exhaustion keep the current iterate
        keep = ls_ok[:, None]
        x_new = jnp.where(keep, x_new, x)
        f_new = jnp.where(ls_ok, f_new, f)
        g_new = jnp.where(keep, g_new, g)

        s = x_new - x
        yv = g_new - g
        sy = (s * yv).sum(axis=-1)
        store = active & (sy > 1e-10)
        slot = jnp.mod(count, history)
        hit = (
            jnp.arange(history)[None, :] == slot[:, None]
        ) & store[:, None]  # (L, history) one-hot of each lane's slot
        S = jnp.where(hit[:, :, None], s[:, None, :], S)
        Y = jnp.where(hit[:, :, None], yv[:, None, :], Y)
        rho = jnp.where(
            hit, (1.0 / jnp.where(sy != 0, sy, 1.0))[:, None], rho
        )
        count = count + store.astype(jnp.int32)

        pg_new = _pseudo_gradient(x_new, g_new, l1w) if use_owlqn else g_new
        converged_new = (
            (jnp.abs(f - f_new) <= tol * jnp.maximum(jnp.abs(f_new), 1.0))
            | (jnp.max(jnp.abs(pg_new), axis=-1) <= tol)
            | (~ls_ok)
        )
        # frozen lanes take no-op updates across the board
        act = active[:, None]
        return (
            jnp.where(act, x_new, x),
            jnp.where(active, f_new, f),
            jnp.where(act, g_new, g),
            S,
            Y,
            rho,
            count,
            it + active.astype(jnp.int32),
            jnp.where(active, converged_new, converged),
            n_evals + n_ls,
            n_scans + trips,
        )

    x, f, g, S, Y, rho, count, it, converged, n_evals, n_scans = jax.lax.while_loop(
        cond, body, state
    )
    return LbfgsResult(
        x=x, f=f, n_iter=it, converged=converged, n_evals=n_evals, n_scans=n_scans
    )


@partial(jax.jit, static_argnames=("value_and_grad", "max_iter", "history", "use_owlqn", "max_ls"))
def minimize_lbfgs(
    value_and_grad: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
    x0: jax.Array,
    l1_weight: jax.Array,
    max_iter: int = 100,
    tol: float = 1e-6,
    history: int = 10,
    use_owlqn: bool = False,
    max_ls: int = 20,
) -> LbfgsResult:
    """Minimize f_smooth(x) + sum(l1_weight * |x|).

    value_and_grad returns (f_smooth, grad_smooth); the L1 term is handled by
    OWL-QN when use_owlqn.  Convergence: |f_k - f_{k-1}| <= tol * max(|f_k|, 1)
    (the classic L-BFGS relative-improvement test) or inf-norm of the
    (pseudo-)gradient <= tol.
    """
    P = x0.shape[0]
    dtype = x0.dtype
    l1w = l1_weight.astype(dtype)

    def full_objective(x):
        with jax.named_scope("lbfgs.eval"):
            f, g = value_and_grad(x)
        if use_owlqn:
            f = f + (l1w * jnp.abs(x)).sum()
        return f, g

    f0, g0 = full_objective(x0)

    class_state = (
        x0,
        f0,
        g0,
        jnp.zeros((history, P), dtype),  # S
        jnp.zeros((history, P), dtype),  # Y
        jnp.zeros((history,), dtype),    # rho
        jnp.array(0, jnp.int32),         # memory count
        jnp.array(0, jnp.int32),         # iteration
        jnp.array(False),                # converged
        jnp.array(1, jnp.int32),         # evaluations (f0 is one)
    )

    def cond(state):
        _, _, _, _, _, _, _, it, converged, _ = state
        return (it < max_iter) & (~converged)

    def body(state):
        x, f, g, S, Y, rho, count, it, _, n_evals = state
        pg = _pseudo_gradient(x, g, l1w) if use_owlqn else g
        with jax.named_scope("lbfgs.direction"):
            d = -_two_loop(pg, S, Y, rho, count, history)
        if use_owlqn:
            # align the direction against the pseudo-gradient's orthant
            d = jnp.where(d * -pg > 0, d, 0.0)
        # reference orthant for the projected line search
        xi = jnp.sign(x)
        xi = jnp.where(x == 0, jnp.sign(-pg), xi) if use_owlqn else xi
        deriv = pg @ d
        # fall back to steepest descent when the direction is not a descent one
        bad_dir = deriv >= 0
        d = jnp.where(bad_dir, -pg, d)
        deriv = jnp.where(bad_dir, -(pg @ pg), deriv)
        t0 = jnp.where(
            count == 0, 1.0 / jnp.maximum(jnp.linalg.norm(pg), 1.0), 1.0
        ).astype(dtype)

        def ls_body(ls_state):
            t, _, _, _, n_ls, _ = ls_state
            x_new = x + t * d
            if use_owlqn:
                x_new = jnp.where(jnp.sign(x_new) == xi, x_new, 0.0)
            f_new, g_new = full_objective(x_new)
            ok = f_new <= f + 1e-4 * t * deriv
            return (t * 0.5, x_new, f_new, g_new, n_ls + 1, ok)

        def ls_cond(ls_state):
            _, _, _, _, n_ls, ok = ls_state
            return (~ok) & (n_ls < max_ls)

        _, x_new, f_new, g_new, n_ls, ls_ok = jax.lax.while_loop(
            ls_cond, ls_body, (t0, x, f, g, jnp.array(0, jnp.int32), jnp.array(False))
        )
        # on line-search exhaustion keep the current iterate (the last trial
        # point failed Armijo and may be worse) and stop
        x_new = jnp.where(ls_ok, x_new, x)
        f_new = jnp.where(ls_ok, f_new, f)
        g_new = jnp.where(ls_ok, g_new, g)

        s = x_new - x
        y = g_new - g
        sy = s @ y
        store = sy > 1e-10
        slot = jnp.mod(count, history)
        S = jnp.where(store, S.at[slot].set(s), S)
        Y = jnp.where(store, Y.at[slot].set(y), Y)
        rho = jnp.where(store, rho.at[slot].set(1.0 / jnp.where(sy != 0, sy, 1.0)), rho)
        count = count + store.astype(jnp.int32)

        pg_new = _pseudo_gradient(x_new, g_new, l1w) if use_owlqn else g_new
        converged = (
            (jnp.abs(f - f_new) <= tol * jnp.maximum(jnp.abs(f_new), 1.0))
            | (jnp.max(jnp.abs(pg_new)) <= tol)
            | (~ls_ok)
        )
        return (
            x_new, f_new, g_new, S, Y, rho, count, it + 1, converged,
            n_evals + n_ls,
        )

    x, f, g, S, Y, rho, count, it, converged, n_evals = jax.lax.while_loop(
        cond, body, class_state
    )
    return LbfgsResult(x=x, f=f, n_iter=it, converged=converged, n_evals=n_evals)
