"""The step spans of a fit and the counts only the program can give (PR 24).

A public fit on the driver-local path is tiled by four spans on the calling
thread (srml.prepare, srml.ingest, srml.fit, srml.finish), srml.fit by the
fit function's five (init, solve, wait, fetch, pack); ingest.h2d_bytes and
fit.d2h_bytes count what crossed the host link; LbfgsResult.n_evals counts
the objective's evaluations; jax.named_scope names the solver loops' parts.

Since PR 43 the job's own steps are opened in one place (core.FitJob), which
the public fit, the executor's fit and the batched sweep all enter.
"""
import threading

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import KMeans, LinearRegression, LogisticRegression, profiling
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops import glm, lbfgs
from spark_rapids_ml_tpu.ops.kmeans import lloyd_iterations
from spark_rapids_ml_tpu.ops.logistic import logistic_fit_kernel
from spark_rapids_ml_tpu.parallel.mesh import get_mesh

N, D, K = 512, 16, 4
TOP = ["srml.prepare", "srml.ingest", "srml.fit", "srml.finish"]
STEPS = ["srml.fit.init", "srml.fit.solve", "srml.fit.wait", "srml.fit.fetch", "srml.fit.pack"]
# between two spans of a tiling lie a context manager's exit and the next one's
# entry, microseconds; the allowance is for a collector pause on a busy runner
STRETCH_S = 1e-3


def _table():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = (X @ rng.standard_normal(D) + 0.3 * rng.standard_normal(N) > 0).astype(np.float32)
    return X, y


def _case(family):
    X, y = _table()
    if family == "kmeans":
        est = KMeans(k=K, maxIter=5, tol=0.0, initMode="random", seed=3, num_workers=2)
        return est, (lambda: DataFrame.from_numpy(X, num_partitions=2)), X, None
    if family == "logreg":
        est = LogisticRegression(maxIter=8, tol=1e-30, regParam=1e-3, num_workers=2)
    else:       # linreg: the closed form; linreg_cd: coordinate descent
        l1 = 0.5 if family == "linreg_cd" else 0.0
        est = LinearRegression(maxIter=6, tol=1e-30, regParam=1e-3, elasticNetParam=l1, num_workers=2)
    return est, (lambda: DataFrame.from_numpy(X, y=y, num_partitions=2)), X, y


def _traced_fit(est, df):
    """One public fit under collect_spans: (entry, return, this thread's records by start)."""
    me = threading.get_ident()
    with profiling.collect_spans():
        t0 = profiling.now()
        est.fit(df)
        t1 = profiling.now()
        mine = sorted((r for r in profiling.span_records() if r[3] == me), key=lambda r: r[1])
    return t0, t1, mine


FAMILIES = ["kmeans", "logreg", "linreg", "linreg_cd"]


@pytest.mark.parametrize("family", FAMILIES)
def test_step_spans_tile_the_public_fit(family):
    est, frame, _X, _y = _case(family)
    est.fit(frame())                    # compiles; the tiling is of a warm fit
    for _attempt in range(3):           # a fit the scheduler cut into is taken again
        t0, t1, mine = _traced_fit(est, frame())
        top = [r for r in mine if r[6] == 0]
        assert [r[0] for r in top] == TOP
        # every instant of the public fit lies in exactly one top-level span
        edges = [t0] + [t for r in top for t in (r[1], r[2])] + [t1]
        assert all(a <= b for a, b in zip(edges, edges[1:]))
        # the fit function's step spans tile srml.fit
        fit = top[2]
        steps = [r for r in mine if r[6] == fit[5]]
        assert [r[0] for r in steps] == STEPS
        inner = [fit[1]] + [t for r in steps for t in (r[1], r[2])] + [fit[2]]
        assert all(a <= b for a, b in zip(inner, inner[1:]))
        outside = sum(b - a for a, b in zip(edges[0::2], edges[1::2]))
        bare = max(b - a for a, b in zip(inner[0::2], inner[1::2]))
        if outside < STRETCH_S and bare < STRETCH_S:
            break
    assert outside < STRETCH_S and bare < STRETCH_S
    # what crossed the host link rides the spans that carried it
    by_name = {}
    for r in mine:
        by_name.setdefault(r[0], []).append(r)
    assert by_name["srml.fit.fetch"][0][7]["bytes"] > 0
    puts = by_name["srml.device_put"]
    assert puts and all(r[6] == top[1][5] and r[7]["bytes"] > 0 for r in puts)


@pytest.mark.parametrize("launcher", ["public", "executor", "sweep"])
def test_every_launcher_enters_the_one_job(launcher):
    """The public fit, the executor's fit (what a live Spark DataFrame runs)
    and the batched sweep record the job's step spans from the one place that
    opens them: the two fits the same four in the same order, with the counted
    uploads inside ingest; the sweep its own tiling, with no outer srml.fit."""
    from spark_rapids_ml_tpu.core import TELEMETRY_ATTR
    from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
    from spark_rapids_ml_tpu.parallel.context import LocalControlPlane
    from spark_rapids_ml_tpu.parallel.runner import run_distributed_fit
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    X, y = _table()
    est = LogisticRegression(maxIter=8, tol=1e-30, regParam=1e-3, num_workers=2)
    frame = DataFrame.from_numpy(X, y=y, num_partitions=2)
    me = threading.get_ident()
    with profiling.collect_spans():
        if launcher == "public":
            moved = est.fit(frame).fit_telemetry().counters
        elif launcher == "executor":
            pdf = pd.DataFrame({"features": list(X), "label": y})
            (attrs,) = run_distributed_fit(est, [pdf], 0, 1, LocalControlPlane())
            moved = profiling.TelemetrySnapshot.from_dict(attrs[TELEMETRY_ATTR]).counters
        else:
            grid = ParamGridBuilder().addGrid(est.regParam, [1e-3, 1e-2]).build()
            cv = CrossValidator(estimator=est, estimatorParamMaps=grid, numFolds=2, collectSubModels=True,
                                evaluator=MulticlassClassificationEvaluator(metricName="logLoss"))
            moved = cv.fit(frame).subModels[0][0].fit_telemetry().counters
        mine = sorted((r for r in profiling.span_records() if r[3] == me), key=lambda r: r[1])
    if launcher == "sweep":         # the refit of the winner is a public fit of its own, after the sweep's job
        refit = next(r for r in mine if r[0] == "tuning.refit")
        mine = [r for r in mine if r[2] <= refit[1]]
    first = next(r for r in mine if r[0] == "srml.prepare")
    steps = [r for r in mine if r[6] == first[6]]   # its siblings: what tiles the job
    inside = lambda outer: [r[0] for r in mine if outer[1] <= r[1] and r[2] <= outer[2] and r is not outer]  # noqa: E731
    if launcher == "sweep":
        assert [r[0] for r in steps] == ["srml.prepare", "tuning.sweep", "srml.finish"]
        within = inside(steps[1])
        assert within[0] == "srml.ingest" and "srml.fit" not in within
        assert [n for n in within if n in STEPS][:3] == STEPS[:3] and set(STEPS) <= set(within)
        assert [r[0] for r in mine if r[0].startswith("srml.")][-1] == "srml.finish"
    else:
        assert [r[0] for r in steps] == TOP
        assert [n for n in inside(steps[2]) if n in STEPS] == STEPS
    # the executor's launcher alone encodes what it returns (PR 49): one srml.finish.encode inside srml.finish,
    # with the base64 characters it made; the public fit and the sweep hand back objects and have no such span
    encodes = [r for r in mine if r[0] == "srml.finish.encode"]
    if launcher == "executor":
        finish = next(r for r in mine if r[0] == "srml.finish")
        assert len(encodes) == 1 and encodes[0][6] == finish[5] and encodes[0][7]["bytes"] >= 4 * (X.shape[1] + 1) * 4 // 3
    else:
        assert encodes == []
    ingest = next(r for r in mine if r[0] == "srml.ingest")
    puts = [r for r in mine if r[0] == "srml.device_put"]
    # the table goes up a device's rows at a time (core.stage_dense_batches): the
    # estimator's two devices, or all of the executor's one rank
    shards = jax.device_count() if launcher == "executor" else 2
    assert len(puts) == shards + 2 and all(r[6] == ingest[5] and r[7]["bytes"] > 0 for r in puts)
    # the table, the row mask, the labels: counted wherever the job ran
    assert moved["ingest.h2d_bytes"] == X.nbytes + 2 * N * 4 == sum(r[7]["bytes"] for r in puts)


def test_a_float64_fit_on_the_executor_path_computes_in_float64(monkeypatch):
    """float32_inputs=False on float64 rows through run_distributed_fit: the
    fit function is handed float64 arrays inside the x64 scope (without it
    jax canonicalizes the uploads to float32 and says nothing), and the
    coefficients are the public fit's at a tolerance float32 cannot reach."""
    from sklearn.linear_model import LinearRegression as SkLinearRegression

    from spark_rapids_ml_tpu.parallel.context import LocalControlPlane
    from spark_rapids_ml_tpu.parallel.runner import decode_attrs, run_distributed_fit

    rng = np.random.default_rng(11)
    X = rng.standard_normal((400, 7))
    y = X @ rng.standard_normal(7) + 0.01 * rng.standard_normal(400)
    assert X.dtype == np.float64
    est = LinearRegression(float32_inputs=False, standardization=False)
    handed = []
    make = est._get_tpu_fit_func

    def spying(df, extra_params=None):
        fit = make(df, extra_params)

        def fit_func(inputs, params):
            handed.append((inputs.X.dtype, inputs.weight.dtype, inputs.y.dtype, jax.config.jax_enable_x64))
            return fit(inputs, params)

        return fit_func

    monkeypatch.setattr(est, "_get_tpu_fit_func", spying)
    public = est.fit(DataFrame.from_numpy(X, y))
    pdf = pd.DataFrame({"features": list(X), "label": y})
    (attrs,) = run_distributed_fit(est, [pdf], 0, 1, LocalControlPlane())
    executor = decode_attrs({k: v for k, v in attrs.items() if not k.startswith("__")})
    f64 = np.dtype(np.float64)
    assert handed == [(f64, f64, f64, True)] * 2
    assert not jax.config.jax_enable_x64           # the scope ended with the job
    sk = SkLinearRegression().fit(X, y)
    for got in (np.asarray(public.coef_), np.asarray(executor["coef_"])):
        np.testing.assert_allclose(got, sk.coef_, atol=1e-10)
    np.testing.assert_allclose(np.asarray(executor["coef_"]), np.asarray(public.coef_), atol=1e-12)
    np.testing.assert_allclose(float(executor["intercept_"]), float(public.intercept_), atol=1e-12)


@pytest.mark.parametrize("family", FAMILIES)
def test_h2d_and_d2h_bytes_equal_the_arrays_nbytes(family):
    est, frame, X, y = _case(family)
    model = est.fit(frame())
    moved = model.fit_telemetry().counters
    vectors = 1 if y is None else 2          # the weight mask; the labels
    assert moved["ingest.h2d_bytes"] == X.nbytes + vectors * N * 4
    if family == "kmeans":
        fetched = K * D * 4 + 4 + 4          # centres, n_iter, inertia
    elif family == "logreg":
        fetched = D * 4 + 4 + 4 + 1 + 4      # W, b, n_iter, converged, n_evals
        assert moved["lbfgs.fits"] == 1 and moved["lbfgs.iters"] == model.num_iters
        assert moved["lbfgs.evals"] >= model.num_iters + 1
    else:
        fetched = D * 4 + D * 4 + 4          # coefficients, column means, label mean
        assert moved["linreg.fits"] == 1
        if family == "linreg_cd":
            fetched += 4                     # the sweeps
            assert model.num_iters == 6 and moved["cd.fits"] == 1
            assert moved["cd.sweeps"] == 6 and moved["cd.coordinates"] == 6 * D
        else:
            assert model.num_iters is None and "cd.fits" not in moved
    assert moved["fit.d2h_bytes"] == fetched


def test_every_map_of_a_fit_multiple_is_solved_before_the_one_wait():
    """One statistics pass, one wait, one fetch for all maps; the counters add up over them."""
    X, y = _table()
    est = LinearRegression(maxIter=6, tol=1e-30, regParam=1e-3, elasticNetParam=0.5, num_workers=2)
    maps = [{est.regParam: 1e-3}, {est.regParam: 1e-2}, {est.elasticNetParam: 0.0}]
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)
    me = threading.get_ident()
    with profiling.collect_spans():
        models = [m for _, m in est.fitMultiple(df, maps)]
        mine = [r[0] for r in profiling.span_records() if r[3] == me]
    assert [mine.count(s) for s in STEPS] == [1] * len(STEPS)
    assert sorted(m.num_iters is None for m in models) == [False, False, True]
    moved = models[0].fit_telemetry().counters
    assert moved["linreg.fits"] == 3 and moved["cd.fits"] == 2 and moved["cd.coordinates"] == 2 * 6 * D
    assert moved["fit.d2h_bytes"] == 3 * D * 4 + 2 * 4 + D * 4 + 4


@pytest.mark.parametrize("case", ["dense_wide", "dense_narrow", "ell_wide"])
def test_gram_triangle_fits_counts_the_dense_fits_of_more_than_one_panel(case):
    """linreg.gram_triangle_fits sits beside linreg.fits for a fit whose Gram
    scan computes one triangle (ops/linalg.gram_panels(columns) > 1: a dense
    table wider than a panel); the ELL pass has a scan of its own."""
    from scipy import sparse

    from spark_rapids_ml_tpu.ops.linalg import GRAM_PANEL_WIDTH, gram_panels

    d = D if case == "dense_narrow" else GRAM_PANEL_WIDTH + 8
    rng = np.random.default_rng(11)
    X = rng.standard_normal((192, d)).astype(np.float32)
    y = (X[:, :4] @ np.arange(1.0, 5.0)).astype(np.float32)
    if case == "ell_wide":
        X = sparse.csr_matrix(np.where(rng.random(X.shape) < 0.02, X, 0.0).astype(np.float32))
    model = LinearRegression(regParam=1e-3, num_workers=2).fit(DataFrame.from_numpy(X, y=y, num_partitions=2))
    moved = model.fit_telemetry().counters
    assert moved["linreg.fits"] == 1 and gram_panels(d) == (1 if case == "dense_narrow" else 2)
    assert moved.get("linreg.gram_triangle_fits", 0) == int(case == "dense_wide")


@pytest.mark.parametrize("case", ["dense_whole_chunks_and_more", "dense_two_short_shards", "ell"])
def test_gram_rows_counts_what_the_dense_scan_was_handed(case):
    """linreg.gram_rows (the staged table's rows) and linreg.gram_rows_multiplied
    (the rows of the scan's blocks, by ops/linalg.scan_rows: the plan the scan
    itself walks by) are equal for a dense fit whatever its rows leave over a
    chunk: no row goes through the products twice (PR 49's clamped last chunk
    would have handed 2 x 32768 for 33,000).  The ELL pass has a scan of its own."""
    from scipy import sparse

    from spark_rapids_ml_tpu.ops.linalg import scan_rows

    rows, workers = (33_000, 1) if case == "dense_whole_chunks_and_more" else (192, 2)
    rng = np.random.default_rng(12)
    X = rng.standard_normal((rows, 8)).astype(np.float32)
    y = (X[:, :4] @ np.arange(1.0, 5.0)).astype(np.float32)
    if case == "ell":
        X = sparse.csr_matrix(np.where(rng.random(X.shape) < 0.2, X, 0.0).astype(np.float32))
    model = LinearRegression(regParam=1e-3, num_workers=workers).fit(DataFrame.from_numpy(X, y=y, num_partitions=2))
    moved = model.fit_telemetry().counters
    assert moved["linreg.fits"] == 1
    if case == "ell":
        assert "linreg.gram_rows" not in moved and "linreg.gram_rows_multiplied" not in moved
    else:
        assert scan_rows(rows // workers, 32768) == ((1, 232) if workers == 1 else (0, 96))
        assert moved["linreg.gram_rows"] == moved["linreg.gram_rows_multiplied"] == rows


@pytest.mark.parametrize("l1,workers", [(0.5, 1), (0.0, 1), (0.5, 2)])
def test_a_repeated_linreg_fit_of_a_device_frame_uploads_nothing(l1, workers):
    """The second fit of a device-resident frame makes no host-to-device copy:
    the frame keeps its labels and weights on the device, and a hyperparameter
    goes to the solver as a scalar that already lies there (a Python float
    would go up once a call, behind the statistics pass)."""
    X, y = _table()
    est = LinearRegression(maxIter=6, tol=1e-30, regParam=1e-3, elasticNetParam=l1, num_workers=workers)
    frame = DataFrame.from_device(jnp.asarray(X), y=y, n_rows=N)
    first = est.fit(frame)
    with jax.transfer_guard_host_to_device("disallow"):
        again = est.fit(frame)
    assert np.array_equal(first.coef_, again.coef_) and first.intercept_ == again.intercept_
    assert again.fit_telemetry().counters.get("ingest.h2d_bytes", 0) == 0


# -- LbfgsResult.n_evals -------------------------------------------------------

_SCALES = np.logspace(0, 3, 12)


def _quartic(x):
    """Badly scaled and not quadratic: unit steps overshoot, the search backtracks."""
    s = jnp.asarray(_SCALES, x.dtype)
    f = 0.5 * (s * x * x).sum() + 0.25 * ((x - 1.0) ** 4).sum()
    return f, s * x + (x - 1.0) ** 3


def test_n_evals_is_the_number_of_times_the_objective_ran():
    ran = []

    def counted(x):
        jax.debug.callback(lambda: ran.append(1))
        return _quartic(x)

    x0 = jnp.full((12,), 2.0, jnp.float32)
    out = lbfgs.minimize_lbfgs(counted, x0, jnp.zeros_like(x0), max_iter=40, tol=1e-9)
    jax.effects_barrier()
    assert int(out.n_evals) == len(ran)
    assert int(out.n_evals) > int(out.n_iter) + 1      # some step was halved


def _reference_lbfgs(vg, x0, max_iter, tol, history=10, max_ls=20):
    """minimize_lbfgs's smooth path in numpy float64 with a Python line
    search; returns (x, iterations, evaluations)."""
    x = np.asarray(x0, np.float64)
    f, g = (np.asarray(v, np.float64) for v in vg(x))
    pairs, evals, it = [], 1, 0
    while it < max_iter:
        q, alphas = g.copy(), []
        for s, yv in reversed(pairs):
            a = (s @ q) / (s @ yv)
            alphas.append(a)
            q = q - a * yv
        if pairs:
            s, yv = pairs[-1]
            q = q * ((s @ yv) / (yv @ yv))
        for (s, yv), a in zip(pairs, reversed(alphas)):
            q = q + (a - (yv @ q) / (s @ yv)) * s
        d, deriv = -q, g @ -q
        if deriv >= 0:
            d, deriv = -g, -(g @ g)
        t = 1.0 / max(np.linalg.norm(g), 1.0) if not pairs else 1.0
        ok = False
        for _ in range(max_ls):
            x_new = x + t * d
            f_new, g_new = (np.asarray(v, np.float64) for v in vg(x_new))
            evals += 1
            ok = f_new <= f + 1e-4 * t * deriv
            t *= 0.5
            if ok:
                break
        it += 1
        if not ok:
            break
        s, yv = x_new - x, g_new - g
        if s @ yv > 1e-10:
            pairs = (pairs + [(s, yv)])[-history:]
        done = abs(f - f_new) <= tol * max(abs(f_new), 1.0) or np.max(np.abs(g_new)) <= tol
        x, f, g = x_new, f_new, g_new
        if done:
            break
    return x, it, evals


def test_n_evals_equals_a_python_side_line_searchs_count():
    def vg64(x):
        f = 0.5 * (_SCALES * x * x).sum() + 0.25 * ((x - 1.0) ** 4).sum()
        return f, _SCALES * x + (x - 1.0) ** 3

    with jax.enable_x64(True):
        x0 = jnp.full((12,), 2.0, jnp.float64)
        out = lbfgs.minimize_lbfgs(_quartic, x0, jnp.zeros_like(x0), max_iter=40, tol=1e-12)
        x, n_iter, n_evals = np.asarray(out.x), int(out.n_iter), int(out.n_evals)
    x_ref, it_ref, evals_ref = _reference_lbfgs(vg64, np.full(12, 2.0), 40, 1e-12)
    assert (n_iter, n_evals) == (it_ref, evals_ref)
    assert n_evals >= n_iter + 1
    np.testing.assert_allclose(x, x_ref, rtol=1e-9, atol=1e-12)


def _lbfgs_without_the_count(vg, x0, max_iter, tol, history=10, max_ls=20):
    """The loop of minimize_lbfgs (smooth path) as it was before n_evals rode
    its state: the twin the carry is compared with, bit for bit."""
    P, dtype = x0.shape[0], x0.dtype
    f0, g0 = vg(x0)
    state = (
        x0, f0, g0, jnp.zeros((history, P), dtype), jnp.zeros((history, P), dtype),
        jnp.zeros((history,), dtype), jnp.array(0, jnp.int32), jnp.array(0, jnp.int32), jnp.array(False),
    )

    def body(state):
        x, f, g, S, Y, rho, count, it, _ = state
        d = -lbfgs._two_loop(g, S, Y, rho, count, history)
        deriv = g @ d
        bad = deriv >= 0
        d = jnp.where(bad, -g, d)
        deriv = jnp.where(bad, -(g @ g), deriv)
        t0 = jnp.where(count == 0, 1.0 / jnp.maximum(jnp.linalg.norm(g), 1.0), 1.0).astype(dtype)

        def ls_body(ls):
            t, _, _, _, n_ls, _ = ls
            x_new = x + t * d
            f_new, g_new = vg(x_new)
            return (t * 0.5, x_new, f_new, g_new, n_ls + 1, f_new <= f + 1e-4 * t * deriv)

        _, x_new, f_new, g_new, _, ok = jax.lax.while_loop(
            lambda ls: (~ls[5]) & (ls[4] < max_ls), ls_body,
            (t0, x, f, g, jnp.array(0, jnp.int32), jnp.array(False)),
        )
        x_new, f_new, g_new = jnp.where(ok, x_new, x), jnp.where(ok, f_new, f), jnp.where(ok, g_new, g)
        s, y = x_new - x, g_new - g
        sy = s @ y
        store = sy > 1e-10
        slot = jnp.mod(count, history)
        S = jnp.where(store, S.at[slot].set(s), S)
        Y = jnp.where(store, Y.at[slot].set(y), Y)
        rho = jnp.where(store, rho.at[slot].set(1.0 / jnp.where(sy != 0, sy, 1.0)), rho)
        converged = (
            (jnp.abs(f - f_new) <= tol * jnp.maximum(jnp.abs(f_new), 1.0))
            | (jnp.max(jnp.abs(g_new)) <= tol) | (~ok)
        )
        return (x_new, f_new, g_new, S, Y, rho, count + store.astype(jnp.int32), it + 1, converged)

    out = jax.lax.while_loop(lambda st: (st[7] < max_iter) & (~st[8]), body, state)
    return out[0], out[1], out[7]


def test_theta_is_identical_with_and_without_the_carry():
    X, y = _table()
    X, y = jnp.asarray(X), jnp.asarray(y)

    def vg(theta):
        def loss(t):
            z = X @ t
            return (jnp.logaddexp(0.0, z) - y * z).mean() + 5e-4 * (t * t).sum()

        return jax.value_and_grad(loss)(theta)

    x0 = jnp.zeros((D,), jnp.float32)
    with_count = lbfgs.minimize_lbfgs(vg, x0, jnp.zeros_like(x0), max_iter=25, tol=1e-30)
    x, f, n_iter = jax.jit(_lbfgs_without_the_count, static_argnums=(0, 2))(vg, x0, 25, 1e-30)
    assert int(with_count.n_iter) == int(n_iter)
    assert np.asarray(with_count.x).tobytes() == np.asarray(x).tobytes()
    assert np.asarray(with_count.f).tobytes() == np.asarray(f).tobytes()


def test_batched_lanes_count_their_own_evaluations():
    """A lane's n_evals is its solo run's: frozen lanes ride along uncounted."""
    def lanes_vg(xs):
        return jax.vmap(_quartic)(xs)

    x0 = jnp.stack([jnp.full((12,), 2.0, jnp.float32), jnp.full((12,), 0.05, jnp.float32)])
    both = lbfgs.minimize_lbfgs_batched(lanes_vg, x0, jnp.zeros_like(x0), max_iter=40, tol=1e-6)
    solos = [
        lbfgs.minimize_lbfgs(_quartic, x0[lane], jnp.zeros((12,), jnp.float32), max_iter=40, tol=1e-6)
        for lane in range(2)
    ]
    lanes, solos = jax.device_get(((both.n_iter, both.n_evals), [(s.n_iter, s.n_evals) for s in solos]))
    assert [(int(i), int(e)) for i, e in zip(*lanes)] == [(int(i), int(e)) for i, e in solos]
    assert all(e >= i + 1 for i, e in solos)


# -- named scopes --------------------------------------------------------------


def test_lowered_solvers_carry_the_scope_names():
    X = jnp.ones((64, 8), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    lloyd = lloyd_iterations.lower(X, w, jnp.ones((4, 8), jnp.float32), get_mesh(2), 3, 0.0, 16)
    text = lloyd.as_text(debug_info=True)
    for scope in ("lloyd.norms", "lloyd.assign", "lloyd.update", "lloyd.inertia"):
        assert scope in text, scope
    logistic = logistic_fit_kernel.lower(X, w, w, 1, 0.0, 0.0, True, 5, 1e-6, False)
    text = logistic.as_text(debug_info=True)
    for scope in ("lbfgs.eval", "lbfgs.direction"):
        assert scope in text, scope
    y = jnp.ones((64,), jnp.float32)
    gram = glm.linreg_sufficient_stats.lower(X, y, w, mesh=get_mesh(2))
    assert "linreg.gram" in gram.as_text(debug_info=True)
    stats = jax.eval_shape(lambda: glm.linreg_sufficient_stats(X, y, w, mesh=get_mesh(2)))
    solve = glm.solve_elasticnet_cd.lower(stats, 1e-3, 0.5, max_iter=3, tol=1e-30)
    assert "cd.sweep" in solve.as_text(debug_info=True)
