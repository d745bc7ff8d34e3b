#
# CrossValidator on a DataFrame.from_device frame: the batched sweep end to end
# with the folds scored where the table lies (ops/logistic.
# sweep_logistic_score_kernel), held to the host frame's route on the same rows,
# to the plain reference chipbench/references/cv_logreg.py (which imports nothing
# of the program), to its no-leakage guarantee and to its counters; and the
# sweep's two kernels compiled at the benchmark cell's real size for a DESCRIBED
# v5e (on-chip-measurement guide, third rehearsal: nothing runs).
#
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.references import cv_logreg as ref  # noqa: E402

from spark_rapids_ml_tpu import LinearRegression, LogisticRegression, profiling  # noqa: E402
from spark_rapids_ml_tpu.dataframe import DataFrame, random_split_ids  # noqa: E402
from spark_rapids_ml_tpu.evaluation import (  # noqa: E402
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.ops import sweep as sweep_ops  # noqa: E402
from spark_rapids_ml_tpu.ops.lbfgs import minimize_lbfgs_batched  # noqa: E402
from spark_rapids_ml_tpu.parallel.mesh import data_sharding, get_mesh  # noqa: E402
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder  # noqa: E402

REGS = [1e-3, 1e-2, 1e-1, 1.0]


def _table(classes, n=960, d=10, seed=11):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    if classes == 2:
        y = (X @ rng.standard_normal(d) + 0.7 * rng.standard_normal(n) > 0).astype(np.float32)
    else:
        y = np.argmax(X @ rng.standard_normal((d, classes)) + 0.7 * rng.standard_normal((n, classes)), axis=1).astype(np.float32)
    return X, y


def _device_df(X, y, num_workers):
    mesh = get_mesh(num_workers)
    n_pad = X.shape[0] + (-X.shape[0]) % mesh.devices.size
    Xp = np.zeros((n_pad, X.shape[1]), X.dtype)
    Xp[: X.shape[0]] = X
    return DataFrame.from_device(jax.device_put(Xp, data_sharding(mesh)), y=y, n_rows=X.shape[0])


def _cv(metric="logLoss", num_workers=1, regs=REGS, seed=5, **est_kwargs):
    est = LogisticRegression(num_workers=num_workers, **{"maxIter": 60, "tol": 1e-12, **est_kwargs})
    grid = ParamGridBuilder().addGrid(est.getParam("regParam"), regs).build()
    return CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=MulticlassClassificationEvaluator(metricName=metric),
        numFolds=3, seed=seed, collectSubModels=True,
    )


def _best_reg(model):
    return model.bestModel.getOrDefault("regParam")


# -- (a) the device frame's route against the host frame's, on the same rows ------

@pytest.mark.parametrize("num_workers", [1, 8])
@pytest.mark.parametrize("classes", [2, 3])
@pytest.mark.parametrize("metric", ["logLoss", "accuracy"])
def test_device_frame_equals_host_frame(metric, classes, num_workers):
    """957 rows: the 8-device mesh pads the staged table, and a padded row is in no fold."""
    X, y = _table(classes, n=957)
    cv = _cv(metric, num_workers)
    host = cv.fit(DataFrame.from_numpy(X, y, num_partitions=3))
    dev = cv.fit(_device_df(X, y, num_workers))
    # the sub-models come out of one kernel on both routes; the held-out sums are float32 on
    # the device and float64 on the host (accuracy is a ratio of whole numbers on both)
    tol = dict(rtol=0, atol=0) if metric == "accuracy" else dict(rtol=2e-6, atol=2e-7)
    np.testing.assert_allclose(dev.avgMetrics, host.avgMetrics, **tol)
    np.testing.assert_allclose(dev.stdMetrics, host.stdMetrics, **tol)
    assert _best_reg(dev) == _best_reg(host)
    np.testing.assert_allclose(dev.bestModel.coef_, host.bestModel.coef_, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dev.bestModel.intercept_, host.bestModel.intercept_, rtol=1e-5, atol=1e-6)
    for f in range(3):
        for a, b in zip(dev.subModels[f], host.subModels[f]):
            np.testing.assert_allclose(a.coef_, b.coef_, rtol=1e-5, atol=1e-6)


# -- (b) against the plain reference -------------------------------------------------

@pytest.mark.parametrize("n,k,seed", [(960, 3, 5), (400_000, 3, 2**31 - 7), (301, 4, 0), (300, 3, 9)])
def test_fold_membership_is_the_references(n, k, seed):
    want = ref.fold_ids(n, k, seed)
    assert np.array_equal(random_split_ids(n, k, seed), want)
    staged = np.asarray(sweep_ops.stage_fold_ids(n, n + 8 - n % 8, k, seed, get_mesh(8)))
    assert np.array_equal(staged[:n], want) and (staged[n:] == -1).all()
    assert sorted(np.bincount(want, minlength=k)) == sorted(np.bincount(want))   # every row in exactly one fold
    assert np.bincount(want).sum() == n and abs(np.bincount(want).max() - np.bincount(want).min()) <= 1


@pytest.fixture(scope="module", params=[1, 8])
def swept(request):
    """One sweep a mesh size, and the reference's own account of it."""
    X, y = _table(2)
    model = _cv("logLoss", request.param).fit(_device_df(X, y, request.param))
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    folds = ref.fold_ids(len(y), 3, 5)
    return model, Xd, yd, folds


def test_every_sub_model_is_the_references_fit_of_its_fold(swept):
    model, Xd, yd, folds = swept
    for f in range(3):
        for i, reg in enumerate(REGS):
            theta, _ = ref.fit(Xd, yd, ref.train_weights(folds, f), reg, 60)
            got = np.concatenate([np.ravel(model.subModels[f][i].coef_), np.ravel(model.subModels[f][i].intercept_)])
            np.testing.assert_allclose(got, theta, rtol=2e-3, atol=2e-4, err_msg=f"fold {f} candidate {i}")


def test_every_held_out_metric_and_the_choice_are_the_references(swept):
    model, Xd, yd, folds = swept
    held = np.array([
        [ref.held_out_log_loss(Xd, yd, (folds == f).astype(np.float32),
                               np.concatenate([np.ravel(m.coef_), np.ravel(m.intercept_)])) for m in model.subModels[f]]
        for f in range(3)
    ])
    avg, std, best = ref.select(held)
    np.testing.assert_allclose(model.avgMetrics, avg, rtol=2e-6)
    np.testing.assert_allclose(model.stdMetrics, std, rtol=1e-4, atol=1e-7)
    assert _best_reg(model) == REGS[best]
    # and the reference's metrics of ITS OWN fits choose the same candidate
    own = np.array([[ref.held_out_log_loss(Xd, yd, (folds == f).astype(np.float32), ref.fit(Xd, yd, ref.train_weights(folds, f), reg, 60)[0])
                     for reg in REGS] for f in range(3)])
    np.testing.assert_allclose(model.avgMetrics, ref.select(own)[0], rtol=1e-4)
    assert ref.select(own)[2] == best


def test_the_best_model_is_the_references_fit_on_all_rows(swept):
    model, Xd, yd, folds = swept
    theta, _ = ref.fit(Xd, yd, np.ones(len(folds), np.float32), _best_reg(model), 60)
    got = np.concatenate([np.ravel(model.bestModel.coef_), np.ravel(model.bestModel.intercept_)])
    np.testing.assert_allclose(got, theta, rtol=2e-3, atol=2e-4)


# -- (c) no leakage --------------------------------------------------------------------

@pytest.mark.parametrize("fold", [0, 1, 2])
def test_a_label_flip_inside_a_fold_changes_no_model_of_that_fold(fold):
    X, y = _table(2, n=600)
    folds = ref.fold_ids(len(y), 3, 5)
    flipped = np.where(folds == fold, 1.0 - y, y).astype(np.float32)
    cv = _cv("logLoss", 1, maxIter=15)
    a, b = cv.fit(_device_df(X, y, 1)), cv.fit(_device_df(X, flipped, 1))
    for f in range(3):
        same = [np.array_equal(m.coef_, n.coef_) and np.array_equal(m.intercept_, n.intercept_)
                for m, n in zip(a.subModels[f], b.subModels[f])]
        assert all(same) if f == fold else not any(same), (f, same)
    # what did change: that fold's held-out metrics
    assert not np.allclose(a.avgMetrics, b.avgMetrics)


# -- (d) counters ------------------------------------------------------------------------

def test_a_sweep_on_a_device_frame_stages_and_uploads_nothing_and_a_repeat_compiles_nothing():
    X, y = _table(2, n=800)
    frame = _device_df(X, y, 1)
    cv = _cv("logLoss", 1, maxIter=10, tol=1e-30)
    cv.fit(frame)       # cold: the sweep's two kernels, the refit's, the frame's labels go up once
    names = ("ingest.staged", "ingest.h2d_bytes", "tuning.score.h2d_bytes", "tuning.score.rows", "tuning.sweep.scans",
             "lbfgs.evals", "lbfgs.fits", "precompile.compile", "precompile.fallback", "tuning.folds")
    before = profiling.counters()
    journal = len(profiling.compile_events())
    model = cv.fit(frame)
    after = profiling.counters()
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in names}
    assert moved["ingest.staged"] == 0 and moved["ingest.h2d_bytes"] == 0       # the refit rides the frame's FitInputs
    assert moved["tuning.score.h2d_bytes"] == 0 and moved["tuning.score.rows"] == 800
    assert moved["precompile.compile"] == 0 and moved["precompile.fallback"] == 0
    assert len(profiling.compile_events()) == journal       # nothing traced, lowered or compiled again
    assert moved["lbfgs.fits"] == 13 and moved["tuning.folds"] == 3
    # the slowest lane ran its 10 iterations: a scan each at least and the one at zero; never
    # more than the lanes' own evaluations add up to
    assert max(int(np.ravel(m.num_iters)[0]) for fold in model.subModels for m in fold) == 10
    assert 11 <= moved["tuning.sweep.scans"] <= moved["lbfgs.evals"]
    phases = model.subModels[0][0].fit_telemetry().phases
    for span in ("tuning.sweep", "tuning.sweep.solve", "tuning.sweep.score", "srml.prepare", "srml.ingest",
                 "srml.fit.init", "srml.fit.solve", "srml.fit.wait", "srml.fit.fetch", "srml.fit.pack"):
        assert phases[span]["count"] >= 1, (span, sorted(phases))
    assert phases["tuning.sweep.score"]["count"] == 1 and phases["srml.fit.solve"]["count"] == 2


def test_a_host_frames_sweep_stages_once_and_counts_the_rows_it_sends_up_again():
    X, y = _table(2, n=800)
    before = profiling.counters()
    _cv("logLoss", 1, maxIter=10).fit(DataFrame.from_numpy(X, y, num_partitions=2))
    after = profiling.counters()
    moved = {k: after.get(k, 0) - before.get(k, 0) for k in ("ingest.staged", "tuning.score.h2d_bytes", "tuning.score.rows")}
    assert moved == {"ingest.staged": 1, "tuning.score.h2d_bytes": 800 * 10 * 4, "tuning.score.rows": 800}


def test_scans_are_the_trips_the_lanes_shared_and_trials_change_nothing_but_them():
    """n_scans against a count made outside the solver: a host callback in the
    objective, which runs once an evaluation of all lanes.  With four steps of the
    halving sequence tried an evaluation, every lane takes the step, the iterations and
    the evaluations of its own search; the lanes share fewer trips."""
    A = jnp.asarray(np.random.default_rng(0).standard_normal((6, 40, 5)), jnp.float32)
    t = jnp.asarray(np.random.default_rng(1).integers(0, 2, (6, 40)), jnp.float32)
    calls = []

    def value_and_grad(x):      # (T * 6, 5) -> (T * 6,), (T * 6, 5): six logistic fits of unlike conditioning
        def f(x):
            z = jnp.einsum("lnd,tld->tln", A, x.reshape(-1, 6, 5)) * jnp.arange(1, 7, dtype=jnp.float32)[:, None]
            per_lane = ((jnp.logaddexp(0.0, z) - t * z).mean(axis=-1) + 0.05 * (x.reshape(-1, 6, 5) ** 2).sum(axis=-1)).reshape(-1)
            return per_lane.sum(), per_lane
        (_, per_lane), g = jax.value_and_grad(f, has_aux=True)(x)
        jax.debug.callback(lambda _: calls.append(1), per_lane[0])
        return per_lane, g

    def run(trials):
        calls.clear()
        out = minimize_lbfgs_batched(value_and_grad, jnp.zeros((6, 5)), jnp.zeros((6, 5)), max_iter=25, tol=1e-4, trials=trials)
        jax.block_until_ready(out)
        jax.effects_barrier()
        out = jax.device_get(out)
        assert int(out.n_scans) == len(calls)
        return out

    one, four = run(1), run(4)
    assert one.n_evals.max() <= int(one.n_scans) <= one.n_evals.sum() - 5 and len(set(one.n_evals.tolist())) > 1
    assert one.n_iter.tolist() == four.n_iter.tolist() and one.n_evals.tolist() == four.n_evals.tolist()
    np.testing.assert_allclose(four.x, one.x, rtol=1e-5, atol=1e-6)
    assert four.n_iter.max() + 1 <= int(four.n_scans) < int(one.n_scans)


# -- (e) the route is the frame's ------------------------------------------------------------

def test_only_a_device_frame_is_scored_on_the_device(monkeypatch):
    X, y = _table(2, n=600)
    splits, collected = [], []
    real_split, real_collect = DataFrame.randomSplit, DataFrame.toPandas
    monkeypatch.setattr(DataFrame, "randomSplit", lambda self, *a, **k: splits.append(1) or real_split(self, *a, **k))
    monkeypatch.setattr(DataFrame, "toPandas", lambda self: collected.append(self._device_features is not None) or real_collect(self))
    cv = _cv("logLoss", 1, maxIter=10)
    cv.fit(_device_df(X, y, 1))
    monkeypatch.setenv("SRML_SWEEP_BATCH", "0")       # the variable is the host frames': it moves no device frame
    cv.fit(_device_df(X, y, 1))
    assert splits == [] and not any(collected)
    monkeypatch.delenv("SRML_SWEEP_BATCH")
    cv.fit(DataFrame.from_numpy(X, y, num_partitions=2))
    assert splits == [1] and not any(collected)


def test_a_device_frame_no_sweep_can_score_is_refused_before_any_fit():
    X, y = _table(2, n=400)
    est = LinearRegression()
    grid = ParamGridBuilder().addGrid(est.getParam("regParam"), [0.0, 0.1]).build()
    cv = CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=RegressionEvaluator(), numFolds=3)
    fits = profiling.counter("ingest.h2d_bytes")
    with pytest.raises(ValueError, match="from_device"):
        cv.fit(_device_df(X, y, 1))
    est = LogisticRegression()
    grid = ParamGridBuilder().addGrid(est.getParam("maxIter"), [5, 10]).build()       # no lane carries maxIter
    with pytest.raises(ValueError, match="from_device"):
        CrossValidator(estimator=est, estimatorParamMaps=grid, evaluator=MulticlassClassificationEvaluator(), numFolds=3).fit(_device_df(X, y, 1))
    assert profiling.counter("ingest.h2d_bytes") == fits


# -- the sweep's kernels at the cell's size, compiled for a described v5e ---------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and cannot be
    read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_the_sweep_and_its_scoring_compile_for_a_v5e_at_the_cells_size_with_one_table(one_chip, no_compile_cache):
    """400,000 x 3000 float32, 8 candidates x 3 folds: both kernels fit the chip's 16 GB
    with X's 4.8 GB counted ONCE (a transposed or padded copy of X would show here first)."""
    from spark_rapids_ml_tpu.ops.logistic import sweep_logistic_fit_kernel, sweep_logistic_score_kernel

    n, d, k, m = 400_000, 3000, 3, 8
    arg = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    X, rows, fid = arg((n, d)), arg((n,)), arg((n,), jnp.int32)
    table = n * d * 4
    fit = sweep_logistic_fit_kernel.lower(
        X, rows, rows, fid, arg((m,)), arg((m,)), arg(()), k_folds=k, kcls=1, fit_intercept=True, max_iter=200, use_owlqn=False,
    ).compile()
    score = sweep_logistic_score_kernel.lower(X, rows, fid, arg((k, m, 1, d)), arg((k, m, 1)), arg(()), num_classes=2).compile()
    for name, compiled in (("fit", fit), ("score", score)):
        mem = compiled.memory_analysis()
        beside = mem.temp_size_in_bytes + mem.output_size_in_bytes
        assert mem.argument_size_in_bytes < table + 16 * 2**20, (name, mem)
        assert beside < table // 2, (name, beside)       # no second table, whole or half
        assert mem.argument_size_in_bytes + beside < 16e9
