"""The public LinearRegression fit against chipbench/references/linreg.py (PR 30).

The reference is coordinate descent in its RESIDUAL form over the table's own
columns, with no Gram matrix; the program solves the covariance-update form on
sufficient statistics.  Algebraically one iteration, arithmetically two: at the
benchmark cell's regParam (1e-5, where the penalty lies below float32's rounding
of a coefficient and the cell cannot see it) and at one where the penalty bites
(coefficients exactly 0 in both, the same support), with and without an
intercept.  Then what the cell's comparison is for: the bf16 control and every
planted fault fail the limit they are held against, at a test's size, by the
limits the cell runs with."""
import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec

from chipbench import control, harness
from chipbench.references import linreg as ref
from chipbench.references.common import seed_words
from spark_rapids_ml_tpu import LinearRegression
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops import cd_sweep, glm
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

N, D, INFORMATIVE, SWEEPS = 2048, 48, 4, 10
BENCH = harness.load_benchmark()
CELL = "linreg_enet_fit"
SMALL = {"data": {"rows_per_chip": 4096, "cols": 64, "informative": 4}}
# the limit each stand-in is held against (chipbench/subjects/linreg.fit_loop.json says why)
HELD_AGAINST = {
    "bf16": "small_coef_gap", "fault_sweeps9": "wrong_iters", "fault_block": "small_coef_gap",
    "fault_no_intercept": "intercept_gap", "fault_gram": "small_coef_gap",
}


def _table(seed):
    make = ref.table_fn(get_mesh(1), DATA_AXIS, N, D, INFORMATIVE, 100.0, 1.0, 0.0)
    X, y = make(seed_words(seed))
    # neighbouring columns correlated (0.4), the test's own doing: on 48 independent columns a
    # penalty that bites is at its fixed point by sweep 6, and a sweep that changes nothing ends the loop
    return X + 0.5 * jax.numpy.roll(X, 1, axis=1), y


def _fit(X, y, reg, fit_intercept):
    est = LinearRegression(
        regParam=reg, elasticNetParam=0.5, maxIter=SWEEPS, tol=1e-30, standardization=False, fitIntercept=fit_intercept,
        num_workers=1,
    )
    return est.fit(DataFrame.from_device(X, y=np.asarray(y), n_rows=N))


@pytest.mark.parametrize("fit_intercept", [True, False])
@pytest.mark.parametrize("reg", [1e-5, 2.0])
@pytest.mark.parametrize("seed", [7, 2**31 + 11, 3_000_000_019])
def test_public_fit_is_the_references_iteration(seed, reg, fit_intercept):
    X, y = _table(seed)
    model = _fit(X, y, reg, fit_intercept)
    b_ref, b0_ref, ran = ref.cd(X, y, reg, 0.5, SWEEPS, fit_intercept)
    b = np.asarray(model.coef_, np.float64)
    assert model.num_iters == ran == SWEEPS          # tol 1e-30: every sweep runs
    assert np.linalg.norm(b - b_ref) / np.linalg.norm(b_ref) < 2e-6
    assert abs(model.intercept_ - b0_ref) < 2e-5 * max(1.0, abs(b0_ref))
    assert (model.intercept_ == 0.0) == (not fit_intercept)
    f = ref.objective(X, y, reg, 0.5)
    assert abs(f(b, model.intercept_) - f(b_ref, b0_ref)) / f(b_ref, b0_ref) < 1e-5
    zeros = int((b_ref == 0).sum())
    if reg == 2.0:
        # the threshold (1.0) is above many an uninformative column's pull: exactly 0, in both
        assert D // 4 <= zeros < D and np.array_equal(b == 0, b_ref == 0)
    else:
        assert zeros == 0 and np.count_nonzero(b) == D


def test_closed_form_has_no_sweep_count():
    X, y = _table(5)
    model = LinearRegression(regParam=0.1, elasticNetParam=0.0, num_workers=1).fit(DataFrame.from_device(X, y=np.asarray(y), n_rows=N))
    assert model.num_iters is None


@pytest.mark.parametrize("how", sorted(HELD_AGAINST))
def test_control_and_faults_fail_the_limit_they_are_held_against(how):
    """Through the unchanged harness at a test's size, by the cell's own limits."""
    r = control.readings(BENCH, dict(harness.find_cell(BENCH, CELL)), 2**31 + 9, 0.2, how, SMALL)
    assert all(c["ok"] for c in r["sound"]), r["sound"]
    stood_in = {c["name"]: c for c in r["control"]}
    held = stood_in[HELD_AGAINST[how]]
    assert not held["ok"] and held["value"] > held["limit"], held


@pytest.mark.parametrize("d,reg,n_dev", [(37, 1e-5, 1), (200, 0.05, 1), (130, 0.3, 1), (130, 0.3, 2), (8, 0.05, 8)])
def test_the_sweep_kernel_is_the_loop(d, reg, n_dev, monkeypatch):
    """ops/cd_sweep.py (the chip's path, here through the Pallas interpreter)
    against the jnp loop (the path everywhere else) on one system: the same
    sweeps, the same support, coefficients within an ulp or two.  On a mesh the
    statistics are replicated and the kernel runs per device."""
    mesh = get_mesh(n_dev)
    rng = np.random.default_rng(d)
    X = rng.standard_normal((960, d)).astype(np.float32)
    X[:, 1] += 0.5 * X[:, 0]
    beta = np.zeros(d)
    beta[:5] = rng.uniform(1, 5, 5)
    y = (X @ beta + 0.1 * rng.standard_normal(960)).astype(np.float32)
    stats = glm.linreg_sufficient_stats(jax.numpy.asarray(X), jax.numpy.asarray(y), jax.numpy.ones(960, jax.numpy.float32), mesh=mesh)
    out = {}
    for kernel in (False, True):
        monkeypatch.setattr(cd_sweep, "pallas_enabled", lambda kernel=kernel: kernel)
        glm.solve_elasticnet_cd.clear_cache()
        out[kernel] = glm.solve_elasticnet_cd(stats, reg, 0.5, fit_intercept=True, normalize=False, max_iter=7, tol=1e-30, mesh=mesh)
    glm.solve_elasticnet_cd.clear_cache()
    (b, b0, sweeps), (bk, bk0, sweeps_k) = jax.device_get((out[False], out[True]))
    assert int(sweeps) == int(sweeps_k) == 7
    assert np.array_equal(b == 0, bk == 0)
    np.testing.assert_allclose(bk, b, rtol=0, atol=2e-6)
    assert abs(float(b0) - float(bk0)) < 1e-6


@pytest.mark.parametrize("what,n_dev", [("fit", 2), ("fit", 4), ("tuning", 2)])
def test_the_sweep_kernel_lowers_for_the_chip_on_a_mesh(what, n_dev, monkeypatch):
    """What the interpreter skips: lowered for the TPU, a Mosaic call in a program
    of more than one device has to sit under a shard_map (jax does not partition
    it).  The solve of statistics replicated on a mesh, and the tuning engine's
    lax.map of such solves, lower with the kernel in them."""
    monkeypatch.setattr(cd_sweep, "pallas_enabled", lambda: True)
    monkeypatch.setattr(cd_sweep, "_interpreted", lambda: False)
    mesh = get_mesh(n_dev)
    d, lead = 300, (3,) if what == "tuning" else ()

    def rep(*shape):
        return jax.ShapeDtypeStruct(shape, np.float32, sharding=NamedSharding(mesh, PartitionSpec()))

    stats = glm.LinregStats(rep(*lead), rep(*lead, d), rep(*lead), rep(*lead, d, d), rep(*lead, d), rep(*lead))
    glm.solve_elasticnet_cd.clear_cache()
    if what == "fit":
        traced = glm.solve_elasticnet_cd.trace(stats, 0.1, 0.5, max_iter=3, tol=1e-30, mesh=mesh)
    else:
        traced = glm.sweep_solve_elasticnet_cd.trace(stats, rep(4), rep(4), rep(), max_iter=3, mesh=mesh)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    glm.solve_elasticnet_cd.clear_cache()
    assert "tpu_custom_call" in text and "cd_sweep" in text


def test_the_kernel_takes_the_widths_it_has_run_at(monkeypatch):
    monkeypatch.setattr(cd_sweep, "pallas_enabled", lambda: True)

    def gram(d, dtype=np.float32):
        return jax.ShapeDtypeStruct((d, d), dtype)

    assert cd_sweep.takes(gram(8)) and cd_sweep.takes(gram(16_384))
    assert not cd_sweep.takes(gram(16_385)) and not cd_sweep.takes(gram(3000, np.float64))
