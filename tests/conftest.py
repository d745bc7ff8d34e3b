# Tests run on a virtual 8-device CPU mesh so multi-chip sharding is exercised
# without TPU hardware (the role Spark local[N] + N GPUs plays in the
# reference, /root/reference/python/tests/conftest.py:44-70): the platform is
# pinned to cpu and the host-device-count flag injected before the first
# backend initialization.
#
# SRML_TPU_TESTS=1 leaves jax on its default backend so the kernel tests run
# compiled on a real chip (tests/test_pallas.py header has the one command).
# A chip belongs to ONE process: never combine SRML_TPU_TESTS=1 with the
# tests that spawn jax children (test_multicontroller, test_netplane,
# test_faults chaos workers, test_native's build) — the parent holds the
# chip and a child that needs it fails or hangs.
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("SRML_TPU_TESTS") != "1":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    # ROADMAP D1(b), cheapest option: the CPU suite runs with jax's
    # persistent compile cache OFF and pays the compiles (the library's
    # rule, ops/precompile.ensure_compile_cache, is tested in
    # tests/test_precompile.py).  Nothing is then written into the
    # checkout, no XLA:CPU executable is reloaded from disk, and the jax
    # children some tests spawn (the variable reaches them) never read a
    # cache file a sibling is still writing.  The SIGSEGV itself had
    # another cause: see _bounded_executable_mappings below.  On a chip
    # (SRML_TPU_TESTS=1) the cache stays on, by the rule.
    jax.config.update("jax_enable_compilation_cache", False)
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

# SRML_SANITIZE=1 runs the whole suite under the runtime sanitizer: per-fit
# transfer-guard scopes activate inside core/runner dispatch, and NaN
# checking goes suite-wide here (sanitize.py documents the split).
from spark_rapids_ml_tpu import sanitize as _sanitize  # noqa: E402

_sanitize.enable_global_debug_nans()


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False, help="run slow tests"
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow to run")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


def _map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _map_budget() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read()) * 3 // 4
    except (OSError, ValueError):
        return 48_000


@pytest.fixture(autouse=True, scope="module")
def _bounded_executable_mappings():
    """ROADMAP D1(b): every live XLA:CPU executable holds memory mappings,
    the one-process suite keeps thousands alive, and the process died with
    SIGSEGV inside XLA compilation when it reached vm.max_map_count (65,530
    here; 64,759 mappings sampled 5 s before the crash, always at
    tests/test_tuning.py::test_cv_classification_single_pass).  At each
    module's end, past three quarters of the limit, drop jax's and the
    precompiler's executables; later modules compile what they need again."""
    yield
    if _map_count() > _map_budget():
        import gc

        import jax

        from spark_rapids_ml_tpu.ops import precompile

        jax.clear_caches()
        if precompile._global is not None:
            precompile._global.clear()
        gc.collect()


@pytest.fixture
def n_devices():
    import jax

    return jax.device_count()


@pytest.fixture
def armed_faults(monkeypatch):
    """Arm an SRML_FAULTS plan for ONE test: `armed_faults(spec)` sets the
    env var and reloads the faults module's plan (arrival counters reset
    with it); teardown disarms and reloads so the suite's unarmed-path
    invariant (faults.plan() is None) holds for every other test."""
    from spark_rapids_ml_tpu.parallel import faults

    def arm(spec: str):
        monkeypatch.setenv(faults.FAULTS_ENV, spec)
        return faults.reload()

    yield arm
    monkeypatch.delenv(faults.FAULTS_ENV, raising=False)
    faults.reload()


@pytest.fixture(scope="session")
def model_zoo():
    """Lazily-fitted tiny models over one shared dataset, keyed by arm name
    ("kmeans", "pca", "linreg", "logreg", "rf_clf", "rf_reg", "umap",
    "knn", "ann", "ivfpq", "ivfpq_opq").  Returns a factory: model_zoo(name) -> (model, X) with X the
    float32 feature matrix the model was fit on.  Session-scoped and cached
    so the persistence matrix and the serving tests share ONE fit per
    class instead of re-fitting per test."""
    import numpy as np

    rng = np.random.default_rng(7)
    X = rng.standard_normal((96, 5)).astype(np.float32)
    y_reg = (X @ np.arange(1.0, 6.0) + 0.1 * rng.standard_normal(96)).astype(
        np.float64
    )
    y_clf = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    cache = {}

    def _build(name):
        from spark_rapids_ml_tpu import (
            ApproximateNearestNeighbors,
            KMeans,
            LinearRegression,
            LogisticRegression,
            NearestNeighbors,
            PCA,
            RandomForestClassifier,
            RandomForestRegressor,
            UMAP,
        )
        from spark_rapids_ml_tpu.dataframe import DataFrame

        df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=2)
        df_reg = DataFrame.from_numpy(X, y=y_reg, num_partitions=2)
        df_clf = DataFrame.from_numpy(X, y=y_clf, num_partitions=2)
        if name == "kmeans":
            return KMeans(k=3, maxIter=4, seed=1).setFeaturesCol("features").fit(df)
        if name == "pca":
            return PCA(k=3).setInputCol("features").fit(df)
        if name == "linreg":
            return LinearRegression(maxIter=20).fit(df_reg)
        if name == "logreg":
            return LogisticRegression(maxIter=10).fit(df_clf)
        if name == "rf_clf":
            return RandomForestClassifier(
                numTrees=3, maxDepth=3, maxBins=8, seed=1
            ).fit(df_clf)
        if name == "rf_reg":
            return RandomForestRegressor(
                numTrees=3, maxDepth=3, maxBins=8, seed=1
            ).fit(df_reg)
        if name == "umap":
            return UMAP(
                n_neighbors=8, n_epochs=30, init="random", random_state=2
            ).setFeaturesCol("features").fit(df)
        if name == "knn":
            return NearestNeighbors(k=4).setFeaturesCol("features").fit(df)
        if name == "ann":
            # nprobe == nlist: every list probed, so serving/persistence
            # equivalence gates are deterministic AND recall-1.0 vs exact
            return ApproximateNearestNeighbors(
                k=4, algoParams={"nlist": 4, "nprobe": 4}
            ).setFeaturesCol("features").fit(df)
        if name == "ivfpq":
            # the PQ tier at tiny geometry (2 subspaces x 16 codewords,
            # every list probed + refine): deterministic end to end, so the
            # serving/persistence gates hold bit-exactly like the flat arm
            return ApproximateNearestNeighbors(
                k=4,
                algorithm="ivfpq",
                algoParams={"nlist": 4, "nprobe": 4, "M": 2, "n_bits": 4},
            ).setFeaturesCol("features").fit(df)
        if name == "ivfpq_opq":
            # the OPQ x fast-scan composition: a learned rotation rides the
            # wire with the payload, codes stay 4-bit packed — persistence
            # must restage BOTH bit-identically on any mesh
            return ApproximateNearestNeighbors(
                k=4,
                algorithm="ivfpq",
                algoParams={
                    "nlist": 4, "nprobe": 4, "M": 2, "n_bits": 4,
                    "opq": True,
                },
            ).setFeaturesCol("features").fit(df)
        raise KeyError(name)

    def get(name):
        if name not in cache:
            cache[name] = (_build(name), X)
        return cache[name]

    return get
