# Precompile subsystem: AOT executable cache (cached_call), profiling
# counters, key helpers, and the persistent on-disk compilation-cache hookup.
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops import precompile
from spark_rapids_ml_tpu.ops.precompile import (
    Precompiler,
    global_precompiler,
    mesh_fingerprint,
    shape_bucket,
)


def test_shape_bucket_pow2():
    assert shape_bucket(1) == 64
    assert shape_bucket(64) == 64
    assert shape_bucket(65) == 128
    assert shape_bucket(137) == 256
    assert shape_bucket(8192) == 8192


def test_mesh_fingerprint_is_value_identity():
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    m1, m2 = get_mesh(), get_mesh()
    assert mesh_fingerprint(m1) == mesh_fingerprint(m2)
    assert mesh_fingerprint(None) == ()
    if m1.devices.size > 1:
        assert mesh_fingerprint(get_mesh(1)) != mesh_fingerprint(m1)


def test_cached_call_hits_without_new_compiles():
    pc = Precompiler(max_workers=2)

    @jax.jit
    def f(x):
        return (x * 3).sum(axis=1)

    x = jnp.asarray(np.ones((8, 4), np.float32))
    c0 = profiling.counters("precompile")

    def delta(name):
        return profiling.counter(name) - c0.get(name, 0)

    r1 = pc.cached_call(("f", x.shape), f, x)
    assert delta("precompile.aot_miss") == 1
    assert delta("precompile.compile") == 1
    r2 = pc.cached_call(("f", x.shape), f, x)
    assert delta("precompile.aot_hit") == 1
    assert delta("precompile.compile") == 1  # unchanged: zero new compiles
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_second_same_shape_search_zero_new_compiles():
    """The acceptance smoke: a second kNN search at the same shapes — with a
    FRESH mesh object, as repeat kneighbors calls produce — performs zero
    new compilations and runs entirely off aot_hit executables."""
    import spark_rapids_ml_tpu.ops.knn as knn_mod
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    rng = np.random.default_rng(41)
    X = rng.standard_normal((1000, 16)).astype(np.float32)
    Q = rng.standard_normal((200, 16)).astype(np.float32)
    ids = np.arange(1000, dtype=np.int64)
    prepared = knn_mod.prepare_items(X, ids, get_mesh())
    d1, i1 = knn_mod.knn_search_prepared(prepared, Q, 7, get_mesh())
    c0 = profiling.counters("precompile")
    d2, i2 = knn_mod.knn_search_prepared(prepared, Q, 7, get_mesh())
    c1 = profiling.counters("precompile")
    assert c1.get("precompile.compile", 0) == c0.get("precompile.compile", 0)
    assert c1.get("precompile.fallback", 0) == c0.get("precompile.fallback", 0)
    assert c1.get("precompile.aot_hit", 0) > c0.get("precompile.aot_hit", 0)
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(i1, i2)


@pytest.mark.parametrize("force_adaptive", [False, True])
def test_warm_search_kernels_covers_first_dispatch(monkeypatch, force_adaptive):
    """A warmed geometry must be the EXACT entry the later dispatch looks
    up: after warm_search_kernels, the first knn_search_prepared records no
    aot_miss (every kernel call lands on a submitted executable) — on the
    exact route AND the adaptive scan route (which dispatches TWO jits,
    candidates + merge; the merge warm was the review finding)."""
    import spark_rapids_ml_tpu.ops.knn as knn_mod
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    if force_adaptive:
        monkeypatch.setenv("SRML_KNN_FORCE_ADAPTIVE", "1")
    rng = np.random.default_rng(43)
    n, d, q_n, k = 800, 24, 120, 6
    X = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q_n, d)).astype(np.float32)
    mesh = get_mesh()
    prepared = knn_mod.prepare_items(X, np.arange(n, dtype=np.int64), mesh)
    keys = knn_mod.warm_search_kernels(
        prepared, k, mesh, n_queries=q_n, d_query=d
    )
    assert keys, "warm path submitted nothing"
    c0 = profiling.counters("precompile")
    knn_mod.knn_search_prepared(prepared, Q, k, get_mesh())
    c1 = profiling.counters("precompile")
    assert c1.get("precompile.aot_miss", 0) == c0.get("precompile.aot_miss", 0)
    assert c1.get("precompile.aot_hit", 0) > c0.get("precompile.aot_hit", 0)
    # a warmed executable that REJECTS its inputs (sharding/placement skew)
    # would silently re-compile on the jit fallback — that is a warm-path
    # bug, not a cache hit (caught live: the merge warm compiled for
    # single-device placement while the sharded scan emits replicated pools)
    assert c1.get("precompile.fallback", 0) == c0.get("precompile.fallback", 0)


def test_cached_call_falls_back_on_plain_callable_and_compile_failure():
    pc = Precompiler(max_workers=1)

    @jax.jit
    def boom(x):
        raise RuntimeError("tracing failure")

    x = jnp.asarray(np.ones((4,), np.float32))
    with pytest.raises(RuntimeError, match="tracing failure"):
        # compile fails on the worker, fallback re-raises at the true site
        pc.cached_call(("boom",), boom, x)


@pytest.fixture
def fresh_cache_rule(monkeypatch):
    """Run ensure_compile_cache as a fresh process would (rule not yet
    applied, no cache directory configured), restoring the suite's
    configuration afterwards."""
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(precompile, "_persist_dir", None)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_compile_cache_env_set_library_sets_no_directory(
    fresh_cache_rule, monkeypatch, tmp_path
):
    """JAX_COMPILATION_CACHE_DIR set: jax owns the directory (it reads the
    variable itself); the library names it and sets no other."""
    monkeypatch.setenv(precompile.CACHE_DIR_ENV, str(tmp_path))
    assert precompile.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # untouched by code
    assert precompile.ensure_compile_cache() == str(tmp_path)  # idempotent


def test_compile_cache_env_unset_uses_checkout_directory(
    fresh_cache_rule, monkeypatch
):
    """No environment setting: ONE fixed directory inside the checkout."""
    import os

    monkeypatch.delenv(precompile.CACHE_DIR_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert precompile.ensure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want)
