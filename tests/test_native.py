#
# Native host-runtime tests (role of the reference's native-layer tests,
# jvm/src/test PCASuite checking JNI cov/SVD vs Spark): every wrapper is
# checked against its numpy fallback so native and fallback paths cannot
# drift. Skipped (except fallback tests) when the library isn't built; CI
# builds it via `make -C native`.
#

import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from spark_rapids_ml_tpu import native  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def built_lib():
    if not native.available():
        # try to build once; skip module if no toolchain
        try:
            subprocess.run(
                ["make", "-C", os.path.join(REPO, "native")],
                check=True,
                capture_output=True,
                timeout=300,
            )
        except Exception:
            pytest.skip("native toolchain unavailable")
        # force re-discovery after the build
        native._lib_tried = False
        native._lib = None
    if not native.available():
        pytest.skip("libsrml_native.so not built")
    yield


def test_version_and_threads():
    assert native.version() == "0.1.0"
    assert native.lib().srml_hardware_threads() >= 1


def test_unusable_library_file_is_an_error(monkeypatch, tmp_path):
    """A .so that exists but cannot be loaded (or is stale) raises — it is
    never skipped in favour of the numpy routes."""
    bad = tmp_path / "libsrml_native.so"
    bad.write_bytes(b"not an ELF object")
    monkeypatch.setenv("SRML_NATIVE_LIB", str(bad))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_tried", False)
    with pytest.raises(RuntimeError, match="exists but is unusable"):
        native.lib()


def test_allocator_reuses_buffers():
    l = native.lib()
    p1 = l.srml_buf_alloc(1 << 20)
    assert p1
    l.srml_buf_free(p1)
    cached = l.srml_buf_cached_bytes()
    assert cached >= (1 << 20)
    p2 = l.srml_buf_alloc(1 << 20)
    assert p2 == p1  # bucket reuse
    l.srml_buf_free(p2)
    l.srml_buf_trim()
    assert l.srml_buf_cached_bytes() == 0


def test_allocator_big_blocks_bypass_pool():
    l = native.lib()
    l.srml_buf_trim()
    big = (64 << 20) + 1  # just over the pooling ceiling
    p = l.srml_buf_alloc(big)
    assert p
    l.srml_buf_free(p)
    # big blocks are returned to the OS, never cached
    assert l.srml_buf_cached_bytes() == 0


@pytest.mark.parametrize(
    "src_dtype,dst_dtype",
    [(np.float32, np.float32), (np.float64, np.float32), (np.float64, np.float64)],
)
def test_concat_matches_numpy(src_dtype, dst_dtype):
    rng = np.random.default_rng(0)
    parts = [
        np.ascontiguousarray(rng.standard_normal((n, 7)).astype(src_dtype))
        for n in (3, 0, 11, 5)
    ]
    got = native.concat_rows(parts, np.dtype(dst_dtype))
    want = np.concatenate(parts).astype(dst_dtype)
    assert got.dtype == dst_dtype and got.flags.c_contiguous
    np.testing.assert_array_equal(got, want)


def test_concat_fallback_mixed_dtypes():
    parts = [np.zeros((2, 3), dtype=np.float32), np.ones((2, 3), dtype=np.float64)]
    got = native.concat_rows(parts, np.dtype(np.float32))
    assert got.shape == (4, 3)


def test_load_csv(tmp_path):
    rng = np.random.default_rng(1)
    want = rng.standard_normal((50, 6)).astype(np.float32)
    path = tmp_path / "data.csv"
    np.savetxt(path, want, delimiter=",", header="a,b,c,d,e,f")
    got = native.load_csv(str(path), 50, 6, skip_rows=1)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_csv_count_rows(tmp_path):
    path = tmp_path / "count.csv"
    path.write_text("h\n1\n2\n3")  # unterminated last line counts
    assert native.csv_count_rows(str(path)) == 4
    got = native.load_csv(str(path), None, 1, skip_rows=1)
    np.testing.assert_allclose(got[:, 0], [1.0, 2.0, 3.0])


def test_load_csv_rejects_short_rows(tmp_path):
    path = tmp_path / "bad.csv"
    path.write_text("1.0,2.0,3.0\n4.0,5.0\n6.0,7.0,8.0\n")
    with pytest.raises(RuntimeError):
        native.load_csv(str(path), 3, 3)


@pytest.mark.slow
def test_out_of_core_knn_matches_in_core():
    from spark_rapids_ml_tpu.ops.knn import knn_search, knn_search_out_of_core
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    rng = np.random.default_rng(7)
    items = rng.standard_normal((600, 8)).astype(np.float32)
    ids = np.arange(600, dtype=np.int64) * 10  # non-trivial user ids
    queries = rng.standard_normal((37, 8)).astype(np.float32)
    mesh = get_mesh()
    d_full, i_full = knn_search(items, ids, queries, 5, mesh)
    d_ooc, i_ooc = knn_search_out_of_core(items, ids, queries, 5, mesh, item_block=256)
    np.testing.assert_allclose(d_ooc, d_full, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(i_ooc, i_full)
    # item_block < k: blocks return fewer than k candidates each, but the
    # merged result must still produce all k true neighbors
    d_tiny, i_tiny = knn_search_out_of_core(items, ids, queries, 16, mesh, item_block=8)
    d_want, i_want = knn_search(items, ids, queries, 16, mesh)
    assert d_tiny.shape == (37, 16)
    np.testing.assert_allclose(d_tiny, d_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(i_tiny, i_want)


def test_covariance_matches_numpy():
    rng = np.random.default_rng(2)
    X = rng.standard_normal((500, 12)) * rng.uniform(0.5, 3.0, 12) + 5.0
    cov, mean = native.covariance(X)
    np.testing.assert_allclose(mean, X.mean(axis=0), rtol=1e-12)
    np.testing.assert_allclose(cov, np.cov(X, rowvar=False), rtol=1e-10)


def test_eigh_jacobi_matches_numpy():
    rng = np.random.default_rng(3)
    M = rng.standard_normal((16, 16))
    A = M @ M.T
    evals, comps = native.eigh_descending(A)
    w_np = np.sort(np.linalg.eigvalsh(A))[::-1]
    np.testing.assert_allclose(evals, w_np, rtol=1e-8)
    # eigen-equation holds and signs are deterministic
    for i in range(16):
        np.testing.assert_allclose(A @ comps[i], evals[i] * comps[i], atol=1e-7)
        assert comps[i, np.argmax(np.abs(comps[i]))] > 0
    # orthonormal
    np.testing.assert_allclose(comps @ comps.T, np.eye(16), atol=1e-9)


def test_topk_select_matches_numpy():
    rng = np.random.default_rng(4)
    tile = rng.standard_normal((40, 100)).astype(np.float32)
    d, i = native.topk_select(tile, 5, id_base=1000)
    want = np.sort(tile, axis=1)[:, :5]
    np.testing.assert_allclose(d, want, rtol=1e-6)
    np.testing.assert_array_equal(np.take_along_axis(tile, i - 1000, axis=1), d)
    assert (np.diff(d, axis=1) >= 0).all()


def test_topk_merge():
    rng = np.random.default_rng(5)
    a = np.sort(rng.standard_normal((30, 8)).astype(np.float32), axis=1)
    b = np.sort(rng.standard_normal((30, 8)).astype(np.float32), axis=1)
    ia = np.arange(8)[None, :].repeat(30, 0).astype(np.int64)
    ib = ia + 100
    d, i = native.topk_merge(a, ia, b, ib)
    want = np.sort(np.concatenate([a, b], axis=1), axis=1)[:, :8]
    np.testing.assert_allclose(d, want, rtol=1e-6)
    assert ((i < 8) | (i >= 100)).all()


def test_wide_pca_host_eigh_route_matches_device_route():
    """PCA beyond HOST_EIGH_MIN_D columns routes eigh through the host native
    runtime; both routes must agree."""
    import pandas as pd

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops import linalg

    assert linalg.HOST_EIGH_MIN_D <= 150
    rng = np.random.default_rng(8)
    X = (rng.standard_normal((400, 150)) @ rng.standard_normal((150, 150))).astype(
        np.float32
    )
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=4)
    model = PCA(k=5).setInputCol("features").fit(df)  # host-eigh route (d>=128)
    from sklearn.decomposition import PCA as SkPCA

    sk = SkPCA(n_components=5).fit(X.astype(np.float64))
    np.testing.assert_allclose(
        model.explained_variance_ratio_, sk.explained_variance_ratio_, rtol=1e-2
    )
    for i in range(5):
        dot = abs(np.dot(model.components_[i], sk.components_[i]))
        assert dot > 0.99


def test_pca_via_native_matches_sklearn():
    """End-to-end: native cov + eigh reproduces sklearn PCA components (the
    reference's JNI PCA fit path, RapidsRowMatrix.scala:59-89)."""
    from sklearn.decomposition import PCA as SkPCA

    rng = np.random.default_rng(6)
    X = rng.standard_normal((300, 10)) @ rng.standard_normal((10, 10))
    cov, mean = native.covariance(X)
    evals, comps = native.eigh_descending(cov)
    sk = SkPCA(n_components=3).fit(X)
    for i in range(3):
        np.testing.assert_allclose(evals[i], sk.explained_variance_[i], rtol=1e-8)
        dot = abs(np.dot(comps[i], sk.components_[i]))
        np.testing.assert_allclose(dot, 1.0, atol=1e-8)
