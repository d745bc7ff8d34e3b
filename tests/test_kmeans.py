# KMeans correctness vs sklearn + param/persistence tests (strategy modeled
# on the reference's test_kmeans.py).
import numpy as np
import pytest

from spark_rapids_ml_tpu import KMeans, KMeansModel
from spark_rapids_ml_tpu.core import load
from spark_rapids_ml_tpu.dataframe import DataFrame


def _blobs(n=600, d=6, k=4, seed=0, spread=0.05):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-5, 5, size=(k, d))
    labels = rng.integers(0, k, size=n)
    X = centers[labels] + spread * rng.normal(size=(n, d))
    return X.astype(np.float64), centers, labels


def _inertia(X, centers):
    d2 = ((X[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
    return d2.min(axis=1).sum()


def test_default_params():
    km = KMeans()
    assert km.tpu_params["n_clusters"] == 2  # k default 2 pushed into solver
    assert km.tpu_params["max_iter"] == 20
    assert km.tpu_params["init"] == "scalable-k-means++"
    km = KMeans(k=10, maxIter=50, tol=1e-6)
    assert km.tpu_params["n_clusters"] == 10
    assert km.tpu_params["max_iter"] == 50
    km = KMeans(initMode="random")
    assert km.tpu_params["init"] == "random"


def test_unsupported_params():
    with pytest.raises(ValueError):
        KMeans(distanceMeasure="cosine")
    with pytest.raises(ValueError):
        KMeans().setWeightCol("w")
    # silently-ignored param accepted
    km = KMeans(initSteps=5)
    assert "initSteps" not in km.tpu_params


def test_kmeans_recovers_blobs():
    X, true_centers, _ = _blobs()
    df = DataFrame.from_numpy(X, num_partitions=4)
    model = KMeans(k=4, initMode="k-means||", maxIter=100, seed=42).fit(df)
    centers = model.cluster_centers_
    assert centers.shape == (4, 6)
    # every true center matched by some learned center
    for tc in true_centers:
        dist = np.min(np.linalg.norm(centers - tc, axis=1))
        assert dist < 0.5, f"center {tc} unmatched (nearest {dist})"
    # inertia close to optimal
    assert model.inertia_ <= 1.5 * _inertia(X, true_centers)


def test_kmeans_random_init_converges():
    # random init can land in a genuine local minimum on tight blobs (the
    # reason k-means|| exists), so assert convergence/sanity, not recovery
    X, true_centers, _ = _blobs()
    df = DataFrame.from_numpy(X, num_partitions=4)
    model = KMeans(k=4, initMode="random", maxIter=100, seed=42).fit(df)
    assert model.cluster_centers_.shape == (4, 6)
    assert np.all(np.isfinite(model.cluster_centers_))
    assert model.n_iter_ >= 1
    assert np.isfinite(model.inertia_)


def test_kmeans_transform_assignments():
    X, true_centers, labels = _blobs(n=300)
    df = DataFrame.from_numpy(X, num_partitions=3)
    model = KMeans(k=4, maxIter=50, seed=1).fit(df)
    out = model.transform(df).toPandas()
    pred = out["prediction"].to_numpy()
    assert pred.dtype.kind in "iu"
    # same-blob rows map to the same cluster id (allow relabeling)
    for b in range(4):
        ids = pred[labels == b]
        assert len(np.unique(ids)) == 1


def test_kmeans_vs_sklearn_quality():
    from sklearn.cluster import KMeans as SkKMeans

    X, _, _ = _blobs(n=500, d=8, k=5, spread=0.5, seed=3)
    df = DataFrame.from_numpy(X, num_partitions=4)
    model = KMeans(k=5, maxIter=300, seed=7).fit(df)
    sk = SkKMeans(n_clusters=5, n_init=1, random_state=7).fit(X)
    assert model.inertia_ <= 1.1 * sk.inertia_


@pytest.mark.slow
def test_kmeans_mesh_invariance():
    X, _, _ = _blobs(n=256, d=5)
    df = DataFrame.from_numpy(X, num_partitions=4)
    m1 = KMeans(k=4, seed=5, maxIter=100, num_workers=1).fit(df)
    m8 = KMeans(k=4, seed=5, maxIter=100, num_workers=8).fit(df)
    # same seed, same data -> same converged centers up to ordering
    c1 = m1.cluster_centers_[np.lexsort(m1.cluster_centers_.T)]
    c8 = m8.cluster_centers_[np.lexsort(m8.cluster_centers_.T)]
    np.testing.assert_allclose(c1, c8, atol=1e-2)


def test_kmeans_persistence(tmp_path):
    X, _, _ = _blobs(n=200)
    df = DataFrame.from_numpy(X, num_partitions=2)
    est = KMeans(k=4, maxIter=30, seed=11)
    est.save(str(tmp_path / "est"))
    est2 = load(str(tmp_path / "est"))
    assert isinstance(est2, KMeans)
    assert est2.getK() == 4

    model = est.fit(df)
    model.save(str(tmp_path / "model"))
    loaded = load(str(tmp_path / "model"))
    assert isinstance(loaded, KMeansModel)
    np.testing.assert_allclose(loaded.cluster_centers_, model.cluster_centers_)
    p1 = model.transform(df).toPandas()["prediction"].to_numpy()
    p2 = loaded.transform(df).toPandas()["prediction"].to_numpy()
    np.testing.assert_array_equal(p1, p2)


def test_kmeans_single_predict():
    X, _, _ = _blobs(n=200)
    model = KMeans(k=4, seed=2).fit(DataFrame.from_numpy(X))
    cid = model.predict(X[0])
    assert 0 <= cid < 4
    assert len(model.clusterCenters()) == 4


# -- lloyd_iterations: rows walked in place (whole chunks, then one tail block) --

CHUNK = 16


def _lloyd_table(n, d=5, seed=0):
    """Blobs with non-uniform weights; row 0 is a far outlier of weight 0.  A
    table of a few rows (the one-row shard) has one centre and no outlier."""
    rng = np.random.default_rng(seed)
    few = n < 16
    k = 1 if few else 4
    true = rng.uniform(-4, 4, size=(k, d))
    X = (true[rng.integers(0, k, size=n)] + 0.3 * rng.normal(size=(n, d))).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    if not few:
        X[0], w[0] = 1e3, 0.0
    centers0 = X[rng.choice(np.arange(1, n) if n > 1 else [0], size=k, replace=False)]
    return X, w, centers0


def _plain_lloyd(X, w, centers, max_iter, tol):
    """Float32 Lloyd, all rows at once, products at HIGHEST: the reference the
    chunked solver is held to."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    X, w, centers = jnp.asarray(X), jnp.asarray(w), jnp.asarray(centers)
    k = centers.shape[0]

    def assign(c):
        d2 = (X * X).sum(1)[:, None] - 2.0 * jnp.matmul(X, c.T, precision=hi) + (c * c).sum(1)[None, :]
        return jnp.argmin(d2, axis=1)

    history, shifts = [], []
    for _ in range(max_iter):
        onehot = jax.nn.one_hot(assign(centers), k, dtype=X.dtype) * w[:, None]
        sums, counts = jnp.matmul(onehot.T, X, precision=hi), onehot.sum(0)
        new = jnp.where((counts > 0)[:, None], sums / jnp.maximum(counts, 1.0)[:, None], centers)
        shifts.append(((new - centers) ** 2).sum())
        history.append(new)
        centers = new
    # the update after which the solver's `while` stops: the first shift <= tol
    stopped = np.nonzero(np.asarray(jax.device_get(shifts)) <= tol)[0]
    n_iter = int(stopped[0]) + 1 if len(stopped) else max_iter
    centers = history[n_iter - 1]
    diff = X - centers[assign(centers)]
    return np.asarray(centers), n_iter, float(((diff * diff).sum(1) * w).sum())


def _run_lloyd(X, w, centers0, devices, chunk, max_iter=25, tol=0.0):
    import jax

    from spark_rapids_ml_tpu.ops.kmeans import lloyd_iterations
    from spark_rapids_ml_tpu.parallel.mesh import data_sharding, get_mesh

    mesh = get_mesh(devices)
    Xd, wd = (jax.device_put(a, data_sharding(mesh)) for a in (X, w))
    centers, n_iter, inertia = jax.device_get(
        lloyd_iterations(Xd, wd, jax.numpy.asarray(centers0), mesh, max_iter, tol, chunk)
    )
    return np.asarray(centers), int(n_iter), float(inertia)


def _assert_same_fit(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    assert got[1] == want[1]
    assert got[2] == pytest.approx(want[2], rel=1e-4, abs=1e-5)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize(
    "n_full,tail",
    [(n_full, tail) for n_full in (0, 1, 3) for tail in (0, 1, CHUNK - 1) if n_full or tail],
)
def test_lloyd_walks_whole_chunks_and_a_tail(n_full, tail, devices):
    """n_loc // chunk whole chunks and n_loc % chunk rows left over, on one
    device and on a mesh: the fit of the plain reference, and of the same
    table walked as one block a device."""
    n_loc = n_full * CHUNK + tail
    X, w, centers0 = _lloyd_table(n_loc * devices, seed=10 * n_full + tail)
    got = _run_lloyd(X, w, centers0, devices, CHUNK)
    assert 1 <= got[1] < 25, "stopped by the shift, not by max_iter"
    _assert_same_fit(got, _plain_lloyd(X, w, centers0, 25, 0.0))
    _assert_same_fit(got, _run_lloyd(X, w, centers0, devices, n_loc))
    assert np.abs(got[0]).max() < 10, "the zero-weight outlier moved no centre"


def _lloyd_eqns(n_loc, d, k, devices, chunk):
    """(equation, inside a `while`) for every equation of lloyd_iterations'
    jaxpr at these shapes, nested jaxprs included."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans import lloyd_iterations
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    mesh = get_mesh(devices)
    X = jax.ShapeDtypeStruct((n_loc * devices, d), jnp.float32)
    w = jax.ShapeDtypeStruct((n_loc * devices,), jnp.float32)
    c = jax.ShapeDtypeStruct((k, d), jnp.float32)
    closed = jax.make_jaxpr(lloyd_iterations, static_argnums=(3, 4, 6))(X, w, c, mesh, 3, 0.0, chunk)

    def walk(jaxpr, looped):
        for eqn in jaxpr.eqns:
            yield eqn, looped
            inner = looped or eqn.primitive.name == "while"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inner)

    return list(walk(closed.jaxpr, False))


def _result_shapes(eqn):
    return [tuple(v.aval.shape) for v in eqn.outvars if hasattr(v.aval, "shape")]


@pytest.mark.parametrize("devices", [1, 4])
def test_lloyd_builds_nothing_of_the_tables_size(devices):
    """The guard that keeps the padded copy from coming back: with a ragged
    tail (3 whole chunks and 5 rows a device) the program pads nothing, and
    inside its loops no result has the table's width and a shard's rows."""
    n_loc, d, k = 3 * CHUNK + 5, 6, 4
    eqns = _lloyd_eqns(n_loc, d, k, devices, CHUNK)
    assert any(looped for _, looped in eqns)
    assert not [e for e, _ in eqns if e.primitive.name == "pad"]
    table_sized = [
        (e.primitive.name, s)
        for e, looped in eqns if looped
        for s in _result_shapes(e) if len(s) == 2 and s[1] == d and s[0] >= n_loc
    ]
    assert not table_sized, table_sized
    # the products run on whole chunks and on the 5 rows left over, and on nothing else
    operands = {tuple(v.aval.shape) for e, _ in eqns if e.primitive.name == "dot_general" for v in e.invars}
    rows = {s[0] for s in operands if len(s) == 2 and s[1] == d and s[0] != k}
    assert rows == {CHUNK, 5}


def test_lloyd_shard_smaller_than_the_chunk_is_one_tail_block():
    """A 250-row shard under a 1024-row chunk: one block of 250 rows a device,
    no loop over chunks, and nothing stretched to the chunk's size."""
    eqns = _lloyd_eqns(250, 6, 4, 4, 1024)
    assert [e.primitive.name for e, _ in eqns if e.primitive.name in ("while", "scan")] == ["while"]
    shapes = [s for e, _ in eqns for s in _result_shapes(e)]
    assert not [s for s in shapes if s and s[0] in (1024, 1000)], "nothing of the chunk's or the table's rows"
    assert (250, 4) in shapes       # the distance block of the shard itself


_TPU_HLO = """\
%fused_computation.1 (p0: bf16[400000,3000], p1: s32[]) -> f32[32768,1000] {
  %p0 = bf16[400000,3000]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %dynamic_slice.1 = bf16[32768,3000]{1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p1, %p1), dynamic_slice_sizes={32768,3000}
  ROOT %dot.1 = f32[32768,1000]{1,0:T(8,128)} convolution(%dynamic_slice.1, %dynamic_slice.1), dim_labels=bf_io->bf
}

%body.1 (arg: (s32[], bf16[400000,3000])) -> (s32[], bf16[400000,3000]) {
  %arg = (s32[]{:T(128)}, bf16[400000,3000]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte.0 = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %gte.1 = bf16[400000,3000]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %fusion.1 = f32[32768,1000]{1,0:T(8,128)} fusion(%gte.1, %gte.0), kind=kOutput, calls=%fused_computation.1
IN_BODY
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[400000,3000]{1,0:T(8,128)(2,1)}) tuple(%gte.0, %gte.1)
}

ENTRY %main.1 (X.1: f32[400000,3000]) -> f32[1000,3000] {
  %X.1 = f32[400000,3000]{0,1:T(8,128)} parameter(0)
  %copy.3 = f32[400000,3000]{1,0:T(8,128)} copy(%X.1), backend_config={"flag_configs":[]}
  %convert.12 = bf16[400000,3000]{1,0:T(8,128)(2,1)} convert(%copy.3)
IN_ENTRY
  %while.1 = (s32[]{:T(128)}, bf16[400000,3000]{1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond.1, body=%body.1
  ROOT %fusion.9 = f32[1000,3000]{1,0:T(8,128)} fusion(%while.1), kind=kLoop, calls=%fused_computation.1
}
"""


@pytest.mark.parametrize(
    "in_body,in_entry,faults",
    [
        ("", "", 0),  # the chunk sliced inside the product's fusion, one copy and one convert once a fit
        ("  %pad.23 = bf16[425984,3000]{1,0:T(8,128)(2,1)} pad(%gte.1, %c), padding=0_25984x0_0", "", 3),
        ("  %copy.9 = bf16[32768,3000]{1,0:T(8,128)(2,1)} copy(%x)", "", 1),
        ("  %dynamic_slice.7 = bf16[32768,3000]{1,0:T(8,128)(2,1)} dynamic-slice(%gte.1, %gte.0, %z)", "", 1),
        ("", "  %copy.4 = f32[400000,3000]{1,0:T(8,128)} copy(%copy.3)", 1),
        ("", "  %pad.6 = f32[425984,3000]{1,0:T(8,128)} pad(%copy.3, %c), padding=0_25984x0_0", 2),
    ],
    ids=["clean", "pad_in_loop", "chunk_copy_in_loop", "chunk_slice_in_loop", "second_copy_once", "pad_once"],
)
def test_tpu_hlo_reader_names_what_moved_the_table(in_body, in_entry, faults):
    """tools/lloyd_tpu_hlo.py reads the TPU compiler's text for what the solver
    must not build; here its reading of a hand-made module (no compiler runs)."""
    from tools.lloyd_tpu_hlo import read_hlo

    text = _TPU_HLO.replace("IN_BODY\n", in_body + "\n" if in_body else "").replace(
        "IN_ENTRY\n", in_entry + "\n" if in_entry else ""
    )
    report, found = read_hlo(text)
    assert len(found) == faults, found
    assert {"copy.3 copy", "convert.12 convert"} <= set(report["table_sized"])
