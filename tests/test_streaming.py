# srml-stream gates (docs/streaming.md is the contract):
#
#   1. Streamed-fit EQUALITY: partial_fit over k chunks vs batch fit on the
#      union — BITWISE for the closed-form engines (linreg coefficients,
#      sign-canonicalized PCA components) on the exact-arithmetic data
#      family (small-integer features, pow2 row count: every chunk partial
#      is an exact f32 sum, the f64 host fold is exact, finalize shares the
#      batch solver kernels), quality-gated for the online approximations
#      (kmeans inertia, logreg accuracy) — across 1/2/8-device batch
#      meshes (streamed states are mesh-independent data by construction).
#   2. ZERO-COMPILE steady ingest: after the first chunk of a bucket,
#      further same-bucket chunks move precompile.aot_hit and never
#      precompile.compile.
#   3. Merge algebra: associative/commutative state merge, wire round
#      trip, control-plane allgather fold, identity-anchor mismatch fails
#      loudly.
#   4. Live IVF mutation: add/delete/repack on a serving index with
#      recall@10 >= 0.95 at every step, tombstoned ids never returned,
#      zero steady-state compiles across a warm-covered repack.
#   5. Train-while-serve: StreamingSession.refresh() through the router
#      under concurrent load — zero client-visible errors, zero new
#      compiles at a same-shape refresh.
import json

import numpy as np
import pytest

from spark_rapids_ml_tpu import (
    ApproximateNearestNeighbors,
    KMeans,
    LinearRegression,
    LogisticRegression,
    PCA,
    profiling,
)
from spark_rapids_ml_tpu.dataframe import DataFrame, stream_chunk_ids
from spark_rapids_ml_tpu.stream import (
    StreamingSession,
    StreamState,
    allgather_merge,
    merge_all,
    streaming_fit,
)

CHUNK = 128


@pytest.fixture(scope="module")
def exact_data():
    """The exact-arithmetic family: small-integer features, pow2 rows —
    every f32 sum in both the batch moment passes and the streamed chunk
    partials is exact, so bitwise streamed==batch is a mathematical
    identity, not a tolerance (same basis as the srml-sweep bitwise gates,
    docs/tuning_engine.md)."""
    rng = np.random.default_rng(3)
    n, d = 512, 8
    X = rng.integers(-4, 5, size=(n, d)).astype(np.float32)
    y = (X @ np.arange(1.0, d + 1.0)).astype(np.float64)
    cid = stream_chunk_ids(n, CHUNK, seed=5)
    return X, y, cid


@pytest.fixture(scope="module")
def clustered_data():
    rng = np.random.default_rng(11)
    n, d, k = 1024, 8, 4
    centers = rng.standard_normal((k, d)) * 8
    X = (centers[rng.integers(0, k, n)] + rng.standard_normal((n, d))).astype(
        np.float32
    )
    cid = stream_chunk_ids(n, 256, seed=7)
    return X, cid, k


def _stream(engine, X, cid, y=None):
    for c in range(int(cid.max()) + 1):
        m = cid == c
        engine.partial_fit(X[m], y=None if y is None else y[m])
    return engine


# -- 1. streamed == batch equality -------------------------------------------


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_streamed_linreg_bitwise_equals_batch(exact_data, n_dev):
    X, y, cid = exact_data
    batch = LinearRegression(maxIter=20, num_workers=n_dev).fit(
        DataFrame.from_numpy(X, y=y, num_partitions=2)
    )
    streamed = _stream(
        LinearRegression(maxIter=20).streaming(), X, cid, y=y
    ).finalize()
    np.testing.assert_array_equal(streamed.coef_, batch.coef_)
    assert streamed.intercept_ == batch.intercept_
    assert streamed.n_cols == batch.n_cols and streamed.dtype == batch.dtype


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_streamed_pca_bitwise_equals_batch(exact_data, n_dev):
    X, _y, cid = exact_data
    batch = (
        PCA(k=3, num_workers=n_dev)
        .setInputCol("features")
        .fit(DataFrame.from_numpy(X, feature_layout="array", num_partitions=2))
    )
    streamed = _stream(
        PCA(k=3).setInputCol("features").streaming(), X, cid
    ).finalize()
    # components are sign-canonicalized by the shared sign_flip inside
    # _pca_from_moments on BOTH routes — bitwise is the bar
    np.testing.assert_array_equal(streamed.components_, batch.components_)
    np.testing.assert_array_equal(streamed.mean_, batch.mean_)
    np.testing.assert_array_equal(
        streamed.explained_variance_, batch.explained_variance_
    )
    np.testing.assert_array_equal(
        streamed.singular_values_, batch.singular_values_
    )


def _inertia(centers, X):
    d2 = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
    return float(d2.min(axis=1).sum())


def test_streamed_kmeans_inertia_quality(clustered_data):
    X, cid, k = clustered_data
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=2)
    batch = KMeans(k=k, maxIter=20, seed=1).setFeaturesCol("features").fit(df)
    streamed = _stream(
        KMeans(k=k, maxIter=20, seed=1).setFeaturesCol("features").streaming(),
        X, cid,
    ).finalize()
    bi = _inertia(np.asarray(batch.cluster_centers_), X)
    si = _inertia(np.asarray(streamed.cluster_centers_), X)
    # one-pass mini-batch Lloyd on clustered data: within 10% of batch
    assert si <= 1.10 * bi, (si, bi)
    assert streamed.n_cols == batch.n_cols
    # the model predicts like any batch model
    assert streamed.predict(X[0]) in range(k)


def test_streamed_logreg_metric_quality(clustered_data):
    X, cid, _k = clustered_data
    rng = np.random.default_rng(5)
    w = rng.standard_normal(X.shape[1])
    margin = X @ w
    y = (margin > np.median(margin)).astype(np.float64)
    df = DataFrame.from_numpy(X, y=y, num_partitions=2)

    def acc(model):
        out = model.transform(df)
        preds = np.concatenate(
            [np.asarray(p["prediction"]) for p in out.partitions if len(p)]
        )
        return float((preds == y).mean())

    batch = LogisticRegression(maxIter=30).fit(df)
    streamed = _stream(
        LogisticRegression(maxIter=30).streaming(), X, cid, y=y
    ).finalize()
    assert acc(streamed) >= acc(batch) - 0.03, (acc(streamed), acc(batch))
    np.testing.assert_array_equal(streamed.classes_, batch.classes_)


# -- 2. zero-compile steady ingest -------------------------------------------


@pytest.mark.parametrize("algo", ["linreg", "kmeans"])
def test_steady_ingest_zero_new_compiles(algo, exact_data):
    X, y, cid = exact_data
    if algo == "linreg":
        eng = LinearRegression(maxIter=20).streaming()
    else:
        eng = KMeans(k=4, maxIter=5, seed=1).setFeaturesCol("features").streaming()
        y = None

    def ingest(m):
        eng.partial_fit(X[m], y=None if y is None else y[m])

    ingest(cid == 0)  # bucket's first chunk
    before = profiling.counters("precompile.")
    for c in range(1, int(cid.max()) + 1):
        ingest(cid == c)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    assert delta.get("precompile.aot_hit", 0) >= int(cid.max()), delta


def test_ingest_counters_and_frame_chunks(exact_data):
    """Frame chunks route through utils.materialize_feature_block (the
    shared ingest path) and ingest volume lands on the
    stream.h2d_transfers/stream.bytes counter pair."""
    X, y, cid = exact_data
    m = cid == 0
    before = profiling.counters("stream.")
    eng_np = LinearRegression(maxIter=20).streaming()
    eng_np.partial_fit(X[m], y=y[m])
    eng_df = LinearRegression(maxIter=20).streaming()
    eng_df.partial_fit(DataFrame.from_numpy(X[m], y=y[m], num_partitions=2))
    delta = profiling.counter_deltas(before, "stream.")
    assert delta.get("stream.h2d_transfers", 0) >= 6, delta  # 3 buffers x 2
    assert delta.get("stream.bytes", 0) > 0, delta
    assert delta.get("stream.rows", 0) == 2 * int(m.sum()), delta
    # identical chunk membership => identical accumulated state
    assert eng_np.state == eng_df.state
    with pytest.raises(ValueError, match="y/weight only with numpy"):
        eng_df.partial_fit(
            DataFrame.from_numpy(X[m], y=y[m]), y=y[m]
        )


# -- 3. merge algebra --------------------------------------------------------


def test_state_merge_commutative_associative_and_wire(exact_data):
    X, y, cid = exact_data
    engines = []
    for c in range(3):
        m = cid == c
        engines.append(
            _stream(
                LinearRegression(maxIter=20).streaming(),
                X[m], np.zeros(int(m.sum()), np.int32), y=y[m],
            )
        )
    a, b, c3 = (e.state for e in engines)
    # exact data => merge order cannot change a single bit
    ab_c = a.merge(b).merge(c3)
    a_bc = a.merge(b.merge(c3))
    ba_c = b.merge(a).merge(c3)
    assert ab_c == a_bc == ba_c
    # wire round trip through the JSON form is lossless
    assert StreamState.from_dict(json.loads(json.dumps(ab_c.to_dict()))) == ab_c
    assert merge_all([a, b, c3]) == ab_c


def test_two_rank_merge_equals_single_stream(exact_data):
    """Rank 0 streams chunks {0,1}, rank 1 streams {2,3}; the merged
    engine finalizes BIT-IDENTICALLY to one engine that saw all four —
    the multi-rank scale-out contract."""
    X, y, cid = exact_data
    r0 = LinearRegression(maxIter=20).streaming()
    r1 = LinearRegression(maxIter=20).streaming()
    for c in range(int(cid.max()) + 1):
        m = cid == c
        (r0 if c < 2 else r1).partial_fit(X[m], y=y[m])
    solo = _stream(LinearRegression(maxIter=20).streaming(), X, cid, y=y)
    merged = r0.merge(r1.state_dict())  # wire-form merge, as ranks would
    np.testing.assert_array_equal(
        merged.finalize().coef_, solo.finalize().coef_
    )


def test_fresh_engine_adopts_peer_state(exact_data):
    """A rank whose partition was empty (zero chunks ingested) must still
    fold peer states — it adopts the gathered state wholesale, anchors
    included, and finalizes identically to the peer."""
    X, y, cid = exact_data
    peer = _stream(LinearRegression(maxIter=20).streaming(), X, cid, y=y)
    fresh = LinearRegression(maxIter=20).streaming()
    fresh.merge(peer.state_dict())
    np.testing.assert_array_equal(fresh.finalize().coef_, peer.finalize().coef_)
    # logreg: the classes anchor must come across too
    yl = (X[:, 0] > 0).astype(np.float64)
    lpeer = _stream(LogisticRegression(maxIter=10).streaming(), X, cid, y=yl)
    lfresh = LogisticRegression(maxIter=10).streaming()
    lfresh.merge(lpeer.state)
    np.testing.assert_array_equal(
        lfresh.finalize().classes_, lpeer.finalize().classes_
    )


def test_chunk_label_length_mismatch_fails_loudly(exact_data):
    X, y, _cid = exact_data
    eng = LinearRegression(maxIter=20).streaming()
    with pytest.raises(ValueError, match="chunk y has 50 rows but X has 100"):
        eng.partial_fit(X[:100], y=y[:50])
    with pytest.raises(ValueError, match="chunk weight has"):
        eng.partial_fit(X[:100], y=y[:100], weight=np.ones(99))
    # frame chunks cannot even CONSTRUCT the mismatch: the facade rejects
    # partitions with differing columns (the frame-branch length check in
    # _chunk_arrays is defensive depth behind this constructor guard)
    import pandas as pd

    from spark_rapids_ml_tpu.dataframe import DataFrame as Facade

    p0 = pd.DataFrame({"features": list(X[:8]), "label": y[:8]})
    p1 = pd.DataFrame({"features": list(X[8:16])})
    with pytest.raises(ValueError, match="same columns"):
        Facade([p0, p1])


def test_allgather_merge_over_control_plane(exact_data):
    from spark_rapids_ml_tpu.parallel.context import LocalControlPlane

    X, y, cid = exact_data
    eng = _stream(LinearRegression(maxIter=20).streaming(), X, cid, y=y)
    merged = allgather_merge(LocalControlPlane(), eng.state)
    assert merged == eng.state  # single-controller: identity fold


def test_merge_anchor_mismatch_fails_loudly(clustered_data):
    X, cid, k = clustered_data
    a = KMeans(k=k, maxIter=5, seed=1).setFeaturesCol("features").streaming()
    b = KMeans(k=k, maxIter=5, seed=2).setFeaturesCol("features").streaming()
    a.partial_fit(X[cid == 0])
    b.partial_fit(X[cid == 1])  # different seed => different init anchor
    with pytest.raises(ValueError, match="init_centers"):
        a.merge(b)
    with pytest.raises(ValueError, match="kind"):
        a.state.merge(
            _stream(
                PCA(k=2).setInputCol("features").streaming(),
                X, np.zeros(len(X), np.int32),
            ).state
        )


def test_logreg_unseen_label_fails_loudly(clustered_data):
    X, cid, _k = clustered_data
    eng = LogisticRegression(maxIter=5).streaming()
    m0 = cid == 0
    y0 = (X[m0, 0] > 0).astype(np.float64)
    eng.partial_fit(X[m0], y=y0)
    m1 = cid == 1
    with pytest.raises(ValueError, match="outside the stream's class set"):
        eng.partial_fit(X[m1], y=np.full(int(m1.sum()), 7.0))


# -- stream_chunk_ids (dataframe satellite) ----------------------------------


def test_stream_chunk_ids_deterministic_and_partitioning():
    ids = stream_chunk_ids(1000, 256, seed=9)
    replay = stream_chunk_ids(1000, 256, seed=9)
    np.testing.assert_array_equal(ids, replay)  # replayed stream: identical
    assert ids.shape == (1000,) and ids.dtype == np.int32
    sizes = np.bincount(ids)
    # EXACT integer cuts: every chunk is chunk_rows except the short tail
    # (never chunk_rows+1 — a drifted row would cross a pow2 bucket
    # boundary and compile mid-stream)
    np.testing.assert_array_equal(sizes, [256, 256, 256, 232])
    for n, c in ((22, 3), (513, 256), (97, 10)):
        s = np.bincount(stream_chunk_ids(n, c, seed=1))
        assert s[:-1].tolist() == [c] * (len(s) - 1) and 0 < s[-1] <= c, (n, c, s)
    assert not np.array_equal(ids, stream_chunk_ids(1000, 256, seed=10))
    assert stream_chunk_ids(0, 256).size == 0
    with pytest.raises(ValueError, match="chunk_rows"):
        stream_chunk_ids(10, 0)


# -- 4. live IVF mutation ----------------------------------------------------


@pytest.fixture(scope="module")
def live_index():
    """A fitted IVF-Flat model + its mutable holder + clustered item/query
    sets (module-scoped: the mutation tests form one ordered story via
    fresh holders per test on a shared model class)."""
    rng = np.random.default_rng(17)
    n, d = 1500, 16
    centers = rng.standard_normal((8, d)) * 6
    X = (centers[rng.integers(0, 8, n)] + rng.standard_normal((n, d))).astype(
        np.float32
    )
    Q = (centers[rng.integers(0, 8, 48)] + rng.standard_normal((48, d))).astype(
        np.float32
    )
    extra = (
        centers[rng.integers(0, 8, 300)] + rng.standard_normal((300, d))
    ).astype(np.float32)
    return X, Q, extra, centers


def _fit_ann(X):
    return (
        ApproximateNearestNeighbors(k=10, algoParams={"nlist": 16, "nprobe": 8})
        .setFeaturesCol("features")
        .fit(DataFrame.from_numpy(X, feature_layout="array"))
    )


def _exact_ids(items, ids, Q, k=10):
    d2 = ((Q[:, None, :].astype(np.float64) - items[None].astype(np.float64)) ** 2).sum(-1)
    return np.asarray(ids)[np.argsort(d2, axis=1)[:, :k]]


def test_live_index_add_delete_repack_recall(live_index):
    from spark_rapids_ml_tpu.ann import recall_at_k

    X, Q, extra, _ = live_index
    n = X.shape[0]
    model = _fit_ann(X)
    holder = model.mutable_index()
    _, ids0 = holder.search(Q, 10, 8)
    assert recall_at_k(ids0, _exact_ids(X, np.arange(n), Q)) >= 0.95

    # add
    holder.add_items(extra, np.arange(n, n + len(extra)))
    items = np.concatenate([X, extra])
    all_ids = np.arange(n + len(extra))
    _, ids1 = holder.search(Q, 10, 8)
    assert recall_at_k(ids1, _exact_ids(items, all_ids, Q)) >= 0.95

    # delete: tombstoned ids must NEVER come back
    dele = np.arange(0, 300)
    assert holder.delete_items(dele) == 300
    assert holder.delete_items(dele) == 0  # idempotent
    keep = np.ones(len(all_ids), bool)
    keep[dele] = False
    _, ids2 = holder.search(Q, 10, 8)
    assert not np.isin(ids2, dele).any()
    assert recall_at_k(ids2, _exact_ids(items[keep], all_ids[keep], Q)) >= 0.95
    st = holder.stats()
    assert st["tombstoned"] == 300 and st["n_items"] == len(all_ids) - 300
    # the packed tombstone bitmap surface covers every slot
    bitmap = holder.tombstone_bitmap()
    assert bitmap.dtype == np.uint8
    assert int(np.unpackbits(bitmap, axis=1).sum()) == 300

    # repack reclaims the tombstones; results stay recall-clean
    holder.repack()
    st = holder.stats()
    assert st["tombstoned"] == 0 and st["repacks"] == 1
    _, ids3 = holder.search(Q, 10, 8)
    assert not np.isin(ids3, dele).any()
    assert recall_at_k(ids3, _exact_ids(items[keep], all_ids[keep], Q)) >= 0.95


def test_live_index_overflow_repack_zero_steady_compiles(live_index):
    """Warm-before-swap across a bucket-growing repack: a burst add that
    overflows L_pad migrates to the next pow2 bucket; because the holder
    re-warms every noted probe geometry before swapping, the next search
    performs ZERO new compilations."""
    from spark_rapids_ml_tpu.ann import recall_at_k

    X, Q, _extra, centers = live_index
    rng = np.random.default_rng(23)
    model = _fit_ann(X)
    holder = model.mutable_index()
    holder.search(Q, 10, 8)  # notes the probe geometry for re-warm
    l_pad0 = holder.stats()["l_pad"]
    burst = (
        centers[0] + 0.5 * rng.standard_normal((4 * l_pad0, X.shape[1]))
    ).astype(np.float32)
    holder.add_items(burst, np.arange(50_000, 50_000 + len(burst)))
    st = holder.stats()
    assert st["l_pad"] > l_pad0 and st["repacks"] == 1
    before = profiling.counters("precompile.")
    _, ids = holder.search(Q, 10, 8)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    items = np.concatenate([X, burst])
    all_ids = np.concatenate(
        [np.arange(len(X)), np.arange(50_000, 50_000 + len(burst))]
    )
    assert recall_at_k(ids, _exact_ids(items, all_ids, Q)) >= 0.95


def test_snapshot_isolated_from_later_mutations(live_index):
    """A search holding an index snapshot must see the WHOLE old state: a
    later delete/add mutates the holder's mirrors, never the snapshot's
    host id table (device buffers are immutable uploads already)."""
    X, _Q, extra, _ = live_index
    model = _fit_ann(X)
    holder = model.mutable_index()
    snap = holder.index
    victim = 7
    pos = holder._pos_of_id[victim]
    assert snap.ids[pos] == victim
    holder.delete_items(np.array([victim]))
    assert snap.ids[pos] == victim  # old snapshot untouched
    assert holder.index.ids[pos] == -1  # new snapshot sees the delete
    holder.add_items(extra[:1], np.array([77_000]))
    assert 77_000 not in snap.ids  # adds invisible to the old snapshot too


def test_search_never_blocks_on_mutator_lock(live_index):
    """The lock-free reader contract, structurally: a search issued while
    another thread HOLDS the mutator lock (as a repack's staging+warm
    would) completes instead of queuing behind it."""
    import threading

    X, Q, _extra, _ = live_index
    model = _fit_ann(X)
    holder = model.mutable_index()
    holder.search(Q, 10, 8)  # warm the probe path first
    done = threading.Event()
    out = {}

    def probe():
        out["ids"] = holder.search(Q, 10, 8)[1]
        done.set()

    with holder._lock:  # simulate an in-flight mutation holding the lock
        t = threading.Thread(target=probe, name="stream-test-probe")
        t.start()
        finished = done.wait(timeout=30)
    t.join(timeout=30)
    assert finished, "search blocked behind the mutator lock"
    assert out["ids"].shape == (len(Q), 10)


def test_exact_search_rejected_while_mutable(live_index):
    """kneighbors(exactSearch=True) reads the persistable packed payload,
    which live mutations do not touch until freeze — serving it would
    return tombstoned ids.  It must refuse, typed, until freeze."""
    X, Q, extra, _ = live_index
    model = _fit_ann(X)
    holder = model.mutable_index()
    holder.add_items(extra[:10], np.arange(90_000, 90_010))
    model.setExactSearch(True)
    try:
        with pytest.raises(ValueError, match="freeze"):
            model.kneighbors(DataFrame.from_numpy(Q[:4], num_partitions=1))
        model.freeze_mutations()
        _, _, knn = model.kneighbors(
            DataFrame.from_numpy(Q[:4], num_partitions=1)
        )
        ids = np.concatenate(
            [np.stack(list(p["indices"])) for p in knn.partitions if len(p)]
        )
        assert ids.shape == (4, 10)  # frozen payload serves the exact route
    finally:
        model.setExactSearch(False)


def test_live_index_validation_errors(live_index):
    X, _Q, extra, _ = live_index
    model = _fit_ann(X)
    holder = model.mutable_index()
    with pytest.raises(ValueError, match="duplicate ids"):
        holder.add_items(extra[:2], np.array([99_000, 99_000]))
    with pytest.raises(ValueError, match="already present"):
        holder.add_items(extra[:1], np.array([0]))
    with pytest.raises(ValueError, match="items must be"):
        holder.add_items(extra[:, :4], np.array([99_001, 99_002])[: len(extra)])
    with pytest.raises(ValueError, match="items vs"):
        holder.add_items(extra[:3], np.array([99_003]))
    pq_model = ApproximateNearestNeighbors(
        k=4, algorithm="ivfpq",
        algoParams={"nlist": 4, "nprobe": 4, "M": 2, "n_bits": 4},
    ).setFeaturesCol("features").fit(
        DataFrame.from_numpy(X[:200, :16], feature_layout="array")
    )
    with pytest.raises(ValueError, match="IVF-Flat-only"):
        pq_model.mutable_index()


def test_served_ann_absorbs_mutations(live_index):
    """The live-index serving gate: an index serving through serve.ann
    absorbs add/delete/repack — every served batch reflects the mutation
    state at dispatch, recall holds at every step, tombstoned ids never
    surface, and the serving plane sees zero errors."""
    from spark_rapids_ml_tpu.ann import recall_at_k
    from spark_rapids_ml_tpu.serving import ModelRegistry

    X, Q, extra, _ = live_index
    n = X.shape[0]
    model = _fit_ann(X)
    holder = model.mutable_index()
    reg = ModelRegistry(max_batch=64, max_wait_ms=2)
    try:
        reg.register("live_ann", model)
        server = reg.get("live_ann")
        out0 = server.predict(Q)
        assert recall_at_k(
            out0["indices"], _exact_ids(X, np.arange(n), Q)
        ) >= 0.95

        holder.add_items(extra, np.arange(n, n + len(extra)))
        items = np.concatenate([X, extra])
        all_ids = np.arange(n + len(extra))
        out1 = server.predict(Q)
        assert recall_at_k(out1["indices"], _exact_ids(items, all_ids, Q)) >= 0.95
        # the added ids are genuinely reachable through serving
        assert np.isin(out1["indices"], np.arange(n, n + len(extra))).any()

        dele = np.arange(0, 200)
        holder.delete_items(dele)
        keep = np.ones(len(all_ids), bool)
        keep[dele] = False
        out2 = server.predict(Q)
        assert not np.isin(out2["indices"], dele).any()
        assert recall_at_k(
            out2["indices"], _exact_ids(items[keep], all_ids[keep], Q)
        ) >= 0.95

        holder.repack()
        before = profiling.counters("precompile.")
        out3 = server.predict(Q)
        delta = profiling.counter_deltas(before, "precompile.")
        assert delta.get("precompile.compile", 0) == 0, delta
        assert recall_at_k(
            out3["indices"], _exact_ids(items[keep], all_ids[keep], Q)
        ) >= 0.95
    finally:
        reg.shutdown(drain=False)


def test_mutable_freeze_persist_roundtrip(live_index, tmp_path):
    from spark_rapids_ml_tpu.core import load as core_load

    X, Q, extra, _ = live_index
    n = X.shape[0]
    model = _fit_ann(X)
    holder = model.mutable_index()
    holder.add_items(extra, np.arange(n, n + len(extra)))
    holder.delete_items(np.arange(0, 100))
    d_live, i_live = holder.search(Q, 10, 8)
    model.freeze_mutations()
    assert model.n_items == n + len(extra) - 100
    path = str(tmp_path / "mutated_ann")
    model.save(path)
    loaded = core_load(path)
    _, _, knn = loaded.kneighbors(DataFrame.from_numpy(Q, num_partitions=1))
    ids = np.concatenate(
        [np.stack(list(p["indices"])) for p in knn.partitions if len(p)]
    )
    # the persisted artifact reflects the mutations: no deleted ids, added
    # ids reachable, and the result set matches the live holder's ID SET
    # row for row (the repacked layout reorders positions, so distances
    # agree but tie order may differ — the id set is the contract)
    assert not np.isin(ids, np.arange(0, 100)).any()
    overlap = [
        np.intersect1d(a, b).size / a.shape[0] for a, b in zip(ids, i_live)
    ]
    assert float(np.mean(overlap)) >= 0.95, float(np.mean(overlap))


# -- 5. train-while-serve ----------------------------------------------------


def test_session_staleness_and_refresh_accounting(clustered_data):
    X, cid, k = clustered_data
    eng = KMeans(k=k, maxIter=5, seed=1).setFeaturesCol("features").streaming()
    session = StreamingSession(eng)
    session.partial_fit(X[cid == 0])
    assert session.staleness_rows == int((cid == 0).sum())
    assert session.staleness_seconds is None  # never refreshed
    model = session.refresh()  # no serving plane: snapshot + clock reset
    assert model.cluster_centers_ is not None
    assert session.staleness_rows == 0 and session.stats()["refreshes"] == 1
    session.partial_fit(X[cid == 1])
    assert session.staleness_rows == int((cid == 1).sum())
    assert session.staleness_seconds is not None
    with pytest.raises(ValueError, match="model name"):
        StreamingSession(eng, registry=object())


def test_session_ingest_refresh_every_rows(clustered_data):
    X, cid, k = clustered_data
    eng = KMeans(k=k, maxIter=5, seed=1).setFeaturesCol("features").streaming()
    session = StreamingSession(eng)
    chunks = [X[cid == c] for c in range(int(cid.max()) + 1)]
    session.ingest(iter(chunks), refresh_every_rows=512)
    assert session.stats()["refreshes"] >= 1
    assert session.rows_ingested == len(X)


def test_session_refresh_through_registry_swap(clustered_data):
    from spark_rapids_ml_tpu.serving import ModelRegistry

    X, cid, k = clustered_data
    eng = KMeans(k=k, maxIter=5, seed=1).setFeaturesCol("features").streaming()
    reg = ModelRegistry(max_batch=16, max_wait_ms=2)
    try:
        session = StreamingSession(eng, name="stream_km", registry=reg)
        session.partial_fit(X[cid == 0])
        session.refresh()  # first refresh registers
        assert "stream_km" in reg
        out = reg.get("stream_km").predict(X[:4])
        assert out["prediction"].shape == (4,)
        session.partial_fit(X[cid == 1])
        before = profiling.counters("precompile.")
        session.refresh()  # same-shape successor: swap from retained cache
        delta = profiling.counter_deltas(before, "precompile.")
        assert delta.get("precompile.compile", 0) == 0, delta
        assert profiling.counter("serving.stream_km.swaps") >= 1
        out = reg.get("stream_km").predict(X[:4])
        assert out["prediction"].shape == (4,)
    finally:
        reg.shutdown(drain=False)


def test_session_refresh_under_router_load_zero_client_errors(clustered_data):
    """The train-while-serve gate: a router serving a streamed model keeps
    answering a concurrent request burst across refresh() — every future
    resolves, zero client-visible errors, zero new compiles at the
    same-shape cut-over (the PR 11 swap guarantees, driven by the
    streaming plane)."""
    import threading

    from spark_rapids_ml_tpu.serving import Router

    X, cid, k = clustered_data
    eng = KMeans(k=k, maxIter=5, seed=1).setFeaturesCol("features").streaming()
    router = Router(max_batch=32, max_wait_ms=2)
    try:
        session = StreamingSession(
            eng, name="stream_rt", router=router, replicas=2
        )
        session.partial_fit(X[cid == 0])
        session.refresh()  # serve
        router.predict("stream_rt", X[:4])  # warm client path
        session.partial_fit(X[cid == 1])

        futures, submit_errors = [], []
        stop = threading.Event()

        def pump():
            import time

            i = 0
            while not stop.is_set() and len(futures) < 512:
                try:
                    futures.append(router.submit("stream_rt", X[i % 64 : i % 64 + 4]))
                except Exception as exc:  # typed shed/overload still counts as error here
                    submit_errors.append(exc)
                i += 4
                time.sleep(0.002)  # paced open loop: the gate is swap
                # correctness under live traffic, not an overload probe

        t = threading.Thread(target=pump, name="stream-load-pump")
        t.start()
        try:
            before = profiling.counters("precompile.")
            session.refresh()  # rolling swap under live load
            delta = profiling.counter_deltas(before, "precompile.")
        finally:
            stop.set()
            t.join(timeout=30)
        assert not t.is_alive()
        assert delta.get("precompile.compile", 0) == 0, delta
        assert not submit_errors, submit_errors[:3]
        assert futures
        for f in futures:
            out = f.result(timeout=60)  # every admitted request resolves
            assert out["prediction"].shape[0] > 0
    finally:
        router.shutdown(drain=False)
