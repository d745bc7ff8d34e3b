#
# Headline benchmark.  Default: cycle EVERY arm in one run — KMeans at the
# flagship shape (k=1000, maxIter=30, initMode=random on 1M x 3000 float32;
# /root/reference/python/benchmark/databricks/run_benchmark.sh:45-55, results
# in databricks/results/running_times.png: CPU 9526 s, GPU 82 s on 2x A10G
# => ~12,195 rows/s) as the headline, the other arms at driver-capturable
# shapes so every claimed multiple has a recorded artifact.
#
# Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
# headline arm (value = MEDIAN rows/sec of SRML_BENCH_REPEATS timed runs,
# default 3), plus "value_best"/"spread_pct"/"times_sec" for the protocol
# and an "arms" map carrying the same stats for every other arm (an arm
# that fails records an "error" string, and the run then exits non-zero).
# Runs on an accelerator only: a CPU backend is an error, never a smaller
# shape under the same metric names.
#
# SRML_BENCH_ALGO=<arm> runs that single arm (same JSON shape, no "arms"
# map).  Arms: kmeans|pca|linreg|logreg|logreg_sparse|knn|rf_clf|rf_reg|umap.
# Size knobs: SRML_BENCH_ROWS / SRML_BENCH_COLS / SRML_BENCH_K /
# SRML_BENCH_ITERS / SRML_BENCH_REPEATS.  Row counts default to a
# memory-safe fraction of the reference's 1M and are normalized to
# rows/sec, so vs_baseline stays comparable.
#

import gc
import glob
import json
import os
import statistics
import sys
import time

import numpy as np

REF_ROWS = 1_000_000
# reference GPU-cluster fit seconds on 1M x 3000 (running_times.png, 2x A10G)
REF_GPU_SECONDS = {
    "kmeans": 82.0,
    "pca": 37.0,
    "linreg": 32.0,   # ridge configuration (fastest GPU arm)
    "logreg": 69.0,
    "knn": 82.0,      # no published kNN bar; reuse the kmeans-scale bar as a floor
    "ann": 82.0,      # no published ANN bar either; same kmeans-scale floor
    "ann_pq": 82.0,   # the PQ tier shares the ANN floor (same workload)
    "rf_clf": 59.0,
    "rf_reg": 52.0,
    "umap": 82.0,     # no published UMAP bar; kmeans-scale floor like knn
    # no published tuning bar; scored against the linreg bar as a floor on
    # trained row-visits/sec (rows x candidates x (folds-1) per sweep)
    "tuning": 32.0,
    # BASELINE.json's "LogisticRegression multinomial on 1Bx100 sparse" has
    # no published time; scored against the dense logreg bar as a floor
    # (different shape: 100 sparse cols vs 3000 dense — see docs)
    "logreg_sparse": 69.0,
    # no published streaming bar (the reference cannot incrementally fit at
    # all); scored against the linreg bar as a conservative floor on
    # ingested rows/sec — streamed ingest re-pays chunk staging per chunk,
    # so beating the batch-fit bar at all is the story
    "streaming": 32.0,
}

# all arms, headline first; cycle-mode shape overrides keep the slower
# host-ingest arms inside a sane wall-clock (rows/sec stays comparable —
# that is the whole point of the normalized metric)
CYCLE_ARMS = [
    "kmeans", "pca", "linreg", "logreg", "logreg_sparse",
    "knn", "ann", "ann_pq", "rf_reg", "rf_clf", "umap", "tuning",
    "streaming",
]
CYCLE_OVERRIDES = {
    # 1M x 100 sparse (the BASELINE.json shape family, 4x smaller)
    "logreg_sparse": {"SRML_BENCH_ROWS": "1000000"},
}


def _sync(x) -> float:
    # the fetch forces execution and puts a host value inside the clock
    return float(np.asarray(x).ravel()[0])


def _timed_repeats(fn, repeats: int):
    """One warmup call (compiles are cached for the timed runs), then
    `repeats` timed calls.  Returns (cold_seconds, per-run seconds,
    per-run phase-time dicts): the cold time captures the first-fit
    experience (compiles + staging) the warm numbers amortize away.  The
    per-repeat phase breakdown (srml-scope) is what lets a spread be ATTRIBUTED to a
    phase instead of eyeballed (the kNN arm's standing 31% mystery)."""
    from spark_rapids_ml_tpu import profiling

    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    times, phases = [], []
    for _ in range(repeats):
        profiling.reset_phase_times()
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
        phases.append(profiling.phase_times())
    return cold, times, phases




def _device_padded_gen(mesh, rows, gen_fn, seed=42):
    """Generate an (n_pad, D) dataset ON DEVICE, row-sharded over the mesh,
    with a weight vector masking the pad rows.  Keeps multi-GB benchmark
    inputs off the host link (uploads are not part of the measured fit)."""
    import jax
    import numpy as np
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, data_sharding

    n_dev = mesh.shape[DATA_AXIS]
    n_pad = rows + (-rows) % n_dev
    Xs = jax.jit(lambda s: gen_fn(jax.random.PRNGKey(s), n_pad),
                 out_shardings=data_sharding(mesh))(seed)
    w = jax.device_put(
        np.r_[np.ones(rows, np.float32), np.zeros(n_pad - rows, np.float32)],
        data_sharding(mesh),
    )
    return Xs, w


def build_arm(algo: str, overrides):
    """Set up one benchmark arm; returns (fit_fn, label, rows) with all
    inputs staged (device-resident where the arm measures device compute).
    `overrides` shadow the SRML_BENCH_* env knobs in cycle mode."""
    import jax

    def _ov(key, default):
        return overrides.get(key) or os.environ.get(key) or default

    rows = int(_ov("SRML_BENCH_ROWS", 400_000))
    cols = int(_ov("SRML_BENCH_COLS", 3000))
    iters = int(_ov("SRML_BENCH_ITERS", 30))

    from spark_rapids_ml_tpu.parallel.mesh import data_sharding, get_mesh

    rng = np.random.default_rng(42)
    mesh = get_mesh()

    if algo == "kmeans":
        k = int(_ov("SRML_BENCH_K", 1000))
        from spark_rapids_ml_tpu import KMeans
        from spark_rapids_ml_tpu.dataframe import DataFrame

        # Unit-scale centers with unit noise: clusters overlap, so Lloyd
        # genuinely uses all maxIter iterations (wider separation converges
        # exactly in ~6 iterations and would overstate throughput vs the
        # reference's 30-iteration arm).  Data is generated on device and
        # enters through DataFrame.from_device — the timed region is the
        # PUBLIC estimator fit (validation, param translation, dispatch,
        # solver, attribute fetch), with ingest untimed the same way the
        # reference's GPU arm starts from plugin-cached device data.
        import jax.numpy as jnp

        def _gen(key, n_pad):
            kc, ka, kn = jax.random.split(key, 3)
            centers_true = jax.random.normal(kc, (k, cols), jnp.float32)
            assign = jax.random.randint(ka, (n_pad,), 0, k)
            return centers_true[assign] + jax.random.normal(
                kn, (n_pad, cols), jnp.float32
            )

        Xs, w = _device_padded_gen(mesh, rows, _gen)
        _sync(Xs.sum())
        df = DataFrame.from_device(Xs, n_rows=rows)
        est = KMeans(k=k, maxIter=iters, tol=0.0, initMode="random", seed=1)

        def fit():
            model = est.fit(df)
            return _sync(np.asarray(model.cluster_centers_))

        return fit, f"kmeans_fit_throughput_k{k}_d{cols}_iter{iters}", rows

    if algo == "pca":
        k = int(_ov("SRML_BENCH_K", 3))
        from spark_rapids_ml_tpu import PCA
        from spark_rapids_ml_tpu.dataframe import DataFrame

        # low-rank + noise generated on device (no 4.8 GB upload); timed
        # region = PCA().fit() at the public API (see kmeans arm note)
        import jax.numpy as jnp

        def _gen(key, n_pad):
            ka, kb, kn = jax.random.split(key, 3)
            A = jax.random.normal(ka, (n_pad, 32), jnp.float32)
            B = jax.random.normal(kb, (32, cols), jnp.float32)
            return A @ B + 0.1 * jax.random.normal(kn, (n_pad, cols), jnp.float32)

        Xs, w = _device_padded_gen(mesh, rows, _gen)
        _sync(Xs.sum())
        df = DataFrame.from_device(Xs, n_rows=rows)
        est = PCA(k=k)

        def fit():
            model = est.fit(df)
            return float(np.asarray(model.components_).ravel()[0])

        return fit, f"pca_fit_throughput_k{k}_d{cols}", rows

    if algo in ("linreg", "logreg"):
        # GLMs through the public estimator fit on a from_device frame —
        # data generated on device like every other arm
        import jax.numpy as jnp

        from spark_rapids_ml_tpu import LinearRegression, LogisticRegression
        from spark_rapids_ml_tpu.dataframe import DataFrame

        coef = rng.standard_normal(cols, dtype=np.float32)

        def _gen(key, n_pad):
            kx, kn = jax.random.split(key)
            X = jax.random.normal(kx, (n_pad, cols), jnp.float32)
            y = X @ jnp.asarray(coef) + 0.1 * jax.random.normal(kn, (n_pad,))
            if algo == "logreg":
                y = (y > 0).astype(jnp.float32)
            return X, y

        n_dev = mesh.devices.size
        n_pad = rows + (-rows) % n_dev
        Xs, ys = jax.jit(
            lambda s: _gen(jax.random.PRNGKey(s), n_pad),
            out_shardings=(data_sharding(mesh), data_sharding(mesh)),
        )(42)
        _sync(Xs.sum())
        y_host = np.asarray(ys)[:rows]  # labels are O(N) scalars
        df = DataFrame.from_device(Xs, y=y_host, n_rows=rows)
        if algo == "linreg":
            est = LinearRegression(regParam=1e-5, maxIter=iters)

            def fit():
                model = est.fit(df)
                return float(np.asarray(model.coefficients).ravel()[0])

            return fit, f"linreg_ridge_fit_throughput_d{cols}", rows
        est = LogisticRegression(regParam=1e-5, maxIter=max(iters, 200))

        def fit():
            model = est.fit(df)
            return float(np.asarray(model.coefficientMatrix).ravel()[0])

        return fit, f"logreg_fit_throughput_d{cols}_iter{max(iters, 200)}", rows

    if algo == "logreg_sparse":
        # BASELINE.json repro config scaled to one chip: multinomial logreg
        # on sparse rows (1Bx100 at 1% nnz in the reference's distributed
        # arm).  Timed region = LogisticRegression().fit() on a CSR-built
        # DataFrame — the ELL kernels underneath (ops/sparse.py) never
        # densify; the device-input cache keeps repeat ingest untimed.
        import scipy.sparse as sp

        from spark_rapids_ml_tpu import LogisticRegression
        from spark_rapids_ml_tpu.dataframe import DataFrame

        rows = int(_ov("SRML_BENCH_ROWS", 4_000_000))
        cols = int(_ov("SRML_BENCH_COLS", 100))
        n_classes = 4
        density = 0.01
        nnz_per_row = max(1, int(cols * density))
        idx = rng.integers(0, cols, size=(rows, nnz_per_row), dtype=np.int32)
        val = rng.standard_normal((rows, nnz_per_row), dtype=np.float32)
        W_true = rng.standard_normal((cols, n_classes), dtype=np.float32)
        # labels from the sparse logits
        logits = np.zeros((rows, n_classes), np.float32)
        for j in range(nnz_per_row):
            logits += val[:, j : j + 1] * W_true[idx[:, j]]
        y = logits.argmax(axis=1).astype(np.float32)
        indptr = np.arange(0, (rows + 1) * nnz_per_row, nnz_per_row, dtype=np.int64)
        csr = sp.csr_matrix(
            (val.ravel(), idx.ravel().astype(np.int64), indptr),
            shape=(rows, cols),
        )
        df = DataFrame.from_numpy(csr, y, num_partitions=1)
        est = LogisticRegression(
            regParam=1e-5, maxIter=max(iters, 100), tol=1e-6
        )

        def fit():
            model = est.fit(df)
            return float(np.asarray(model.coefficientMatrix).ravel()[0])

        return (
            fit,
            f"logreg_sparse_fit_throughput_d{cols}_nnz{nnz_per_row}",
            rows,
        )

    if algo == "knn":
        k = int(_ov("SRML_BENCH_K", 200))

        # brute-force kNN is FLOP-bound: 2*n_items*d FLOP per query row
        # (2.4 GFLOP at the 400k x 3000 default), so the per-chip query
        # budget is what keeps the arm's wall-clock sane.  16384 = two
        # dispatch blocks, so result fetches overlap the next block's
        # compute (the steady state a real serving loop runs in)
        n_query = int(_ov("SRML_BENCH_QUERIES", min(rows, 16384)))
        import jax.numpy as jnp

        from spark_rapids_ml_tpu import NearestNeighbors
        from spark_rapids_ml_tpu.dataframe import DataFrame
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        # Timed region = the PUBLIC model.kneighbors(query_df) call.  Index
        # + queries are GENERATED on device (a 4.9 GB index upload is
        # untimed setup) and installed in the model's own staging caches —
        # the state any user reaches after one prior kneighbors call on
        # the same model (the reference's GPU arm likewise queries data
        # already resident on the GPUs).  The host-side frames carry
        # placeholder feature blocks whose values are never read on the
        # cached path.
        n_dev = mesh.shape[DATA_AXIS]
        n_pad = rows + (-rows) % n_dev
        items_dev = jax.jit(
            lambda s: jax.random.normal(
                jax.random.PRNGKey(s), (n_pad, cols), jnp.float32
            ),
            out_shardings=data_sharding(mesh),
        )(0)
        Q_dev = jax.jit(
            lambda s: jax.random.normal(
                jax.random.PRNGKey(s), (n_query, cols), jnp.float32
            )
        )(7)
        _sync(items_dev.sum())
        _sync(Q_dev.sum())

        from spark_rapids_ml_tpu.core import extract_partition_features
        from spark_rapids_ml_tpu.ops.knn import prepare_items

        # zeros, NOT np.empty: uninitialized NaN pages fail the zero-copy
        # block guard's row equality (NaN != NaN) and would silently defeat
        # the seeded staging caches, re-uploading garbage inside the clock
        item_df = DataFrame.from_numpy(
            np.zeros((rows, cols), np.float32), num_partitions=1
        )
        query_df = DataFrame.from_numpy(
            np.zeros((n_query, cols), np.float32), num_partitions=1
        )
        est = NearestNeighbors(k=k)
        model = est.fit(item_df)
        # stage the device-resident index through prepare_items: the device
        # path tile-aligns it once, so the fused kernels never re-pad
        # (shuffle off — the data is i.i.d.-generated)
        prepared = prepare_items(
            items_dev[:rows], np.arange(rows, dtype=np.int64), mesh,
            shuffle=False,
        )
        q_block = extract_partition_features(
            query_df.partitions[0], "features", None, np.float32
        )
        model.seed_staging(
            prepared, query_blocks={0: (q_block, Q_dev)}, mesh=mesh
        )

        def fit():
            _, _, knn_df = model.kneighbors(query_df)
            d0 = knn_df.partitions[0]["distances"].iloc[0]
            return float(np.asarray(d0).ravel()[0])

        # throughput counts completed query rows
        return fit, f"knn_query_throughput_n{rows}_d{cols}_k{k}", n_query

    if algo in ("ann", "ann_pq"):
        # IVF probed query throughput (srml-ann / srml-pq).  Shape: the ANN
        # regime is many rows x embedding-scale dims (the exact arm's
        # 3000-col FLOP wall is exactly what IVF probing removes), so the
        # arm defaults to 400k x 256 clustered rows.  The timed region is
        # the PUBLIC model.kneighbors probed search with the index staged
        # and kernels warm (the warmup call); index build (quantizer +
        # assignment + layout + upload) lands in cold_sec.  recall@k vs
        # the exact path is measured by benchmark/bench_approximate_nn.py
        # on the same engine and asserted in tests (>= 0.95 flat, >= 0.9
        # refined pq) — the arms report throughput at the documented
        # operating points.  BOTH arms record index_bytes_per_item, so
        # every round's artifact carries the flat-vs-pq compression ratio.
        k = int(_ov("SRML_BENCH_K", 200))
        rows = int(_ov("SRML_BENCH_ROWS", 400_000))
        cols = int(_ov("SRML_BENCH_COLS", 256))
        n_query = int(_ov("SRML_BENCH_QUERIES", min(rows, 16384)))
        from spark_rapids_ml_tpu import ApproximateNearestNeighbors
        from spark_rapids_ml_tpu.ann.ivfflat import default_nlist, default_nprobe
        from spark_rapids_ml_tpu.dataframe import DataFrame

        nlist = int(_ov("SRML_BENCH_NLIST", default_nlist(rows)))
        nprobe = int(_ov("SRML_BENCH_NPROBE", default_nprobe(nlist)))
        # clustered items (the workload IVF exists for; uniform data would
        # spread every query's true neighbors over all lists and report a
        # recall no real embedding table exhibits)
        n_blobs = max(32, nlist)
        centers_h = 10.0 * rng.standard_normal((n_blobs, cols), dtype=np.float32)
        lab = rng.integers(0, n_blobs, size=rows)
        X_host = centers_h[lab] + rng.standard_normal(
            (rows, cols), dtype=np.float32
        )
        item_bdf = DataFrame.from_numpy(X_host)
        query_bdf = DataFrame.from_numpy(X_host[:n_query].copy())
        if algo == "ann_pq":
            from spark_rapids_ml_tpu.ann.pq import default_m_sub

            m_sub = int(_ov("SRML_BENCH_PQ_M", default_m_sub(cols)))
            est = ApproximateNearestNeighbors(
                k=k,
                algorithm="ivfpq",
                algoParams={"nlist": nlist, "nprobe": nprobe, "M": m_sub},
            ).setInputCol("features")
            label = (
                f"annpq_query_throughput_n{rows}_d{cols}_k{k}"
                f"_l{nlist}_p{nprobe}_m{m_sub}"
            )
        else:
            est = ApproximateNearestNeighbors(
                k=k, algoParams={"nlist": nlist, "nprobe": nprobe}
            ).setInputCol("features")
            label = f"ann_query_throughput_n{rows}_d{cols}_k{k}_l{nlist}_p{nprobe}"
        model = est.fit(item_bdf)  # index build: untimed setup (cold_sec
        # still captures staging + compiles via the warmup call)
        _ARM_EXTRAS[algo] = {
            "index_bytes_per_item": round(model.index_bytes_per_item(), 2)
        }

        def fit():
            _, _, knn_df = model.kneighbors(query_bdf)
            d0 = knn_df.partitions[0]["distances"].iloc[0]
            return float(np.asarray(d0).ravel()[0])

        return fit, label, n_query

    if algo in ("rf_clf", "rf_reg"):
        # the reference's published regressor arm: 30 trees, bins=128,
        # depth=6 on 1M x 3000 synthetic (run_benchmark.sh:113-122; GPU pair
        # 52 s).  Timed region = the PUBLIC RandomForest*.fit() on a
        # from_device frame — estimator preprocessing, device-side binning
        # sample + edges, MXU histogram growth (ops/forest_mxu), and the
        # forest-attribute fetch all inside the clock, matching what cuML's
        # fit() does after plugin-cached ingest.
        import jax.numpy as jnp

        from spark_rapids_ml_tpu import (
            RandomForestClassifier,
            RandomForestRegressor,
        )
        from spark_rapids_ml_tpu.dataframe import DataFrame

        rows = int(_ov("SRML_BENCH_ROWS", 400_000))
        if algo == "rf_reg":
            # 30 trees, depth 6, onethird feature subsets (Spark 'auto')
            est = RandomForestRegressor(
                numTrees=30, maxDepth=6, maxBins=128,
                featureSubsetStrategy="onethird", seed=3,
            )
            n_trees, depth = 30, 6
        else:
            # 50 trees, depth 13 (deep bucketed phase), sqrt subsets
            est = RandomForestClassifier(
                numTrees=50, maxDepth=13, maxBins=128,
                featureSubsetStrategy="sqrt", seed=3,
            )
            n_trees, depth = 50, 13
        n_informative = 10  # sklearn make_regression default, as the
        # reference's gen_data uses (gen_data.py)
        coef = np.zeros(cols, np.float32)
        coef[rng.choice(cols, n_informative, replace=False)] = (
            rng.standard_normal(n_informative).astype(np.float32)
        )

        def _gen(key, n_pad):
            kx, kn = jax.random.split(key)
            X = jax.random.normal(kx, (n_pad, cols), jnp.float32)
            y = X @ jnp.asarray(coef) + 0.1 * jax.random.normal(kn, (n_pad,))
            if algo == "rf_clf":
                y = (y > 0).astype(jnp.float32)
            return X, y

        Xs, ys = jax.jit(lambda s: _gen(jax.random.PRNGKey(s), rows))(42)
        _sync(Xs.sum())
        y_host = np.asarray(ys)  # labels are O(N) scalars, features stay put
        df = DataFrame.from_device(Xs, y=y_host, n_rows=rows)

        def fit():
            model = est.fit(df)
            return float(model.getNumTrees)

        return (
            fit,
            f"{algo}_fit_throughput_d{cols}_t{n_trees}_depth{depth}",
            rows,
        )

    if algo == "tuning":
        # srml-sweep: an m-candidate x k-fold CrossValidator through the
        # batched one-dispatch engine (docs/tuning_engine.md).  Host-facade
        # frame on purpose: the sweep's scoring pass reads host partitions
        # (from_device frames are fit-input-only), and the repeat runs ride
        # the device-input cache so the staging is untimed after warm-up —
        # what the clock holds is the sweep itself (masked-fold stats,
        # lane solves, fold scoring, winner refit).  Throughput counts
        # TRAINED ROW-VISITS: rows x candidates x (folds-1)/folds x folds.
        from spark_rapids_ml_tpu import LinearRegression
        from spark_rapids_ml_tpu.dataframe import DataFrame
        from spark_rapids_ml_tpu.evaluation import RegressionEvaluator
        from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

        rows = int(_ov("SRML_BENCH_ROWS", 100_000))
        cols = int(_ov("SRML_BENCH_COLS", 512))
        m = int(_ov("SRML_BENCH_GRID", 8))
        k_folds = int(_ov("SRML_BENCH_FOLDS", 3))
        X_host = rng.standard_normal((rows, cols), dtype=np.float32)
        coef = rng.standard_normal(cols, dtype=np.float32)
        y = (X_host @ coef + 0.1 * rng.standard_normal(rows)).astype(
            np.float32
        )
        df = DataFrame.from_numpy(X_host, y=y, num_partitions=4)
        grid = ParamGridBuilder().addGrid(
            LinearRegression.regParam, np.geomspace(1e-3, 1.0, m).tolist()
        ).build()

        def fit():
            cv = CrossValidator(
                estimator=LinearRegression(standardization=False),
                estimatorParamMaps=grid,
                evaluator=RegressionEvaluator(),
                numFolds=k_folds,
                seed=7,
            )
            return float(cv.fit(df).avgMetrics[0])

        return (
            fit,
            f"tuning_sweep_throughput_n{rows}_d{cols}_m{m}_k{k_folds}",
            rows * m * (k_folds - 1),
        )

    if algo == "umap":
        from spark_rapids_ml_tpu import UMAP
        from spark_rapids_ml_tpu.dataframe import DataFrame

        rows = int(_ov("SRML_BENCH_ROWS", 50_000))
        cols = int(_ov("SRML_BENCH_COLS", 128))
        X_host = rng.standard_normal((rows, cols), dtype=np.float32)
        df = DataFrame.from_numpy(X_host, num_partitions=8)
        est = UMAP(n_components=2, n_neighbors=15, n_epochs=200, random_state=1)

        def fit():
            model = est.fit(df)
            return float(np.asarray(model.embedding_).ravel()[0])

        return fit, f"umap_fit_throughput_n{rows}_d{cols}", rows

    if algo == "streaming":
        # srml-stream: steady-state partial_fit ingest through the linreg
        # streaming engine (docs/streaming.md).  The timed region is the
        # full chunked ingest + finalize of a fresh engine per run — chunk
        # staging IS the workload here (a streaming system re-pays it per
        # chunk by construction), while the bucket compile lands in the
        # warm-up run like every other arm's cold cost.  Throughput counts
        # ingested rows/sec; benchmark/bench_streaming.py carries the
        # refresh-blip and refit-cost detail numbers.
        from spark_rapids_ml_tpu import LinearRegression

        rows = int(_ov("SRML_BENCH_ROWS", 400_000))
        cols = int(_ov("SRML_BENCH_COLS", 512))
        chunk = int(_ov("SRML_BENCH_CHUNK", 8192))
        X_host = rng.standard_normal((rows, cols), dtype=np.float32)
        coef = rng.standard_normal(cols, dtype=np.float32)
        y = (X_host @ coef + 0.1 * rng.standard_normal(rows)).astype(
            np.float64
        )
        bounds = list(range(0, rows, chunk))

        def fit():
            eng = LinearRegression(standardization=False).streaming()
            for s in bounds:
                eng.partial_fit(X_host[s : s + chunk], y=y[s : s + chunk])
            return float(eng.finalize().coef_[0])

        return (
            fit,
            f"streaming_ingest_throughput_n{rows}_d{cols}_c{chunk}",
            rows,
        )

    raise SystemExit(f"unknown SRML_BENCH_ALGO={algo}")


# measurement assumptions that must travel WITH the numbers (advisor
# round-4: the caveat lived only in comments, so cross-framework
# comparisons could silently drop it)
ARM_NOTES = {
    "ann": (
        "probed IVF-Flat search at the documented operating point "
        "(nlist/nprobe in the metric label) on clustered data; index build "
        "is untimed setup; recall@k vs the exact path is gated >= 0.95 in "
        "tests/test_ann_engine.py and reported per-run by "
        "benchmark/bench_approximate_nn.py; index_bytes_per_item in the "
        "record pairs with the ann_pq arm's for the compression ratio"
    ),
    "ann_pq": (
        "probed IVF-PQ ADC search + f32 refine at the documented operating "
        "point (nlist/nprobe/M in the metric label) on the SAME clustered "
        "shape as the ann arm; refined recall@10 >= 0.9 is gated in "
        "tests/test_pq_engine.py and reported per-run by "
        "benchmark/bench_approximate_nn.py --algorithm ivfpq; "
        "index_bytes_per_item vs the ann arm is the compression headline"
    ),
    "knn": (
        "timed region is model.kneighbors with the item index and query "
        "upload pre-seeded in the model staging caches (the steady state "
        "after one prior call on the same model); query/index ingest is "
        "NOT in the clock"
    ),
    "streaming": (
        "steady-state chunked partial_fit ingest + finalize through the "
        "linreg streaming engine; chunk staging stays IN the clock (a "
        "streaming system re-pays it per chunk by construction); the "
        "bucket compile lands in the untimed warm-up; refresh-blip and "
        "batch-refit comparison numbers come from "
        "benchmark/bench_streaming.py"
    ),
}


# Per-arm minimum timed repeats: the kNN arm's short timed region (two
# dispatch blocks) showed a 31.4% max-min spread at 3 repeats (BENCH_r05) —
# more samples tighten the median without touching the timed region itself.  Applied as a floor so SRML_BENCH_REPEATS can
# still raise everything globally.
ARM_MIN_REPEATS = {"knn": 7, "ann": 7, "ann_pq": 7}  # short timed regions

# per-arm extra record fields set by build_arm (e.g. the ann arms'
# index_bytes_per_item) and merged into the stats dict by run_arm — the
# timed metric stays ONE number per arm; extras ride the artifact
_ARM_EXTRAS: dict = {}


def run_arm(algo: str, overrides, repeats: int):
    """Build, warm up, and time one arm; returns its stats dict.  cold_sec
    records the first (warmup) call — compiles + device staging included —
    so the first-fit experience is a captured artifact, not a claim."""
    from spark_rapids_ml_tpu.parallel.exchange import byte_totals

    repeats = max(repeats, ARM_MIN_REPEATS.get(algo, 1))
    _x0_total, x0_per = byte_totals()
    fit, label, rows = build_arm(algo, overrides)
    cold, times, phases = _timed_repeats(fit, repeats)
    med, best = statistics.median(times), min(times)
    value = rows / med
    baseline = REF_ROWS / REF_GPU_SECONDS.get(algo, REF_GPU_SECONDS["kmeans"])
    out = {
        "metric": label,
        "value": round(value, 1),
        "unit": "rows/sec",
        "vs_baseline": round(value / baseline, 3),
        "value_best": round(rows / best, 1),
        "spread_pct": round(100.0 * (max(times) - best) / med, 1),
        "times_sec": [round(t, 3) for t in times],
        "cold_sec": round(cold, 3),
        "repeats": repeats,  # can exceed the global knob (ARM_MIN_REPEATS)
        # backend tag (standings.py keys older CPU-fallback captures on it)
        "backend": __import__("jax").devices()[0].platform,
    }
    # per-arm exchange byte totals (parallel/exchange section counters):
    # host sections count per call, device sections per compiled geometry
    # (trace time), so the number captures what ONE steady-state dispatch
    # set moves — which is exactly where the all-gather -> ring-permute
    # candidate-traffic reduction (~n_dev x) shows up.  standings.py
    # renders the total as the kNN arm's `bytes moved` column.
    x1_total, x1_per = byte_totals()
    sections = {
        name: v - x0_per.get(name, 0)
        for name, v in sorted(x1_per.items())
        if v - x0_per.get(name, 0) > 0
    }
    out["exchange_bytes"] = int(sum(sections.values()))
    if sections:
        out["exchange_sections"] = sections
    # per-repeat phase breakdown + the phase the spread lives in (srml-scope
    # satellites: standings.py renders the attribution next to the ⚠ flag)
    from spark_rapids_ml_tpu import profiling

    attribution = profiling.spread_attribution(phases, med)
    if attribution:
        out["spread_attribution"] = attribution
        out["spread_phase"] = next(iter(attribution))
    if phases and phases[-1]:
        out["phase_times_per_repeat"] = [
            {k: round(v, 4) for k, v in sorted(p.items())} for p in phases
        ]
    if algo in ARM_NOTES:
        out["notes"] = ARM_NOTES[algo]
    out.update(_ARM_EXTRAS.pop(algo, {}))
    return out


def _release_arm_state():
    """Free device buffers between arms (the fit closures pin the staged
    datasets; the estimator arms also pin the device-input cache slot).
    After the cache clear + gc, any still-live device array of arm scale is
    a leak — delete it outright (nothing legitimate survives between arms)
    and report it, then sync the stream so queued deallocations land before
    the next arm's multi-GB staging races them (run r4a: rf/umap arms
    RESOURCE_EXHAUSTED behind the knn arm's lingering 4.8 GB)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.core import clear_fit_cache

    clear_fit_cache()
    gc.collect()
    leaked = [a for a in jax.live_arrays() if a.nbytes >= (64 << 20)]
    if leaked:
        total = sum(a.nbytes for a in leaked) / 2**30
        print(
            f"[bench] releasing {len(leaked)} leaked device buffers "
            f"({total:.2f} GB)",
            file=sys.stderr,
        )
        for a in leaked:
            a.delete()
    _sync(jnp.zeros(1))  # flush pending deallocations


def main() -> None:
    import jax

    from spark_rapids_ml_tpu.ops.precompile import ensure_compile_cache

    platform = jax.devices()[0].platform
    if platform == "cpu":
        raise SystemExit(
            "bench.py measures an accelerator; jax found only the cpu "
            "backend (no smaller CPU shape is run under these metric names)"
        )
    ensure_compile_cache()

    repeats = max(1, int(os.environ.get("SRML_BENCH_REPEATS", "3")))
    algo = os.environ.get("SRML_BENCH_ALGO", "")

    if algo and algo != "all":
        print(json.dumps(run_arm(algo, {}, repeats)))
        return

    # cycle mode (the default): headline kmeans first, then every other arm
    # — one captured artifact per claimed multiple (a failing arm records
    # its error, the other arms still run, and the exit code is non-zero)
    results = {}
    for arm in CYCLE_ARMS:
        try:
            results[arm] = run_arm(arm, CYCLE_OVERRIDES.get(arm, {}), repeats)
        except Exception as e:  # noqa: BLE001 — any arm failure is recorded
            results[arm] = {"error": f"{type(e).__name__}: {e}"}
        _release_arm_state()
    headline = dict(results.get("kmeans") or {"error": "headline arm failed"})
    headline["repeats"] = repeats
    headline["arms"] = {a: r for a, r in results.items() if a != "kmeans"}
    # prior-round pointer: the newest BENCH_r*.json present when THIS run
    # started is what this artifact should be diffed against —
    # benchmark/standings.py renders the Δ% regression column from it, so
    # the bench trajectory is itself observable (srml-watch satellite)
    prior = sorted(
        glob.glob(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r*.json"))
    )
    headline["prev_round"] = os.path.basename(prior[-1]) if prior else None
    print(json.dumps(headline))
    failed = sorted(a for a, r in results.items() if "error" in r)
    if failed:
        print(f"[bench] arms failed: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
