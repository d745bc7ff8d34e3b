#!/usr/bin/env python3
#
# chip_smoke.py — the quickest proof that the system still starts on the chip.
#
# Drives the main path once through the entry points a user calls, at the
# reference's published width (upstream's run_benchmark.sh:45-55:
# KMeans k=1000, initMode="random", tol=0.0 on 3000 float32 columns):
#
#   stage 0  device: what jax found; anything but a TPU stops the run
#   stage 1  fit -> transform -> write/core.load at the published width
#   stage 2  serve: a ModelServer answers requests on that model
#   stage 3  every Pallas kernel the library routes to on a TPU, through its
#            public route at a production shape, against the XLA formulation
#            the same module falls back to off-TPU (and the forest's prefix
#            sums, an MXU product, to the bit against whole-number arithmetic;
#            and the Gram scan's one triangle against the whole product)
#   stage 4  (>= 4 devices) the same path over the whole mesh, and KMeans on a
#            feature-major table sharded over it against its one-chip fit
#
# One process (a chip belongs to one process), no network, no git.  Data is
# random, made from --seed.  Any failing stage raises and the run ends
# non-zero; nothing is caught and carried past.  The last two stdout lines are
# JSON: first {"stages": {...}, "compile": {...}, "wall_s": ...}, what each
# stage showed; then, LAST, the result and nothing else:
# {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
# The result line is printed only when every stage passed on a TPU.
#
# --rehearsal runs the same control flow at toy sizes on whatever backend jax
# has (the CPU included), without requiring the TPU routes: a way to debug
# this script before spending chip time.  It is never the default and its
# output says so.
#

import argparse
import gc
import inspect
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

SIZES = {
    "rows_per_chip": 400_000, "cols": 3000, "k": 1000, "fit_iters": 5,
    "host_rows": 65_536, "host_parts": 8, "host_iters": 2, "heldout": 8192,
    "requests": 16,
    "md_n": 131_072, "md_d": 32, "md_k": 16_384,
    "rf_rows": 65_536, "rf_trees": 4, "rf_depth": 6, "rf_bins": 128,
    "reg_features": 1024,
    "gather_rows": 401_408, "gather_subsets": (54, 1000), "gather_odd_rows": 197 * 2048,
    "knn_items": 65_536, "knn_queries": 8192, "knn_k": 200, "knn_ref": 1024,
    "pq_rows": 262_144, "pq_d": 256, "pq_m": 32, "pq_queries": 1024,
    "pq_k": 10,
    "cd_rows": 32_768, "cd_widths": (8, 100, 3000, 16_000), "cd_sweeps": 5,
    "cd_reg": 0.012,
    "gram_rows": 40_000, "gram_widths": (3000, 700),
    "ell_rows": 1_000_000, "ell_kernel_rows": 20 * 2048 + 1,
    "tall_rows_per_chip": 6_250_000, "tall_cols": 30, "tall_k": 20,
    "tall_kernel_rows": 3_000_000 + 77,
}
TOY_SIZES = {
    "rows_per_chip": 4096, "cols": 64, "k": 16, "fit_iters": 5,
    "host_rows": 2048, "host_parts": 8, "host_iters": 2, "heldout": 4096,
    "requests": 16,
    "md_n": 2048, "md_d": 32, "md_k": 1024,
    "rf_rows": 4096, "rf_trees": 4, "rf_depth": 4, "rf_bins": 128,
    "reg_features": 32,
    "gather_rows": 4096, "gather_subsets": (5, 40), "gather_odd_rows": 3 * 2048,
    "knn_items": 4096, "knn_queries": 256, "knn_k": 10, "knn_ref": 128,
    "pq_rows": 8192, "pq_d": 32, "pq_m": 8, "pq_queries": 128,
    "pq_k": 10,
    "cd_rows": 2048, "cd_widths": (8, 100, 200), "cd_sweeps": 5,
    "cd_reg": 0.05,
    "gram_rows": 1000, "gram_widths": (700, 40),
    "ell_rows": 4096, "ell_kernel_rows": 2 * 256 + 1,
    "tall_rows_per_chip": 5000, "tall_cols": 30, "tall_k": 20,
    "tall_kernel_rows": 3 * 1024 + 77,
}

REHEARSAL = False
S = SIZES


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def require_route(name, got, want):
    """The stage fails unless the TPU route ran (a rehearsal has none)."""
    log(f"route {name}: {got}")
    if not REHEARSAL:
        check(got == want, f"{name}: route {got!r}, expected {want!r}")


def release():
    """Drop device buffers a finished stage pinned."""
    from spark_rapids_ml_tpu.core import clear_fit_cache

    clear_fit_cache()
    gc.collect()


def cache_file_count(path):
    return sum(len(files) for _, _, files in os.walk(path))


# -- seeded data --------------------------------------------------------------


def blob_rows(mesh, rows_per_dev, centers, seed, n_chunks=16):
    """(n_dev * rows_per_dev, cols) blobs generated ON the mesh, row-sharded:
    each device fills its shard chunk by chunk, so the peak is the shard plus
    one chunk (a 400k x 3000 shard is 4.8 GB of a 16 GB chip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    k, cols = centers.shape
    while rows_per_dev % n_chunks:
        n_chunks //= 2
    chunk = rows_per_dev // n_chunks

    def per_device(c):
        dev_key = jax.random.fold_in(
            jax.random.key(seed), jax.lax.axis_index(DATA_AXIS)
        )

        def one(i):
            ka, kn = jax.random.split(jax.random.fold_in(dev_key, i))
            assign = jax.random.randint(ka, (chunk,), 0, k)
            return c[assign] + jax.random.normal(kn, (chunk, cols), jnp.float32)

        return jax.lax.map(one, jnp.arange(n_chunks)).reshape(
            rows_per_dev, cols
        )

    gen = jax.jit(
        jax.shard_map(
            per_device, mesh=mesh, in_specs=P(), out_specs=P(DATA_AXIS),
            check_vma=False,
        )
    )
    return gen(centers)


def host_blobs(seed, rows, k_true, cols):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((k_true, cols), dtype=np.float32)
    X = rng.standard_normal((rows, cols), dtype=np.float32)
    X += centers[rng.integers(0, k_true, size=rows)]
    return X


def host_embeddings(seed, rows, cols, latent=16, blobs=256):
    """Rows of low intrinsic dimension (a clustered latent space projected
    to `cols`, plus a little noise): the structure retrieval indexes are
    built for.  Isotropic noise at full width leaves every neighbor of a
    blob equidistant, and no quantizer can rank those."""
    rng = np.random.default_rng(seed)
    centers = 3.0 * rng.standard_normal((blobs, latent), dtype=np.float32)
    Z = centers[rng.integers(0, blobs, size=rows)]
    Z += rng.standard_normal((rows, latent), dtype=np.float32)
    A = rng.standard_normal((latent, cols), dtype=np.float32)
    X = Z @ (A / np.float32(np.sqrt(latent)))
    X += np.float32(0.05) * rng.standard_normal((rows, cols), dtype=np.float32)
    return X


# -- stage 0 ------------------------------------------------------------------


def stage_device():
    import importlib.metadata

    import jax
    import jaxlib

    from spark_rapids_ml_tpu import native
    from spark_rapids_ml_tpu.ops.precompile import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    info = {
        "stage": 0,
        "rehearsal": REHEARSAL,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "bytes_limit": stats.get("bytes_limit"),
        "jax_compilation_cache_dir": cache_dir,
        "cache_files_at_start": cache_file_count(cache_dir),
        "native": "available" if native.available() else "absent",
    }
    print(json.dumps(info), flush=True)
    if dev.platform != "tpu" and not REHEARSAL:
        sys.exit(
            f"chip_smoke: jax found no TPU (platform {dev.platform!r}); "
            "this script proves the chip path and does not fall back"
        )
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }, cache_dir


# -- stage 1 / stage 4: fit at the published width ---------------------------


def fit_device_leg(seed, n_dev):
    """KMeans on rows generated on device, entered through
    DataFrame.from_device, over the first n_dev devices.  Returns the model,
    the true centers and per-shard placement facts."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    mesh = get_mesh(n_dev)
    centers = jax.random.normal(
        jax.random.key(seed), (S["k"], S["cols"]), jnp.float32
    )
    X = blob_rows(mesh, S["rows_per_chip"], centers, seed + 1)
    X.block_until_ready()
    shards = X.addressable_shards
    shard_bytes = shards[0].data.nbytes
    placement = {
        "shards": len(shards),
        "devices": sorted(s.device.id for s in shards),
        "shard_bytes": shard_bytes,
    }
    check(
        len({s.device.id for s in shards}) == n_dev,
        f"X is on {placement['devices']}, expected one shard per {n_dev} devices",
    )
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in mesh.devices.flat
    ]
    placement["bytes_in_use"] = in_use
    if all(b is not None for b in in_use):
        check(
            min(in_use) >= shard_bytes,
            f"a device holds less than its {shard_bytes}-byte shard: {in_use}",
        )
    df = DataFrame.from_device(X, n_rows=X.shape[0])
    est = KMeans(
        k=S["k"], initMode="random", tol=0.0, maxIter=S["fit_iters"],
        seed=seed, num_workers=n_dev,
    )
    t0 = time.perf_counter()
    model = est.fit(df)
    wall = time.perf_counter() - t0
    check(
        np.isfinite(model.cluster_centers_).all()
        and model.cluster_centers_.shape == (S["k"], S["cols"]),
        "device-leg centers are not finite (k, cols)",
    )
    check(np.isfinite(model.inertia_), "device-leg inertia is not finite")
    log(
        f"fit[{n_dev} dev] {X.shape[0]} x {S['cols']} k={S['k']}: "
        f"iterations run {model.n_iter_}, inertia {model.inertia_:.6g}, "
        f"wall {wall:.1f}s (first call: compile included)"
    )
    result = {
        "rows": int(X.shape[0]), "n_iter": model.n_iter_,
        "inertia": model.inertia_, "wall_s": round(wall, 2), **placement,
    }
    del X, df
    release()
    return model, centers, result


def fit_host_leg(df, seed, n_dev):
    """KMeans from host partitions: the block-ingest path, staged a partition
    at a time (core.stage_dense_batches)."""
    from spark_rapids_ml_tpu import KMeans

    est = KMeans(
        k=S["k"], initMode="random", tol=0.0, maxIter=S["host_iters"],
        seed=seed, num_workers=n_dev,
    )
    model = est.fit(df)
    check(
        np.isfinite(model.cluster_centers_).all()
        and np.isfinite(model.inertia_),
        "host-leg centers/inertia are not finite",
    )
    log(
        f"fit[{n_dev} dev] host {S['host_rows']} x {S['cols']} in "
        f"{S['host_parts']} partitions: iterations run {model.n_iter_}"
    )
    return model


def ingest_batches(seed):
    """Four Arrow-made pandas batches (the last one short) through the view
    rule and the batch-wise staging: every batch ONE view of its buffer, the
    device table the rows in order, bit for bit.  A batch here is larger than
    a staged piece, so the cut is driven too."""
    import pyarrow as pa

    from spark_rapids_ml_tpu import KMeans, profiling
    from spark_rapids_ml_tpu.core import stage_dense_batches
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    step, cols = S["host_rows"] // 4, S["cols"]
    rows = 3 * step + step // 2
    X = host_blobs(seed + 4, rows, S["k"], cols)
    parts = []
    for lo in range(0, rows, step):
        block = X[lo:lo + step]
        offsets = pa.array(np.arange(0, (len(block) + 1) * cols, cols, dtype=np.int32))
        lists = pa.ListArray.from_arrays(offsets, pa.array(block.reshape(-1)))
        parts.append(pa.table({"features": lists}).to_pandas())
    before = profiling.counters()
    feats, _labels, _weights, _dtype = KMeans()._pre_process_data(DataFrame(parts))
    t0 = time.perf_counter()
    table = stage_dense_batches(feats, rows, get_mesh(1))
    wall = time.perf_counter() - t0
    moved = profiling.counter_deltas(before)
    check(moved.get("ingest.view_batches") == 4 and not moved.get("ingest.stacked_batches"),
          f"Arrow-made batches were not taken as views: {moved}")
    check(moved.get("ingest.rows") == rows and moved.get("ingest.h2d_bytes") == X.nbytes,
          f"staging counted {moved}, sent {rows} rows of {X.nbytes} bytes")
    check(not any(k.startswith("ingest.link_") for k in moved), f"a staging outside a fit job kept a landing journal: {moved}")
    got = np.asarray(table)
    check(got.shape == X.shape and got.tobytes() == X.tobytes(), "the staged table is not the batches' rows in order")
    log(f"ingest: 4 Arrow-made batches, {rows} x {cols}, staged in {wall:.3f} s ({X.nbytes / 1e9 / wall:.2f} GB/s)")
    return {"rows": rows, "batches": 4, "view_batches": int(moved["ingest.view_batches"]), "wall_s": round(wall, 3)}


def stage_fit(seed):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import core
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    model, centers, result = fit_device_leg(seed, 1)
    df_host = DataFrame.from_numpy(
        host_blobs(seed + 2, S["host_rows"], S["k"], S["cols"]),
        num_partitions=S["host_parts"],
    )
    host_model = fit_host_leg(df_host, seed, 1)
    result["host_n_iter"] = host_model.n_iter_
    release()
    result["ingest"] = ingest_batches(seed)
    release()

    # transform on held-out rows against a plain jax.numpy nearest center
    held = np.asarray(blob_rows(get_mesh(1), S["heldout"], centers, seed + 3))
    pred = model.transform(DataFrame.from_numpy(held)).toPandas()[
        "prediction"
    ].to_numpy()
    C = jnp.asarray(model.cluster_centers_, jnp.float32)
    Xh = jnp.asarray(held)
    with jax.default_matmul_precision("highest"):
        d2 = (
            (Xh * Xh).sum(axis=1)[:, None]
            - 2.0 * (Xh @ C.T)
            + (C * C).sum(axis=1)[None, :]
        )
        ref = np.asarray(jnp.argmin(d2, axis=1))
    agree = float((pred == ref).mean())
    log(f"transform vs plain reference on {len(held)} rows: {agree:.5f} equal")
    check(agree >= 0.999, f"transform agrees with the reference on {agree}")
    result["transform_agreement"] = agree

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "kmeans_model")
        model.write().save(path)
        loaded = core.load(path)
    pred2 = loaded.transform(DataFrame.from_numpy(held)).toPandas()[
        "prediction"
    ].to_numpy()
    check(np.array_equal(pred, pred2), "loaded model transforms differently")
    check(
        np.array_equal(loaded.cluster_centers_, model.cluster_centers_),
        "centers changed through write/load",
    )
    result["save_load_bitwise"] = True
    made = {
        "model": model, "held": held, "pred": pred,
        "df_host": df_host, "host_model": host_model,
    }
    return made, result


# -- stage 2: serve -----------------------------------------------------------


def stage_serve(made, seed):
    from spark_rapids_ml_tpu import profiling
    from spark_rapids_ml_tpu.serving import ModelServer

    model, held, pred = made["model"], made["held"], made["pred"]
    rng = np.random.default_rng(seed + 4)
    sizes = [1, 256] + [int(n) for n in rng.integers(1, 257, S["requests"] - 2)]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    check(bounds[-1] <= len(held), "held-out set too small for the requests")
    server = ModelServer("smoke", model)
    try:
        compiles0 = profiling.counter("precompile.compile")
        futures = [
            server.submit(held[lo:hi]) for lo, hi in zip(bounds[:-1], bounds[1:])
        ]
        answers = [f.result(timeout=300) for f in futures]
        server.assert_steady_state()
        moved = profiling.counter("precompile.compile") - compiles0
    finally:
        server.shutdown()
    for (lo, hi), ans in zip(zip(bounds[:-1], bounds[1:]), answers):
        check(
            np.array_equal(np.asarray(ans["prediction"]), pred[lo:hi]),
            f"served rows {lo}:{hi} differ from model.transform",
        )
    check(moved == 0, f"{moved} compiles after warm-up")
    for t in threading.enumerate():
        if t.name.startswith("srml-serve-"):
            t.join(timeout=30)
            check(not t.is_alive(), f"server thread {t.name} outlived shutdown")
    log(f"served {len(sizes)} requests of {min(sizes)}-{max(sizes)} rows")
    return {"requests": len(sizes), "steady_compiles": int(moved)}


# -- stage 3: kernels through their public routes ----------------------------


def kernel_min_dist(seed):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import KMeansModel
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops import pallas_tpu

    n, d, k = S["md_n"], S["md_d"], S["md_k"]
    rng = np.random.default_rng(seed + 10)
    X = rng.standard_normal((n, d), dtype=np.float32)
    C = rng.standard_normal((k, d), dtype=np.float32)
    require_route(
        "min_dist_argmin", pallas_tpu.min_dist_route(n, d, k, 4), "pallas"
    )
    model = KMeansModel(cluster_centers_=C, n_cols=d, dtype="float32")
    got = model.transform(DataFrame.from_numpy(X)).toPandas()[
        "prediction"
    ].to_numpy()
    # the XLA formulation, a row block at a time: its (rows, k) distance
    # matrix is 8.6 GB at the full shape
    xla = jax.jit(pallas_tpu._min_dist_argmin_xla)
    Cd = jnp.asarray(C)
    c_norm = (Cd * Cd).sum(axis=1)
    want = []
    for lo in range(0, n, 32_768):
        xb = jnp.asarray(X[lo : lo + 32_768])
        want.append(np.asarray(xla(xb, Cd, (xb * xb).sum(axis=1), c_norm)[1]))
    agree = float((got == np.concatenate(want)).mean())
    check(agree >= 0.999, f"min_dist_argmin agrees with XLA on {agree}")
    return {"shape": [n, d, k], "agreement": agree}


TOP = 2**24 - 1      # the largest whole number a float32 holds with its neighbours


def hard_histograms(rng, rows, n_bins):
    """(rows, n_bins) whole numbers >= 0 whose running sums stay <= 2^24 - 1,
    built to break a prefix sum that rounds: small counts; one bin holding
    2^24 - 1 (the first row's first bin, the second's last); random
    compositions of it; and rows of six large parts among small odd counts
    that add up to it, so that bfloat16 ROUNDINGS of the parts add up, half
    the time, to an odd number above 2^24, which no float32 holds."""
    x = rng.poisson(1.0, (rows, n_bins)).astype(np.int64)
    kind = np.arange(rows) % 4

    def compose(total, parts):      # (len(total), parts) whole numbers that add up to total
        cuts = np.sort(
            (rng.random((total.size, parts - 1)) * (total[:, None] + 1)).astype(np.int64), axis=1
        )
        ends = np.concatenate(
            [np.zeros((total.size, 1), np.int64), cuts, total[:, None]], axis=1
        )
        return np.diff(ends, axis=1)

    one = np.flatnonzero(kind == 1)
    x[one] = 0
    x[one, rng.integers(0, n_bins, one.size)] = TOP
    x[one[:2]] = 0
    x[one[0], 0] = x[one[1], n_bins - 1] = TOP
    comp = np.flatnonzero(kind == 2)
    x[comp] = compose(np.full(comp.size, TOP), n_bins)
    over = np.flatnonzero(kind == 3)
    x[over] = rng.integers(0, 256, (over.size, n_bins))
    big = np.argsort(rng.random((over.size, n_bins)), axis=1)[:, :6]
    x[over[:, None], big] = 0
    x[over[:, None], big] = compose(TOP - x[over].sum(axis=1), 6)
    x[over[0]] = 0      # bfloat16 rounds these to 2^23 + 2^15, 2^23 - 2^15, 3
    x[over[0], :3] = (2**23 + 2**15 + 1, 2**23 - 2**15 - 5, 3)
    assert x.min() >= 0 and x.cumsum(axis=1).max() == TOP
    return x


def garbage_past(rows, live, bins, seed):
    """`rows` (f_pad, n) int8 with the rows from `live` on replaced by bins that
    are never 0: what a histogram kernel that still read a block's padding
    rows would turn into sums (a zeroed row and a skipped row look alike)."""
    import jax
    import jax.numpy as jnp

    garbage = jax.random.randint(jax.random.key(seed), rows.shape, 1, bins).astype(jnp.int8)
    return jnp.where((jnp.arange(rows.shape[0]) < live)[:, None], rows, garbage)


def kernel_forest(seed):
    """Binning kernel, histogram kernel, the split search's prefix sums and
    the MXU builder on one dataset."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import RandomForestClassifier
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops import forest, forest_hist, forest_mxu
    from spark_rapids_ml_tpu.ops.precompile import executable_cache_stats
    from spark_rapids_ml_tpu.parallel.mesh import data_sharding, get_mesh

    n, d, bins = S["rf_rows"], S["cols"], S["rf_bins"]
    mesh = get_mesh(1)
    key = jax.random.key(seed + 20)
    X = jax.device_put(
        jax.random.normal(key, (n, d), jnp.float32), data_sharding(mesh)
    )
    informative = 8
    y = (X[:, :informative].sum(axis=1) > 0).astype(jnp.float32)
    out = {}

    # feature binning: the fused kernel against the XLA compare-accumulate
    edges = jnp.asarray(
        forest.compute_bin_edges(np.asarray(X[:4096]), bins), jnp.float32
    )
    require_route(
        "bin_features_feature_major",
        forest.bin_route(X, edges.shape[1]), "pallas",
    )
    n_pad = -(-n // forest_hist._ROW_TILE) * forest_hist._ROW_TILE
    bins_fm = forest.bin_features_feature_major(X, edges, n_pad=n_pad)
    want = forest._bin_features_fm_xla(X, edges, 65536, n_pad)
    check(
        bins_fm.shape == want.shape and bool((bins_fm == want).all()),
        "fused binning differs from the XLA formulation",
    )
    out["bin"] = {"shape": [n, d, bins], "equal": True}

    # MXU histogram kernel against the scatter formulation (ops/forest
    # ._chunk_histogram) on integer-valued stats, where both are exact
    t_pack, nodes, s_dim = 4, 8, 2
    k1, k2, k3 = jax.random.split(jax.random.key(seed + 21), 3)
    sub = bins_fm[: forest_hist._F_BLOCK]
    node_rel = jax.random.randint(k1, (t_pack, n_pad), 0, nodes + 2)
    cls = jax.random.randint(k2, (n_pad,), 0, s_dim)
    boot = jax.random.poisson(k3, 1.0, (t_pack, n_pad)).astype(jnp.float32)
    stats = jnp.stack(
        [boot[t] * (cls == s) for t in range(t_pack) for s in range(s_dim)]
    )
    H = forest_hist.node_histograms(
        sub, node_rel, stats, t_pack=t_pack, nodes=nodes, s_dim=s_dim,
        n_bins=bins, interpret=REHEARSAL and jax.default_backend() != "tpu",
    )
    hist_xla = jax.jit(
        forest._chunk_histogram, static_argnames=("lo", "node_batch", "n_bins")
    )
    for t in range(t_pack):
        ref = hist_xla(
            sub.T.astype(jnp.int32), stats[t * s_dim : (t + 1) * s_dim].T,
            node_rel[t], lo=0, node_batch=nodes, n_bins=bins,
        )  # (S, nodes, F, B)
        got = H[:, t * nodes * s_dim : (t + 1) * nodes * s_dim, :].reshape(
            -1, nodes, s_dim, bins
        )  # (F, nodes, S, B)
        check(
            bool((jnp.transpose(got, (2, 1, 0, 3)) == ref).all()),
            f"MXU histogram differs from the scatter formulation (tree {t})",
        )
    out["hist"] = {"shape": [forest_hist._F_BLOCK, n_pad, bins], "equal": True}

    # a block's padding rows are not built (forest_hist's `f_rows`): 28 live rows
    # of 32 (the loop's static bound) and 54 of 64 (the short last block: the
    # rows past it one region under one test of the block's index, whose
    # lowering is Mosaic's and is what the interpreter cannot show), the
    # padding rows of the input garbage: the live rows to the bit against the
    # scatter formulation, the rows past them exactly zero
    shapes = []
    for live in (28, 54):
        f_pad = -(-live // forest_hist._F_BLOCK) * forest_hist._F_BLOCK
        sub = garbage_past(bins_fm[:f_pad], live, bins, seed + live)
        H = forest_hist.node_histograms(
            sub, node_rel, stats, t_pack=t_pack, nodes=nodes, s_dim=s_dim, n_bins=bins,
            f_rows=live, interpret=REHEARSAL and jax.default_backend() != "tpu",
        )
        check(not bool(H[live:].any()), f"the shallow kernel wrote sums into the padding rows ({live} of {f_pad})")
        for t in range(t_pack):
            ref = hist_xla(
                sub[:live].T.astype(jnp.int32), stats[t * s_dim : (t + 1) * s_dim].T,
                node_rel[t], lo=0, node_batch=nodes, n_bins=bins,
            )
            got = H[:live, t * nodes * s_dim : (t + 1) * nodes * s_dim, :].reshape(-1, nodes, s_dim, bins)
            check(
                bool((jnp.transpose(got, (2, 1, 0, 3)) == ref).all()),
                f"MXU histogram of {live} live rows of {f_pad} differs from the scatter formulation (tree {t})",
            )
        shapes.append([live, f_pad, n_pad, bins])
    out["hist_rows"] = {"shapes": shapes, "equal": True, "padding_rows_zero": True}
    del bins_fm, want, H, sub

    # the split search's prefix sums (forest_mxu._prefix_sums: ONE product at
    # Precision.HIGHEST, whose cut of a float32 into bfloat16 pieces is the
    # compiler's and is not documented) to the bit against whole-number
    # arithmetic, at _deep_step's deepest chunk: a compiler that cuts
    # otherwise fails here, and does not grow other forests unseen
    prefix = jax.jit(forest_mxu._prefix_sums)
    shapes = []
    for b in (32, bins):
        per = 2 if REHEARSAL else forest_mxu._SPLIT_CHUNK_BYTES // (64 * 64 * b * 4)
        shape = (per, 2, 4, 8, b) if REHEARSAL else (per, 2, 32, 64, b)
        x = hard_histograms(
            np.random.default_rng(seed + b), int(np.prod(shape[:-1])), b
        )
        got = np.asarray(prefix(jnp.asarray(x.reshape(shape), jnp.float32)))
        wrong = int((got.reshape(x.shape).astype(np.float64) != x.cumsum(axis=1)).sum())
        check(
            wrong == 0,
            f"{wrong} prefix sums of whole numbers below 2^24 are not exact (B = {b})",
        )
        shapes.append(list(shape))
    out["prefix"] = {"shapes": shapes, "equal": True}

    # the deep histogram kernel (forest_hist_deep) on a layout whose stray
    # tiles hold poison, to the bit against the scatter formulation, at the
    # slot shapes of rf_clf_fit's level 12 (64 slot rows) and level 7 (8).
    # The window is the array's last two trees: the first keeps one tile a
    # segment, so its stray tiles start beside its first tiles and most of
    # its grid steps are skipped (their index maps clamp to the last kept
    # tile); the second has no stray tile, so the array's last block is read
    tile = forest_hist._ROW_TILE_DEEP
    n_segs, n_tiles, f_deep = (3, 8, 32) if REHEARSAL else (8, 96, 64)
    rng = np.random.default_rng(seed + 22)
    spread = np.sort(rng.choice(np.arange(1, n_tiles), n_segs - 1, replace=False))
    maps = np.stack([
        np.minimum(np.arange(n_tiles), n_segs),                        # not in the window
        np.minimum(np.arange(n_tiles), n_segs),                        # a tile a segment, then stray
        np.searchsorted(spread, np.arange(n_tiles), side="right"),     # no stray tile
    ]).astype(np.int32)
    stray = np.repeat(maps == n_segs, tile, axis=1)                    # (3, n2)
    n2 = n_tiles * tile
    shapes = []
    # (local nodes, live feature rows): the last shape a short last block with
    # garbage in its padding rows (the shallow kernel's `hist_rows`, above)
    for local, live in ((32, f_deep), (1, f_deep), (4, f_deep - 10)):
        bins_d = rng.integers(0, bins, (3, f_deep, n2)).astype(np.int8)
        bins_d[:, live:] = rng.integers(1, bins, bins_d[:, live:].shape)
        node_d = rng.integers(0, local + 1, (3, n2)).astype(np.int32)  # == local: at no node
        cls_d = rng.integers(0, s_dim, (3, n2))
        stats_d = (
            rng.poisson(1.0, (3, 1, n2)) * (cls_d[:, None, :] == np.arange(s_dim)[None, :, None])
        ).astype(np.float32)
        clean = np.where(stray[:, None, :], 0.0, stats_d).astype(np.float32)
        bins_d[np.broadcast_to(stray[:, None, :], bins_d.shape)] = 127
        stats_d[np.broadcast_to(stray[:, None, :], stats_d.shape)] = np.nan
        node_p = np.where(stray, 0, node_d).astype(np.int32)
        Hd = forest_hist.node_histograms_segmented(
            jnp.asarray(bins_d), jnp.asarray(node_p)[:, None, :], jnp.asarray(stats_d),
            jnp.asarray(maps[1:].reshape(-1)), jnp.asarray(1, jnp.int32),
            t_chunk=2, n_segs=n_segs, nodes=local, s_dim=s_dim, n_bins=bins, f_pad=f_deep,
            f_rows=live, interpret=REHEARSAL and jax.default_backend() != "tpu",
        )
        slots = local * s_dim
        check(Hd.shape[0] == 2 * n_segs, "the deep kernel still writes a stray block")
        check(not bool(Hd[:, live:].any()), f"the deep kernel wrote sums into the padding rows ({live} of {f_deep})")
        Hd = Hd.reshape((2, n_segs) + Hd.shape[1:])[:, :, :live, :slots, :]
        for t in (1, 2):
            # a row's node among its tree's n_segs x local; stray or at no node: past them all
            seg_row = np.repeat(maps[t], tile)
            at = np.where((node_d[t] < local) & ~stray[t], seg_row * local + node_d[t], n_segs * local)
            ref = hist_xla(
                jnp.asarray(np.where(stray[t][None, :], 0, bins_d[t, :live]).T.astype(np.int32)), jnp.asarray(clean[t].T),
                jnp.asarray(at.astype(np.int32)), lo=0, node_batch=n_segs * local, n_bins=bins,
            )  # (S, n_segs * local, F, B)
            got = Hd[t - 1].reshape(n_segs, live, local, s_dim, bins)  # (seg, F, local, S, B)
            want_t = jnp.transpose(ref.reshape(s_dim, n_segs, local, live, bins), (1, 3, 2, 0, 4))
            check(
                bool((got == want_t).all()) and float(want_t[-1].sum()) > 0,
                f"deep histogram differs from the scatter formulation (tree {t}, {slots} slot rows, {live} live rows of {f_deep})",
            )
        shapes.append([2 * n_segs, f_deep, max(8, slots), bins, live])
    out["deep"] = {
        "shapes": shapes, "tiles": n_tiles, "kept": [int((m < n_segs).sum()) for m in maps[1:]], "equal": True,
    }

    # the public estimator on the MXU builder
    df = DataFrame.from_device(X, y=np.asarray(y), n_rows=n)
    model = RandomForestClassifier(
        numTrees=S["rf_trees"], maxDepth=S["rf_depth"], maxBins=bins,
        featureSubsetStrategy="onethird", seed=seed, num_workers=1,
    ).fit(df)
    kernels = executable_cache_stats()["kernels"]
    require_route(
        "RandomForestClassifier",
        "mxu" if "shallow_step" in kernels else "scatter", "mxu",
    )
    held = np.asarray(X[:8192])
    pred = model.transform(DataFrame.from_numpy(held)).toPandas()[
        "prediction"
    ].to_numpy()
    acc = float((pred == np.asarray(y[:8192])).mean())
    log(f"forest accuracy on planted labels: {acc:.4f}")
    check(acc > 0.6, f"forest accuracy {acc} on planted labels")
    out["fit"] = {"trees": S["rf_trees"], "depth": S["rf_depth"], "accuracy": acc}

    # the per-node feature subset (a table no wider than one feature block:
    # forest_mxu's second rule): 28 columns, 5 a split, depth 8 so that level 7
    # splits in the deep phase.  EVERY internal node against the plain
    # reference's exact search of the node's own subset, drawn again from the
    # model's words: the mask on the gains, on the chip, in both phases
    from chipbench.references import forest_pernode as pernode

    cols, width, depth = 28, 5, 8
    Xn = X[:, :cols]
    model = RandomForestClassifier(
        numTrees=S["rf_trees"], maxDepth=depth, maxBins=bins, seed=seed, num_workers=1,
    ).fit(DataFrame.from_device(Xn, y=np.asarray(y), n_rows=n))
    draw = model.feature_subset_draw_
    if REHEARSAL and draw is None:  # off the chip the mesh engine grew it: no words, nothing to hold
        log("per-node subsets: not the one-chip builder here; skipped")
        return out
    check(
        draw is not None and list(draw) == [forest_mxu.SUBSET_RULE, seed & 0x7FFFFFFF, width, cols],
        f"the per-node fit keeps the words {draw}",
    )
    Xh, yh = np.asarray(Xn), np.asarray(y)
    e = pernode.quantile_edges(Xh[pernode.sample_rows(n, cols)], bins)
    table = pernode.binned_table(Xn, e)
    w = np.asarray(pernode.bootstrap_weights(model.bootstrap_draw_))[:, :n].astype(np.float64)
    paths = pernode.paths_host(Xh, model.features_, model.thresholds_, depth)
    searched, shortfall, deep_nodes = 0, 0.0, 0
    for t in range(S["rf_trees"]):
        nodes = np.flatnonzero(model.features_[t] >= 0)
        r = pernode.examine_splits(
            table, e, paths[:, t], yh, w[t], 2, draw, t, model.features_[t], model.thresholds_[t], nodes,
        )
        check(bool(r["inside"].all()), f"tree {t}: {int((~r['inside']).sum())} nodes split on a column outside their subset")
        shortfall = max(shortfall, float(r["shortfall"].max()))
        searched += len(nodes)
        deep_nodes += int((nodes >= 2**7 - 1).sum())
    check(shortfall <= 1e-6, f"a node's split is {shortfall:.2e} of its impurity short of the best of its subset")
    check(deep_nodes > 0, "no node split in the deep phase")
    out["pernode"] = {"shape": [n, cols, width, depth], "nodes": searched, "deep_nodes": deep_nodes, "shortfall": shortfall}
    return out


def kernel_forest_reg(seed):
    """A regressor's sums on the chip, at float32's precision: the shallow
    histogram kernel with a continuous label in its three bfloat16 pieces (two
    products a feature: ops/forest_hist's header) and the node totals
    (forest_mxu._node_totals, products at Precision.HIGHEST), at the
    benchmark's scan shape (1024 feature rows, 128 bins, a level-5 window of
    two trees: 128 slots), held to float64 on sampled feature rows.  The
    label crossing the MXU in ONE piece (a classifier's single product on the
    same rows) and the totals at default precision are read beside them and
    must be far off: the gap this stage exists for."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import forest_hist, forest_mxu

    n = S["rf_rows"]
    f_rows, bins, t_pack, nodes = S["reg_features"], S["rf_bins"], 2, 32
    interpret = REHEARSAL and jax.default_backend() != "tpu"
    kb, kn, kw, ky = jax.random.split(jax.random.key(seed + 30), 4)
    sub = jax.random.randint(kb, (f_rows, n), 0, bins).astype(jnp.int8)
    node_rel = jax.random.randint(kn, (t_pack, n), 0, nodes + 2)     # some rows at no node
    w = jax.random.poisson(kw, 1.0, (t_pack, n)).astype(jnp.float32)
    y = 25.0 + 180.0 * jax.random.normal(ky, (n,), jnp.float32)      # gen_data's labels' scale
    base = jnp.stack([jnp.ones_like(y), y])

    def hist(kind, sub=sub, live=None):
        rows = forest_mxu._stats_rows(base, w, t_pack, 2, kind)
        H = forest_hist.node_histograms(
            sub, node_rel, rows, t_pack=t_pack, nodes=nodes, s_dim=2, n_bins=bins,
            products=forest_mxu._hist_products(kind), f_rows=live, interpret=interpret,
        )
        return forest_hist.fold_label_products(H, t_pack * nodes * 2) if kind == "regression" else H

    # the cell's 1000 live rows of 1024 (31 full blocks and one of 8; the rows
    # past a short last block are one guarded region a grid step), garbage in
    # the padding rows: the live rows' sums must be the every-row form's to
    # the bit, the padding rows' exactly zero
    live = f_rows - 24
    short = garbage_past(sub, live, bins, seed + 31)
    fns = {
        "pieces": jax.jit(lambda: hist("regression")), "one_piece": jax.jit(lambda: hist("gini")),
        "live_rows": jax.jit(lambda: hist("regression", short, live)),
    }
    H, ms = {}, {}
    for name, fn in fns.items():
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        H[name] = np.asarray(jax.block_until_ready(fn()))
        ms[name] = 1e3 * (time.perf_counter() - t0)

    bounded = H.pop("live_rows")
    check(np.array_equal(bounded[:live], H["pieces"][:live]), f"the {live} live rows' sums differ from the every-row form's")
    check(not bounded[live:].any(), f"the kernel wrote sums into the padding rows ({live} of {f_rows})")

    # float64 on the host: sampled feature rows, every (tree, node, stat, bin)
    sub_h, rel_h = np.asarray(sub), np.asarray(node_rel)
    w_h, y_h = np.asarray(w, np.float64), np.asarray(y, np.float64)
    gap = dict.fromkeys(H, 0.0)
    for f in sorted({0, 1, f_rows // 2, f_rows - 1}):
        exact = np.zeros((t_pack, nodes, 2, bins))
        for t in range(t_pack):
            at = rel_h[t] < nodes
            flat = rel_h[t][at] * bins + sub_h[f][at]
            for s_, val in enumerate((w_h[t], w_h[t] * y_h)):
                exact[t, :, s_] = np.bincount(flat, weights=val[at], minlength=nodes * bins).reshape(nodes, bins)
        for name in H:
            got = H[name][f].reshape(t_pack, nodes, 2, bins)
            check(np.array_equal(got[:, :, 0], exact[:, :, 0]), f"{name}: weight histogram of feature row {f} is not exact")
            gap[name] = max(gap[name], float(np.abs(got[:, :, 1] - exact[:, :, 1]).max() / np.abs(exact[:, :, 1]).max()))

    # node totals (w, w*y, w*y*y): HIGHEST as the program states it, and one pass
    stats3 = jnp.stack([w, w * y[None, :], w * (y * y)[None, :]], axis=1)
    on = (rel_h[:, None, :] == np.arange(nodes)[None, :, None])
    exact_tot = np.einsum("tcn,tsn->tcs", on.astype(np.float64), np.asarray(stats3, np.float64))
    tot = np.asarray(forest_mxu._node_totals(node_rel, stats3, nodes))
    one_pass = np.asarray(jax.jit(lambda a, b: jnp.einsum(
        "tcn,tsn->tcs", a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
    ))(jnp.asarray(on), stats3))
    tot_gap = {k: float((np.abs(v - exact_tot) / np.abs(exact_tot).max(axis=(0, 1))).max()) for k, v in (("highest", tot), ("one_pass", one_pass))}
    log(
        f"forest_reg {f_rows} x {n} x {bins}: label in three pieces {gap['pieces']:.2e} of the largest "
        f"entry from float64 in {ms['pieces']:.1f} ms; in one piece {gap['one_piece']:.2e} in "
        f"{ms['one_piece']:.1f} ms; node totals {tot_gap['highest']:.2e}, in one pass {tot_gap['one_pass']:.2e}"
    )
    # float32 accumulation over a node's ~2,000 rows reads 1e-7 to 2e-6; one bfloat16 pass 1e-3
    check(gap["pieces"] <= 1e-5, f"the label's histogram is {gap['pieces']:.2e} off float64")
    check(tot_gap["highest"] <= 1e-5, f"the node totals are {tot_gap['highest']:.2e} off float64")
    check(gap["one_piece"] >= 3e-4 and tot_gap["one_pass"] >= 3e-4, "one bfloat16 pass read as float32: the stage holds nothing")
    return {"shape": [f_rows, n, bins], "gap": gap, "totals_gap": tot_gap, "ms": ms, "live_rows": [live, f_rows]}


def kernel_forest_gather(seed):
    """The forest's subset gather (ops/forest_hist.gather_rows_matmul: a copy
    of the rows it selects out of the binned table laid out a feature a slice,
    tile_feature_rows) at the benchmark's two shapes, a classifier's 54 and a
    regressor's 1000 of 3000 columns x 401,408 rows, and on a table of an odd
    number of 2048-row tiles (the last block of the copy partial), against
    numpy to the byte with the padding rows zero.  Timed beside it, and held
    to the same bytes: the forms it was chosen over (PERF.md section 6,
    PR 33): `jnp.take` on the leading axis with XLA's copy back to
    row-interleaved tiles, and the one-hot selection product over the whole
    table that the function was named for, in bfloat16 as it ran and in int8."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import forest_hist

    d = S["cols"]
    interpret = REHEARSAL and jax.default_backend() != "tpu"
    rng = np.random.default_rng(seed + 33)

    # the forms take their arrays as arguments: a table closed over would be
    # a constant of gigabytes in the lowered module
    @partial(jax.jit, static_argnames=("f_pad", "n"))
    def take(table, feats, f_pad, n):
        rows = jnp.take(table, feats, axis=0).reshape(feats.shape[0], -1)[:, :n]
        return jnp.pad(rows, ((0, f_pad - feats.shape[0]), (0, 0)))

    @partial(jax.jit, static_argnames=("f_pad", "n", "dtype", "acc", "chunk"))
    def product(bins, feats, f_pad, n, dtype, acc, chunk=2048):
        sel = (feats[:, None] == jnp.arange(bins.shape[0])[None, :]).astype(dtype)
        sel = jnp.pad(sel, ((0, f_pad - feats.shape[0]), (0, 0)))

        def body(_, i):
            blk = jax.lax.dynamic_slice_in_dim(bins, i * chunk, chunk, axis=1)
            return 0, jnp.dot(sel, blk.astype(dtype), preferred_element_type=acc).astype(jnp.int8)

        cols = jax.lax.scan(body, 0, jnp.arange(n // chunk))[1]
        return jnp.moveaxis(cols, 0, 1).reshape(f_pad, n)

    out = {}
    for n, subsets in ((S["gather_rows"], S["gather_subsets"]), (S["gather_odd_rows"], S["gather_subsets"][:1])):
        bins_h = rng.integers(0, S["rf_bins"], (d, n), dtype=np.int8)
        bins = jax.device_put(bins_h)
        jax.block_until_ready(forest_hist.tile_feature_rows(bins))    # compiles
        t0 = time.perf_counter()
        table = jax.block_until_ready(forest_hist.tile_feature_rows(bins))
        tile_ms = 1e3 * (time.perf_counter() - t0)
        for F in subsets:
            f_pad = -(-F // forest_hist._F_BLOCK) * forest_hist._F_BLOCK
            feats_h = rng.choice(d, F, replace=False).astype(np.int32)
            feats = jax.device_put(feats_h)
            want = np.zeros((f_pad, n), np.int8)
            want[:F] = bins_h[feats_h]
            forms = {
                "copy": lambda: forest_hist.gather_rows_matmul(table, feats, f_pad=f_pad, n_pad=n, interpret=interpret),
                "take": lambda: take(table, feats, f_pad=f_pad, n=n),
                "product_bf16": lambda: product(bins, feats, f_pad=f_pad, n=n, dtype=jnp.bfloat16, acc=jnp.float32),
                "product_int8": lambda: product(bins, feats, f_pad=f_pad, n=n, dtype=jnp.int8, acc=jnp.int32),
            }
            ms = {}
            for name, fn in forms.items():
                got = jax.block_until_ready(fn())
                check(
                    got.shape == want.shape and got.dtype == jnp.int8 and np.array_equal(np.asarray(got), want),
                    f"gather {F} of {d} x {n}: the {name} form is not bins[feats] with zero padding rows",
                )
                del got
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready([fn() for _ in range(3)])
                    best = min(best, 1e3 * (time.perf_counter() - t0) / 3)
                ms[name] = best
            moved = 2 * f_pad * n
            log(
                f"forest_gather {F} of {d} x {n} ({f_pad} rows written): the row copy {ms['copy']:.3f} ms "
                f"({moved / ms['copy'] / 1e6:.0f} GB/s read and written), take and XLA's relayout {ms['take']:.3f}, "
                f"the selection product in bfloat16 {ms['product_bf16']:.3f} and in int8 {ms['product_int8']:.3f}; "
                f"the table's relayout, once a fit, {tile_ms:.2f} ms"
            )
            out[f"{F}_of_{d}x{n}"] = {"rows_written": f_pad, "ms": ms, "tile_ms": tile_ms, "equal": True}
            del want
        del bins, table, bins_h
        release()
    return out


def knn_reference(items, queries, k):
    """Plain jax.numpy k nearest: full distance matrix + top_k."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x, q = jnp.asarray(items), jnp.asarray(queries)
        d2 = (
            (q * q).sum(axis=1)[:, None]
            - 2.0 * (q @ x.T)
            + (x * x).sum(axis=1)[None, :]
        )
        neg, idx = jax.lax.top_k(-d2, k)
    return np.sqrt(np.maximum(-np.asarray(neg), 0.0)), np.asarray(idx)


def check_knn(model, items, queries, k, label):
    from spark_rapids_ml_tpu.dataframe import DataFrame

    _, _, knn_df = model.kneighbors(DataFrame.from_numpy(queries))
    pdf = knn_df.toPandas()
    dist = np.stack(pdf["distances"].to_numpy())
    ids = np.stack(pdf["indices"].to_numpy())
    check(dist.shape == (len(queries), k), f"{label}: result shape {dist.shape}")
    nref = S["knn_ref"]
    ref_d, ref_i = knn_reference(items, queries[:nref], k)
    np.testing.assert_allclose(dist[:nref], ref_d, rtol=1e-3, atol=1e-3)
    overlap = float(
        np.mean([
            np.intersect1d(a, b).size / k for a, b in zip(ids[:nref], ref_i)
        ])
    )
    log(f"{label}: neighbor-set overlap with the plain reference {overlap:.5f}")
    check(overlap >= 0.99, f"{label}: neighbor overlap {overlap}")
    return overlap


def kernel_knn(seed):
    from spark_rapids_ml_tpu import NearestNeighbors
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops import knn as knn_ops
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    n, q, k = S["knn_items"], S["knn_queries"], S["knn_k"]
    rng = np.random.default_rng(seed + 30)
    items = rng.standard_normal((n, S["cols"]), dtype=np.float32)
    queries = rng.standard_normal((q, S["cols"]), dtype=np.float32)
    model = NearestNeighbors(k=k, num_workers=1).fit(DataFrame.from_numpy(items))
    overlap = check_knn(model, items, queries, k, "kNN fused")
    staged = model._staged_items[1]
    plan = knn_ops._adaptive_plan(
        staged.items.shape[0], staged.items.shape[1], q, get_mesh(1), k
    )
    require_route("NearestNeighbors.kneighbors", plan[0], "pallas")
    if plan[0] == "pallas":
        require_route("kNN merge epilogue", f"fused={plan[2]}", "fused=True")
    return {"shape": [n, S["cols"], q, k], "plan": list(plan), "overlap": overlap}


def kernel_pq(seed, n_bits):
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import ApproximateNearestNeighbors
    from spark_rapids_ml_tpu.ann.ivfflat import recall_at_k
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops import pallas_pq

    n, d, m, k = S["pq_rows"], S["pq_d"], S["pq_m"], S["pq_k"]
    require_route(f"IVF-PQ n_bits={n_bits} scan", pallas_pq.lut_route(), "pallas")

    # the scan kernel, at the engine's tile shape, against the XLA gather
    # formulation on the same tables and codes
    rng = np.random.default_rng(seed + 41)
    ksub = 1 << n_bits
    b, r = 8, 1024 if REHEARSAL else 16_384
    tables = jnp.asarray(rng.standard_normal((b, m, ksub), dtype=np.float32))
    codes = rng.integers(0, ksub, (b, r, m), dtype=np.uint8)
    if n_bits == 4:
        packed = jnp.asarray(
            pallas_pq.pack_codes4(codes.reshape(-1, m)).reshape(b, r, m // 2)
        )
        got = pallas_pq.fastscan_lut_accumulate(tables, packed)
        want = pallas_pq._fastscan_xla(tables, packed)
    else:
        got = pallas_pq.lut_accumulate(tables, jnp.asarray(codes))
        want = pallas_pq._lut_accumulate_xla(tables, jnp.asarray(codes))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )

    # the public estimator: probed search against its own exactSearch route
    X = host_embeddings(seed + 40, n, d)
    qdf = DataFrame.from_numpy(X[: S["pq_queries"]].copy())
    model = ApproximateNearestNeighbors(
        k=k, algorithm="ivfpq", algoParams={"M": m, "n_bits": n_bits},
        num_workers=1,
    ).fit(DataFrame.from_numpy(X))
    _, _, probed = model.kneighbors(qdf)
    model.setExactSearch(True)
    _, _, exact = model.kneighbors(qdf)
    recall = recall_at_k(
        np.stack(probed.toPandas()["indices"].to_numpy()),
        np.stack(exact.toPandas()["indices"].to_numpy()),
    )
    log(f"IVF-PQ n_bits={n_bits}: recall@{k} vs exactSearch {recall:.4f}")
    # the CPU backend's XLA route gives 0.999 (8-bit) and 0.957 (4-bit) on
    # these rows at the full size
    floor = 0.95 if n_bits == 8 else 0.9
    check(recall >= floor, f"IVF-PQ n_bits={n_bits} recall {recall} < {floor}")
    release()
    return {"shape": [n, d, m, n_bits], "recall": recall, "scan_matches_xla": True}


def regression_rows(mesh, rows_per_dev, cols, seed):
    """(X, y) made ON the mesh, row-sharded: unit normal columns, the first
    four with coefficients 1, 2, 3, 4, unit normal noise."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    rows = rows_per_dev * mesh.devices.size

    def make(key):
        kx, kn = jax.random.split(key)
        X = jax.random.normal(kx, (rows, cols), jnp.float32)
        y = X[:, :4] @ jnp.arange(1.0, 5.0, dtype=jnp.float32)
        return X, y + jax.random.normal(kn, (rows,), jnp.float32)

    by_rows = NamedSharding(mesh, P(DATA_AXIS))
    return jax.jit(make, out_shardings=(by_rows, by_rows))(jax.random.key(seed))


def enet(n_dev, reg):
    from spark_rapids_ml_tpu import LinearRegression

    return LinearRegression(
        regParam=reg, elasticNetParam=0.5, maxIter=S["cd_sweeps"], tol=1e-30,
        standardization=False, num_workers=n_dev,
    )


def labelled(X, y):
    from spark_rapids_ml_tpu.dataframe import DataFrame

    return DataFrame.from_device(X, y=np.asarray(y), n_rows=X.shape[0])


def same_fit(a, b):
    """Two elastic-net fits of one table: coefficients that differ by a
    dot's order of summation, the same support but for a column whose pull
    lies within that rounding of the threshold."""
    ca, cb = np.asarray(a.coef_), np.asarray(b.coef_)
    return bool(
        np.abs(ca - cb).max() <= 1e-5
        and ((ca == 0) == (cb == 0)).mean() >= 0.999
    )


def kernel_cd_sweep(seed):
    """LinearRegression's elastic-net fit through ops/cd_sweep.py, at a small
    width, the benchmark's and a large one, with a regParam at which the soft
    threshold sets coefficients to exactly 0 (the benchmark cell's own, 1e-5,
    lies below float32's rounding of a coefficient and its comparison cannot
    see the penalty: chipbench/subjects/linreg.fit_loop.json), against the
    fori_loop the solver runs off the chip.  A planted fault, the kernel's fit
    with the penalty all but left out, has to read as another fit."""
    import jax

    from spark_rapids_ml_tpu.ops import cd_sweep, glm, pallas_tpu
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    out = {}
    for d in S["cd_widths"]:
        X, y = regression_rows(get_mesh(1), S["cd_rows"], d, seed + 60 + d)
        df = labelled(X, y)
        G = jax.ShapeDtypeStruct((d, d), X.dtype)
        require_route(
            f"cd_sweep d={d}", "pallas" if cd_sweep.takes(G) else "xla", "pallas"
        )
        t0 = time.perf_counter()
        kernel = enet(1, S["cd_reg"]).fit(df)
        wall = time.perf_counter() - t0
        fault = enet(1, 1e-12).fit(df)
        os.environ[pallas_tpu.DISABLE_ENV] = "1"
        glm.solve_elasticnet_cd.clear_cache()
        try:
            loop = enet(1, S["cd_reg"]).fit(df)
        finally:
            del os.environ[pallas_tpu.DISABLE_ENV]
            glm.solve_elasticnet_cd.clear_cache()
        zeros = int((np.asarray(kernel.coef_) == 0).sum())
        log(
            f"cd_sweep d={d}: {kernel.num_iters} sweeps (the loop "
            f"{loop.num_iters}), {zeros} coefficients "
            f"exactly 0 (the loop {int((np.asarray(loop.coef_) == 0).sum())}, "
            f"no penalty {int((np.asarray(fault.coef_) == 0).sum())}), "
            f"wall {wall:.1f}s"
        )
        check(2 <= kernel.num_iters <= S["cd_sweeps"], f"d={d}: {kernel.num_iters} sweeps")
        check(d < 100 or d // 4 <= zeros <= d - 4, f"d={d}: {zeros} zeros, the penalty does not bite")
        check(same_fit(kernel, loop), f"d={d}: the kernel's fit is not the loop's")
        check(not same_fit(fault, loop), f"d={d}: a fit without the penalty read as sound")
        out[str(d)] = {"zeros": zeros, "sweeps": kernel.num_iters}
        del X, y, df
        release()
    return out


def kernel_gram_triangle(seed):
    """The statistics pass of LinearRegression, PCA and the tuning engine
    (ops/linalg._local_moments): one triangle of X'WX through the MXU as
    column panels and the other mirrored in, at the benchmark's width and at a
    ragged one, on rows that are no multiple of the chunk (40,000 under a
    chunk of 32,768: one whole chunk through the loop and 7,232 rows left
    over as a block of their own, both halves of the walk since PR 50) and
    weights that hold zeros; the same rows walked as ONE block beside it.
    Held against the float64 scatter on three slices of columns
    (all rows of the matrix, so both triangles), not against another float32
    product: on the chip the panels read 2.1e-6 to 2.8e-6 of the largest
    entry from it, the scan at one panel 2.9e-6, the whole product in one
    contraction (the pass without a mesh, timed here beside the panels)
    6.6e-6 to 7.1e-6 at these 40,000 rows and 1.9e-5 at 120,000 (PERF.md,
    PR 31); the loop and the left-over block together 2.9e-6 and 3.2e-6, the
    one block 6.8e-6 and 7.1e-6 (one contraction over all 40,000 rows rounds
    as the whole product does: PERF.md, PR 50).  The scatter equals its
    transpose to the bit."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import glm
    from spark_rapids_ml_tpu.ops.linalg import gram_panels, scan_rows
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    out = {}
    chunk = inspect.signature(glm.linreg_sufficient_stats).parameters["chunk"].default
    for d in S["gram_widths"]:
        X, y = regression_rows(get_mesh(1), S["gram_rows"], d, seed + 80 + d)
        i = jnp.arange(X.shape[0])
        w = ((i % 7 != 0) * (1.0 + 0.25 * (i % 5))).astype(X.dtype)
        G, ms = {}, {}
        forms = (("panels", get_mesh(1), chunk), ("one_block", get_mesh(1), X.shape[0]), ("whole", None, chunk))
        for form, mesh, rows in forms:
            jax.block_until_ready(glm.linreg_sufficient_stats(X, y, w, mesh=mesh, chunk=rows))
            t0 = time.perf_counter()
            stats = jax.block_until_ready(glm.linreg_sufficient_stats(X, y, w, mesh=mesh, chunk=rows))
            ms[form] = 1e3 * (time.perf_counter() - t0)
            G[form] = np.asarray(stats.G)
        Xh, wh = np.asarray(X, np.float64), np.asarray(w, np.float64)
        gap = dict.fromkeys(G, 0.0)
        span = min(128, d)
        for c0 in (0, (d - span) // 2, d - span):
            exact = (Xh * wh[:, None]).T @ Xh[:, c0:c0 + span]
            for form in G:
                off = np.abs(G[form][:, c0:c0 + span] - exact).max() / np.abs(exact).max()
                gap[form] = max(gap[form], float(off))
        panels = gram_panels(d)
        n_full, tail = scan_rows(X.shape[0], chunk)
        log(
            f"gram d={d}: {panels} panels over {n_full} whole chunks and {tail} rows left over "
            f"{ms['panels']:.2f} ms, {gap['panels']:.2e} of the largest entry from float64; "
            f"the same rows as one block {ms['one_block']:.2f} ms, {gap['one_block']:.2e}; "
            f"the whole product {ms['whole']:.2f} ms, {gap['whole']:.2e}"
        )
        check(gap["panels"] <= 1e-5, f"d={d}: the panel scatter is {gap['panels']:.2e} off float64")
        check(gap["one_block"] <= 1e-5, f"d={d}: the one-block scatter is {gap['one_block']:.2e} off float64")
        check(
            panels == 1 or np.array_equal(G["panels"], G["panels"].T),
            f"d={d}: the panel scatter is not its own transpose",
        )
        out[str(d)] = {"panels": panels, "walk": [n_full, tail], "gap": gap, "ms": ms}
        del X, y, w, stats, Xh, wh
        release()
    return out


def kernel_softmax_ell(seed):
    """The one-pass sparse softmax kernel (ops/softmax_ell_pass.py, through
    Mosaic) against its plain jnp twin over the same rows, at the shapes a
    layout gets wrong: 3 and 10 classes, 100 and 128 columns, a last block of
    ONE row (the twin's, behind the kernel's whole blocks), rows with every
    slot full, rows that are all padding, a real nonzero at column 0 beside
    padding slots, and weights with zeros.  The tolerance is the one
    tests/test_logreg_sparse_reference.py holds the interpreter to.  The rows'
    lengths are uneven by block of rows (full rows in every fourth grid step,
    every fourth all padding, one value in the last slot behind empty ones), so
    the kernel's slot bounds differ from block to block: its sums are held to
    the same kernel's with every bound at the width TO THE BIT, and the share
    of slot steps it skipped is logged."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import softmax_ell_pass as sp
    from spark_rapids_ml_tpu.ops.sparse import EllMatrix

    rows, width = S["ell_kernel_rows"], 32
    tile = 256 if REHEARSAL else None
    out = {}
    for classes, cols in ((3, 100), (10, 100), (10, 128), (3, 128)):
        rng = np.random.default_rng(seed + 90 + classes + cols)
        count = rng.binomial(cols, 0.1, rows).clip(0, width)
        block_rows = tile or sp.row_tile(rows)
        block = np.arange(rows) // block_rows
        count[::5] = np.where(block[::5] % 4 == 0, width, count[::5])         # every slot full, in every fourth block
        count[1::5] = 0                               # all padding
        count[block % 4 == 1] = 0                     # a block of padding rows
        live = np.arange(width)[:, None] < count[None, :]
        if rows > 3 * block_rows:
            live[width - 1, 2 * block_rows + 7] = True                        # one value in the last slot, behind empty ones
        idx = np.where(live, np.sort(rng.integers(0, cols, (width, rows)), axis=0), 0).astype(np.int32)
        idx[0, 2::5] = 0                              # a real nonzero at column 0
        val = np.where(live, rng.normal(size=(width, rows)), 0.0).astype(np.float32)
        y = jnp.asarray(rng.integers(0, classes, rows), jnp.int32)
        w = rng.uniform(0.0, 2.0, rows).astype(np.float32)
        w[::7] = 0.0
        W = jnp.asarray(0.5 * rng.normal(size=(classes, cols)), jnp.float32)
        b = jnp.asarray(rng.normal(size=classes), jnp.float32)
        X = EllMatrix(jnp.asarray(idx), jnp.asarray(val), cols)
        require_route(f"softmax_ell k={classes} d={cols}", "pallas" if sp.takes(X, classes) else "xla", "pallas")
        got = jax.block_until_ready(sp.ell_pass_sums(X, y, jnp.asarray(w), tile=tile)(W, b))
        want = sp.softmax_block_sums(X.idx, X.val, y, jnp.asarray(w), W, b)
        gap = 0.0
        for name, a, c in zip(("loss", "g", "gb"), got, want):
            a, c = np.asarray(a, np.float64), np.asarray(c, np.float64)
            check(
                np.allclose(a, c, rtol=2e-5, atol=2e-5),
                f"k={classes} d={cols}: the kernel's {name} is {np.abs(a - c).max():.2e} off its twin",
            )
            gap = max(gap, float(np.abs(a - c).max() / np.abs(c).max()))
        # the same kernel over the whole blocks, every bound at the width: the same bits
        bounds = sp.block_bounds(X, tile=tile)
        yw = jnp.stack([y.astype(jnp.float32), jnp.asarray(w)])
        walk = lambda bounds: jax.block_until_ready(sp.pass_sums(
            X.idx, X.val, yw, bounds, W, b, n_cols=cols, tile=block_rows, interpret=REHEARSAL))
        for name, a, c in zip(("loss", "g", "gb"), walk(bounds), walk(jnp.full_like(bounds, width))):
            check(np.array_equal(np.asarray(a), np.asarray(c)), f"k={classes} d={cols}: the bounded walk's {name} is not the full walk's to the bit")
        skipped = 1.0 - float(bounds.sum()) / (bounds.size * width)
        check(0.2 < skipped < 0.8, f"k={classes} d={cols}: the table skips {skipped:.1%} of its slot steps: no test of the bounds")
        log(f"softmax_ell k={classes} d={cols}: {rows} rows, {gap:.2e} of the largest entry from the twin, "
            f"the full walk's bits with {skipped:.1%} of the slot steps skipped")
        out[f"{classes}x{cols}"] = gap
        out["skip_share"] = skipped
    return out


def kernel_lloyd_tall(seed):
    """Lloyd's one-read update pass (ops/lloyd_tall_pass.py, through Mosaic)
    against its XLA twin ops/kmeans._tall_assign_stats over the same rows of a
    feature-major table of a few million rows in blobs, at two values of k
    (20 in 24 centre rows, thirty-two lane tiles a trip; 130 in 136, four) with the
    tile the rule picks, float32 weights with zeros, and rows left over behind
    the whole tiles (the twin's own block takes them in the solver).  The
    counts say which centre every row chose: whole weights, so a row that
    changed sides moves them by 0.25 or more; |x|^2, which the kernel leaves
    out of the argmin, may turn a tie of the last bit, so a few in a million
    may."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import lloyd_tall_pass as lp
    from spark_rapids_ml_tpu.ops.kmeans import _tall_assign_stats
    from spark_rapids_ml_tpu.ops.tall import padded_features

    rows, cols, chunk = S["tall_kernel_rows"], S["tall_cols"], 1024 if REHEARSAL else 32768
    d_pad = padded_features(cols)
    out = {}
    for k in (20, 130):
        key = jax.random.key(seed + 700 + k)
        kc, ka, kn, km = jax.random.split(key, 4)
        true = jax.random.uniform(kc, (k, cols), jnp.float32, -10.0, 10.0)
        xt = true.T[:, jax.random.randint(ka, (rows,), 0, k)] + jax.random.normal(kn, (cols, rows), jnp.float32)
        xt = jnp.pad(xt, ((0, d_pad - cols), (0, 0)))
        w = jnp.where(jnp.arange(rows) % 7 == 0, 0.0, 0.5 + 0.25 * (jnp.arange(rows) % 5)).astype(jnp.float32)
        centres = jnp.pad(true + 0.3 * jax.random.normal(km, (k, cols), jnp.float32), ((0, 0), (0, d_pad - cols)))
        tile = lp.row_tile(k, d_pad, rows, chunk)
        done = rows // tile * tile
        check(0 < done < rows, f"k={k}: {rows} rows in tiles of {tile} leave no rows over: no test of the whole tiles' extent")
        got = jax.block_until_ready(lp.pass_sums(xt, lp.weight_tiles(w, tile), centres, interpret=REHEARSAL))
        x_norm = (xt * xt).sum(axis=0)
        want = jax.jit(lambda xt, w, c, xn: _tall_assign_stats(xt, w, c, chunk, xn)[:2])(
            xt[:, :done], w[:done], centres, x_norm[:done])
        sums, counts = (np.asarray(a, np.float64) for a in got)
        tsums, tcounts = (np.asarray(a, np.float64) for a in want)
        moved = float(np.abs(counts - tcounts).max())
        gap = float(np.abs(sums - tsums).max() / np.abs(tsums).max())
        check(moved <= 2e-5 * done, f"k={k}: the counts lie {moved} from the twin's over {done} rows: rows changed sides")
        check(gap <= 2e-5, f"k={k}: the kernel's sums lie {gap:.2e} of the largest from the twin's")
        check(not sums[:, cols:].any(), f"k={k}: a padding feature row took a sum")
        log(f"lloyd_tall k={k}: {done} of {rows} rows in tiles of {tile}, sums {gap:.2e} of the largest from the twin, "
            f"counts within {moved:g}")
        out[str(k)] = {"tile": tile, "sums_gap": gap, "counts_moved": moved}
    return out


def fit_sparse(seed):
    """The public multinomial fit of a device-resident ELL frame
    (DataFrame.from_device(EllMatrix)) through the one-pass kernel: the table
    chipbench's reference makes, 100 columns, 10 classes, width 32."""
    from chipbench.references import logreg_sparse as ref
    from chipbench.references.common import seed_words
    from spark_rapids_ml_tpu import LogisticRegression
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops.logistic import ell_pass_objective
    from spark_rapids_ml_tpu.ops.sparse import EllMatrix
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "chipbench/configs/logreg-sparse-d100-k10-iter30.json")) as f:
        d = json.load(f)["data"]        # the cell's own table, at the smoke's rows
    rows, classes = S["ell_rows"], d["classes"]
    make = ref.table_fn(
        get_mesh(1), DATA_AXIS, rows, d["cols"], classes, d["density"], d["width"], d["coef_scale"], d["intercept_spread"],
        d["population"],
    )
    idx, val, y, nnz = make(seed_words(seed + 95))
    X = EllMatrix(idx, val, 100, nnz=int(nnz))
    require_route("sparse multinomial fit", "ell_pass" if ell_pass_objective(X, classes) else "autodiff", "ell_pass")
    t0 = time.perf_counter()
    model = LogisticRegression(num_workers=1, maxIter=10, tol=1e-30, regParam=1e-5).fit(
        DataFrame.from_device(X, y=np.asarray(y), n_rows=rows)
    )
    wall = time.perf_counter() - t0
    counted = model.fit_telemetry().counters
    labels = np.asarray(y)
    # the fit's own coefficients on its first rows, scored on the host
    n = min(rows, 8192)
    dense = np.zeros((n, 100), np.float32)
    np.add.at(dense, (np.arange(n)[None, :], np.asarray(idx[:, :n])), np.asarray(val[:, :n]))
    pred = model.classes_[(dense @ np.asarray(model.coef_).T + np.asarray(model.intercept_)).argmax(axis=1)]
    acc, base = float((pred == labels[:n]).mean()), float(np.bincount(labels.astype(int)).max() / rows)
    log(
        f"sparse fit: {rows} x 100 (width 32, {int(nnz)} nonzeros), {model.num_iters} iterations, "
        f"{counted.get('lbfgs.evals')} evaluations, accuracy {acc:.3f} (the commonest class {base:.3f}), wall {wall:.1f}s"
    )
    check(model.num_iters == 10, f"{model.num_iters} iterations of 10")
    check(REHEARSAL or counted.get("lbfgs.ell_pass_fits") == 1, f"the fit did not count the one-pass kernel: {counted}")
    check(REHEARSAL or counted.get("ell.slots") == rows * 32, f"ell.slots {counted.get('ell.slots')}")
    check(acc > base + 0.05, f"accuracy {acc:.3f} is no better than the commonest class's {base:.3f}")
    return {"iters": int(model.num_iters), "accuracy": acc, "wall_s": wall}


def stage_kernels(seed):
    out = {"min_dist": kernel_min_dist(seed)}
    release()
    out["forest"] = kernel_forest(seed)
    release()
    out["forest_reg"] = kernel_forest_reg(seed)
    release()
    out["forest_gather"] = kernel_forest_gather(seed)
    release()
    out["knn"] = kernel_knn(seed)
    release()
    out["pq8"] = kernel_pq(seed, 8)
    out["pq4"] = kernel_pq(seed, 4)
    release()
    out["cd_sweep"] = kernel_cd_sweep(seed)
    release()
    out["gram_triangle"] = kernel_gram_triangle(seed)
    release()
    out["softmax_ell"] = kernel_softmax_ell(seed)
    out["fit_sparse"] = fit_sparse(seed)
    release()
    out["lloyd_tall"] = kernel_lloyd_tall(seed)
    return out


# -- stage 4: the whole mesh --------------------------------------------------


def mesh_fit(made, seed, n_dev):
    _, _, out = fit_device_leg(seed, n_dev)
    # a mesh fit of the host rows equals stage 1's one-device fit of them
    wide = fit_host_leg(made["df_host"], seed, n_dev)
    same = np.isclose(
        wide.cluster_centers_, made["host_model"].cluster_centers_,
        rtol=1e-4, atol=1e-4,
    ).all(axis=1)
    log(f"mesh fit vs one-device fit: {int(same.sum())}/{same.size} centers equal")
    # partial sums combine in another order on a mesh; a row whose two
    # nearest centers tie to the last bit may then switch sides, which
    # moves those two centers and no others
    check(same.mean() >= 0.99, f"only {same.mean():.4f} of centers equal")
    out["centers_equal_to_one_device"] = float(same.mean())
    return out


def mesh_tall_fit(seed, n_dev):
    """KMeans on a feature-major table (ops/tall.TallMatrix) sharded over the
    mesh, a quarter of the one-chip cell's rows a chip (the deployment is
    25,000,000 a chip, which one chip could not hold four times over for the
    comparison), against the one-chip fit of the same rows.  Untimed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu import KMeans
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.ops.tall import TallMatrix, padded_features
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

    rows, cols, k = S["tall_rows_per_chip"], S["tall_cols"], S["tall_k"]
    mesh = get_mesh(n_dev)
    centers = jax.random.uniform(jax.random.key(seed), (k, cols), jnp.float32, -10.0, 10.0)

    def per_device(c):
        ka, kn = jax.random.split(jax.random.fold_in(jax.random.key(seed + 1), jax.lax.axis_index(DATA_AXIS)))
        xt = c.T[:, jax.random.randint(ka, (rows,), 0, k)] + jax.random.normal(kn, (cols, rows), jnp.float32)
        return jnp.pad(xt, ((0, padded_features(cols) - cols), (0, 0)))

    xt = jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=P(), out_specs=P(None, DATA_AXIS), check_vma=False,
    ))(centers)
    check(
        len({s.device.id for s in xt.addressable_shards}) == n_dev
        and xt.addressable_shards[0].data.shape == (padded_features(cols), rows),
        f"xt is not one ({padded_features(cols)}, {rows}) shard a device",
    )

    def fit(table, workers):
        est = KMeans(k=k, initMode="random", tol=0.0, maxIter=S["fit_iters"], seed=seed, num_workers=workers)
        model = est.fit(DataFrame.from_device(table))
        counters = model.fit_telemetry().counters
        check(counters.get("lloyd.tall_fits") == 1, "the fit did not take Lloyd's tall pass")
        check(counters.get("lloyd.tall_kernel_fits") == 1, "the fit's update passes did not take the kernel")
        return model

    wide = fit(TallMatrix(xt, cols), n_dev)
    one = fit(TallMatrix(jax.device_put(xt, jax.devices()[0]), cols), 1)
    check(wide.n_iter_ == one.n_iter_ == S["fit_iters"], f"iterations {wide.n_iter_} and {one.n_iter_}")
    gap = float(np.abs(wide.cluster_centers_ - one.cluster_centers_).max())
    cost = abs(wide.inertia_ - one.inertia_) / one.inertia_
    log(f"tall fit[{n_dev} dev] {xt.shape[1]} x {cols} k={k}: centres differ by {gap:.3g}, inertia by {cost:.3g} of itself")
    # partial sums combine in another order on a mesh, and a row whose two nearest
    # centres tie to the last bit may switch sides
    check(gap <= 1e-3 and cost <= 1e-5, f"mesh and one-chip tall fits differ: centres {gap}, inertia {cost}")
    return {"rows": int(xt.shape[1]), "n_iter": wide.n_iter_, "centres_gap": gap, "inertia_gap": cost}


def mesh_knn(seed, n_dev):
    """kneighbors over the mesh: the default ring exchange."""
    from spark_rapids_ml_tpu import NearestNeighbors, profiling
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.parallel import exchange

    n, q, k = S["knn_items"], S["knn_queries"], S["knn_k"]
    rng = np.random.default_rng(seed + 50)
    items = rng.standard_normal((n, S["cols"]), dtype=np.float32)
    queries = rng.standard_normal((q, S["cols"]), dtype=np.float32)
    ring0 = profiling.counter("knn.exchange_route.ring")
    nn = NearestNeighbors(k=k).fit(DataFrame.from_numpy(items))
    overlap = check_knn(nn, items, queries, k, f"kNN ring[{n_dev}]")
    ring = profiling.counter("knn.exchange_route.ring") - ring0
    routes = profiling.counters("knn.exchange_route.")
    log(f"kneighbors exchange routes: {routes}")
    check(ring > 0, f"kneighbors did not take the ring route: {routes}")
    require_route(
        "ring hop",
        "remote_dma" if exchange._remote_dma_enabled() else "ppermute",
        "remote_dma",
    )
    return {"overlap": overlap, "ring_dispatches": int(ring)}


def mesh_linreg(seed, n_dev):
    """Elastic-net LinearRegression over the mesh: the statistics are
    replicated, so the sweep kernel runs per device under a shard_map.  One
    fit against the one-device fit of the same rows, fitMultiple's maps on one
    statistics pass, and a CrossValidator's sweep (ops/glm's lax.map over
    folds and candidates) against the fold-by-fold route."""
    import jax

    from spark_rapids_ml_tpu import LinearRegression
    from spark_rapids_ml_tpu.dataframe import DataFrame
    from spark_rapids_ml_tpu.evaluation import RegressionEvaluator
    from spark_rapids_ml_tpu.ops import cd_sweep
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    reg = S["cd_reg"]
    X, y = regression_rows(get_mesh(n_dev), S["cd_rows"], S["cols"], seed + 70)
    G = jax.ShapeDtypeStruct((S["cols"], S["cols"]), X.dtype)
    require_route("cd_sweep on the mesh", "pallas" if cd_sweep.takes(G) else "xla", "pallas")
    df = labelled(X, y)
    wide = enet(n_dev, reg).fit(df)
    one = enet(1, reg).fit(labelled(jax.device_put(X, jax.devices()[0]), y))
    zeros = int((np.asarray(wide.coef_) == 0).sum())
    log(f"elastic net[{n_dev} dev]: {wide.num_iters} sweeps, {zeros} coefficients exactly 0")
    check(zeros >= S["cols"] // 4, f"{zeros} zeros, the penalty does not bite")
    check(same_fit(wide, one), "the mesh's elastic-net fit is not the one-device fit")
    maps = [{LinearRegression.regParam: r} for r in (reg, 2 * reg)]
    many = dict(enet(n_dev, reg).fitMultiple(df, maps))
    check(
        np.array_equal(many[0].coef_, wide.coef_),
        "fitMultiple's first map is not the single fit",
    )
    check(
        (np.asarray(many[1].coef_) == 0).sum() >= zeros
        and np.abs(many[1].coef_).sum() < np.abs(wide.coef_).sum(),
        "twice the penalty does not shrink the coefficients",
    )
    grid = ParamGridBuilder().addGrid(LinearRegression.regParam, [reg, 8.0]).build()
    # a validator scores folds from host partitions: one device's rows
    n = S["cd_rows"]
    host = DataFrame.from_numpy(
        np.asarray(X[:n]), y=np.asarray(y[:n]), num_partitions=S["host_parts"]
    )
    scores = {}
    for batched in ("1", "0"):
        os.environ["SRML_SWEEP_BATCH"] = batched
        try:
            cv = CrossValidator(
                estimator=enet(n_dev, reg), estimatorParamMaps=grid,
                evaluator=RegressionEvaluator(metricName="rmse"), numFolds=3,
                seed=seed,
            )
            scores[batched] = cv.fit(host).avgMetrics
        finally:
            del os.environ["SRML_SWEEP_BATCH"]
    log(f"tuning sweep rmse, batched {scores['1']}, fold by fold {scores['0']}")
    check(scores["1"][0] < scores["1"][1], "the small penalty does not win")
    check(
        np.allclose(scores["1"], scores["0"], rtol=1e-4),
        "the batched sweep's scores are not the fold-by-fold route's",
    )
    return {"zeros": zeros, "sweeps": wide.num_iters, "cv_rmse": scores["1"]}


def mesh_replicas(made, n_dev):
    """One-chip replicas in this one process, each on its own device."""
    import jax

    from spark_rapids_ml_tpu.serving import Router, SlicePool

    model, held, pred = made["model"], made["held"], made["pred"]
    shape = model.cluster_centers_.shape
    pool = SlicePool(slice_devices=1)
    router = Router(replicas=n_dev, pool=pool)
    try:
        replicas = router.serve("smoke", model)
        check(len(replicas) == n_dev, f"{len(replicas)} replicas for {n_dev}")
        for srv in replicas:
            ans = srv.submit(held[:64]).result(timeout=300)
            check(
                np.array_equal(np.asarray(ans["prediction"]), pred[:64]),
                f"replica {srv.name} differs from model.transform",
            )
        homes = sorted({
            next(iter(a.devices())).id
            for a in jax.live_arrays()
            if a.shape == shape and len(a.devices()) == 1
        })
        check(
            len(homes) >= n_dev,
            f"replica centers live on devices {homes}, expected {n_dev} distinct",
        )
        routed = router.submit("smoke", held[:64]).result(timeout=300)
        check(
            np.array_equal(np.asarray(routed["prediction"]), pred[:64]),
            "routed answer differs from model.transform",
        )
    finally:
        router.shutdown()
        pool.close()
    return {"replicas": n_dev, "devices": homes}


def stage_mesh(made, seed):
    import jax

    n_dev = jax.device_count()
    out = {"fit": mesh_fit(made, seed, n_dev)}
    release()
    out["tall_fit"] = mesh_tall_fit(seed, n_dev)
    release()
    out["knn"] = mesh_knn(seed, n_dev)
    release()
    out["linreg"] = mesh_linreg(seed, n_dev)
    release()
    out["replicas"] = mesh_replicas(made, n_dev)
    return out


# -- main ---------------------------------------------------------------------


def main():
    global REHEARSAL, S
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="toy sizes on any backend; debugs this script, proves nothing",
    )
    args = ap.parse_args()
    REHEARSAL = args.rehearsal
    if REHEARSAL:
        S = TOY_SIZES
        log("REHEARSAL: toy sizes, TPU routes not required, proves nothing")

    import jax

    from spark_rapids_ml_tpu import profiling

    t_start = time.perf_counter()
    device, cache_dir = stage_device()
    stages = {}

    def run(name, fn, *a):
        t0 = time.perf_counter()
        log(f"stage {name} ...")
        out = fn(*a)
        log(f"stage {name} ok in {time.perf_counter() - t0:.1f}s")
        return out

    made, stages["fit"] = run("1 fit", stage_fit, args.seed)
    stages["serve"] = run("2 serve", stage_serve, made, args.seed)
    stages["kernels"] = run("3 kernels", stage_kernels, args.seed)
    if jax.device_count() >= 4:
        stages["mesh"] = run("4 mesh", stage_mesh, made, args.seed)
    else:
        stages["mesh"] = "not run: fewer than 4 devices"

    compile_counts = {
        "precompile.compile": profiling.counter("precompile.compile"),
        "precompile.aot_hit": profiling.counter("precompile.aot_hit"),
        "precompile.aot_miss": profiling.counter("precompile.aot_miss"),
        "precompile.fallback": profiling.counter("precompile.fallback"),
        "cache_dir": cache_dir,
        "cache_files_at_end": cache_file_count(cache_dir),
    }
    check(
        compile_counts["precompile.fallback"] == 0,
        f"{compile_counts['precompile.fallback']} AOT compile fallbacks",
    )
    summary = {
        "stages": stages,
        "compile": compile_counts,
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    if REHEARSAL:
        summary["rehearsal"] = True
        print(json.dumps(summary), flush=True)
        log("REHEARSAL done: no result line, nothing was proven")
        return
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
