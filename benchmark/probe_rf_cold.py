# Phase-level breakdown of the rf_clf cold fit at the bench shape.
# Instruments wall-clock around the major fit stages by wrapping them.
# Run manually: python benchmark/probe_rf_cold.py [rows]
import sys
import time

import numpy as np

import jax

ROWS = int(sys.argv[1]) if len(sys.argv) > 1 else 400_000
SEED = int(sys.argv[2]) if len(sys.argv) > 2 else 0
COLS = 3000

marks = []


def mark(label):
    marks.append((label, time.perf_counter()))


def wrap(mod, name):
    real = getattr(mod, name)

    def shim(*a, **k):
        t0 = time.perf_counter()
        out = real(*a, **k)
        print(f"  {name:>28}: {time.perf_counter() - t0:7.2f}s", flush=True)
        return out

    setattr(mod, name, shim)


def main():
    import spark_rapids_ml_tpu.models.random_forest as rf_mod
    import spark_rapids_ml_tpu.ops.forest_mxu as fmxu
    from spark_rapids_ml_tpu import RandomForestClassifier
    from spark_rapids_ml_tpu.dataframe import DataFrame

    # wrap the BINDINGS random_forest actually calls (module-local names),
    # covering both the host-gather and the device-edges paths
    wrap(rf_mod, "_binning_sample")
    wrap(rf_mod, "_binning_sample_device")
    wrap(rf_mod, "compute_bin_edges")
    wrap(rf_mod, "compute_bin_edges_device")
    wrap(rf_mod, "bin_features_feature_major")
    wrap(fmxu, "grow_forest_mxu")

    t0 = time.perf_counter()
    import jax.numpy as jnp

    rng = np.random.default_rng(SEED)
    coef = np.zeros(COLS, np.float32)
    coef[rng.choice(COLS, 10, replace=False)] = rng.standard_normal(10).astype(
        np.float32
    )

    def _gen(key):
        kx, kn = jax.random.split(key)
        X = jax.random.normal(kx, (ROWS, COLS), jnp.float32)
        y = X @ jnp.asarray(coef) + 0.1 * jax.random.normal(kn, (ROWS,))
        return X, (y > 0).astype(jnp.float32)

    Xs, ys = jax.jit(lambda s: _gen(jax.random.PRNGKey(s)))(42 + SEED)
    float(np.asarray(Xs.sum()))
    df = DataFrame.from_device(Xs, y=np.asarray(ys))
    print(f"device datagen: {time.perf_counter() - t0:.2f}s", flush=True)

    est = RandomForestClassifier(
        numTrees=50, maxDepth=13, maxBins=128, featureSubsetStrategy="sqrt",
        seed=42,
    )
    t0 = time.perf_counter()
    model = est.fit(df)
    print(f"COLD FIT TOTAL: {time.perf_counter() - t0:.2f}s", flush=True)
    t0 = time.perf_counter()
    est.fit(df)
    print(f"warm fit: {time.perf_counter() - t0:.2f}s", flush=True)


if __name__ == "__main__":
    main()
