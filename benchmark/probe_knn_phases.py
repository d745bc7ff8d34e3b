# Phase-level hardware probe for the adaptive kNN block at the bench shape.
# Times each device phase around block_until_ready.  Not part of CI — run
# manually:
#   python benchmark/probe_knn_phases.py [n] [d] [k]
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sync = jax.block_until_ready


def main():
    from spark_rapids_ml_tpu.ops.precompile import ensure_compile_cache

    ensure_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 400_000
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 3000
    k = int(sys.argv[3]) if len(sys.argv) > 3 else 200
    q_n = 8192

    from spark_rapids_ml_tpu.ops import knn as knn_mod
    from spark_rapids_ml_tpu.ops.pallas_knn import knn_candidates_pallas
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    rng = np.random.default_rng(0)
    X = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q_n, d)).astype(np.float32)
    mesh = get_mesh()
    prepared = knn_mod.prepare_items(X, np.arange(n, dtype=np.int64), mesh)
    qd = jnp.asarray(Q)
    if qd.shape[1] != prepared.items.shape[1]:
        qd = jnp.pad(qd, ((0, 0), (0, prepared.items.shape[1] - qd.shape[1])))
    n_pad = prepared.items.shape[0]
    m = knn_mod._select_m(k, 1024, n_pad)
    print(f"n_pad={n_pad} d_pad={prepared.items.shape[1]} m={m}")

    def timeit(label, fn, reps=3):
        fn()  # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        print(f"{label:>28}: {min(ts):.3f}s  (reps {['%.3f' % t for t in ts]})")

    cv, ci = knn_candidates_pallas(
        prepared.items, prepared.norm, prepared.valid, qd, k, m, n_pad
    )
    sync(cv)

    for tq, ti, td in (
        (256, 1024, 3072), (512, 1024, 3072), (1024, 1024, 3072),
        (128, 1024, 3072),
    ):
        try:
            timeit(
                f"candidates tq={tq} ti={ti} td={td}",
                lambda tq=tq, ti=ti, td=td: sync(
                    knn_candidates_pallas(
                        prepared.items, prepared.norm, prepared.valid, qd,
                        k, m, n_pad, tile_q=tq, tile_i=ti, tile_d=td,
                    )[0]
                ),
            )
        except Exception as e:  # VMEM overflow at large tiles
            print(f"tq={tq} ti={ti} td={td}: {type(e).__name__}: {str(e)[:160]}")
    timeit(
        "merge_self",
        lambda: sync(
            knn_mod._adaptive_merge_self(cv, ci, k, m=m)[0]
        ),
    )
    timeit(
        "full dispatch+collect",
        lambda: sync(
            knn_mod.knn_block_adaptive_dispatch(
                prepared.items, prepared.norm, prepared.pos, prepared.valid,
                qd, mesh, k,
            )[0]
        ),
    )


if __name__ == "__main__":
    main()
