# Hardware ground-truth audit of the adaptive kNN's verification contract.
#
# Runs BOTH verification routes at a substantial shape on the real device —
# the default pool-resident self-verify and the SRML_KNN_AUDIT_COUNT=1
# bitwise count pair — and scores each against float64 brute-force ground
# truth for a query sample.  This is the check that caught the round-5
# precision regression (XLA's --xla_allow_excess_precision folding a
# precomputed bf16 hi/lo split to zero): the CPU test suite cannot see
# Mosaic/XLA hardware lowering differences, so run this after ANY change
# to ops/pallas_knn.py or the adaptive phases.
#
#   python benchmark/audit_knn.py [n_items] [d] [k]
#
# run_audit() is the callable core: tests/test_knn_audit.py promotes it
# into the @slow suite (TPU-gated by capability probe, so the audit runs
# on every hardware CI pass instead of only when someone remembers).
import sys

import numpy as np


def run_audit(n_items=200_000, d=3000, k=200, qn=8192, sample_stride=1024):
    """Both adaptive-kNN verification routes vs f64 brute-force truth on a
    query sample; returns a self-describing dict with per-route top-k set
    agreement, the self-verify flag count, the audit count-pair mismatch
    count, and the pass verdict (`ok`: both routes agree > 0.999)."""
    import os

    import jax
    import jax.numpy as jnp

    import spark_rapids_ml_tpu.ops.knn as knn_mod
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh

    rng = np.random.default_rng(123)
    X = rng.standard_normal((n_items, d)).astype(np.float32)
    mesh = get_mesh()
    p = knn_mod.prepare_items(X, np.arange(n_items, dtype=np.int64), mesh)
    Q = X[:qn] + 1e-3  # near-duplicates force tight distances
    qd = jnp.pad(jnp.asarray(Q), ((0, 0), (0, p.items.shape[1] - d)))
    args = (p.items, p.norm, p.pos, p.valid, qd, mesh, k)

    _, fp_s, flags, zeros = jax.device_get(
        knn_mod.knn_block_adaptive_dispatch(*args)
    )
    os.environ["SRML_KNN_AUDIT_COUNT"] = "1"
    try:
        _, fp_a, sg, sa = jax.device_get(
            knn_mod.knn_block_adaptive_dispatch(*args)
        )
    finally:
        del os.environ["SRML_KNN_AUDIT_COUNT"]

    ids_s, ids_a = p.ids[fp_s], p.ids[fp_a]
    Xd = X.astype(np.float64)
    tot_s = tot_a = 0.0
    cnt = 0
    for i in range(0, qn, sample_stride):  # f64 brute force is host-bound
        d2 = ((Xd - Q[i].astype(np.float64)) ** 2).sum(axis=1)
        order = np.argsort(d2)[:k]
        tot_s += len(np.intersect1d(ids_s[i], order)) / k
        tot_a += len(np.intersect1d(ids_a[i], order)) / k
        cnt += 1
    self_agreement = tot_s / cnt
    audit_agreement = tot_a / cnt
    return {
        "n_items": n_items,
        "d": d,
        "k": k,
        "queries_sampled": cnt,
        "self_verify_flags": int((flags != zeros).sum()),
        "audit_count_mismatches": int((sg != sa).sum()),
        "self_agreement": self_agreement,
        "audit_agreement": audit_agreement,
        "ok": self_agreement > 0.999 and audit_agreement > 0.999,
    }


def main():
    from spark_rapids_ml_tpu.ops.precompile import ensure_compile_cache

    ensure_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    d = int(sys.argv[2]) if len(sys.argv) > 2 else 3000
    k = int(sys.argv[3]) if len(sys.argv) > 3 else 200

    res = run_audit(n, d, k)
    print(
        f"self-verify flags: {res['self_verify_flags']}   "
        f"audit count mismatches: {res['audit_count_mismatches']}"
    )
    print(
        f"top-k set agreement vs f64 truth — self: {res['self_agreement']:.5f}   "
        f"audit: {res['audit_agreement']:.5f}"
    )
    print("AUDIT PASS" if res["ok"] else "AUDIT FAIL")
    sys.exit(0 if res["ok"] else 1)


if __name__ == "__main__":
    main()
