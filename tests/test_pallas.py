# Pallas TPU kernel correctness (interpreter mode on the CPU test mesh).
# The same kernel compiles with Mosaic on real TPU; the hardware-exactness
# A/B record (v5e, argmin mismatch 0 vs the XLA path) is quoted in the
# ops/pallas_tpu.py module header.  SRML_TPU_TESTS=1 re-runs the kernel
# tests compiled by Mosaic (interpret=False) on a real chip — ONE command,
# one process:
#   SRML_TPU_TESTS=1 python -m pytest tests/test_pallas.py \
#     tests/test_forest_mxu.py tests/test_pq_engine.py \
#     tests/test_knn_audit.py --runslow -q
# (never with the tests that spawn jax children: tests/conftest.py header).
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.pallas_tpu import (
    DISABLE_ENV,
    _min_dist_argmin_pallas,
    _min_dist_argmin_xla,
    min_dist_argmin,
    pallas_enabled,
)

# On a real TPU run the compiled Mosaic kernel; on the CPU mesh interpret.
ON_TPU = jax.devices()[0].platform == "tpu"
KERNEL_INTERPRET = not ON_TPU


@pytest.mark.parametrize(
    "n,d,k",
    [
        (300, 70, 33),     # nothing aligned
        (512, 256, 128),   # everything aligned
        (129, 1, 2),       # degenerate feature dim
        (64, 515, 700),    # k > n, unaligned d
    ],
)
def test_min_dist_argmin_matches_xla(n, d, k):
    rng = np.random.default_rng(n + d + k)
    X = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    C = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    xn = (X**2).sum(axis=1)
    cn = (C**2).sum(axis=1)
    md, am = _min_dist_argmin_pallas(X, C, xn, cn, interpret=KERNEL_INTERPRET)
    md_ref, am_ref = _min_dist_argmin_xla(X, C, xn, cn)
    assert md.shape == (n,) and am.shape == (n,)
    # padded center slots (norm=+inf) must never win
    assert int(np.asarray(am).max()) < k
    np.testing.assert_array_equal(np.asarray(am), np.asarray(am_ref))
    np.testing.assert_allclose(
        np.asarray(md), np.asarray(md_ref), rtol=1e-4, atol=1e-4
    )


def test_min_dist_argmin_precomputed_norms():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((100, 40)).astype(np.float32))
    C = jnp.asarray(rng.standard_normal((7, 40)).astype(np.float32))
    xn = (X**2).sum(axis=1)
    cn = (C**2).sum(axis=1)
    md1, am1 = min_dist_argmin(X, C, xn, cn, interpret=KERNEL_INTERPRET)
    md2, am2 = min_dist_argmin(X, C, interpret=KERNEL_INTERPRET)
    np.testing.assert_array_equal(np.asarray(am1), np.asarray(am2))
    np.testing.assert_allclose(np.asarray(md1), np.asarray(md2), rtol=1e-5)


def test_pallas_disabled_by_env(monkeypatch):
    monkeypatch.setenv(DISABLE_ENV, "1")
    assert not pallas_enabled()


@pytest.mark.parametrize(
    "n,d,k,expect_pallas",
    [
        (4096, 64, 4096, True),    # low-d, large-k: memory-bound, pallas wins
        (4096, 64, 512, False),    # small k: distance matrix cheap
        (4096, 512, 4096, False),  # wide d: FLOPs dominate, XLA wins
        (256, 64, 4096, False),    # batch below one row tile
    ],
)
def test_min_dist_argmin_routing(monkeypatch, n, d, k, expect_pallas):
    # the heuristic itself, independent of backend: force pallas_enabled and
    # record which implementation min_dist_argmin dispatches to
    import spark_rapids_ml_tpu.ops.pallas_tpu as pt

    calls = []
    monkeypatch.setattr(pt, "pallas_enabled", lambda: True)
    monkeypatch.setattr(
        pt,
        "_min_dist_argmin_pallas",
        lambda *a, **kw: calls.append("pallas"),
    )
    monkeypatch.setattr(
        pt, "_min_dist_argmin_xla", lambda *a, **kw: calls.append("xla")
    )
    X = jnp.zeros((n, d), jnp.float32)
    C = jnp.zeros((k, d), jnp.float32)
    pt.min_dist_argmin(X, C)
    assert calls == (["pallas"] if expect_pallas else ["xla"])


def test_cpu_fallback_is_xla_path():
    # on the CPU test mesh, min_dist_argmin without interpret must route to
    # the XLA formulation and still be correct
    if jax.devices()[0].platform == "tpu":
        pytest.skip("CPU-only routing test")
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((50, 9)).astype(np.float32))
    C = jnp.asarray(rng.standard_normal((4, 9)).astype(np.float32))
    md, am = min_dist_argmin(X, C)
    brute = np.argmin(
        ((np.asarray(X)[:, None, :] - np.asarray(C)[None]) ** 2).sum(-1), axis=1
    )
    np.testing.assert_array_equal(np.asarray(am), brute)


# -- fused kNN distance + per-group top-m kernel (ops/pallas_knn.py) ---------

from spark_rapids_ml_tpu.ops.pallas_knn import knn_candidates_pallas
from spark_rapids_ml_tpu.ops.knn import _adaptive_merge_self, _select_m


def _knn_pool_topk(items, norms, valid, Q, k, m):
    """Run the pallas candidate kernel + the self-verified exact merge
    (the production route, including the pallas m_pad pool stride); return
    host (distances ascending, positions).  Asserts no overflow flag fired
    — with _select_m-sized (or >= k) budgets on these shapes the pool
    provably contains the exact top-k."""
    cv, ci = knn_candidates_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, items.shape[0],
        interpret=KERNEL_INTERPRET,
    )
    fv, fpos, flags, _z = _adaptive_merge_self(cv, ci, k, m=m)
    assert not np.asarray(flags).any()
    return np.asarray(fv), np.asarray(fpos)  # fv is distances already


@pytest.mark.parametrize(
    "n,d,q,k",
    [
        (2048, 128, 256, 16),    # aligned everything
        (2100, 300, 256, 10),    # ragged N (last group) and ragged D tail
        (2560, 515, 384, 33),    # unaligned d, ragged N, q above one tile
        (1024, 64, 130, 7),      # q pads up to a tile
    ],
)
def test_knn_candidates_pool_contains_exact_topk(n, d, q, k):
    """The merged candidate pool must reproduce the exact top-k whenever no
    group overflowed m — with m from _select_m on shuffled data, overflow
    probability at these sizes is ~0, so the comparison is deterministic in
    practice; rows that would overflow are exactly what the count-verify
    phase catches in production."""
    rng = np.random.default_rng(n + d + k)
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)  # small n: one group may hold all k
    dists, pos = _knn_pool_topk(items, norms, valid, Q, k, m)
    d2 = ((Q[:, None, :] - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(dists, want, rtol=1e-3, atol=1e-3)
    # positions agree except on genuine distance ties
    agree = (pos == order).mean()
    assert agree > 0.95, agree


def test_knn_candidates_masks_invalid_rows():
    rng = np.random.default_rng(5)
    n, d, q, k = 1536, 96, 128, 8
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = items[:q] + 1e-3  # near-duplicates force tight distances
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    valid[700:] = False  # half the set invalid (padding rows)
    m = max(_select_m(k, 1024, 700), k)
    dists, pos = _knn_pool_topk(items, norms, valid, Q, k, m)
    assert int(pos.max()) < 700, "an invalid row entered the top-k"
    assert np.isfinite(dists).all()


def test_knn_candidates_duplicate_distances_stay_distinct():
    """Position-masked selection: duplicated items must occupy separate
    candidate slots (value-masking would collapse them)."""
    rng = np.random.default_rng(9)
    n, d, k = 1024, 64, 6
    base = rng.standard_normal((n // 2, d)).astype(np.float32)
    items = np.concatenate([base, base])  # every item duplicated
    Q = base[:128]
    norms = (items**2).sum(axis=1)
    m = max(_select_m(k, 1024, n), k)
    dists, pos = _knn_pool_topk(items, norms, np.ones(n, bool), Q, k, m)
    # the query IS an item (distance 0), and its duplicate must also be in
    # the top-k with distance ~0.  The norm-expansion form cancels
    # catastrophically at zero distance (|d2| residual ~|q|^2 * 2^-19 under
    # 3-pass bf16 -> sqrt up to ~3e-2 at d=64, varying with fusion/rounding
    # across compiles) — the STRUCTURAL claim is what is exact: both
    # duplicate slots present, congruent positions.
    assert np.allclose(dists[:, 0], 0, atol=5e-2)
    assert np.allclose(dists[:, 1], 0, atol=5e-2)
    assert (pos[:, 0] % (n // 2) == pos[:, 1] % (n // 2)).all()


# -- fused merge epilogue (ops/pallas_knn.knn_fused_pallas) ------------------

from spark_rapids_ml_tpu.ops.pallas_knn import knn_fused_pallas


def _lex_oracle(items, Q, k):
    """numpy lexicographic (d2, pos) top-k oracle: unique total order, so
    the comparison against the fused kernel is EXACT on positions whenever
    d2 bits agree — and on crafted integer-valued data they do."""
    d2 = ((Q[:, None, :].astype(np.float64)
           - items[None].astype(np.float64)) ** 2).sum(-1)
    order = np.lexsort((np.arange(items.shape[0])[None].repeat(len(Q), 0),
                        d2), axis=1)[:, :k]
    return np.sqrt(np.take_along_axis(d2, order, axis=1)), order


@pytest.mark.parametrize(
    "n,d,q,k",
    [
        (2048, 128, 256, 16),   # aligned
        (2100, 300, 256, 10),   # ragged N and ragged D tail
        (1024, 64, 130, 7),     # q pads up to a tile
    ],
)
def test_knn_fused_epilogue_matches_merge_and_oracle(n, d, q, k):
    """The fused merge kernel must agree with the XLA merge route
    (identical pool in, identical distances out) AND with brute force."""
    rng = np.random.default_rng(n + d + k)
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)
    dist, pos, flags, zeros = knn_fused_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET,
    )
    assert not np.asarray(flags).any() and not np.asarray(zeros).any()
    # route parity: same pool -> same distances as the XLA merge
    fv_d, _fv_p = _knn_pool_topk(items, norms, valid, Q, k, m)
    np.testing.assert_allclose(np.asarray(dist), fv_d, rtol=1e-5, atol=1e-6)
    # ground truth
    d2 = ((Q[:, None, :] - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(np.asarray(dist), want, rtol=1e-3, atol=1e-3)
    assert (np.asarray(pos) == order).mean() > 0.95


def test_knn_fused_epilogue_lex_tie_contract():
    """The tie contract vs the numpy oracle: on integer-valued data with
    every item DUPLICATED, d2 values tie in pairs and the fused merge must
    return the lexicographically smaller position first — exact equality
    against np.lexsort, not a tolerance check."""
    rng = np.random.default_rng(11)
    n, d, q, k = 1024, 128, 128, 8
    base = rng.integers(-3, 4, size=(n // 2, d)).astype(np.float32)
    items = np.concatenate([base, base])     # every distance tied pairwise
    Q = base[:q].astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)
    dist, pos, flags, _z = knn_fused_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET,
    )
    assert not np.asarray(flags).any()
    want_d, want_pos = _lex_oracle(items, Q, k)
    # integer-valued inputs: the 3-pass bf16 dot is exact, so positions
    # must match the lex oracle EXACTLY — including which duplicate of
    # each tied pair comes first
    np.testing.assert_array_equal(np.asarray(pos), want_pos)
    np.testing.assert_allclose(np.asarray(dist), want_d, rtol=1e-5, atol=1e-5)


def test_knn_fused_epilogue_multi_kblock():
    """nb > 1 K-block geometry through the fused route: tile_d=128 at
    d=330 (d_pad=384 -> 3 K blocks) must keep the same results as the
    single-block default."""
    rng = np.random.default_rng(13)
    n, d, q, k = 1056, 330, 128, 6
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)
    out_multi = knn_fused_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET, tile_d=128,
    )
    d2 = ((Q[:, None, :] - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(
        np.asarray(out_multi[0]), want, rtol=1e-3, atol=1e-3
    )
    assert (np.asarray(out_multi[1]) == order).mean() > 0.95


def test_knn_fused_epilogue_flags_route_exact_fallback():
    """Forced self-verify failure through the fused path: an m far below
    the _select_m envelope with the whole true top-k packed into ONE item
    group must (a) raise the in-kernel overflow flag and (b) come back
    EXACT after knn_block_adaptive_collect's per-row rerun."""
    import jax

    from jax.sharding import Mesh
    from spark_rapids_ml_tpu.ops.knn import knn_block_adaptive_collect
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    rng = np.random.default_rng(17)
    n, d, q, k, m = 2048, 128, 128, 10, 4
    items = rng.standard_normal((n, d)).astype(np.float32) + 50.0
    Q = rng.standard_normal((q, d)).astype(np.float32)
    # rows 0..k-1 live in group 0 and are the UNIQUE top-k of every query:
    # group 0 keeps only m=4 of them, so the merged list misses 6 and the
    # worst-kept-vs-threshold flag MUST fire
    items[:k] = Q[:k].mean(axis=0) + 0.01 * rng.standard_normal(
        (k, d)
    ).astype(np.float32)
    Q[:] = items[:k].mean(axis=0) + 0.01 * rng.standard_normal(
        (q, d)
    ).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    handles = knn_fused_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET,
    )
    flags = np.asarray(handles[2])
    assert flags.any(), "crafted overflow did not raise the fused flag"
    mesh = Mesh(np.array(jax.devices()[:1]), (DATA_AXIS,))
    d_out, p_out = knn_block_adaptive_collect(
        handles,
        jnp.asarray(items), jnp.asarray(norms),
        jnp.arange(n, dtype=jnp.int32), jnp.asarray(valid),
        jnp.asarray(Q), mesh, k,
    )
    d2 = ((Q[:, None, :].astype(np.float64) - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(d_out, want, rtol=1e-3, atol=1e-3)
    assert (p_out == order).mean() > 0.95


# -- fused feature binning kernel (ops/pallas_tpu.bin_features_fm_pallas) ----

from spark_rapids_ml_tpu.ops.pallas_tpu import bin_features_fm_pallas


@pytest.mark.parametrize(
    "n,d,b,n_pad",
    [
        (1024, 512, 128, 1024),   # aligned, max int8 bins
        (700, 300, 16, 1024),     # ragged rows+cols, padded target
        (513, 130, 64, 520),      # everything unaligned
    ],
)
def test_bin_features_pallas_matches_xla(n, d, b, n_pad):
    from spark_rapids_ml_tpu.ops.forest import _bin_chunk_t

    rng = np.random.default_rng(n + d + b)
    X = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    edges = jnp.asarray(
        np.sort(rng.standard_normal((d, b - 1)).astype(np.float32), axis=1)
    )
    got = np.asarray(
        bin_features_fm_pallas(X, edges, n_pad, interpret=KERNEL_INTERPRET)
    )
    want = np.asarray(_bin_chunk_t(X, edges))
    assert got.shape == (d, n_pad)
    np.testing.assert_array_equal(got[:, :n], want)
    assert (got[:, n:] == 0).all(), "padding rows must be bin 0"


def test_knn_audit_pair_runs_and_agrees():
    """The SRML_KNN_AUDIT_COUNT=1 route (legacy candidates kernel + count
    kernel, bitwise-paired) must still run — it is the ground-truth audit
    for the default self-verify route — and agree with it on clean data.
    Regression guard: the count kernel's _neg_d2 call broke when the
    helper moved to value inputs and no default-CI test exercised the
    pallas audit pairing."""
    import spark_rapids_ml_tpu.ops.knn as knn_mod
    from spark_rapids_ml_tpu.ops.pallas_knn import knn_count_pallas

    rng = np.random.default_rng(21)
    n, d, q, k = 1536, 128, 256, 9
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)

    cv, ci = knn_candidates_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET, legacy=True,
    )
    fv, fpos, tu, sg = knn_mod._adaptive_merge(cv, ci, k)
    sa = knn_count_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), tu, n, interpret=KERNEL_INTERPRET,
    )
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(sa))
    # and the audit merge agrees with the self-verify route's results
    fv_s, fpos_s, flags, _z = _adaptive_merge_self(cv, ci, k, m=m)
    assert not np.asarray(flags).any()
    np.testing.assert_allclose(
        np.asarray(fv_s), np.sqrt(np.maximum(-np.asarray(fv), 0)), rtol=1e-5
    )


def test_knn_candidates_qres_multi_kblock_matches_reference():
    """Multi-K-block query-resident kernel (nb > 1): tile_d=1024 at d=3100
    (d_pad=3200) forces several K blocks, the geometry whose previous
    (j, b, i) grid was undefined behavior (output blocks revisited with the
    revisiting dimension NOT innermost — ADVICE medium).  The restructured
    (j, i, b) grid must reproduce BOTH the XLA candidates-scan route and
    the brute-force ground truth through the unchanged self-verified
    merge."""
    import jax.numpy as jnp

    import spark_rapids_ml_tpu.ops.knn as knn_mod

    rng = np.random.default_rng(31)
    n, d, q, k = 1100, 3100, 128, 9
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)

    cv, ci = knn_candidates_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET, tile_d=1024,
    )
    fv, fpos, flags, _z = _adaptive_merge_self(cv, ci, k, m=m)
    assert not np.asarray(flags).any()

    # ground truth
    d2 = ((Q[:, None, :] - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(np.asarray(fv), want, rtol=1e-3, atol=1e-3)
    assert (np.asarray(fpos) == order).mean() > 0.95

    # XLA reference route: same pool contract, same merge
    chunk = min(knn_mod._ADAPTIVE_CHUNK, n)
    cv_x, ci_x = knn_mod._adaptive_candidates_single(
        jnp.asarray(items), jnp.asarray(norms),
        jnp.arange(n, dtype=jnp.int32), jnp.asarray(valid),
        jnp.asarray(Q), k=k, chunk=chunk,
    )
    G, m_x = knn_mod._scan_geometry(k, chunk, n)
    fv_x, fpos_x, flags_x, _zx = _adaptive_merge_self(cv_x, ci_x, k, m=m_x)
    assert not np.asarray(flags_x).any()
    np.testing.assert_allclose(
        np.asarray(fv), np.asarray(fv_x), rtol=1e-3, atol=1e-3
    )
    assert (np.asarray(fpos) == np.asarray(fpos_x)).mean() > 0.95


def test_knn_candidates_qres_multi_kblock_ragged_tail():
    """nb > 1 with a RAGGED D tail (d_pad > d): the qres route must keep
    the zero-padded columns exact no-ops across every K block."""
    import jax.numpy as jnp

    rng = np.random.default_rng(33)
    n, d, q, k = 1056, 330, 128, 6  # d_pad=384; tile_d=128 -> nb=3
    items = rng.standard_normal((n, d)).astype(np.float32)
    Q = rng.standard_normal((q, d)).astype(np.float32)
    norms = (items**2).sum(axis=1)
    valid = np.ones(n, bool)
    m = max(_select_m(k, 1024, n), k)
    cv, ci = knn_candidates_pallas(
        jnp.asarray(items), jnp.asarray(norms), jnp.asarray(valid),
        jnp.asarray(Q), k, m, n, interpret=KERNEL_INTERPRET, tile_d=128,
    )
    fv, fpos, flags, _z = _adaptive_merge_self(cv, ci, k, m=m)
    assert not np.asarray(flags).any()
    d2 = ((Q[:, None, :] - items[None]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    want = np.sqrt(np.take_along_axis(d2, order, axis=1))
    np.testing.assert_allclose(np.asarray(fv), want, rtol=1e-3, atol=1e-3)


# -- the one-pass logistic data term (ops/logistic_pass.py) -------------------
# The fused value and gradient against jax.value_and_grad of the loss L-BFGS
# had (ops/logistic._binary_data_loss) at HIGHEST, the kernel interpreted.


def _logistic_case(n, d, seed, uniform=False, pad=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (rng.random(n) > 0.4).astype(np.float32)
    w = np.ones(n, np.float32) if uniform else (0.25 + rng.random(n)).astype(np.float32)
    if pad:
        w[-pad:] = 0.0           # padding rows: weight 0, and rows that must not count
        X[-pad:] = 1e3 * rng.standard_normal((pad, d)).astype(np.float32)
    theta = (rng.standard_normal(d + 1) / np.sqrt(d)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (X, y, w, theta))


def _autodiff_reference(X, y, w, theta, fit_intercept):
    from spark_rapids_ml_tpu.ops.logistic import _binary_data_loss

    d = X.shape[1]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_binary_data_loss)(
            theta if fit_intercept else theta[:d], X, y, w, d, fit_intercept
        )


def _assert_sums_match(sums, X, y, w, theta, fit_intercept):
    d = X.shape[1]
    b = theta[d] if fit_intercept else jnp.zeros((), jnp.float32)
    loss, g, gb = sums(theta[:d], b)
    f_ref, g_ref = _autodiff_reference(X, y, w, theta, fit_intercept)
    got = jnp.concatenate([g, gb[None]]) if fit_intercept else g
    scale = float(jnp.abs(g_ref).max())
    np.testing.assert_allclose(float(loss / w.sum()), float(f_ref), rtol=2e-6)
    np.testing.assert_allclose(
        np.asarray(got / w.sum()), np.asarray(g_ref), atol=5e-6 * scale
    )


@pytest.mark.parametrize("fit_intercept", [True, False], ids=["intercept", "no_intercept"])
@pytest.mark.parametrize(
    "n,d,tile",
    [
        (512, 7, 256),      # whole tiles, a width under one sublane group
        (700, 7, 256),      # whole tiles and a tail through the plain form
        (100, 7, None),     # under one tile: the plain form alone
        (768, 128, 256),
        (900, 128, 256),
        (90, 128, None),
        (1024, 3000, None), # the benchmark's width: the tile follows from it (512)
        (1100, 3000, None),
        (300, 3000, None),  # under the width's tile: 256 rows in the kernel, 44 past it
    ],
)
def test_logistic_one_pass_matches_autodiff(n, d, tile, fit_intercept):
    from spark_rapids_ml_tpu.ops.logistic_pass import one_pass_sums

    X, y, w, theta = _logistic_case(n, d, seed=n + d)
    sums = one_pass_sums(X, y, w, tile=tile, interpret=KERNEL_INTERPRET)
    _assert_sums_match(sums, X, y, w, theta, fit_intercept)


@pytest.mark.parametrize(
    "uniform,pad",
    [(True, 0), (False, 0), (True, 37), (False, 300)],
    ids=["uniform", "weighted", "uniform_padded", "weighted_padded_past_a_tile"],
)
def test_logistic_one_pass_weights_and_padding_rows(uniform, pad):
    from spark_rapids_ml_tpu.ops.logistic_pass import one_pass_sums

    X, y, w, theta = _logistic_case(840, 40, seed=pad, uniform=uniform, pad=pad)
    sums = one_pass_sums(X, y, w, tile=256, interpret=KERNEL_INTERPRET)
    _assert_sums_match(sums, X, y, w, theta, True)
    if pad:  # the same sums as the table without its padding rows
        keep = 840 - pad
        short = one_pass_sums(X[:keep], y[:keep], w[:keep], tile=256, interpret=KERNEL_INTERPRET)
        for a, b in zip(sums(theta[:40], theta[40]), short(theta[:40], theta[40])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_logistic_row_tile_follows_the_width():
    from spark_rapids_ml_tpu.ops.logistic_pass import row_tile, takes_table

    assert takes_table(400_000, 3000)           # 4.8 GB a device: streamed from HBM
    assert not takes_table(8192, 3000)          # 98 MB: XLA holds it in VMEM
    assert not takes_table(400_000, 20_000)     # too wide for the kernel

    assert row_tile(400_000, 3000) == 512       # 6 MB a buffer of the (3000, tile) block
    assert row_tile(8192, 256) == 2048          # narrow tables: the cap
    assert row_tile(300, 3000) == 256           # never more than the table
    assert row_tile(100, 3000) == 0             # under 128 rows: no kernel
    assert row_tile(400_000, 20_000) == 0       # too wide for the (D, 128) residents


@pytest.mark.skipif(ON_TPU, reason="the 8-device mesh is the CPU suite's")
@pytest.mark.parametrize("n_loc", [256, 300, 96], ids=["whole_tiles", "tail", "plain_only"])
def test_logistic_one_pass_on_the_mesh_is_one_psum(n_loc):
    """Row-sharded over the 8-device mesh: every device walks its own rows, and
    ONE psum (of g, gb and the loss together) joins them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.logistic_pass import one_pass_sums
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, get_mesh

    mesh = get_mesh(8)
    X, y, w, theta = _logistic_case(8 * n_loc, 24, seed=n_loc, pad=50)
    rows = NamedSharding(mesh, P(DATA_AXIS))
    Xs, ys, ws = (jax.device_put(a, rows) for a in (X, y, w))

    def run(theta):
        return one_pass_sums(Xs, ys, ws, mesh, tile=128, interpret=True)(theta[:24], theta[24])

    _assert_sums_match(lambda W, b: jax.jit(run)(theta), X, y, w, theta, True)
    assert str(jax.make_jaxpr(run)(theta)).count("psum") == 1
