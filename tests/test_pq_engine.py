# srml-pq IVF-PQ engine contracts (ann/pq.py + ops/pallas_pq.py + the
# ApproximateNearestNeighbors ivfpq tier): the ADC LUT-accumulation kernel
# EXACT against a numpy oracle in interpret mode, the encode/decode
# round-trip against a numpy argmin/reconstruction oracle (error monotone
# in m_sub), refined recall@10 >= 0.9 vs exact kneighbors at the documented
# defaults (the acceptance gate), BITWISE 1-dev-vs-8-dev parity of probed
# AND refined results, zero-new-compile repeat/warmed searches, the
# k>n / empty-list / -1-sentinel edges the IVF-Flat suite gates, and the
# ivfpq model param surface.
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import ApproximateNearestNeighbors, profiling
from spark_rapids_ml_tpu.ann.ivfflat import recall_at_k
from spark_rapids_ml_tpu.ann.pq import (
    DEFAULT_N_BITS,
    build_ivfpq_packed,
    default_m_sub,
    index_from_packed_pq,
    ivfpq_search_prepared,
    pq_geometry,
    reconstruct,
    warm_pq_probe_kernels,
)
from spark_rapids_ml_tpu.dataframe import DataFrame
from spark_rapids_ml_tpu.ops.knn import knn_search_prepared, prepare_items
from spark_rapids_ml_tpu.ops.pallas_pq import (
    _lut_accumulate_pallas,
    lut_accumulate,
)
from spark_rapids_ml_tpu.parallel.mesh import get_mesh

# On a real TPU (SRML_TPU_TESTS=1) run the compiled Mosaic kernels; on the
# CPU mesh interpret — the tests/test_pallas.py switch.
KERNEL_INTERPRET = jax.devices()[0].platform != "tpu"


def _clustered(n=2500, d=16, n_blobs=24, seed=0):
    rng = np.random.default_rng(seed)
    centers = 20.0 * rng.normal(size=(n_blobs, d))
    lab = rng.integers(0, n_blobs, size=n)
    X = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    ids = np.arange(n, dtype=np.int64) * 7 + 3  # non-contiguous user ids
    return X, ids


@pytest.fixture(scope="module")
def pq_setup():
    """ONE shared build at the DOCUMENTED defaults (default_m_sub,
    n_bits=8, default nlist) on clustered data — the recall, parity, and
    zero-compile gates all score the same index, so the m_sub*ksub
    codebook training cost is paid once per test session."""
    from spark_rapids_ml_tpu.ann.ivfflat import default_nlist

    X, ids = _clustered()
    nlist = default_nlist(X.shape[0])  # 50 at n=2500
    packed = build_ivfpq_packed(
        X, ids, nlist, m_sub=default_m_sub(X.shape[1]),
        n_bits=DEFAULT_N_BITS, seed=1,
    )
    return X, ids, packed


# -- the ADC LUT kernel (interpret mode, exact) -------------------------------


def test_lut_kernel_matches_numpy_adc_oracle():
    """out[b, r] = sum_j T[b, j, codes[b, r, j]] with SEQUENTIAL f32
    accumulation over j — the kernel's select-sum gather is exact (one
    nonzero lane per compare tile), so interpret mode must equal the
    oracle bit for bit, on aligned and ragged row counts and at sub-256
    table widths (n_bits < 8)."""
    rng = np.random.default_rng(5)
    cases = []
    for B, R, m_sub, ksub in [(3, 700, 4, 16), (1, 512, 2, 256), (2, 33, 8, 5)]:
        T = rng.standard_normal((B, m_sub, ksub)).astype(np.float32)
        C = rng.integers(0, ksub, size=(B, R, m_sub)).astype(np.uint8)
        want = np.zeros((B, R), np.float32)
        for j in range(m_sub):
            want += np.take_along_axis(
                T[:, j, :], C[:, :, j].astype(np.int64), axis=1
            )
        cases.append(
            (
                (B, R, m_sub, ksub),
                want,
                _lut_accumulate_pallas(
                    jnp.asarray(T), jnp.asarray(C), interpret=KERNEL_INTERPRET
                ),
                # the routed entry (XLA on this backend) computes the same
                # sum to float tolerance — the route is per-backend, never
                # per-mesh, so this is a formulation check, not parity
                lut_accumulate(jnp.asarray(T), jnp.asarray(C)),
            )
        )
    fetched = jax.device_get([(p, x) for *_a, p, x in cases])  # ONE fetch
    for (shape, want, *_h), (got, got_xla) in zip(cases, fetched):
        np.testing.assert_array_equal(got, want, err_msg=f"{shape}")
        np.testing.assert_allclose(got_xla, want, rtol=1e-6, atol=1e-6)


# -- encode / decode round-trip -----------------------------------------------


def test_encode_matches_numpy_argmin_oracle():
    """Per-subspace codes must pick each residual's nearest codeword (the
    fused distance+argmin kernel vs a numpy expanded-form oracle; a >=
    99.9%% match bar absorbs low-bit argmin ties on near-equidistant
    codewords, which both sides resolve arbitrarily)."""
    X, ids = _clustered(n=600, d=8, n_blobs=8, seed=3)
    packed = build_ivfpq_packed(X, ids, 8, m_sub=2, n_bits=4, seed=2)
    m_sub, dsub, d_pad = pq_geometry(packed.dim, packed.m_sub)
    # residuals of the PACKED (list-sorted) items against their coarse cell
    row_list = np.repeat(np.arange(packed.counts.shape[0]), packed.counts)
    cpad = np.zeros((packed.centroids.shape[0], d_pad), np.float32)
    cpad[:, : packed.dim] = packed.centroids
    res = np.zeros((packed.items.shape[0], d_pad), np.float32)
    res[:, : packed.dim] = packed.items
    res -= cpad[row_list]
    match = 0
    for j in range(m_sub):
        rj = res[:, j * dsub : (j + 1) * dsub]
        cb = packed.codebooks[j]
        d2 = (
            (rj**2).sum(1)[:, None]
            - 2.0 * rj @ cb.T
            + (cb**2).sum(1)[None, :]
        )
        match += (np.argmin(d2, axis=1) == packed.codes[:, j]).sum()
    assert match / (res.shape[0] * m_sub) >= 0.999


def test_reconstruction_error_monotone_in_m_sub():
    """Decode round-trip: reconstruction MSE must shrink as m_sub grows
    (more codes per item = finer residual quantization) and always beat
    the coarse-only reconstruction."""
    X, ids = _clustered(n=800, d=8, n_blobs=6, seed=4)
    errs = []
    for m_sub in (1, 2, 4):
        packed = build_ivfpq_packed(X, ids, 6, m_sub=m_sub, n_bits=4, seed=5)
        rec = reconstruct(packed)
        errs.append(float(np.mean((rec - packed.items) ** 2)))
        # coarse-only error: residual variance around the assigned centroid
        row_list = np.repeat(np.arange(packed.counts.shape[0]), packed.counts)
        coarse = float(
            np.mean((packed.items - packed.centroids[row_list]) ** 2)
        )
        assert errs[-1] < coarse, (m_sub, errs[-1], coarse)
    assert errs[0] > errs[1] > errs[2], errs


# -- the acceptance gates ------------------------------------------------------


def test_refined_recall_at_10(pq_setup):
    """Acceptance: refined recall@10 >= 0.9 vs the exact kneighbors path at
    the DOCUMENTED defaults (default_m_sub, n_bits=8, nprobe=nlist/4,
    refine_ratio=4) on clustered data; raw ADC recall is reported-but-lower
    (quantization error), refine must not lose recall."""
    from spark_rapids_ml_tpu.ann.ivfflat import default_nprobe

    X, ids, packed = pq_setup
    mesh = get_mesh()
    nprobe = default_nprobe(packed.counts.shape[0])
    index = index_from_packed_pq(packed, mesh)
    Q = X[:512]
    _, i_raw = ivfpq_search_prepared(index, Q, 10, nprobe, mesh)
    d_ref, i_ref = ivfpq_search_prepared(
        index, Q, 10, nprobe, mesh, refine_items=packed.items, refine_ratio=4
    )
    prepared = prepare_items(X, ids, mesh)
    _, i_exact = knn_search_prepared(prepared, Q, 10, mesh)
    r_raw = recall_at_k(i_raw, i_exact)
    r_ref = recall_at_k(i_ref, i_exact)
    assert r_ref >= 0.9, (r_ref, r_raw)
    assert r_ref >= r_raw, (r_ref, r_raw)
    # refined distances are true f32 euclidean: ascending, self leads
    assert np.all(np.diff(d_ref, axis=1) >= 0)
    assert np.mean(i_ref[:, 0] == ids[:512]) >= 0.95


def test_mesh_parity_bitwise(pq_setup):
    """Acceptance: probed ADC results AND refined results are BITWISE
    identical on a 1-device and an 8-device mesh (the flat kernel's
    lex/merge helpers are reused verbatim; refine is deterministic host
    math over the already-identical candidate set)."""
    X, ids, packed = pq_setup
    Q = X[:300]
    out = {}
    for name, mesh in (("one", get_mesh(1)), ("all", get_mesh())):
        index = index_from_packed_pq(packed, mesh)
        out[name] = (
            ivfpq_search_prepared(index, Q, 10, 6, mesh),
            ivfpq_search_prepared(
                index, Q, 10, 6, mesh,
                refine_items=packed.items, refine_ratio=3,
            ),
        )
    for arm in (0, 1):
        d1, i1 = out["one"][arm]
        d8, i8 = out["all"][arm]
        np.testing.assert_array_equal(i1, i8)
        np.testing.assert_array_equal(
            d1.astype(np.float32).view(np.uint32),
            d8.astype(np.float32).view(np.uint32),
        )


def test_repeat_and_warm_zero_new_compiles(pq_setup):
    """Acceptance: a repeat same-shape probed PQ search performs ZERO new
    compilations, and warm_pq_probe_kernels submits the EXACT executable
    the dispatch looks up (fresh query-block geometry, straight aot_hit)."""
    from spark_rapids_ml_tpu.ops.precompile import global_precompiler

    X, ids, packed = pq_setup
    mesh = get_mesh()
    index = index_from_packed_pq(packed, mesh)
    kw = dict(refine_items=packed.items, refine_ratio=2)
    ivfpq_search_prepared(index, X[:200], 5, 4, mesh, **kw)  # compiles once
    before = profiling.counters("precompile.")
    d1, i1 = ivfpq_search_prepared(index, X[:200], 5, 4, mesh, **kw)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    assert delta.get("precompile.aot_hit", 0) >= 1, delta
    d2, i2 = ivfpq_search_prepared(index, X[:200], 5, 4, mesh, **kw)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2)
    # warm at a geometry no search has touched (k=7 > any dispatched k)
    keys = warm_pq_probe_kernels(
        index, 7, 4, mesh, n_queries=200, refine=True, refine_ratio=2
    )
    assert keys
    global_precompiler().wait(keys)
    before = profiling.counters("precompile.")
    ivfpq_search_prepared(index, X[:200], 7, 4, mesh, **kw)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.aot_miss", 0) == 0, delta


def test_compression_vs_flat_index(pq_setup):
    """The memory headline: at this geometry the staged PQ index must sit
    far below the flat index per item (>= 8x is the CI bar at d >= 256;
    even at d=16 the code layout wins by ~2x, asserted here so the
    device_bytes accounting itself is gated in tier-1)."""
    from spark_rapids_ml_tpu.ann.ivfflat import (
        build_ivfflat_packed,
        index_from_packed,
    )

    X, ids, packed = pq_setup
    mesh = get_mesh()
    pq_bytes = index_from_packed_pq(packed, mesh).device_bytes()
    flat = build_ivfflat_packed(X, ids, packed.counts.shape[0], seed=1)
    flat_bytes = index_from_packed(flat, mesh).device_bytes()
    n = packed.n_items
    assert pq_bytes / n < (flat_bytes / n) / 2.0, (pq_bytes / n, flat_bytes / n)


# -- edges ---------------------------------------------------------------------


def test_unfillable_slots_and_empty_lists():
    """k beyond the probed pool yields the -1/inf sentinel contract on BOTH
    the raw and the refined route; empty coarse lists (nlist > occupied
    cells) and k > n_items are absorbed the same way the flat engine's
    suite gates."""
    rng = np.random.default_rng(0)
    X = np.concatenate(
        [
            rng.normal(size=(16, 4)).astype(np.float32),
            (100.0 + rng.normal(size=(16, 4))).astype(np.float32),
        ]
    )
    ids = np.arange(32, dtype=np.int64)
    mesh = get_mesh()
    # nlist=8 over two far blobs leaves most lists nearly/fully empty
    packed = build_ivfpq_packed(X, ids, 8, m_sub=2, n_bits=4, seed=5)
    index = index_from_packed_pq(packed, mesh)
    for kw in (
        {},
        {"refine_items": packed.items, "refine_ratio": 2},
    ):
        d, i = ivfpq_search_prepared(index, X[:4], 30, 1, mesh, **kw)
        assert d.shape == (4, 30) and i.shape == (4, 30)
        assert (i == -1).any()
        assert np.all(np.isinf(d[i == -1]))
        assert np.all(i[:, 0] >= 0)
    # k > n_items clamps to k_eff, full coverage probing everything
    d, i = ivfpq_search_prepared(
        index, X[:4], 64, index.nlist_pad, mesh,
        refine_items=packed.items, refine_ratio=2,
    )
    assert d.shape == (4, 32) and i.shape == (4, 32)
    assert np.all(i >= 0)


# -- model surface -------------------------------------------------------------


def test_model_pq_param_surface():
    X, _ = _clustered(n=120, d=6, n_blobs=4, seed=7)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=1)
    with pytest.raises(ValueError, match="unknown algoParams"):
        ApproximateNearestNeighbors(
            algorithm="ivfpq", algoParams={"M": 2, "nbits": 4}
        ).setFeaturesCol("features").fit(df)
    with pytest.raises(ValueError, match="n_bits"):
        ApproximateNearestNeighbors(
            algorithm="ivfpq", algoParams={"n_bits": 11}
        ).setFeaturesCol("features").fit(df)
    # M is an ivfpq-only key: the flat tier must reject it loudly
    with pytest.raises(ValueError, match="unknown algoParams"):
        ApproximateNearestNeighbors(
            algorithm="ivfflat", algoParams={"M": 2}
        ).setFeaturesCol("features").fit(df)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        model = ApproximateNearestNeighbors(
            k=3,
            algorithm="ivfpq",
            algoParams={
                "nlist": 4, "nprobe": 4, "M": 2, "n_bits": 4,
                "usePrecomputedTables": True,
            },
        ).setFeaturesCol("features").fit(df)
        assert any(
            "usePrecomputedTables" in str(w.message) for w in caught
        ), [str(w.message) for w in caught]
    _, _, knn_df = model.kneighbors(
        DataFrame.from_numpy(X[:5], num_partitions=1)
    )
    ids = np.concatenate(
        [np.asarray(list(p["indices"])) for p in knn_df.partitions if len(p)]
    )
    assert ids.shape == (5, 3)
    # probed self-match leads every row after refine
    np.testing.assert_array_equal(ids[:, 0], np.arange(5))
    # a flat-fit model has no PQ payload to stage
    flat = ApproximateNearestNeighbors(
        k=3, algoParams={"nlist": 4, "nprobe": 4}
    ).setFeaturesCol("features").fit(df)
    with pytest.raises(ValueError, match="no PQ payload"):
        flat._packed_pq()


def test_default_m_sub_geometry():
    assert default_m_sub(256) == 32   # the ~32x operating point
    assert default_m_sub(3000) == 64  # clamped
    assert default_m_sub(5) == 4
    assert pq_geometry(5, 4) == (4, 2, 8)    # pow2-padded subspaces
    assert pq_geometry(256, 32) == (32, 8, 256)
    assert pq_geometry(16, 64) == (16, 1, 16)  # m_sub clamped to dim


# -- 4-bit fast-scan ----------------------------------------------------------


def test_fastscan_kernel_matches_numpy_adc_oracle():
    """out[b, r] = sum_j T[b, j, nibble_j(packed[b, r])] with SEQUENTIAL
    f32 accumulation in subspace order — the packed two-codes-per-byte
    kernel must equal the oracle bit for bit in interpret mode, on ragged
    row counts and sub-16 table widths, and the routed XLA unpack path
    must compute the identical sum."""
    from spark_rapids_ml_tpu.ops.pallas_pq import (
        _fastscan_pallas,
        fastscan_lut_accumulate,
        pack_codes4,
        unpack_codes4,
    )

    rng = np.random.default_rng(11)
    cases = [(3, 700, 4, 16), (1, 512, 2, 16), (2, 33, 8, 5)]
    wants, outs = [], []   # device outputs batched; ONE fetch after the loop
    for B, R, m_sub, ksub in cases:
        T = rng.standard_normal((B, m_sub, ksub)).astype(np.float32)
        C = rng.integers(0, ksub, size=(B, R, m_sub)).astype(np.uint8)
        packed = np.stack([pack_codes4(C[b]) for b in range(B)])
        want = np.zeros((B, R), np.float32)
        for j in range(m_sub):  # sequential j — the accumulation contract
            want += np.take_along_axis(
                T[:, j, :], C[:, :, j].astype(np.int64), axis=1
            )
        wants.append((want, C))
        outs.append((
            _fastscan_pallas(jnp.asarray(T), jnp.asarray(packed), interpret=KERNEL_INTERPRET),
            fastscan_lut_accumulate(jnp.asarray(T), jnp.asarray(packed)),
            unpack_codes4(jnp.asarray(packed)),
        ))
    for case, (want, C), (got, got_routed, unpacked) in zip(
        cases, wants, jax.device_get(outs)
    ):
        np.testing.assert_array_equal(got, want, err_msg=f"{case}")
        np.testing.assert_allclose(got_routed, want, rtol=1e-6, atol=1e-6)
        # the unpack round-trip is lossless (nibble order: low = even j)
        np.testing.assert_array_equal(unpacked, C)


def test_fastscan_typed_rejections():
    """Odd m_sub cannot pack two codes per byte and a 4-bit nibble cannot
    address ksub > 16 — both are typed errors at the packing/kernel
    layer.  The ROUTE derivation keeps odd-m_sub payloads off the packed
    layout entirely (they build and search on the unpacked byte-per-code
    route, the pre-fast-scan behavior), so the typed errors guard the
    kernel's contract, not the user's geometry choice."""
    from spark_rapids_ml_tpu.ann.pq import (
        index_from_packed_pq,
        pq_fastscan,
    )
    from spark_rapids_ml_tpu.ops.pallas_pq import pack_codes4

    with pytest.raises(ValueError, match="even"):
        pack_codes4(np.zeros((4, 3), np.uint8))
    with pytest.raises(ValueError, match="16"):
        pack_codes4(np.full((4, 2), 16, np.uint8))
    assert pq_fastscan(4, 2) and not pq_fastscan(8, 2)
    assert not pq_fastscan(4, 3)  # odd m_sub: unpacked route, no error
    X, ids = _clustered(n=200, d=8, n_blobs=4, seed=9)
    packed = build_ivfpq_packed(X, ids, 4, m_sub=3, n_bits=4, seed=0)
    index = index_from_packed_pq(packed, get_mesh())
    assert not index.fastscan
    _, i = ivfpq_search_prepared(index, X[:8], 3, 4, get_mesh())
    np.testing.assert_array_equal(np.asarray(i)[:, 0], ids[:8])


# -- OPQ ----------------------------------------------------------------------


def test_opq_recall_at_equal_index_bytes():
    """The OPQ acceptance gate, at EQUAL device code bytes per item (4-bit
    at M vs 8-bit at M/2 — both M/2 bytes of codes): the OPQ+4-bit
    PIPELINE (refined against the host-side f32 payload, which costs zero
    HBM) must reach at least the recall@10 of the raw ADC-only 8-bit arm
    on the clustered bench shape.  Rotation must also strictly help the
    4-bit arm's own raw ADC recall — that is the part OPQ buys.  (An
    ADC-vs-ADC flip at equal rate is NOT gated: a joint 256-word codebook
    over 2*dsub dims is structurally at least as expressive as the product
    of two 16-word codebooks, so 8-bit raw ADC >= 4-bit raw ADC at equal
    bytes always — docs/ann_engine.md carries the measured table.)"""
    X, ids = _clustered(n=2000, d=16, n_blobs=24, seed=13)
    mesh = get_mesh()
    nlist, nprobe, M = 16, 8, 8
    prepared = prepare_items(X, ids, mesh)
    Q = X[:256]
    _, i_exact = knn_search_prepared(prepared, Q, 10, mesh)
    arms = {
        "raw8_halfM": (M // 2, 8, False),
        "opq4": (M, 4, True),
        "raw4": (M, 4, False),
    }
    raw, ref = {}, {}
    for label, (m_sub, n_bits, opq) in arms.items():
        packed = build_ivfpq_packed(
            X, ids, nlist, m_sub=m_sub, n_bits=n_bits, seed=1, opq=opq
        )
        index = index_from_packed_pq(packed, mesh)
        _, i_raw = ivfpq_search_prepared(index, Q, 10, nprobe, mesh)
        raw[label] = recall_at_k(i_raw, i_exact)
        _, i_ref = ivfpq_search_prepared(
            index, Q, 10, nprobe, mesh,
            refine_items=packed.items, refine_ratio=8,
        )
        ref[label] = recall_at_k(i_ref, i_exact)
    # the equal-HBM-bytes headline: refined opq4 >= raw 8-bit at half M
    assert ref["opq4"] >= raw["raw8_halfM"], (ref, raw)
    assert ref["opq4"] >= 0.9, ref
    # the rotation itself must pay for its training loop
    assert raw["opq4"] > raw["raw4"], raw


def test_opq_reduces_reconstruction_error():
    """The rotation exists to cut quantization error: OPQ reconstruction
    MSE must not exceed the unrotated build's at the same geometry, and
    reconstruct() must un-rotate (error far below residual variance)."""
    X, ids = _clustered(n=800, d=8, n_blobs=6, seed=4)
    errs = {}
    for opq in (False, True):
        packed = build_ivfpq_packed(X, ids, 6, m_sub=2, n_bits=4, seed=5, opq=opq)
        rec = reconstruct(packed)
        errs[opq] = float(np.mean((rec - packed.items) ** 2))
    assert errs[True] <= errs[False] * 1.001, errs
    packed = build_ivfpq_packed(X, ids, 6, m_sub=2, n_bits=4, seed=5, opq=True)
    assert packed.rotation is not None
    R = packed.rotation.astype(np.float64)
    np.testing.assert_allclose(R @ R.T, np.eye(R.shape[0]), atol=1e-5)


def test_opq_and_fastscan_mesh_parity_bitwise():
    """Acceptance: probed AND refined results BITWISE identical on 1-dev
    and 8-dev meshes for the opq arm and the 4-bit fast-scan arm (and
    their composition)."""
    X, ids = _clustered(n=800, d=16, n_blobs=12, seed=17)
    Q = X[:200]
    for n_bits, opq in ((8, True), (4, False), (4, True)):
        packed = build_ivfpq_packed(
            X, ids, 8, m_sub=4, n_bits=n_bits, seed=2, opq=opq
        )
        out = {}
        for name, mesh in (("one", get_mesh(1)), ("all", get_mesh())):
            index = index_from_packed_pq(packed, mesh)
            out[name] = (
                ivfpq_search_prepared(index, Q, 10, 4, mesh),
                ivfpq_search_prepared(
                    index, Q, 10, 4, mesh,
                    refine_items=packed.items, refine_ratio=3,
                ),
            )
        for arm in (0, 1):
            d1, i1 = out["one"][arm]
            d8, i8 = out["all"][arm]
            np.testing.assert_array_equal(i1, i8, err_msg=f"{n_bits}/{opq}")
            np.testing.assert_array_equal(
                d1.astype(np.float32).view(np.uint32),
                d8.astype(np.float32).view(np.uint32),
                err_msg=f"{n_bits}/{opq}",
            )


# -- tiered residency ---------------------------------------------------------


def test_tiered_matches_resident_bitwise(pq_setup):
    """Acceptance: a hot_fraction=0.25 tiered index answers the SAME
    probed+refined search BITWISE identically to the all-resident staging
    (the tiered kernel is the resident body plus one slot indirection),
    and the cold->warm page-in sweep performs ZERO new compilations after
    the first block geometry."""
    from spark_rapids_ml_tpu.ann.pq import tiered_index_from_packed_pq

    X, ids, packed = pq_setup
    mesh = get_mesh()
    resident = index_from_packed_pq(packed, mesh)
    tiered = tiered_index_from_packed_pq(packed, mesh, hot_fraction=0.25)
    kw = dict(refine_items=packed.items, refine_ratio=3)
    Q = X[:192]
    d_r, i_r = ivfpq_search_prepared(resident, Q, 10, 6, mesh, **kw)
    d_t, i_t = ivfpq_search_prepared(tiered, Q, 10, 6, mesh, **kw)
    np.testing.assert_array_equal(i_r, i_t)
    np.testing.assert_array_equal(
        np.asarray(d_r, np.float32).view(np.uint32),
        np.asarray(d_t, np.float32).view(np.uint32),
    )
    # cold->warm sweep: disjoint query slices probe different lists, so
    # the pager keeps paging — but never compiles anew at this geometry
    before = profiling.counters("precompile.")
    t0 = profiling.counter("ann.tier.hits") + profiling.counter("ann.tier.misses")
    for lo in range(192, 2112, 192):
        ivfpq_search_prepared(tiered, X[lo:lo + 192], 10, 6, mesh, **kw)
    delta = profiling.counter_deltas(before, "precompile.")
    assert delta.get("precompile.compile", 0) == 0, delta
    assert delta.get("precompile.fallback", 0) == 0, delta
    # the pager actually worked (counters are the observability surface)
    assert (
        profiling.counter("ann.tier.hits")
        + profiling.counter("ann.tier.misses")
    ) > t0
    assert profiling.counter("ann.tier.stage_bytes") > 0


def test_tiered_tombstone_interaction():
    """Tiered + live mutation: lists paged in from host AFTER a delete
    must honor the tombstone bitmap — the tier's host planes are views of
    the holder's mirrors and delete_items refreshes resident slots, so a
    tombstoned id must never resurface from ANY list, hot, resident-warm,
    or paged-in-later cold."""
    from spark_rapids_ml_tpu.ann.ivfflat import build_ivfflat_packed
    from spark_rapids_ml_tpu.ann.mutable import MutableIVFIndex

    rng = np.random.default_rng(23)
    X = rng.standard_normal((1200, 16)).astype(np.float32)
    ids = np.arange(1200, dtype=np.int64)
    mesh = get_mesh()
    packed = build_ivfflat_packed(X, ids, 16, seed=0)
    holder = MutableIVFIndex(packed, mesh, hot_fraction=0.25)
    # warm only a few lists so most stay cold on host
    holder.search(X[:16], 5, 2)
    victims = ids[:48]
    holder.delete_items(victims)
    # nprobe = nlist forces EVERY list through the pager, including cold
    # lists first touched after the delete
    d, i = holder.search(X[:128], 10, 16)
    assert not np.isin(i, victims).any()
    assert holder.stats()["tombstoned"] == 48
    # the paged-in rows carry live neighbors, not garbage
    live = ids[48:]
    hits = i[i >= 0]
    assert np.isin(hits, live).all()


def test_model_refine_ratio_edge_semantics():
    """Satellite regression: refine_ratio=0 used to pass the `>= 0` guard
    and silently behave like 1 (the refine gate keys off `> 1`); it is now
    a typed error, while refine_ratio=1 is the documented "ADC only, no
    refine" mode and must equal the engine's raw probed route."""
    X, _ = _clustered(n=300, d=8, n_blobs=6, seed=29)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=1)
    for bad in (0, -2):
        with pytest.raises(ValueError, match="refine_ratio"):
            ApproximateNearestNeighbors(
                algorithm="ivfpq",
                algoParams={"nlist": 4, "nprobe": 4, "M": 2, "refine_ratio": bad},
            ).setFeaturesCol("features").fit(df)
    base = {"nlist": 4, "nprobe": 4, "M": 2, "n_bits": 8}
    model = ApproximateNearestNeighbors(
        k=5, algorithm="ivfpq", algoParams={**base, "refine_ratio": 1},
    ).setFeaturesCol("features").fit(df)
    _, _, knn_df = model.kneighbors(DataFrame.from_numpy(X[:8], num_partitions=1))
    got = np.concatenate(
        [np.asarray(list(p["indices"])) for p in knn_df.partitions if len(p)]
    )
    mesh = get_mesh(model.num_workers)
    index = model._ensure_staged_pq(mesh)
    _, want = ivfpq_search_prepared(index, X[:8], 5, 4, mesh)  # raw ADC
    np.testing.assert_array_equal(got, want)


def test_model_hot_fraction_param_surface():
    X, _ = _clustered(n=200, d=8, n_blobs=4, seed=31)
    df = DataFrame.from_numpy(X, feature_layout="array", num_partitions=1)
    with pytest.raises(ValueError, match="hot_fraction"):
        ApproximateNearestNeighbors(
            algoParams={"nlist": 4, "hot_fraction": 1.5}
        ).setFeaturesCol("features").fit(df)
    model = ApproximateNearestNeighbors(
        k=3, algoParams={"nlist": 4, "nprobe": 4, "hot_fraction": 0.5},
    ).setFeaturesCol("features").fit(df)
    _, _, knn_df = model.kneighbors(DataFrame.from_numpy(X[:6], num_partitions=1))
    got = np.concatenate(
        [np.asarray(list(p["indices"])) for p in knn_df.partitions if len(p)]
    )
    np.testing.assert_array_equal(got[:, 0], np.arange(6))
    res = model.index_residency()
    assert res["hbm_bytes_per_item"] > 0
    assert res["host_bytes_per_item"] > 0
    assert res["items_per_device"] >= 1


# -- the public estimator's arms, paired on one dataset ------------------------


def _estimator_arm(X, algorithm, algo_params, k=10, raw=False):
    """One arm through the public estimator: build, a warm probed search, a
    repeat that may compile nothing, recall@k against the same model's exact
    search, and where its bytes live."""
    tier0 = profiling.counters("ann.tier")
    model = ApproximateNearestNeighbors(
        k=k, algorithm=algorithm, algoParams=algo_params
    ).setInputCol("features").fit(DataFrame.from_numpy(X))
    df = DataFrame.from_numpy(X[:512])

    def ids_of(knn_df):
        return np.concatenate(
            [np.asarray(list(p["indices"])) for p in knn_df.partitions if len(p)]
        )

    model.kneighbors(df)  # stages the index, compiles every probe geometry
    before = profiling.counters("precompile.")
    _, _, knn_df = model.kneighbors(df)
    delta = profiling.counter_deltas(before, "precompile.")
    steady = delta.get("precompile.compile", 0) + delta.get("precompile.fallback", 0)
    tier = profiling.counter_deltas(tier0, "ann.tier")
    model.setExactSearch(True)
    _, _, exact_df = model.kneighbors(df)
    model.setExactSearch(False)
    out = {
        "recall": recall_at_k(ids_of(knn_df), ids_of(exact_df)),
        "steady_compiles": steady,
        "index_bytes_per_item": model.index_bytes_per_item(),
        "tier": tier,
        **model.index_residency(),
    }
    if raw:
        # the raw ADC recall (refine off) beside the refined one: the gap is
        # the quantization error the f32 re-score recovers
        model.setAlgoParams({**algo_params, "refine_ratio": 1})
        _, _, raw_df = model.kneighbors(df)
        out["recall_raw"] = recall_at_k(ids_of(raw_df), ids_of(exact_df))
    return out


@pytest.mark.parametrize("pair", ["flat_vs_pq", "8bit_vs_4bit_opq", "tiered"])
def test_estimator_arms_at_ci_smoke_size(pair):
    """What the CI smokes of the removed harness asserted of the ANN tiers, at
    their sizes (PR 28): every arm's refined recall@10 and zero compiles on a
    repeat search; PQ's device bytes an item at most 1/8 of the flat index's;
    4-bit + OPQ at most 0.6 of the 8-bit arm's HBM bytes at like-for-like
    residency; and a tiered arm that really pages."""
    if pair == "flat_vs_pq":
        X, _ = _clustered(n=2000, d=32, n_blobs=8, seed=41)
        flat = _estimator_arm(X, "ivfflat", {"nlist": 8, "nprobe": 4})
        # every list probed + x8 refine: raw ADC recall at 2k x 32 is about a
        # half, and the refine's recovery is what the bar is about; n_bits=6 so
        # the fixed codebook bytes do not swamp the ratio at this item count
        pq = _estimator_arm(
            X, "ivfpq",
            {"nlist": 8, "nprobe": 8, "M": 8, "n_bits": 6, "refine_ratio": 8},
            raw=True,
        )
        assert flat["recall"] >= 0.95 and flat["steady_compiles"] == 0, flat
        assert pq["recall"] >= 0.9 and pq["steady_compiles"] == 0, pq
        assert pq["recall_raw"] <= pq["recall"], pq
        ratio = flat["index_bytes_per_item"] / pq["index_bytes_per_item"]
        assert ratio >= 8.0, (flat["index_bytes_per_item"], pq["index_bytes_per_item"])
        return
    X, _ = _clustered(n=2048, d=32, n_blobs=16, seed=43)
    opq4 = {"nlist": 16, "nprobe": 16, "M": 16, "n_bits": 4, "opq": True,
            "refine_ratio": 8}
    if pair == "8bit_vs_4bit_opq":
        b8 = _estimator_arm(
            X, "ivfpq",
            {"nlist": 16, "nprobe": 16, "M": 16, "n_bits": 8, "refine_ratio": 8},
        )
        b4 = _estimator_arm(X, "ivfpq", opq4)
        for arm in (b8, b4):
            assert arm["recall"] >= 0.9 and arm["steady_compiles"] == 0, arm
        assert b4["hbm_bytes_per_item"] <= 0.6 * b8["hbm_bytes_per_item"], (
            b4["hbm_bytes_per_item"], b8["hbm_bytes_per_item"],
        )
        return
    # half the 16 lists pinned, 4 probed: the cold ones cycle through the pool
    tiered = _estimator_arm(X, "ivfpq", {**opq4, "nprobe": 4, "hot_fraction": 0.5})
    assert tiered["recall"] >= 0.9 and tiered["steady_compiles"] == 0, tiered
    tc = tiered["tier"]
    assert tc.get("ann.tier.hits", 0) > 0 and tc.get("ann.tier.misses", 0) > 0, tc
    assert tc.get("ann.tier.page_bytes", 0) > 0, tc
    assert tiered["host_bytes_per_item"] > 0, tiered
