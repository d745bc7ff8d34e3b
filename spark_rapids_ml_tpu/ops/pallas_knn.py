#
# Fused distance + per-group partial-top-k Pallas TPU kernel for exact kNN.
#
# This is the structural fix for the kNN arm named in rounds 2-3: the
# adaptive block search (ops/knn.py) pays its selection cost OUTSIDE the
# matmul — the XLA candidates scan re-reads the (Q, chunk) distance tile
# from HBM for every one of the m iterated (argmax, max, mask) passes, ~1 s
# of pure VPU/HBM traffic per 8192-query block at the 400k x 3000 k=200
# benchmark shape.  Here the (TQ, G) distance tile never leaves VMEM: each
# grid cell accumulates the query x item-group dot product over D blocks
# (MXU), and at the last D block runs the m selection passes on the
# VMEM-resident tile (VPU) — selection rides the matmul's memory traffic
# instead of repeating it.
#
# The kernel produces the same per-group top-m candidate pool as
# ops/knn._candidates_scan (position-masked selection, so duplicate
# distances stay distinct candidates); the pool then flows through the
# UNCHANGED exact machinery — _adaptive_merge_self (exact top-k over the
# pool + pool-resident overflow verification) and the per-row exact
# fallback — so the result keeps the tie-tolerant exactness contract
# documented at knn_block_adaptive.  The global count scan
# (knn_count_pallas below) remains as the SRML_KNN_AUDIT_COUNT=1 audit
# route that cross-checks the pool-resident flag against ground truth.
#
# Output layout: (n_groups, m_pad, Q_pad) rather than (Q, n_groups*m) —
# the last dim stays the 128-aligned query tile and the m_pad rows satisfy
# the f32/int32 (8, 128) min-tile, so every store is lane-aligned.  The
# wrapper transposes to the (Q, pool) layout _adaptive_merge expects (one
# cheap HBM pass over the ~100 MB pool vs. the ~25 full-tile HBM sweeps
# the fusion removes).
#
# Reference context: cuML brute-force kNN kernels behind NearestNeighborsMG
# (used by spark-rapids-ml knn.py:486-560) fuse the distance epilogue the
# same way on GPU.
#

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_tpu import _round_up, pallas_enabled

# tile geometry: TQ queries x TI items per grid cell, D consumed in KB-wide
# blocks.  VMEM at (256, 1024, 512): 2x double-buffered q/item blocks
# (2*(256+1024)*512*4 = 5.2 MB) + the f32 accumulator tile (1 MB) + norm
# slivers — comfortably inside the ~15 MB scoped budget.
_TILE_Q = 256
_TILE_I = 1024
_TILE_D = 512


# minimum item rows for the adaptive/pallas path (ops/knn._ADAPTIVE_MIN_LOCAL;
# duplicated here to keep the import DAG acyclic)
_MIN_ALIGN_ROWS = 1 << 15

# K-block cap for the query-resident kernel (the whole D when it fits):
# (tile_i, kb) f32 in-blocks double-buffered + the bf16 hi/lo scratch cost
# ~(4 + 4 + 2 + 2) bytes x tile_i x kb = 36 MB at (1024, 3072), which stays
# inside the raised 100 MB scoped budget alongside the (TQ, TI) accumulator
# tile and the epilogue temporaries.
_TILE_D_QRES = 3072


def pallas_align_dims(n_rows: int, d: int, n_dev: int):
    """(row_multiple, col_target) that prepare_items should pad item sets
    to so the fused kernels' block reads are in-bounds WITHOUT a per-call
    pad copy (review finding: _aligned_items re-padded the multi-GB
    invariant item array on every dispatch).  None when the pallas path
    cannot serve the shape anyway — small sets, d < 128, or shapes whose
    column alignment would waste >25% HBM (those keep the scan path, see
    pallas_knn_eligible)."""
    if (
        not pallas_enabled()
        or n_dev != 1  # the fused kernels are single-shard only
        or n_rows < _MIN_ALIGN_ROWS
        or d < 128
    ):
        return None
    d_al = _col_target(d)
    if d_al * 4 > d * 5:
        return None
    return _TILE_I, d_al


def _col_target(d: int) -> int:
    from .pallas_tpu import _round_up

    d_pad = _round_up(d, 128)
    kb = min(_TILE_D, d_pad)
    return _round_up(d, kb)


def _aligned_items(items: jax.Array, inorm: jax.Array, kb: int, tile_i: int = _TILE_I):
    """Pad the item array/norms to (TILE_I, kb) multiples so every block
    read is IN BOUNDS.  Out-of-bounds block DMA past an array's HBM extent
    is not a safe pad-with-garbage on real hardware: a ~17 MB overread left
    the device in a FAILED_PRECONDITION state (see bin_features_fm_pallas —
    same hazard, same fix).  The pad is one HBM copy (~12 ms at 400k x
    3000) and a no-op when already aligned; padded rows carry +inf norms so
    they can never enter a top-m list, padded columns are zeros on both
    operands of the dot."""
    from .pallas_tpu import _round_up as _ru

    n_pad, d = items.shape
    n_al = _ru(n_pad, tile_i)
    d_al = _ru(d, kb)
    if (n_al, d_al) != (n_pad, d):
        items = jnp.pad(items, ((0, n_al - n_pad), (0, d_al - d)))
        inorm = jnp.pad(
            inorm, (0, n_al - n_pad), constant_values=jnp.inf
        )
    return items, inorm, n_al // tile_i


def _accum_dot(q_ref, it_ref, acc, kb, d_true: int, kd: int) -> None:
    """Shared partial-dot accumulation for the candidate and count kernels.
    MUST stay byte-for-byte identical between them: the count verification
    compares counts derived from the two kernels' d2 values, and identical
    tiling + identical ops on the same hardware make those values BITWISE
    equal — so verification failures are genuine candidate-overflow misses,
    never scan-to-scan rounding noise.

    The dot runs at 3-pass bf16 precision — the explicit hi/lo decomposition
    of lax.Precision.HIGH (~2^-19 relative), which Mosaic's dot lowering
    does not accept as a precision flag.  A single-pass bf16 dot (~2^-8)
    would break sklearn-level distance parity."""
    it = it_ref[:]
    if d_true % kd != 0:
        # ragged D tail: the item array is (N_pad, d_true) and the last D
        # block reads past it — undefined values (a NaN would survive the
        # zero-padded query columns, 0 * NaN = NaN), so zero the tail
        # in-VMEM.  Statically elided when D divides the block width.
        dcol = kb * kd + jax.lax.broadcasted_iota(jnp.int32, it.shape, 1)
        it = jnp.where(dcol < d_true, it, 0.0)
    q = q_ref[:]
    q_hi = q.astype(jnp.bfloat16)
    q_lo = (q - q_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    it_hi = it.astype(jnp.bfloat16)
    it_lo = (it - it_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    acc[:] += (
        jnp.dot(q_hi, it_hi.T, preferred_element_type=jnp.float32)
        + jnp.dot(q_hi, it_lo.T, preferred_element_type=jnp.float32)
        + jnp.dot(q_lo, it_hi.T, preferred_element_type=jnp.float32)
    )


def _neg_d2(qn_ref, inorm_ref, a, j, n_items: int, tile_i: int):
    """Masked negated squared distances for a finished (TQ, TI) tile value
    — shared epilogue entry for all kernels (see _accum_dot on why)."""
    tq = a.shape[0]
    neg = -(qn_ref[:] - 2.0 * a + inorm_ref[:])
    # mask columns past the item set (ragged last group: OOB block reads
    # are undefined, and NaN garbage would poison the argmax/count)
    col = j * tile_i + jax.lax.broadcasted_iota(jnp.int32, (tq, tile_i), 1)
    return jnp.where(col < n_items, neg, -jnp.inf)


def _select_topm_store(neg, m: int, m_pad: int, j, tile_i: int,
                       vals_ref, idx_ref):
    """The per-group top-m selection epilogue shared by both candidates
    kernels: m iterated (argmax, max, position-mask) passes over the
    VMEM-resident (TQ, TI) tile.  Position-masking (not value-masking)
    keeps duplicate distances as distinct candidates — exact multiset
    semantics, same as ops/knn._group_topm."""
    tq = neg.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tq, tile_i), 1)
    vals, idxs = [], []
    v = neg
    for _ in range(m):
        am = jnp.argmax(v, axis=1).astype(jnp.int32)
        vals.append(jnp.max(v, axis=1))
        idxs.append(am + j * tile_i)
        v = jnp.where(iota == am[:, None], -jnp.inf, v)
    for _ in range(m_pad - m):
        vals.append(jnp.full((tq,), -jnp.inf, jnp.float32))
        idxs.append(jnp.zeros((tq,), jnp.int32))
    vals_ref[0] = jnp.stack(vals)
    idx_ref[0] = jnp.stack(idxs)


def _knn_topm_kernel(
    qn_ref, inorm_ref, q_ref, it_ref, vals_ref, idx_ref, acc,
    *, m: int, m_pad: int, n_items: int, tile_i: int, d_true: int, kd: int,
):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    _accum_dot(q_ref, it_ref, acc, kb, d_true, kd)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        neg = _neg_d2(qn_ref, inorm_ref, acc[:], j, n_items, tile_i)
        _select_topm_store(neg, m, m_pad, j, tile_i, vals_ref, idx_ref)


def _knn_topm_kernel_qres(
    qn_ref, inorm_ref, q_ref, it_ref, vals_ref, idx_ref,
    acc, ith, itl,
    *, m: int, m_pad: int, n_items: int, tile_i: int, d_true: int, kd: int,
    tq: int,
):
    """Query-resident variant: grid (j, i, b) — item group, query tile,
    K (D) block, with the K block INNERMOST.

    Grid contract (the load-bearing property): the output block map
    (j, 0, i) ignores b, so every output block is revisited once per K
    block.  Pallas TPU only defines revisited output blocks when the
    revisiting dimension is innermost — consecutive visits keep the block
    VMEM-resident and flush it exactly once, after the b == nb-1 epilogue
    writes it.  (The previous (j, b, i) grid revisited outputs with b NOT
    innermost: every intermediate visit copied stale double-buffered VMEM
    over the same HBM region with no ordering guarantee against the final
    epilogue DMA — undefined behavior whenever nb > 1.)

    Single-K-block case (nb == 1, covers the d<=3072 bench shapes): the
    item block's index map (j, b=0) is constant across the whole innermost
    i sweep, so Mosaic skips the repeated DMA and the multi-GB item set
    crosses HBM ONCE per group — the property the old grid bought (the
    plain (i, j, b) kernel re-reads it q_pad/tq times: 157 GB at the
    400k x 3000 bench shape).  The bf16 hi/lo split of the resident block
    is computed once (at i == 0) into scratch.

    Multi-K-block case (nb > 1, D > the VMEM cap): the item block map
    (j, b) changes every step, so item blocks are re-fetched per query
    tile — correctness costs item-side HBM traffic here, and the hi/lo
    split is computed inline per block (the i == 0 scratch would be stale:
    it would hold block nb-1 from the previous sweep).  Accumulation uses
    a per-tile (tq, tile_i) f32 scratch zeroed at b == 0 — no q_pad-sized
    slab, so the route no longer needs a query-count budget gate.

    The QUERY hi/lo split happens IN-KERNEL like _accum_dot's —
    precomputing it in XLA was measured precision-UNSAFE on this backend:
    the terminal forces --xla_allow_excess_precision=true, which legally
    cancels the f32 -> bf16 -> f32 round-trip so q_lo folds to ZERO and
    the scan silently degrades to ~1-pass bf16 (d2 abs err 0.14 vs 4e-4;
    caught by the hardware audit vs f64 ground truth).  Mosaic performs
    the casts as written."""
    import jax.experimental.pallas as pl

    j = pl.program_id(0)
    i = pl.program_id(1)
    b = pl.program_id(2)

    single = d_true <= kd  # whole D in one K block: no cross-step state

    # no D-tail masking in either case: the qres route picks kb to DIVIDE
    # the padded width, and _aligned_items/qp zero-pad their columns, so
    # every block read is in-bounds zero-padded data
    if single:
        @pl.when(i == 0)
        def _():
            it = it_ref[:]
            hi = it.astype(jnp.bfloat16)
            ith[:] = hi
            itl[:] = (it - hi.astype(jnp.float32)).astype(jnp.bfloat16)

        it_hi = ith[:]
        it_lo = itl[:]
    else:
        it = it_ref[:]
        it_hi = it.astype(jnp.bfloat16)
        it_lo = (it - it_hi.astype(jnp.float32)).astype(jnp.bfloat16)

    q = q_ref[:]
    q_hi = q.astype(jnp.bfloat16)
    q_lo = (q - q_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    dots = (
        jnp.dot(q_hi, it_hi.T, preferred_element_type=jnp.float32)
        + jnp.dot(q_hi, it_lo.T, preferred_element_type=jnp.float32)
        + jnp.dot(q_lo, it_hi.T, preferred_element_type=jnp.float32)
    )

    def _epilogue(a):
        neg = _neg_d2(qn_ref, inorm_ref, a, j, n_items, tile_i)
        _select_topm_store(neg, m, m_pad, j, tile_i, vals_ref, idx_ref)

    if single:
        _epilogue(dots)
    else:

        @pl.when(b == 0)
        def _():
            acc[:] = jnp.zeros_like(acc)

        acc[:] += dots

        @pl.when(b == pl.num_programs(2) - 1)
        def _():
            _epilogue(acc[:])


def _knn_count_kernel(
    qn_ref, inorm_ref, t_ref, q_ref, it_ref, out_ref, acc,
    *, n_items: int, tile_i: int, d_true: int, kd: int,
):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    @pl.when((j == 0) & (kb == 0))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _accum_dot(q_ref, it_ref, acc, kb, d_true, kd)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        neg = _neg_d2(qn_ref, inorm_ref, acc[:], j, n_items, tile_i)
        cnt = jnp.sum(neg > t_ref[:], axis=1).astype(jnp.int32)
        out_ref[:] += cnt[:, None]


def _candidates_pool(
    items: jax.Array,
    item_norm: jax.Array,
    valid: jax.Array,
    queries: jax.Array,
    m: int,
    n_items: int,
    interpret: bool,
    tile_q: int,
    tile_i: int,
    tile_d: int,
    legacy: bool,
):
    """The candidates pallas_call shared by knn_candidates_pallas (which
    transposes the pool to the (Q, ng*m) merge layout) and knn_fused_pallas
    (which keeps the pool in its native (ng, m_pad, q_pad) layout and feeds
    it straight into the fused merge kernel — no transpose ever
    materializes in HBM).  Returns (vals, idxs, (ng, m_pad, q_pad, tq))."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, d = queries.shape
    tq = min(tile_q, _round_up(Q, 128))
    d_pad = _round_up(d, 128)
    q_pad = _round_up(Q, tq)
    m_pad = _round_up(m, 8)
    use_qres = not legacy
    if use_qres:
        # one K block spanning as much of D as VMEM allows (hardware A/B:
        # 6 x 512 K blocks 0.57 s -> one 3072 block 0.455 s per bench
        # query block — fewer acc read-modify-writes, deeper MXU dots);
        # kb is chosen to DIVIDE d_pad so prepared 512-aligned item sets
        # never pay a per-dispatch pad copy
        cap = tile_d or _TILE_D_QRES
        nb = -(-d_pad // cap)
        while (d_pad // 128) % nb:
            nb += 1
        kb = d_pad // nb
    else:
        kb = min(tile_d or _TILE_D, d_pad)
    d_blk = _round_up(d_pad, kb)

    qp = jnp.pad(
        queries.astype(jnp.float32), ((0, q_pad - Q), (0, d_blk - d))
    )
    qn = (qp * qp).sum(axis=1, keepdims=True)  # (q_pad, 1), zeros rows safe
    # invalid (padding) rows get +inf norms so their d2 is inf — they can
    # never enter a top-m list
    inorm = jnp.where(valid, item_norm, jnp.inf).astype(jnp.float32)
    items, inorm, ng = _aligned_items(items, inorm, kb, tile_i)
    inorm = inorm.reshape(1, -1)

    out_specs = [
        pl.BlockSpec(
            (1, m_pad, tq), lambda i, j, b: (j, 0, i),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, m_pad, tq), lambda i, j, b: (j, 0, i),
            memory_space=pltpu.VMEM,
        ),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((ng, m_pad, q_pad), jnp.float32),
        jax.ShapeDtypeStruct((ng, m_pad, q_pad), jnp.int32),
    ]
    if use_qres:
        # query-resident grid (j, i, b), K blocks innermost: output blocks
        # are revisited CONSECUTIVELY across b (defined Pallas semantics for
        # nb > 1), and at nb == 1 the item block stays VMEM-resident across
        # the whole i sweep — items cross HBM once per group (kernel header)
        vals, idxs = pl.pallas_call(
            functools.partial(
                _knn_topm_kernel_qres,
                m=m, m_pad=m_pad, n_items=n_items, tile_i=tile_i,
                d_true=d_blk, kd=kb, tq=tq,
            ),
            grid=(ng, q_pad // tq, d_blk // kb),
            in_specs=[
                pl.BlockSpec((tq, 1), lambda j, i, b: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tile_i), lambda j, i, b: (0, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((tq, kb), lambda j, i, b: (i, b), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_i, kb), lambda j, i, b: (j, b), memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, m_pad, tq), lambda j, i, b: (j, 0, i),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, m_pad, tq), lambda j, i, b: (j, 0, i),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_shape=out_shape,
            scratch_shapes=[
                # per-tile accumulator, only live when D spans multiple K
                # blocks; at nb == 1 the dots feed the epilogue directly
                # and the scratch degenerates to one min-tile
                pltpu.VMEM(
                    (tq, tile_i) if d_blk > kb else (8, 128), jnp.float32
                ),
                # resident item hi/lo cache, only read at nb == 1 (the
                # multi-block case recomputes inline; see kernel header)
                pltpu.VMEM(
                    (tile_i, kb) if d_blk <= kb else (8, 128), jnp.bfloat16
                ),
                pltpu.VMEM(
                    (tile_i, kb) if d_blk <= kb else (8, 128), jnp.bfloat16
                ),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 << 20
            ),
            interpret=interpret,
        )(qn, inorm, qp, items)
    else:
        vals, idxs = pl.pallas_call(
            functools.partial(
                _knn_topm_kernel,
                m=m, m_pad=m_pad, n_items=n_items, tile_i=tile_i,
                d_true=d_blk, kd=kb,
            ),
            grid=(q_pad // tq, ng, d_blk // kb),
            in_specs=[
                pl.BlockSpec((tq, 1), lambda i, j, b: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tile_i), lambda i, j, b: (0, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((tq, kb), lambda i, j, b: (i, b), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_i, kb), lambda i, j, b: (j, b), memory_space=pltpu.VMEM),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((tq, tile_i), jnp.float32)],
            # the epilogue's unrolled selection passes carry several
            # (tq, tile_i) f32 temporaries at once; the default 16 MB
            # scoped budget caps the tile at (256, 1024) — larger query
            # tiles need the raised limit
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=96 << 20
            ),
            interpret=interpret,
        )(qn, inorm, qp, items)
    return vals, idxs, (ng, m_pad, q_pad, tq)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "m", "n_items", "interpret", "tile_q", "tile_i", "tile_d",
        "legacy",
    ),
)
def knn_candidates_pallas(
    items: jax.Array,       # (N_pad, D) f32, device-resident
    item_norm: jax.Array,   # (N_pad,) f32 squared norms
    valid: jax.Array,       # (N_pad,) bool
    queries: jax.Array,     # (Q, D) f32
    k: int,
    m: int,
    n_items: int,           # static: N_pad (cols past it are masked)
    interpret: bool = False,
    tile_q: int = _TILE_Q,
    tile_i: int = _TILE_I,
    tile_d: int = 0,  # 0 = route default (legacy 512, qres cap 3072)
    legacy: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Per-group top-m candidate pool for every query: returns
    (values (Q, ng*m) negated squared distances, positions (Q, ng*m) int32
    into the padded item set), ready for ops.knn._adaptive_merge_self with
    stride=m.  The kernel stores m_pad = round_up(m, 8) rows per group to
    satisfy the f32/int32 min-tile; the wrapper's transpose drops the
    padding rows so the downstream merge sort never pays for them (44% of
    the pool at the bench shape's m=9)."""
    Q = queries.shape[0]
    vals, idxs, (ng, _m_pad, q_pad, _tq) = _candidates_pool(
        items, item_norm, valid, queries, m, n_items, interpret,
        tile_q, tile_i, tile_d, legacy,
    )
    # (ng, m_pad, q_pad) -> compact (Q, ng*m) pool layout for the merge
    cand_v = jnp.transpose(vals[:, :m], (2, 0, 1)).reshape(q_pad, ng * m)[:Q]
    cand_i = jnp.transpose(idxs[:, :m], (2, 0, 1)).reshape(q_pad, ng * m)[:Q]
    return cand_v, cand_i


# -- fused merge epilogue ------------------------------------------------------
# The candidates kernel's (ng, m_pad, q_pad) pool used to flow through an
# XLA transpose + grouped top-k + flag pass (_adaptive_merge_self): a second
# full HBM materialization of the pool, a sort-shaped selection, and the
# epilogue the pre-round capture pinned as the kNN arm's "knn.collect"
# spread (PERF.md section 7).  The fused merge kernel below consumes the
# pool in
# its NATIVE layout — one (ng, m_pad, tq) VMEM block per query tile — and
# emits the FINAL per-block (distance, position, self-verify flag) arrays,
# so the only thing left for the host is the id map: no transpose slab, no
# XLA merge, one kernel boundary fewer.
#
# Selection contract: lexicographic (-d2, pos).  Each of the k passes takes
# the pool's maximum value and, among the entries that hold it, the SMALLEST
# position — stated as a max and a min, never as an argmax: Mosaic's argmax
# does not promise the first occurrence among equal values (on a v5e the
# first-occurrence form returned the later duplicate of a tied pair in ~2%
# of the entries of tests/test_pallas.py's tie test).  Positions are unique
# in the pool, so the passes return the UNIQUE lex top-k whatever order the
# candidates kernel left tied entries in.  That makes the fused route's
# output deterministic under any pool partitioning — the same total-order
# property the ANN engine's mesh-parity gate rides — and testable against a
# plain numpy lexsort oracle (tests/test_pallas.py).

# pool-block VMEM budget for the fused merge kernel: the (ng, m_pad, tq)
# f32+i32 blocks plus the selection temporaries must fit the scoped budget;
# beyond it the route falls back to the XLA merge (knn_fused_eligible).
_FUSED_POOL_BUDGET = 48 << 20


def _knn_fused_merge_kernel(
    pool_v_ref, pool_i_ref, dist_ref, pos_ref, flag_ref,
    *, k: int, m: int, m_pad: int, ng: int, tq: int, k_pad: int,
):
    """Merge one query tile's pool: k iterated (max value, min position
    among its holders, mask) passes over the VMEM-resident (ng*m_pad, tq)
    pool view — the lex (-d2, pos) order, stated without argmax (header).
    Also computes the self-verify overflow flag in-kernel: a group whose
    m-th kept value beats the margined k-th threshold might have overflowed
    (same contract as ops/knn._adaptive_merge_self)."""
    C = ng * m_pad
    v = pool_v_ref[:].reshape(C, tq)
    pidx = pool_i_ref[:].reshape(C, tq)
    no_pos = jnp.iinfo(jnp.int32).max
    vals, poss = [], []
    for _ in range(k):
        best = jnp.max(v, axis=0, keepdims=True)      # (1, tq)
        holds = v == best
        pos = jnp.min(jnp.where(holds, pidx, no_pos), axis=0, keepdims=True)
        vals.append(best[0])
        poss.append(pos[0])
        # an exhausted pool (best == -inf) re-reads spent rows: harmless,
        # their -inf distance is what the id map keys on
        v = jnp.where(holds & (pidx == pos), -jnp.inf, v)
    fv = jnp.stack(vals)   # (k, tq) negated d2, descending
    fp = jnp.stack(poss)   # (k, tq)
    # margined threshold + per-group overflow flag (ops/knn._merge_pool's
    # delta contract: entries within ~8 ulps of the kth value are
    # computational ties, excluded from the must-be-present set)
    t = fv[k - 1]
    delta = jnp.abs(t) * 1e-6 + 1e-30
    tu = jnp.where(jnp.isfinite(t), t + delta, t)
    worst_kept = pool_v_ref[:, m - 1, :].reshape(ng, tq)
    flags = (worst_kept > tu[None, :]).any(axis=0).astype(jnp.int32)
    dist = jnp.sqrt(jnp.maximum(-fv, 0.0))
    if k_pad > k:
        dist = jnp.concatenate(
            [dist, jnp.full((k_pad - k, tq), jnp.inf, jnp.float32)]
        )
        fp = jnp.concatenate([fp, jnp.zeros((k_pad - k, tq), jnp.int32)])
    dist_ref[:] = dist.T   # (tq, k_pad): lane-aligned store
    pos_ref[:] = fp.T
    flag_ref[:] = flags[:, None]


def knn_fused_eligible(n_al: int, m: int, tile_i: int = _TILE_I,
                       tile_q: int = _TILE_Q) -> bool:
    """Whether the fused merge's pool block fits the VMEM budget at this
    aligned item count (ng = n_al / tile_i groups of m_pad kept rows)."""
    ng = n_al // tile_i
    m_pad = _round_up(m, 8)
    return ng * m_pad * tile_q * 8 <= _FUSED_POOL_BUDGET


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "m", "n_items", "interpret", "tile_q", "tile_i", "tile_d",
    ),
)
def knn_fused_pallas(
    items: jax.Array,       # (N_pad, D) f32, device-resident
    item_norm: jax.Array,   # (N_pad,) f32 squared norms
    valid: jax.Array,       # (N_pad,) bool
    queries: jax.Array,     # (Q, D) f32
    k: int,
    m: int,
    n_items: int,
    interpret: bool = False,
    tile_q: int = _TILE_Q,
    tile_i: int = _TILE_I,
    tile_d: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Device-complete fused route: candidates kernel -> fused merge kernel,
    both Pallas, one jit.  Returns (distances (Q, k) ascending euclidean,
    positions (Q, k) int32, flags (Q,) int32, zeros (Q,) int32) — the exact
    dispatch contract of ops/knn._adaptive_merge_self, so the collect /
    fallback machinery is route-agnostic.  Rows with flags != 0 need the
    exact per-row rerun (possible group overflow), same as ever."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q = queries.shape[0]
    vals, idxs, (ng, m_pad, q_pad, tq) = _candidates_pool(
        items, item_norm, valid, queries, m, n_items, interpret,
        tile_q, tile_i, tile_d, legacy=False,
    )
    k_pad = _round_up(k, 128)
    dist, pos, flags = pl.pallas_call(
        functools.partial(
            _knn_fused_merge_kernel,
            k=k, m=m, m_pad=m_pad, ng=ng, tq=tq, k_pad=k_pad,
        ),
        grid=(q_pad // tq,),
        in_specs=[
            pl.BlockSpec((ng, m_pad, tq), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ng, m_pad, tq), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tq, k_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tq, k_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tq, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((q_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 << 20),
        interpret=interpret,
    )(vals, idxs)
    zeros = jnp.zeros((Q,), jnp.int32)
    return dist[:Q, :k], pos[:Q, :k], flags[:Q, 0], zeros


@functools.partial(jax.jit, static_argnames=("n_items", "interpret"))
def knn_count_pallas(
    items: jax.Array,       # (N_pad, D) f32
    item_norm: jax.Array,   # (N_pad,) f32
    valid: jax.Array,       # (N_pad,) bool
    queries: jax.Array,     # (Q, D) f32
    thresh: jax.Array,      # (Q,) f32 margined negated-d2 thresholds
    n_items: int,
    interpret: bool = False,
) -> jax.Array:
    """Exact global #{-d2 > thresh} per query (the verification count,
    ops/knn._adaptive_count) computed with the SAME tiling and dot
    decomposition as knn_candidates_pallas — the two kernels' d2 values are
    bitwise identical, so the count check only fires on genuine overflow
    misses.  Returns (Q,) int32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, d = queries.shape
    tq = min(_TILE_Q, _round_up(Q, 128))
    d_pad = _round_up(d, 128)
    kb = min(_TILE_D, d_pad)
    d_blk = _round_up(d_pad, kb)
    q_pad = _round_up(Q, tq)

    qp = jnp.pad(
        queries.astype(jnp.float32), ((0, q_pad - Q), (0, d_blk - d))
    )
    qn = (qp * qp).sum(axis=1, keepdims=True)
    inorm = jnp.where(valid, item_norm, jnp.inf).astype(jnp.float32)
    items, inorm, ng = _aligned_items(items, inorm, kb)
    inorm = inorm.reshape(1, -1)
    # padded query rows: -inf threshold would count everything; +inf counts
    # nothing (they are sliced off anyway, this just keeps sums small)
    tp = jnp.pad(
        thresh.astype(jnp.float32), (0, q_pad - Q), constant_values=jnp.inf
    ).reshape(q_pad, 1)

    grid = (q_pad // tq, ng, d_blk // kb)
    counts = pl.pallas_call(
        functools.partial(
            _knn_count_kernel,
            n_items=n_items, tile_i=_TILE_I, d_true=d_blk, kd=kb,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, 1), lambda i, j, b: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _TILE_I), lambda i, j, b: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((tq, 1), lambda i, j, b: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tq, kb), lambda i, j, b: (i, b), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_I, kb), lambda i, j, b: (j, b), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tq, 1), lambda i, j, b: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((q_pad, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tq, _TILE_I), jnp.float32)],
        interpret=interpret,
    )(qn, inorm, tp, qp, items)
    return counts[:Q, 0]


def pallas_knn_eligible(mesh_shards: int, d: int, q: int) -> bool:
    """The fused kernel serves the single-shard TPU fast path (the only
    configuration this chip can run; multi-shard meshes keep the shard_map
    scan).  Queries narrower than one lane tile would pad 2x+, and shapes
    whose column alignment wastes >25% HBM keep the scan path (their item
    padding would otherwise be re-paid per dispatch)."""
    return (
        pallas_enabled()
        and mesh_shards == 1
        and q >= 128
        and d >= 128
        and _col_target(d) * 4 <= d * 5
    )
