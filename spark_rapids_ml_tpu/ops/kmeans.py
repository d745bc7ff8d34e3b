#
# Distributed KMeans solver (Lloyd + k-means|| init), pure jax, mesh-aware.
#
# TPU-native replacement for cuML's KMeansMG (used by the reference at
# clustering.py:324-341), redesigned for the MXU/HBM model rather than
# translated:
#   - the assignment step is expressed per device via shard_map: each device
#     walks its resident rows in place, in fixed-size row chunks
#     (max_samples_per_batch, the same knob cuML exposes): a loop over the
#     whole chunks, each sliced out of X where it lies, then one block of
#     the rows left over.  Nothing of the table's size is built in the loop.
#     Each block computes a (chunk, k) distance matrix on the MXU and
#     accumulates per-cluster weighted sums/counts locally, then one psum
#     over the data axis merges them — one collective per Lloyd iteration.
#   - iteration is a lax.while_loop on (shift > tol) & (iter < max_iter):
#     no host round-trips inside the fit.
#   - scalable k-means++ init keeps static shapes by drawing exactly
#     round_size candidates per round with Gumbel top-k sampling
#     (prob ∝ cost), then runs weighted k-means++ on the small replicated
#     candidate set.
#   - jax.named_scope marks the parts lloyd_iterations' device time divides
#     into: lloyd.norms (row norms, once a fit), lloyd.assign (distances +
#     argmin), lloyd.update (one-hot sums, psum, division) and lloyd.inertia
#     (the last, exact pass).  Metadata on the operations, read in xprof's
#     trace viewer and op profile; free at run time.
#

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from ..parallel.mesh import DATA_AXIS
from . import lloyd_tall_pass


def _chunked_assign_stats(X_loc, w_loc, centers, chunk, x_norm_loc, exact_inertia=False):
    """Walk local rows in `chunk`-sized blocks; returns (sums[k,D], counts[k],
    inertia) for this device's rows.  Distances use the expanded form
    ||x||^2 - 2 x·c + ||c||^2 so the hot op is a (chunk, D) @ (D, k) matmul.
    ||x||^2 is invariant across Lloyd iterations, so it is computed once per
    fit and passed in — recomputing it per iteration costs a full extra HBM
    sweep over X (measured ~45% of iteration time at d=3000).

    Rows are read where they lie: the n_loc // chunk whole chunks are sliced
    out of X_loc by a loop over the chunk index, and the n_loc % chunk rows
    left over are one more block of their own (smaller, static) shape.
    Nothing of the table's size is built: no padded copy, no padding rows
    through the products.  Either half drops out when it is empty (a table
    or mesh shard smaller than one chunk is a tail only).

    exact_inertia=True recomputes each row's cost as ||x - c_assign||^2 from
    a gathered-center difference: the expanded form cancels catastrophically
    when distances are small relative to the norms, and on TPU the MXU's
    single-pass bf16 products make that error ~0.4% of the *norm* magnitude
    (measured 4.7x inflated inertia on tight blobs).  The difference form is
    O(chunk*D) elementwise work — cheaper than the matmul it corrects."""
    n_loc, d = X_loc.shape
    k = centers.shape[0]
    n_full, tail = divmod(n_loc, chunk)
    with jax.named_scope("lloyd.assign"):
        c_norm = (centers * centers).sum(axis=1)

    def block(carry, xb, wb, x_norm):
        sums, counts, inertia = carry
        with jax.named_scope("lloyd.assign"):
            d2 = x_norm[:, None] - 2.0 * (xb @ centers.T) + c_norm[None, :]
            assign = jnp.argmin(d2, axis=1)
        with jax.named_scope("lloyd.update"):
            onehot = jax.nn.one_hot(assign, k, dtype=xb.dtype) * wb[:, None]
            sums = sums + onehot.T @ xb
            counts = counts + onehot.sum(axis=0)
        if exact_inertia:
            diff = xb - centers[assign]
            inertia = inertia + ((diff * diff).sum(axis=1) * wb).sum()
        return sums, counts, inertia

    def rows(start, size):
        return tuple(
            jax.lax.dynamic_slice_in_dim(a, start, size)
            for a in (X_loc, w_loc, x_norm_loc)
        )

    carry = (
        jnp.zeros((k, d), dtype=X_loc.dtype),
        jnp.zeros((k,), dtype=X_loc.dtype),
        jnp.zeros((), dtype=X_loc.dtype),
    )
    if n_full:
        carry = jax.lax.fori_loop(
            0, n_full, lambda i, c: block(c, *rows(i * chunk, chunk)), carry
        )
    if tail:
        carry = block(carry, *rows(n_full * chunk, tail))
    return carry


@partial(
    jax.jit,
    static_argnames=("mesh", "max_iter", "chunk"),
)
def lloyd_iterations(
    X: jax.Array,
    w: jax.Array,
    centers0: jax.Array,
    mesh: Mesh,
    max_iter: int,
    tol: float,
    chunk: int,
):
    """Run Lloyd iterations until center-shift^2 <= tol or max_iter.

    X (N_pad, D) and w (N_pad,) are row-sharded over `mesh`; centers are
    replicated.  Returns (centers, n_iter, inertia).
    """

    def per_device(X_loc, w_loc, centers0):
        with jax.named_scope("lloyd.norms"):
            x_norm_loc = (X_loc * X_loc).sum(axis=1)  # hoisted out of the loop

        def cond(state):
            _, prev_shift, it = state
            return (it < max_iter) & (prev_shift > tol)

        def body(state):
            centers, _, it = state
            sums, counts, _ = _chunked_assign_stats(
                X_loc, w_loc, centers, chunk, x_norm_loc
            )
            with jax.named_scope("lloyd.update"):
                sums = jax.lax.psum(sums, DATA_AXIS)
                counts = jax.lax.psum(counts, DATA_AXIS)
                nonempty = counts > 0
                new_centers = jnp.where(
                    nonempty[:, None], sums / jnp.maximum(counts, 1.0)[:, None], centers
                )
                shift = ((new_centers - centers) ** 2).sum()
            return (new_centers, shift, it + 1)

        init = (centers0, jnp.array(jnp.inf, X_loc.dtype), jnp.array(0, jnp.int32))
        centers, _, n_iter = jax.lax.while_loop(cond, body, init)
        # one final stats pass so inertia reflects the returned centers
        # (exact difference-form cost: the reported inertia must not carry
        # the training loop's fast-matmul cancellation error)
        with jax.named_scope("lloyd.inertia"):
            _, _, final_inertia = _chunked_assign_stats(
                X_loc, w_loc, centers, chunk, x_norm_loc, exact_inertia=True
            )
            final_inertia = jax.lax.psum(final_inertia, DATA_AXIS)
        return centers, n_iter, final_inertia

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(X, w, centers0)


def _tall_assign_stats(xt_loc, w_loc, centers, chunk, x_norm_loc, exact_inertia=False):
    """_chunked_assign_stats over a device's columns of a feature-major table
    (ops/tall.TallMatrix), in plain jnp: lloyd_tall's exact-inertia pass, the
    block of rows its update passes leave over behind the kernel's whole tiles
    (ops/lloyd_tall_pass.py), the whole update pass where the kernel does not
    take the table (float64, fewer than 128 rows a device), and the kernel's
    twin in tests.  `xt_loc` (D_pad, n_loc) with the rows on the lanes,
    `centers` (k, D_pad) with the padding features zero.  The same equations
    with the rows as the minor axis of everything a block builds, so that at
    k = 20 nothing is a (rows, k) tile of 20 lanes in 128: for a block b of C
    rows, S = centers @ xt[:, b] (k, C); d2 = |m|^2[:, None] - 2 S +
    |x|^2[None, b]; a = argmin over axis 0 (lowest index on ties);
    H = onehot(a) * w[b] (k, C); sums += H @ xt[:, b].T, counts += H.sum(1).

    Both products run at Precision.HIGHEST.  Centres in a box of +-10 give
    |x|^2 near 1000 and the cross term near it, while two centres that share
    one blob differ in d2 by a few units over the whole blob: operands
    rounded to bfloat16 (default precision) move the cross term by some
    tenths, which is the boundary between the two by a tenth of the blob's
    width, another tenth every iteration as the centres' roundings change.

    A block is `chunk` rows, as the row-major pass's, and not more: the MXU
    accumulates a product's float32 sums along the contraction, here the
    block's rows, and at blocks of 524,288 rows the centres that have stopped
    lay 6.9e-6 of their norm from the exact means of their rows, ten times
    what they do at 32,768 and twice what bfloat16 operands cost (a pass
    was 15.9 ms for 21.1: PERF.md section 6, PR 52).  The kernel's chains
    are a lane tile's 128 rows, its lane tiles and tiles added on the vector
    unit (the tiles compensated), which is why its tile may be as large as
    VMEM holds and this block may not.

    The walk is _chunked_assign_stats' own: the n_loc // chunk whole chunks
    where they lie by a loop over the chunk index, the rows left over one
    block of their own static shape.  Returns (sums (k, D_pad), counts (k,),
    inertia), the inertia in the difference form where `exact_inertia`."""
    d_pad, n_loc = xt_loc.shape
    k = centers.shape[0]
    n_full, tail = divmod(n_loc, chunk)
    highest = jax.lax.Precision.HIGHEST
    with jax.named_scope("lloyd.assign"):
        c_norm = (centers * centers).sum(axis=1)

    def block(carry, xb, wb, x_norm):
        sums, counts, inertia = carry
        with jax.named_scope("lloyd.assign"):
            cross = jnp.matmul(centers, xb, precision=highest)
            d2 = c_norm[:, None] - 2.0 * cross + x_norm[None, :]
            assign = jnp.argmin(d2, axis=0)
        with jax.named_scope("lloyd.update"):
            mine = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0) == assign[None, :]
            hot = jnp.where(mine, wb[None, :], jnp.zeros((), xb.dtype))
            sums = sums + jax.lax.dot_general(
                hot, xb, (((1,), (1,)), ((), ())), precision=highest
            )
            counts = counts + hot.sum(axis=1)
        if exact_inertia:
            diff = xb - jnp.matmul(centers.T, mine.astype(xb.dtype), precision=highest)
            inertia = inertia + ((diff * diff).sum(axis=0) * wb).sum()
        return sums, counts, inertia

    def cols(start, size):
        return (
            jax.lax.dynamic_slice_in_dim(xt_loc, start, size, axis=1),
            jax.lax.dynamic_slice_in_dim(w_loc, start, size),
            jax.lax.dynamic_slice_in_dim(x_norm_loc, start, size),
        )

    carry = (
        jnp.zeros((k, d_pad), dtype=xt_loc.dtype),
        jnp.zeros((k,), dtype=xt_loc.dtype),
        jnp.zeros((), dtype=xt_loc.dtype),
    )
    if n_full:
        carry = jax.lax.fori_loop(
            0, n_full, lambda i, c: block(c, *cols(i * chunk, chunk)), carry
        )
    if tail:
        carry = block(carry, *cols(n_full * chunk, tail))
    return carry


@partial(
    jax.jit,
    static_argnames=("mesh", "max_iter", "chunk"),
)
def lloyd_tall(
    X,
    w: jax.Array,
    centers0: jax.Array,
    mesh: Mesh,
    max_iter: int,
    tol: float,
    chunk: int,
):
    """lloyd_iterations over a feature-major table (ops/tall.TallMatrix):
    X.xt (D_pad, N_pad) and w (N_pad,) are row-sharded over `mesh` (X.xt along
    axis 1), centers0 (k, D) replicated.  The same loop, the same stopping
    rule, the same guarantees (ties to the lowest index, an emptied centre
    keeps its place, exactly max_iter updates at tol 0, the inertia in the
    difference form) and the same named scopes; its own name, so that a
    profile tells the two passes apart.

    An update pass of a float32 table is the Pallas call `lloyd_tall_pass`
    (ops/lloyd_tall_pass.py) over each device's whole tiles: a tile read once,
    cut into its bfloat16 pieces once, distances, argmin and sums from them in
    VMEM, both products the six partial products Precision.HIGHEST computes.
    Its tile follows from k, D_pad, the device's rows and `chunk` (row_tile:
    never more than `chunk`); it leaves |x|^2 out of the argmin, so there a
    tie is a tie of |m|^2 - 2 m.x.  The rows behind the whole tiles, the
    exact-inertia pass, and every pass of a float64 table or of fewer than
    128 rows a device are _tall_assign_stats'.  Off the chip the call runs
    through Pallas's interpreter.  Returns (centers (k, D), n_iter, inertia)."""
    d = X.n_cols

    def per_device(xt_loc, w_loc, centers0):
        with jax.named_scope("lloyd.norms"):
            x_norm_loc = (xt_loc * xt_loc).sum(axis=0)

        def cond(state):
            _, prev_shift, it = state
            return (it < max_iter) & (prev_shift > tol)

        # the update passes' whole tiles go through the kernel, the rows left
        # over (fewer than a tile, so than a chunk) through XLA's block
        d_pad, n_loc = xt_loc.shape
        tile = lloyd_tall_pass.row_tile(centers0.shape[0], d_pad, n_loc, chunk) if xt_loc.dtype == jnp.float32 else 0
        done = n_loc // tile * tile if tile else 0
        w_tiles = lloyd_tall_pass.weight_tiles(w_loc, tile) if done else None
        xt_left, w_left, norm_left = xt_loc[:, done:], w_loc[done:], x_norm_loc[done:]

        def body(state):
            centers, _, it = state
            parts = []
            if done:
                parts.append(lloyd_tall_pass.pass_sums(xt_loc, w_tiles, centers, interpret=lloyd_tall_pass.interpreted()))
            if done < n_loc:
                parts.append(_tall_assign_stats(xt_left, w_left, centers, chunk, norm_left)[:2])
            sums, counts = (sum(p) for p in zip(*parts))
            with jax.named_scope("lloyd.update"):
                sums = jax.lax.psum(sums, DATA_AXIS)
                counts = jax.lax.psum(counts, DATA_AXIS)
                new_centers = jnp.where(
                    (counts > 0)[:, None], sums / jnp.maximum(counts, 1.0)[:, None], centers
                )
                shift = ((new_centers - centers) ** 2).sum()
            return (new_centers, shift, it + 1)

        # the padding features of the table are zero: so are the centres'
        padded = jnp.pad(centers0, ((0, 0), (0, d_pad - d)))
        init = (padded, jnp.array(jnp.inf, xt_loc.dtype), jnp.array(0, jnp.int32))
        centers, _, n_iter = jax.lax.while_loop(cond, body, init)
        with jax.named_scope("lloyd.inertia"):
            _, _, final_inertia = _tall_assign_stats(
                xt_loc, w_loc, centers, chunk, x_norm_loc, exact_inertia=True
            )
            final_inertia = jax.lax.psum(final_inertia, DATA_AXIS)
        return centers[:, :d], n_iter, final_inertia

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(X.xt, w, centers0)


def _masked_min_dist2(X, w, centers, valid):
    """Weighted squared distance of every row to its nearest *valid* center.
    Invalid center slots are zeroed before the matmul (never inf: inf*0 -> nan
    would poison the MXU product) and masked to +inf afterwards."""
    c = jnp.where(valid[:, None], centers, 0.0)
    c_norm = (c * c).sum(axis=1)
    x_norm = (X * X).sum(axis=1)
    d2 = x_norm[:, None] - 2.0 * (X @ c.T) + c_norm[None, :]
    d2 = jnp.where(valid[None, :], d2, jnp.inf)
    return jnp.maximum(jnp.min(d2, axis=1), 0.0) * w


@partial(jax.jit, static_argnames=("k", "rounds", "round_size"))
def scalable_kmeans_pp_init(
    X: jax.Array,
    w: jax.Array,
    k: int,
    seed: int,
    oversampling_factor: float,
    rounds: int = 4,
    round_size: int = 0,
):
    """k-means|| with static shapes (candidate pool = 1 + rounds*round_size):
    each round draws exactly `round_size` rows without replacement with
    probability ∝ current cost via Gumbel top-k, then weighted k-means++
    reduces the candidate pool to k centers.  Replaces cuML's
    init="scalable-k-means++" behaviorally."""
    n, d = X.shape
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    # first center: weighted random row
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
    first = jnp.argmax(logw + jax.random.gumbel(k0, (n,)))
    pool = jnp.zeros((1 + rounds * round_size, d), X.dtype).at[0].set(X[first])
    pool_valid = jnp.zeros((1 + rounds * round_size,), bool).at[0].set(True)

    def round_body(i, state):
        pool, pool_valid, key = state
        key, kr = jax.random.split(key)
        cost = _masked_min_dist2(X, w, pool, pool_valid)
        logp = jnp.where((w > 0) & (cost > 0), jnp.log(jnp.maximum(cost, 1e-30)), -jnp.inf)
        _, idx = jax.lax.top_k(logp + jax.random.gumbel(kr, (n,)), round_size)
        start = 1 + i * round_size
        pool = jax.lax.dynamic_update_slice(pool, X[idx], (start, 0))
        pool_valid = jax.lax.dynamic_update_slice(
            pool_valid, jnp.ones((round_size,), bool), (start,)
        )
        return pool, pool_valid, key

    pool, pool_valid, key = jax.lax.fori_loop(
        0, rounds, round_body, (pool, pool_valid, key)
    )

    # weight candidates by the mass of the points they attract
    masked_pool = jnp.where(pool_valid[:, None], pool, 0.0)
    c_norm = jnp.where(pool_valid, (masked_pool * masked_pool).sum(axis=1), jnp.inf)
    d2 = (X * X).sum(axis=1)[:, None] - 2.0 * (X @ masked_pool.T) + c_norm[None, :]
    d2 = jnp.where(pool_valid[None, :], d2, jnp.inf)
    assign = jnp.argmin(d2, axis=1)
    cand_w = jax.ops.segment_sum(w, assign, num_segments=pool.shape[0])

    # weighted k-means++ on the (small, replicated) candidate pool
    m = pool.shape[0]

    def pp_body(j, state):
        centers, centers_valid, key = state
        key, kj = jax.random.split(key)
        cost = _masked_min_dist2(pool, cand_w * pool_valid, centers, centers_valid)
        logp = jnp.where(cost > 0, jnp.log(jnp.maximum(cost, 1e-30)), -jnp.inf)
        # degenerate case (fewer distinct candidates than k): fall back to any
        # valid candidate
        logp = jnp.where(
            jnp.any(jnp.isfinite(logp)), logp, jnp.where(pool_valid, 0.0, -jnp.inf)
        )
        pick = jnp.argmax(logp + jax.random.gumbel(kj, (m,)))
        return centers.at[j].set(pool[pick]), centers_valid.at[j].set(True), key

    centers0 = jnp.zeros((k, d), X.dtype).at[0].set(pool[0])
    centers_valid0 = jnp.zeros((k,), bool).at[0].set(True)
    centers, _, _ = jax.lax.fori_loop(1, k, pp_body, (centers0, centers_valid0, key))
    return centers


@partial(jax.jit, static_argnames=("k",))
def random_init(X: jax.Array, w: jax.Array, k: int, seed: int):
    """init="random": k distinct weighted-random data rows."""
    n = X.shape[0]
    key = jax.random.PRNGKey(seed)
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
    _, idx = jax.lax.top_k(logw + jax.random.gumbel(key, (n,)), k)
    return X[idx]


@partial(jax.jit, static_argnames=("k", "mesh"))
def random_init_tall(X, w: jax.Array, k: int, seed: int, mesh: Mesh):
    """random_init on a feature-major table row-sharded over `mesh`: the same
    k rows for the same seed, weights and padded row count.  A row is a
    column of X.xt, read on the device that holds it as the 128-lane tile
    around it with the other lanes masked out, one row after another, and
    summed over the mesh (every other device adds zeros).  Left to XLA, a
    gather along the lanes or a slice one lane wide lays the whole table
    out row-major first (12.8 GB of temporaries at 25M x 32), and a dynamic
    slice of a sharded table gathers all of it on every device (both seen
    in compiles for a described v5e)."""
    n = X.xt.shape[1]
    key = jax.random.PRNGKey(seed)
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
    _, idx = jax.lax.top_k(logw + jax.random.gumbel(key, (n,)), k)

    def per_device(xt_loc, idx):
        n_loc = xt_loc.shape[1]
        lanes = min(128, n_loc)

        def row(i):
            at = i - jax.lax.axis_index(DATA_AXIS) * n_loc
            here = (at >= 0) & (at < n_loc)
            at = jnp.clip(at, 0, n_loc - 1)
            start = jnp.minimum(at // lanes * lanes, n_loc - lanes)
            tile = jax.lax.dynamic_slice_in_dim(xt_loc, start, lanes, axis=1)
            mine = (jnp.arange(lanes) == at - start) & here
            return jnp.where(mine[None, :], tile, 0).sum(axis=1)[: X.n_cols]

        return jax.lax.psum(jax.lax.map(row, idx), DATA_AXIS)

    return shard_map(
        per_device, mesh=mesh, in_specs=(P(None, DATA_AXIS), P()), out_specs=P(), check_vma=False
    )(X.xt, idx)


@jax.jit
def stream_kmeans_chunk_kernel(
    X: jax.Array, w: jax.Array, centers: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One streamed chunk's mini-batch Lloyd statistics against the CURRENT
    running centers: (per-center weighted sums (k, D), counts (k,),
    difference-form chunk cost) — the srml-stream kmeans update kernel.
    Assignment math mirrors _chunked_assign_stats (expanded-form distances
    on the MXU, exact difference-form cost so the reported running inertia
    never carries the fast-matmul cancellation error); no scan — streamed
    chunks are already bucket-sized blocks."""
    k = centers.shape[0]
    x_norm = (X * X).sum(axis=1)
    c_norm = (centers * centers).sum(axis=1)
    d2 = x_norm[:, None] - 2.0 * (X @ centers.T) + c_norm[None, :]
    assign = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=X.dtype) * w[:, None]
    sums = onehot.T @ X
    counts = onehot.sum(axis=0)
    diff = X - centers[assign]
    cost = ((diff * diff).sum(axis=1) * w).sum()
    return sums, counts, cost


def kmeans_predict_kernel(X: jax.Array, centers: jax.Array) -> jax.Array:
    # min_dist_argmin routes by regime: the fused Pallas kernel on TPU in the
    # memory-bound low-d/large-k regime (the (N, k) distance tile never
    # touches HBM), exact-f32 XLA everywhere else — see
    # pallas_tpu.min_dist_argmin for the measured crossover.
    from .pallas_tpu import min_dist_argmin

    _, assign = min_dist_argmin(X, centers)
    return assign


@jax.jit
def lane_kmeans_predict_kernel(
    X: jax.Array, lanes: jax.Array, centers: jax.Array
) -> jax.Array:
    """Multiplexed nearest-center assignment (srml-lanes): centers is the
    lane-stacked (L, k, D) buffer and row r is assigned against lane
    lanes[r]'s centers.  Identical math to the exact-f32 XLA formulation
    of pallas_tpu.min_dist_argmin (norms in f32, HIGHEST-precision cross
    term, first-index argmin) — the fused Pallas route reads ONE shared
    center set per program so the lane-gathered path always takes the XLA
    program, and on integer-exact data the two formulations are bitwise
    equal anyway."""
    cg = jnp.take(centers, lanes, axis=0)  # (N, k, D)
    x_norm = (X.astype(jnp.float32) ** 2).sum(axis=1)
    c_norm = (cg.astype(jnp.float32) ** 2).sum(axis=2)
    cross = jnp.einsum(
        "nd,nkd->nk",
        X,
        cg,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = x_norm[:, None] - 2.0 * cross + c_norm
    return jnp.argmin(d2, axis=1).astype(jnp.int32)
