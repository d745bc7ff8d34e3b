#
# One sweep of covariance-update coordinate descent as ONE kernel.
#
# A sweep is d dependent steps, each a d-long dot of a Gram row with the
# coefficients, a soft threshold and a one-element update.  As a
# lax.fori_loop XLA:TPU runs every step as nine small operations (four
# scalar dynamic-slices, the row slice, the multiply-reduce, the update, the
# loop's own bookkeeping): 4.9 us a coordinate on a v5e at d = 3000, 146 ms of
# a 10-sweep fit, and so many device events (some 6 million in a 10 s window
# of such fits) that ending a profiler trace of the window took 150 s
# (PERF.md, PR 30).  Here the sweep is the Pallas call
# `cd_sweep`: a 1-D grid walks the Gram in blocks of whole rows (Mosaic
# prefetches the next block while this one's rows are worked through); the
# coefficients stay in VMEM for the whole sweep as one (1, Dp) lane vector;
# a step is a lane product and two lane reductions (the dot, and b_j picked
# out by its lane); c_j, the diagonal and the denominator are scalars read
# from SMEM.
#
# The arithmetic is solve_elasticnet_cd's jnp loop to the letter
# (ops/glm.py: rho = (c_j - G_j.b + G_jj b_j) / n, the soft threshold, the
# division), in float32; what differs is the order in which a 3000-long dot is
# summed.  The sweeps' while_loop, and with it the stopping rule, stays in
# ops/glm.py.
#
# Shapes: d is padded up to Dp, a multiple of 128, with zero rows and columns
# of the Gram, zeros of c and ones of the denominator: a padded coordinate's
# rho is 0, so it stays 0 and moves nothing.
#
# Pallas is imported where the kernel is built, not with this module (see
# ops/logistic_pass.py).
#

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .pallas_tpu import _round_up, pallas_enabled

_LANES = 128
# two buffers of a block of Gram rows, within the default scoped VMEM
_BLOCK_BYTES = 4 * 1024 * 1024
# the widest system the kernel has run at on the chip (chip_smoke.py: 8, 100,
# 3000 and 16,000 columns, PR 30): c, the diagonal and the denominator lie
# whole in SMEM, 3 x 4 bytes a column.  A wider one sweeps through the loop.
_MAX_COLS = 16_384


def takes(G: jax.Array) -> bool:
    """Whether solve_elasticnet_cd sweeps through the kernel: a float32 system
    of at most _MAX_COLS columns, where Pallas kernels run.  Static at trace
    time."""
    return G.dtype == jnp.float32 and G.shape[0] <= _MAX_COLS and pallas_enabled()


def padded(d: int) -> int:
    return _round_up(d, _LANES)


def _block_rows(dp: int) -> int:
    rows = _LANES
    while rows > 8 and rows * dp * 4 > _BLOCK_BYTES:
        rows //= 2
    return rows


def _sweep_kernel(scal_ref, c_ref, diag_ref, denom_ref, g_ref, b_in_ref, b_ref, delta_ref):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    rows, dp = g_ref.shape

    @pl.when(i == 0)
    def _():
        b_ref[...] = b_in_ref[...]
        delta_ref[...] = jnp.zeros_like(delta_ref)

    n, thresh = scal_ref[0], scal_ref[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, dp), 1)

    def coord(k, carry):
        j = i * rows + k
        b = b_ref[...]
        at = lane == j
        gj = jnp.sum(g_ref[pl.ds(k, 1), :] * b, axis=1, keepdims=True)
        bj = jnp.sum(jnp.where(at, b, 0.0), axis=1, keepdims=True)
        rho = (c_ref[j] - gj + diag_ref[j] * bj) / n
        # sign(rho) * max(|rho| - thresh, 0), spelled without sign()
        soft = jnp.where(rho > thresh, rho - thresh, jnp.where(rho < -thresh, rho + thresh, 0.0))
        new = soft / denom_ref[j]
        delta_ref[...] = jnp.maximum(delta_ref[...], jnp.abs(new - bj))
        b_ref[...] = jnp.where(at, new, b)
        return carry

    jax.lax.fori_loop(0, rows, coord, 0)


def _interpreted() -> bool:
    return jax.default_backend() != "tpu"


def sweep(
    G: jax.Array,
    c: jax.Array,
    diag: jax.Array,
    denom: jax.Array,
    b: jax.Array,
    n: jax.Array,
    thresh: jax.Array,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """One cyclic sweep over all coordinates, in order: (b (1, Dp), the largest
    |change| of a coefficient).  G (Dp, Dp), c, diag (G's diagonal) and denom
    (Dp,), b (1, Dp): all padded (see `padded`); n and thresh scalars.  `mesh`
    is the mesh the system is replicated on (None: one device): every device
    then runs the same sweep under a shard_map, since a Pallas call is no
    program GSPMD can partition (ops/logistic.py says the same of its own).
    The kernel runs through the interpreter anywhere but on the chip."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dp = G.shape[0]
    rows = _block_rows(dp)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        _sweep_kernel,
        grid=(dp // rows,),
        in_specs=[
            smem, smem, smem, smem,
            pl.BlockSpec((rows, dp), lambda i: (i, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(flops=2 * dp * dp, transcendentals=0, bytes_accessed=4 * dp * dp),
        interpret=_interpreted(),
        name="cd_sweep",
    )
    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        call = shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    b_new, delta = call(jnp.stack([n, thresh]).astype(jnp.float32), c, diag, denom, G, b)
    return b_new, delta[0, 0]
