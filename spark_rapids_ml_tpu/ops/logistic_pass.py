#
# Value and gradient of the dense binary logistic data term from ONE read of X.
#
# jax.value_and_grad of the log-loss reads the table twice an evaluation: the
# forward product X @ w and its transpose X.T @ r are a row reduction and a
# column reduction with a nonlinearity between them, which XLA emits as two
# fusions.  A GLM's gradient is closed-form, so one walk over row tiles gives
#
#     z = X_t w + b ;  ll = logaddexp(0, z) - y z ;  r = (sigmoid(z) - y) wt
#     loss += sum(ll wt) ;  g += X_t^T r ;  gb += sum(r)
#
# from one read of each tile (binary_pointwise is the per-row part, shared by
# the kernel and the plain jnp form below).
#
# The kernel walks X FEATURE-MAJOR: its operand is X.T, (D, N), in blocks of
# (D, tile) rows-on-lanes.  Two reasons.  (1) A float32 table whose width is
# not a multiple of 128 lies on the chip in the {0,1} tiled layout (the
# runtime's compact layout: 3000 columns would pad to 3072 row-major), so X.T
# is a bitcast there and the kernel reads the table WHERE IT LIES: no layout
# copy, no padded copy, no second table in HBM.  Where X does lie row-major
# (D a multiple of 128) the transpose is one copy a fit, outside the loop.
# (2) Everything per row (z, the loss, the residual, y, the weights) is then
# lane-dense: z is a sum over sublane groups (VPU adds, one 8 -> 1 sublane
# reduction a tile), r multiplies the block broadcast along sublanes, and g
# accumulates as (D, 128) lane partials that are summed once, outside.  No
# lane reduction, no relayout and no MXU pass in the loop: float32 products
# and float32 sums on the VPU.
#
# Rows: the grid covers n // tile whole tiles (tile a multiple of 128, chosen
# from D so that two buffers of the block fit scoped VMEM); the n % tile rows
# left, and a table of fewer than 128 rows, go through the plain jnp form.
# Padding rows carry weight 0 and so add nothing on either path.
#
# Which tables (takes_table): those a device cannot hold in VMEM.  A smaller
# one XLA copies into VMEM once a fit and its two fusions then read no HBM at
# all, which no walk over HBM beats (v5e, 8192 x 3000, 10 warm iterations:
# 1.72 ms so against 2.07 ms through this kernel).
#
# Pallas is imported where the kernel is built, not with this module: the
# import costs over a second, and a fit that never takes the kernel (the CPU,
# a streamed chunk, the other estimators that import the classifier's
# modules) does not pay it.
#

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.mesh import DATA_AXIS
from .linalg import exact_matmul

_LANES = 128
_SUBLANES = 8
# one block of X.T: (D, tile) float32.  Mosaic double-buffers it, and holds the
# (D, 128) weights and gradient partials beside it (1.5 MB each at D = 3000)
_BLOCK_BYTES = 6 * 1024 * 1024
_MAX_TILE = 2048
# wider tables go through the plain form: the (D, 128) residents outgrow VMEM
_MAX_WIDTH = 8192
_VMEM_LIMIT = 48 * 1024 * 1024
# a device's rows XLA keeps in VMEM (128 MiB on a v5e) across the optimizer's
# loop when they fit: compiled for a v5e, 9216 x 3000 (105 MiB; 108 padded to
# 3072 columns) is held there, 10240 x 3000 (117 MiB) is not
_RESIDENT_BYTES = 112 * 1024 * 1024


def binary_pointwise(z, y01, wt):
    """Per row, from the score z: (weighted log-loss, weighted residual
    d loss / d z).  logaddexp for stability; y01 in {0, 1}."""
    ll = jnp.logaddexp(0.0, z) - y01 * z
    r = (jax.nn.sigmoid(z) - y01) * wt
    return ll * wt, r


def binary_block_sums(Xb, y01, wt, W, b):
    """(sum of weighted log-loss, X_b^T r (D,), sum r) over the rows of one
    block, plain jnp: the tail of the walk, and the whole table off the TPU.
    exact_matmul: float32 operands in both products wherever this runs."""
    z = exact_matmul(Xb, W) + b
    lw, r = binary_pointwise(z, y01, wt)
    return lw.sum(), exact_matmul(r, Xb), r.sum()


def row_tile(n: int, d: int) -> int:
    """Rows a grid step takes: the multiple of 128 whose (D, tile) float32
    block fills _BLOCK_BYTES, at most _MAX_TILE and at most the table; 0 when
    the kernel does not take the table (fewer than 128 rows, or too wide)."""
    if d > _MAX_WIDTH:
        return 0
    d_pad = -(-d // _SUBLANES) * _SUBLANES
    by_width = _BLOCK_BYTES // (4 * d_pad) // _LANES * _LANES
    return min(max(by_width, _LANES), _MAX_TILE, n // _LANES * _LANES)


def takes_table(n_loc: int, d: int) -> bool:
    """Whether the kernel walks a device's (n_loc, d) float32 rows: a table
    XLA cannot keep in VMEM, of a width the kernel's residents fit."""
    return 4 * n_loc * d > _RESIDENT_BYTES and row_tile(n_loc, d) > 0


def _pass_kernel(b_ref, xt_ref, wb_ref, yw_ref, g_ref, sums_ref):
    from jax.experimental import pallas as pl

    d, tile = xt_ref.shape
    cols = [slice(t * _LANES, (t + 1) * _LANES) for t in range(tile // _LANES)]
    groups, d8 = d // _SUBLANES, d // _SUBLANES * _SUBLANES

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def group(i):
        return pl.ds(pl.multiple_of(i * _SUBLANES, _SUBLANES), _SUBLANES)

    # z = w . x for the tile's rows: eight sublane partials a lane tile
    def z_step(i, acc):
        wv = wb_ref[group(i), :]
        return tuple(a + xt_ref[group(i), c] * wv for a, c in zip(acc, cols))

    zero = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
    acc = (zero,) * len(cols)
    if groups:
        acc = jax.lax.fori_loop(0, groups, z_step, acc)
    z = [a.sum(axis=0, keepdims=True) for a in acc]
    if d8 < d:  # the width's last rows, fewer than a sublane group
        wv = wb_ref[d8:d, :]
        z = [zt + (xt_ref[d8:d, c] * wv).sum(axis=0, keepdims=True)
             for zt, c in zip(z, cols)]

    b = b_ref[0, 0]
    rb = []
    for zt, c in zip(z, cols):
        lw, r = binary_pointwise(zt + b, yw_ref[0:1, c], yw_ref[1:2, c])
        sums_ref[0:1, c] += lw
        sums_ref[1:2, c] += r
        rb.append(jnp.broadcast_to(r, (_SUBLANES, _LANES)))

    # g += x r: lane partials, one (8, 128) a sublane group
    def g_step(i, carry):
        part = xt_ref[group(i), cols[0]] * rb[0]
        for c, rt in zip(cols[1:], rb[1:]):
            part += xt_ref[group(i), c] * rt
        g_ref[group(i), :] += part
        return carry

    if groups:
        jax.lax.fori_loop(0, groups, g_step, 0)
    if d8 < d:
        part = xt_ref[d8:d, cols[0]] * rb[0][: d - d8]
        for c, rt in zip(cols[1:], rb[1:]):
            part += xt_ref[d8:d, c] * rt[: d - d8]
        g_ref[d8:d, :] += part


@partial(jax.jit, static_argnames=("tile", "interpret"))
def pass_sums(
    Xt: jax.Array,   # (D, N) float32: X.T
    yw: jax.Array,   # (2, N) float32: labels in {0, 1}, then weights
    W: jax.Array,    # (D,)
    b: jax.Array,    # ()
    tile: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """binary_block_sums over the first N // tile whole tiles of rows, from
    one read of them: the Pallas call `logistic_pass`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, n = Xt.shape
    steps = n // tile
    assert steps > 0 and tile % _LANES == 0, (n, tile)
    rows = steps * tile
    g, sums = pl.pallas_call(
        _pass_kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((d, tile), lambda i: (0, i)),
            pl.BlockSpec((d, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((2, tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((d, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((2, tile), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((2, tile), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * d * rows, transcendentals=3 * rows,
            bytes_accessed=4 * d * rows,
        ),
        interpret=interpret,
        name="logistic_pass",
    )(
        b.reshape(1, 1),
        Xt,
        jnp.broadcast_to(W[:, None], (d, _LANES)),
        yw,
    )
    return sums[0].sum(), g.sum(axis=1), sums[1].sum()


def one_pass_sums(
    X: jax.Array,
    y01: jax.Array,
    wt: jax.Array,
    mesh=None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """(W (D,), b ()) -> binary_block_sums over all of X's rows, each row read
    once an evaluation where the kernel takes it.  What no evaluation changes
    (X.T, the packed labels and weights) is made here, once: call this outside
    the optimizer's loop.  On a mesh X is row-sharded over DATA_AXIS: every
    device walks its own rows and ONE psum of (g, gb, loss) joins them.  A
    mesh of one device goes the same way: inside shard_map both of L-BFGS's
    call sites (the first evaluation, the line search) see the same types,
    and the kernel is traced and lowered once for the two."""
    d = X.shape[1]
    Xt = X.T
    yw = jnp.stack([y01, wt])
    if interpret is None:
        # the kernel runs through the interpreter anywhere but on the chip
        interpret = jax.default_backend() != "tpu"

    def local(Xt, yw, W, b):
        n = Xt.shape[1]
        rows = row_tile(n, d) if tile is None else tile
        done = n // rows * rows if rows else 0
        parts = []
        if done:
            parts.append(pass_sums(Xt, yw, W, b, tile=rows, interpret=interpret))
        if done < n:
            parts.append(
                binary_block_sums(Xt[:, done:].T, yw[0, done:], yw[1, done:], W, b)
            )
        return tuple(sum(p) for p in zip(*parts))

    if mesh is None:
        return lambda W, b: local(Xt, yw, W, b)

    from jax import shard_map
    from jax.sharding import PartitionSpec as PSpec

    def joined(Xt, yw, W, b):
        loss, g, gb = local(Xt, yw, W, b)
        out = jax.lax.psum(jnp.concatenate([g, gb[None], loss[None]]), DATA_AXIS)
        return out[d + 1], out[:d], out[d]

    sharded = shard_map(
        joined,
        mesh=mesh,
        in_specs=(PSpec(None, DATA_AXIS), PSpec(None, DATA_AXIS), PSpec(), PSpec()),
        out_specs=PSpec(),
        check_vma=False,
    )
    return lambda W, b: sharded(Xt, yw, W, b)
