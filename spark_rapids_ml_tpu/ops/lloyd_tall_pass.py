#
# One update pass of Lloyd's algorithm over a feature-major table
# (ops/tall.TallMatrix) from ONE read of the table.
#
# Left to XLA (ops/kmeans._tall_assign_stats) a pass is two product fusions a
# block of rows: the distances' product with the argmin fused into its output,
# then the sums' product with the one-hot built in its operand.  Each slices
# the block out of HBM for itself, each issues the six passes of
# Precision.HIGHEST as products of their own that cut their operands into
# bfloat16 pieces again, and the row assignments go out to HBM and come back
# between the two: 21.1 ms a pass of 25,000,000 x 32 where the table's bytes
# take 3.9 (PERF.md section 5).  Here a grid step takes a TILE of rows
# (D_pad, T) through BlockSpec's pipeline, and for every lane tile of 128 rows
# of it, with everything in registers or VMEM:
#
#     t    = [x_hi; x_mid; x_lo; x_hi]                (4 D_pad, 128) bfloat16: the
#                                                     lane tile cut ONCE, for
#                                                     both products
#     d2   = |m|^2 - 2 m . x                          (k_pad, 128): one dot of the
#                                                     centres' stack against t
#     a    = the first row of d2 that holds its least value
#     hot  = where(row == a, w, 0)                    (k_pad, 128), as its three
#                                                     stacked pieces
#     acc += [hot_hi; hot_mid; hot_lo] . t^T          one dot; counts += hot
#
# so nothing of (k, rows) and no row assignment reaches HBM.  |x|^2 is the
# same for every centre of a row and is left out: ties are ties of
# |m|^2 - 2 m . x.  The factor -2 is folded into the centres' pieces outside
# the kernel (a power of two: exact in every piece), and the padding centre
# rows (k rounded up to a sublane group) carry |m|^2 = +inf and zero pieces:
# never the least, so never chosen; a row's first least row is found by the
# row ids, so ties go to the lowest index.
#
# Both products are float32-exact in the sense Precision.HIGHEST is: the six
# partial products of the operands' bfloat16 pieces (bf16_pieces of
# ops/softmax_ell_pass.py, imported as it is),
#   a_hi t_hi + a_hi t_mid + a_mid t_hi + a_hi t_lo + a_lo t_hi + a_mid t_mid,
# with float32 sums.  That module issues them as three dots a product, the
# small operand's pieces stacked on the ROWS that stream through a latched
# piece of the tile: right where the contraction is the tile's 128 columns,
# but here it is D_pad = 32, a quarter of the MXU's, and the streamed rows and
# the result registers popped and added behind them are what a lane tile
# costs (144 rows and 18 pops a product at 24 centre rows).  So the pieces
# are stacked along the CONTRACTION instead: the centres' stack
# (centre_stack) [m_hi m_hi m_hi m_mid; m_lo m_mid 0 0] against t gives the
# six as two row groups of ONE dot of contraction 4 D_pad (48 rows, 6 pops:
# the MXU adds the partial products of a group in float32 as it adds any
# product's terms), and the one-hot's pieces against t^T give all nine
# pairings as blocks of one (3 k_pad, 4 D_pad) result, accumulated as it comes
# and read once a tile (six_blocks picks the six).  The same six partial
# products, float32 accumulation, no piece dropped.
#
# A trip of the kernel's loop takes a GROUP of lane tiles (lane_group:
# thirty-two at the cell's widths) as ONE array of 4096 lanes, the distances
# of all of them before the sums of any: within one lane tile the work is a
# chain (product, pop, argmin, one-hot, product, pop, add) that the compiler's
# list scheduler fills with other lane tiles' work only when it finds them
# side by side, and as one array the group is one set of operations in the
# kernel's trace (PERF.md section 6, PR 53 has the bundle counts and the times
# of every form tried).
#
# The sums' chains: the MXU accumulates one lane tile's 128 rows; the lane
# tiles of a grid step are added in float32 on the vector unit, and the grid
# steps' partial sums are added compensated (softmax_ell_pass._add), so the
# tile's size costs the sums no digit (XLA's product is one accumulation chain
# a block: at blocks of 524,288 rows it lost one, PERF.md section 6, PR 52).
#
# The tile (row_tile): as many rows as a VMEM budget holds of the table's
# double-buffered tile beside a group's temporaries, which grow with k;
# never more than the caller's chunk (max_samples_per_batch stays the most
# rows one grid step takes).  Nothing to set: it follows from k, D_pad, the
# device's rows and the chunk.  The grid covers n // tile whole tiles; the
# caller hands the rows left over to ops/kmeans._tall_assign_stats.
#
# Pallas is imported where the kernel is built, not with this module (see
# ops/logistic_pass.py), and without Mosaic GPU's interpreter (_pallas).
#

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .softmax_ell_pass import _add, bf16_pieces

_LANES = 128
# the centres' rows are rounded up to this: a sublane group
_CENTRE_ROWS = 8
# what Mosaic may use of a core's VMEM (128 MiB on a v5e; 32 MiB is what
# ops/softmax_ell_pass.py asks for), and the share of it the tile rule plans
# with: the rest is the compiler's own
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 20 * 1024 * 1024
# centre rows (or feature rows) x lane tiles a trip of the kernel's loop holds
# at most: lane_group's bound, 32 lane tiles at 32 rows
_TRIP_ROWS = 1024
# (k_pad, lanes of a trip) float32 arrays a trip's work holds at once, should
# the compiler keep every one in VMEM: the distances' dot's two row groups, d2,
# the least row and the mask, the one-hot, its three pieces and their stack
# (1.5), the second dot's three row groups, the row ids, |m|^2, the counts
_LIVE_ARRAYS = 16
# what a trip's stacks take at most: the table's [hi; mid; lo; hi] and the
# one-hot's three pieces, bfloat16
_STACK_BYTES = _TRIP_ROWS * (4 + 3) * _LANES * 2


def centre_rows(k: int) -> int:
    """Rows the kernel holds k centres in."""
    return -(-int(k) // _CENTRE_ROWS) * _CENTRE_ROWS


def row_tile(k: int, d_pad: int, n_loc: int, chunk: int) -> int:
    """Rows a grid step takes of a device's (d_pad, n_loc) float32 columns
    for k centres: a multiple of 128 (of 4096 where it can be: whole groups of
    thirty-two lane tiles, see lane_group), at most `chunk` and the device's
    rows, such that the double-buffered tile and its weights, a group's
    temporaries and the resident sums fit the budget.  0 where not even one
    lane tile does, or the device holds fewer than 128 rows: the pass then
    stays XLA's."""
    kp = centre_rows(k)
    fixed = _LIVE_ARRAYS * max(kp, _TRIP_ROWS) * _LANES * 4 + 6 * kp * d_pad * 4 + _STACK_BYTES
    a_row = 2 * (d_pad + 1) * 4
    rows = min((_VMEM_BUDGET - fixed) // a_row, int(chunk), int(n_loc))
    if rows >= 32 * _LANES:
        return rows // (32 * _LANES) * (32 * _LANES)
    return max(rows, 0) // _LANES * _LANES


def lane_group(kp: int, d_pad: int, lane_tiles: int) -> int:
    """Lane tiles a trip of the kernel's loop takes as one array: the
    distances of all of them before the sums of any, so that the scheduler has
    products to issue while others' results come back.  Thirty-two at the
    cell's widths (on the chip a pass of 25,000,000 rows is 10.2 ms at eight,
    7.0 at sixteen, 6.3 at thirty-two, 5.9 at sixty-four, whose kernel costs a
    fit's set-up a quarter of a second more), fewer as the centres' rows or
    the table's features pass 32 (a trip holds every lane tile's stacks), a
    power of two that divides the tile's lane tiles."""
    group = 32
    while group > 1 and (group * max(kp, d_pad) > _TRIP_ROWS or lane_tiles % group):
        group //= 2
    return group


def takes(X, k: int, chunk: int, devices: int = 1) -> bool:
    """Whether the update passes of a fit of the TallMatrix X, row-sharded
    over `devices`, go through the kernel: a float32 table whose shard holds
    a whole tile."""
    d_pad, n = X.xt.shape
    return X.dtype == jnp.float32 and row_tile(k, d_pad, n // devices, chunk) > 0


def interpreted() -> bool:
    """Whether the call runs through Pallas's interpreter: anywhere but on the
    chip (ops/cd_sweep._interpreted's rule)."""
    return jax.default_backend() != "tpu"


def centre_stack(centers: jax.Array) -> jax.Array:
    """(kp, d) float32 centres -> the small operand of the distances' product,
    (2 kp, 4 d) bfloat16: against a lane tile's stack [x_hi; x_mid; x_lo; x_hi]
    (table_stack) the rows [m_hi m_hi m_hi m_mid] and [m_lo m_mid 0 0] give, as
    two row groups of ONE dot, the six partial products Precision.HIGHEST
    computes, the four largest in the first group and m_lo x_hi + m_mid x_mid
    in the second."""
    hi, mid, lo = bf16_pieces(centers)
    zero = jnp.zeros_like(hi)
    rows = [jnp.concatenate(r, axis=1) for r in ((hi, hi, hi, mid), (lo, mid, zero, zero))]
    return jnp.concatenate(rows, axis=0).astype(jnp.bfloat16)


def table_stack(x: jax.Array) -> jax.Array:
    """(d, lanes) float32 -> (4 d, lanes) bfloat16, [hi; mid; lo; hi]: a lane
    tile's pieces, cut once for both products (joined as float32 and packed
    once: joined as bfloat16 every piece is unpacked and packed again)."""
    hi, mid, lo = bf16_pieces(x)
    return jnp.concatenate([hi, mid, lo, hi], axis=0).astype(jnp.bfloat16)


def six_blocks(acc: jax.Array, kp: int, d: int) -> jax.Array:
    """The sums from what the second product accumulates: acc (3 kp, 4 d) holds
    hot_i . x_j^T for the pieces i of the weighted one-hot (row groups) and the
    groups j of table_stack (column groups: hi, mid, lo, and hi again, which
    nothing reads: a slice of the packed stack would unpack it); the six that
    Precision.HIGHEST computes are added, the smallest first (the three left
    out lie under float32's last bit)."""
    g = lambda i, j: acc[i * kp:(i + 1) * kp, j * d:(j + 1) * d]
    return (g(2, 0) + g(1, 1) + g(0, 2)) + (g(1, 0) + g(0, 1)) + g(0, 0)


def _dot(a, t, contract: int):
    """a . t over a's axis 1 and t's axis `contract`: bfloat16 operands,
    float32 sums, one pass of the MXU a latch."""
    dims = (((1,), (contract,)), ((), ()))
    return jax.lax.dot_general(a, t, dims, preferred_element_type=jnp.float32)


def _pallas():
    """(pallas, pallas.tpu).  With pallas_call jax imports Mosaic GPU's
    interpreter (for a GPU kernel's `interpret=InterpretParams`) and does
    without it where it cannot be imported (pallas_call.py's own `except
    ImportError`): 0.8 s of the 1.24 s the import takes on the chip's host,
    where a tall fit's whole set-up is 2.9 s and nothing of this package is a
    GPU kernel.  So where Pallas is not imported yet, it is imported with that
    one module refused; an import that came first stands as it is."""
    import sys

    gpu = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"
    if "jax.experimental.pallas" not in sys.modules and gpu not in sys.modules:
        sys.modules[gpu] = None         # an import of it raises ImportError while this stands
        try:
            import jax.experimental.pallas  # noqa: F401
        finally:
            del sys.modules[gpu]
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _pass_kernel(xt_ref, w_ref, m_ref, cn_ref, sums_ref, counts_ref, sums_lost, counts_lost):
    pl, _ = _pallas()

    d_pad, tile = xt_ref.shape
    kp = cn_ref.shape[0]
    group = lane_group(kp, d_pad, tile // _LANES)
    width = group * _LANES              # a group of lane tiles, as one array

    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref in (sums_ref, counts_ref, sums_lost, counts_lost):
            ref[...] = jnp.zeros_like(ref)

    m = m_ref[...]
    cn = jnp.concatenate([cn_ref[...]] * group, axis=1)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (kp, width), 0)

    def of_group(g, carry):
        # a group of lane tiles as ONE array of `width` lanes: the distances of
        # all of them before the sums of any (lane tile by lane tile the
        # compiler's schedule is a chain of waits), and one set of operations
        # in the trace whatever the group
        acc, counts = carry
        t = table_stack(xt_ref[:, pl.ds(pl.multiple_of(g * width, width), width)])
        with jax.named_scope("lloyd.assign"):
            cross = _dot(m, t, 0)
            d2 = cn + (cross[kp:] + cross[:kp])
            least = d2.min(axis=0, keepdims=True)
            first = jnp.where(d2 == least, row_ids, kp).min(axis=0, keepdims=True)
            mine = row_ids == first
        with jax.named_scope("lloyd.update"):
            # the rows' weights, a lane tile a row of the block, side by side
            rows = w_ref[pl.ds(pl.multiple_of(g * group, group), group), :]
            w = jnp.concatenate([rows[i:i + 1] for i in range(group)], axis=1)
            # the pieces of where(mine, w, 0) are where(mine, the pieces of w, 0)
            hot = jnp.concatenate([jnp.where(mine, p, 0.0) for p in bf16_pieces(w)], axis=0)
            acc = acc + _dot(hot.astype(jnp.bfloat16), t, 1)
            mass = jnp.where(mine, w, 0.0)
            counts = counts + sum(mass[:, i * _LANES:(i + 1) * _LANES] for i in range(group))
        return acc, counts

    zeros = (jnp.zeros((3 * kp, 4 * d_pad), jnp.float32), jnp.zeros((kp, _LANES), jnp.float32))
    acc, counts = jax.lax.fori_loop(0, tile // width, of_group, zeros)
    _add(sums_ref, sums_lost, six_blocks(acc, kp, d_pad))
    _add(counts_ref, counts_lost, counts)


def weight_tiles(w: jax.Array, tile: int) -> jax.Array:
    """(N,) weights -> (N // tile, tile // 128, 128): the whole tiles' weights a
    lane tile a row, as the kernel reads them.  For whole sublane groups of
    rows these are the vector's own bytes, where a (1, N) array would be laid
    out with eight sublanes a row.  Made once a fit, outside the loop."""
    steps = w.shape[0] // tile
    return w[: steps * tile].reshape(steps, tile // _LANES, _LANES)


@partial(jax.jit, static_argnames=("interpret",))
def pass_sums(
    xt: jax.Array,       # (D_pad, N) float32, the rows on the lanes
    w_tiles: jax.Array,  # weight_tiles(w, tile)
    centers: jax.Array,  # (k, D_pad) float32, the padding features zero
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(sums (k, D_pad), counts (k,)) of ops/kmeans._tall_assign_stats over the
    first N // tile whole tiles of rows, from one read of them: the Pallas
    call `lloyd_tall_pass`.  Ties of |m|^2 - 2 m . x go to the lowest index."""
    pl, pltpu = _pallas()
    d_pad, n = xt.shape
    k = centers.shape[0]
    steps, lane_tiles, _ = w_tiles.shape
    tile = lane_tiles * _LANES
    assert 0 < steps <= n // tile, (n, w_tiles.shape)
    rows = steps * tile
    kp = centre_rows(k)
    with jax.named_scope("lloyd.assign"):
        # what no tile changes: the centres' pieces and norms, once a pass
        padded = jnp.zeros((kp, d_pad), jnp.float32).at[:k].set(centers)
        m = centre_stack(-2.0 * padded)
        cn = jnp.full((kp,), jnp.inf, jnp.float32).at[:k].set((centers * centers).sum(axis=1))
        cn = jnp.broadcast_to(cn[:, None], (kp, _LANES))
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    with jax.named_scope("lloyd.update"):
        sums, counts = pl.pallas_call(
            _pass_kernel,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((d_pad, tile), lambda i: (0, i)),
                pl.BlockSpec((None, lane_tiles, _LANES), lambda i: (i, 0, 0)),
                resident((2 * kp, 4 * d_pad)),
                resident((kp, _LANES)),
            ],
            out_specs=[resident((kp, d_pad)), resident((kp, _LANES))],
            out_shape=[
                jax.ShapeDtypeStruct((kp, d_pad), jnp.float32),
                jax.ShapeDtypeStruct((kp, _LANES), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((kp, d_pad), jnp.float32), pltpu.VMEM((kp, _LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
            ),
            cost_estimate=pl.CostEstimate(
                flops=2 * 6 * 2 * kp * d_pad * rows,
                transcendentals=0,
                bytes_accessed=(d_pad + 1) * 4 * rows,
            ),
            interpret=interpret,
            name="lloyd_tall_pass",
        )(xt, w_tiles, m, cn)
    return sums[:k], counts[:k].sum(axis=1)
