#
# Pallas TPU lookup-table accumulation kernel for IVF-PQ ADC search.
#
# A new kernel SHAPE for this repo: every earlier Pallas kernel is a fused
# distance computation (MXU matmul + epilogue).  PQ's asymmetric-distance
# scan has no matmul at all — per query it reduces to
#
#     out[r] = sum_j  T[j, codes[r, j]]          j in [0, m_sub)
#
# a gather from a tiny per-query table T (m_sub, ksub) over an int8 code
# tile.  The table lives in VMEM for the whole row sweep (its block index
# map ignores the row-tile grid axis), the code tile is the ONLY per-item
# HBM traffic (m_sub bytes/item vs 4*D for IVF-Flat — the ~32x bandwidth
# win IS the point of the kernel), and the lookup itself is a
# compare-select sweep over the ksub table lanes on the VPU: Mosaic has no
# general vector gather, but `(code == c) ? T[j,c] : 0` summed over c is
# exact — every row of the compare tile has exactly ONE nonzero, and
# x + 0.0 == x in f32 — so the select-sum IS the gather, bit for bit
# (the same trick ops/pallas_tpu._bin_kernel uses for feature binning).
# MXU-free by construction: the usual TPU alternative (one-hot codes
# matmul'd against the table) materializes a (rows, m_sub*ksub) one-hot
# slab, 256x the code bytes, to feed an MXU the scan doesn't need.
#
# Layout: everything arrives pre-transposed so stores land along lanes —
# tables  (B, ksub, m_sub): T[:, j] is a sublane column, broadcast to lanes
# codes   (B, m_sub, R):    code row j is a lane vector
# out     (B, 1, R):        one (1, TILE_R) store per grid cell
# Grid (B, R / TILE_R), table block resident across the R sweep.
#
# Accumulation ORDER is part of the contract: the j-loop is a static
# unroll, so out[r] is the SEQUENTIAL f32 running sum over j=0..m_sub-1 of
# exactly-gathered table values.  The numpy oracle in tests/test_pq_engine
# reproduces that order and asserts EXACT equality in interpret mode.
#
# CPU / non-TPU fallback: lut_accumulate routes through an identical-math
# XLA take_along_axis formulation (tier-1 searches ride it; the kernel
# itself is gated in interpret mode).
#

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .pallas_tpu import _round_up, pallas_enabled

# rows of the code tile swept per grid cell; the (ksub, TILE_R) f32
# compare-select tile is the kernel's only big intermediate (512 KB at
# ksub=256) and the table block is ksub * m_sub * 4 bytes (32 KB at
# ksub=256, m_sub=32) — VMEM stays far under budget at any supported shape
_LUT_TILE_R = 512


def _lut_out_spec():
    """Output block of both scan kernels: one (1, TILE_R) row per grid cell
    of a (B, 1, R) array.  The unit middle axis is what Mosaic needs — the
    last two block dims must divide by (8, 128) or equal the array's, and a
    (1, TILE_R) block of a (B, R) array does neither once B > 1."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(
        (1, 1, _LUT_TILE_R), lambda qi, ri: (qi, 0, ri),
        memory_space=pltpu.VMEM,
    )


def _lut_accum_kernel(t_ref, c_ref, o_ref, *, m_sub: int):
    # t_ref (1, ksub, m_sub) f32 — this query's ADC table, grid-resident
    # c_ref (1, m_sub, TILE_R) int8 — code tile, rows along lanes
    # o_ref (1, 1, TILE_R) f32
    ksub = t_ref.shape[1]
    codes = c_ref[0].astype(jnp.int32)                 # (m_sub, TILE_R)
    tile_r = codes.shape[1]
    cls = jax.lax.broadcasted_iota(jnp.int32, (ksub, tile_r), 0)
    acc = jnp.zeros((1, tile_r), jnp.float32)
    for j in range(m_sub):
        # exactly one lane of `eq` is True per row: the masked sublane sum
        # gathers T[j, code] bit-exactly (x + 0.0 == x).  Row j of the code
        # tile and column j of the table stay 2-D slices, so both broadcasts
        # are plain sublane/lane splats (no 1-D relayout for Mosaic)
        eq = codes[j : j + 1, :] == cls                # (ksub, TILE_R)
        acc = acc + jnp.sum(
            jnp.where(eq, t_ref[0, :, j : j + 1], 0.0),
            axis=0,
            keepdims=True,
        )
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lut_accumulate_pallas(
    tables: jax.Array,  # (B, m_sub, ksub) f32
    codes: jax.Array,   # (B, R, m_sub) uint8
    interpret: bool = False,
) -> jax.Array:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, m_sub, ksub = tables.shape
    r = codes.shape[1]
    r_pad = _round_up(max(r, 1), _LUT_TILE_R)
    # pre-transpose into the lane-major layouts documented above; pad rows
    # carry code 0 (a valid table column — the result is sliced off)
    t_t = jnp.swapaxes(tables, 1, 2)                   # (B, ksub, m_sub)
    c_t = jnp.swapaxes(codes, 1, 2)                    # (B, m_sub, R)
    if r_pad != r:
        c_t = jnp.pad(c_t, ((0, 0), (0, 0), (0, r_pad - r)))
    out = pl.pallas_call(
        functools.partial(_lut_accum_kernel, m_sub=m_sub),
        grid=(b, r_pad // _LUT_TILE_R),
        in_specs=[
            pl.BlockSpec(
                (1, ksub, m_sub), lambda qi, ri: (qi, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, m_sub, _LUT_TILE_R), lambda qi, ri: (qi, 0, ri),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=_lut_out_spec(),
        out_shape=jax.ShapeDtypeStruct((b, 1, r_pad), jnp.float32),
        interpret=interpret,
    )(t_t, c_t)
    return out[:, 0, :r]


def _lut_accumulate_xla(tables: jax.Array, codes: jax.Array) -> jax.Array:
    """Identical-math XLA formulation: gather every subspace's table value
    (take_along_axis over the ksub axis), reduce over m_sub.  Same
    fixed-shape per-item reduction on every mesh size — the bitwise
    mesh-parity basis for the CPU/tier-1 route."""
    idx = jnp.swapaxes(codes, 1, 2).astype(jnp.int32)  # (B, m_sub, R)
    gathered = jnp.take_along_axis(tables, idx, axis=2)
    return jnp.sum(gathered, axis=1)                   # (B, R)


# -- n_bits=4 fast-scan (two codes per byte, 16-entry tables) ----------------
#
# André et al. ("Cache locality is not enough", VLDB 2015) observed that
# 4-bit codes turn the ADC gather into a 16-entry table sweep.  Here the
# packed byte layout halves the per-item HBM traffic (m_sub/2 bytes/item)
# and the compare-select sweep shrinks from ksub=256 lanes to 16 — the
# (16, TILE_R) compare tile is 16x smaller than the 8-bit kernel's, so the
# whole per-subspace table column pair stays VPU-hot.  Layouts, grid, the
# sequential-j f32 accumulation order, and the exact-gather argument are
# the 8-bit kernel's verbatim; the only new step is the nibble unpack
# (j even -> low nibble of byte j//2, j odd -> high nibble), which both
# routes and the numpy oracle in tests/test_pq_engine.py share.


def _fastscan_check(tables: jax.Array, packed: jax.Array) -> int:
    """Validate the packed fast-scan geometry; returns m_sub.  Odd m_sub
    cannot pack two codes per byte — a TYPED rejection, not a silent
    repack (the build layer refuses to produce such a payload and this
    guard keeps hand-built calls honest)."""
    m_sub = int(tables.shape[1])
    if m_sub % 2 != 0:
        raise ValueError(
            f"fast-scan requires an even m_sub (two 4-bit codes pack per "
            f"byte); got m_sub={m_sub} — use n_bits=8 or an even M"
        )
    if int(tables.shape[2]) > 16:
        raise ValueError(
            f"fast-scan tables must have ksub <= 16 (4-bit codes); got "
            f"ksub={int(tables.shape[2])}"
        )
    if int(packed.shape[2]) * 2 != m_sub:
        raise ValueError(
            f"packed codes carry {int(packed.shape[2])} bytes/item but "
            f"tables expect m_sub={m_sub} subspaces ({m_sub // 2} bytes)"
        )
    return m_sub


def _fastscan_kernel(t_ref, c_ref, o_ref, *, m_sub: int):
    # t_ref (1, ksub<=16, m_sub) f32 — this query's ADC table, grid-resident
    # c_ref (1, m_sub//2, TILE_R) uint8 — packed code tile, rows along lanes
    # o_ref (1, 1, TILE_R) f32
    ksub = t_ref.shape[1]
    packed = c_ref[0].astype(jnp.int32)                # (m_sub//2, TILE_R)
    lo = packed & 0xF
    hi = packed >> 4
    tile_r = packed.shape[1]
    cls = jax.lax.broadcasted_iota(jnp.int32, (ksub, tile_r), 0)
    acc = jnp.zeros((1, tile_r), jnp.float32)
    for j in range(m_sub):
        nib = (lo if j % 2 == 0 else hi)[j // 2 : j // 2 + 1, :]
        # exactly one of the 16 lanes matches per row: the masked sublane
        # sum gathers T[j, code] bit-exactly (x + 0.0 == x), same argument
        # as the 8-bit kernel with a 16x smaller compare tile
        eq = nib == cls                                # (ksub, TILE_R)
        acc = acc + jnp.sum(
            jnp.where(eq, t_ref[0, :, j : j + 1], 0.0),
            axis=0,
            keepdims=True,
        )
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fastscan_pallas(
    tables: jax.Array,  # (B, m_sub, ksub<=16) f32
    packed: jax.Array,  # (B, R, m_sub//2) uint8, two codes per byte
    interpret: bool = False,
) -> jax.Array:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, m_sub, ksub = tables.shape
    m_half = packed.shape[2]
    r = packed.shape[1]
    r_pad = _round_up(max(r, 1), _LUT_TILE_R)
    t_t = jnp.swapaxes(tables, 1, 2)                   # (B, ksub, m_sub)
    c_t = jnp.swapaxes(packed, 1, 2)                   # (B, m_sub//2, R)
    if r_pad != r:
        c_t = jnp.pad(c_t, ((0, 0), (0, 0), (0, r_pad - r)))
    out = pl.pallas_call(
        functools.partial(_fastscan_kernel, m_sub=m_sub),
        grid=(b, r_pad // _LUT_TILE_R),
        in_specs=[
            pl.BlockSpec(
                (1, ksub, m_sub), lambda qi, ri: (qi, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, m_half, _LUT_TILE_R), lambda qi, ri: (qi, 0, ri),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=_lut_out_spec(),
        out_shape=jax.ShapeDtypeStruct((b, 1, r_pad), jnp.float32),
        interpret=interpret,
    )(t_t, c_t)
    return out[:, 0, :r]


def pack_codes4(codes: np.ndarray) -> np.ndarray:
    """HOST-side packer, the unpack_codes4 inverse: (N, m_sub even) uint8
    4-bit codes -> (N, m_sub//2) bytes, byte p = code[:, 2p] |
    code[:, 2p+1] << 4.  The stager packs once at layout time; the wire
    payload keeps unpacked codes (one persistence format across n_bits)."""
    codes = np.asarray(codes, np.uint8)
    if codes.ndim != 2 or codes.shape[1] % 2:
        raise ValueError(
            f"pack_codes4 needs (N, even m_sub) codes; got {codes.shape}"
        )
    if codes.size and int(codes.max()) > 0xF:
        raise ValueError("pack_codes4 codes must be 4-bit (values < 16)")
    return (codes[:, 0::2] | (codes[:, 1::2] << 4)).astype(np.uint8)


def unpack_codes4(packed: jax.Array) -> jax.Array:
    """(B, R, m_sub//2) packed bytes -> (B, R, m_sub) 4-bit codes in the
    j order the kernels sweep: byte p holds codes for subspaces j=2p (low
    nibble) and j=2p+1 (high nibble).  Shared by the XLA route and the
    oracle-building tests (one unpack convention, stated once)."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = p >> 4
    b, r, m_half = p.shape
    return jnp.stack([lo, hi], axis=-1).reshape(b, r, m_half * 2)


def _fastscan_xla(tables: jax.Array, packed: jax.Array) -> jax.Array:
    """Identical-math XLA unpack route: nibble unpack, then EXACTLY the
    8-bit route's gather+reduce (take_along_axis over ksub, sum over the
    m_sub axis) — the same fixed-shape per-item reduction, so 4-bit probed
    results keep the bitwise mesh-parity basis on the CPU/tier-1 route."""
    return _lut_accumulate_xla(tables, unpack_codes4(packed))


def fastscan_lut_accumulate(
    tables: jax.Array,  # (B, m_sub, ksub<=16) f32 per-query ADC tables
    packed: jax.Array,  # (B, R, m_sub//2) uint8 packed candidate codes
    interpret: bool = False,
) -> jax.Array:
    """Fast-scan ADC accumulation over 4-bit packed codes:
    out[b, r] = sum_j tables[b, j, code(b, r, j)] with code unpacked from
    two-per-byte nibbles.  Pallas on TPU (or interpret=True for tests),
    the identical-math XLA unpack route elsewhere — the lut_accumulate
    routing contract at half the code bytes.  Rejects odd m_sub and
    ksub > 16 with typed errors."""
    _fastscan_check(tables, packed)
    if lut_route(interpret) == "pallas":
        return _fastscan_pallas(tables, packed, interpret=interpret)
    return _fastscan_xla(tables, packed)


def lut_route(interpret: bool = False) -> str:
    """'pallas' or 'xla': the route both LUT accumulations take."""
    return "pallas" if interpret or pallas_enabled() else "xla"


def lut_accumulate(
    tables: jax.Array,  # (B, m_sub, ksub) f32 per-query ADC tables
    codes: jax.Array,   # (B, R, m_sub) uint8 gathered candidate codes
    interpret: bool = False,
) -> jax.Array:
    """ADC lookup-table accumulation: out[b, r] = sum_j tables[b, j,
    codes[b, r, j]].  Pallas on TPU (or interpret=True for tests), the
    identical-math XLA gather elsewhere — same routing contract as
    ops/pallas_tpu.min_dist_argmin.  Code values must lie in [0, ksub)
    (the PQ encoder guarantees it; out-of-range values contribute 0 on the
    pallas route and clamp on the XLA route — both masked upstream)."""
    if lut_route(interpret) == "pallas":
        return _lut_accumulate_pallas(tables, codes, interpret=interpret)
    return _lut_accumulate_xla(tables, codes)
