#
# Hand-written Pallas TPU kernels for the hot ops.
#
# This module fuses the nearest-center search
#
#     d2 = ||x||^2 - 2 x.c + ||c||^2 ;  argmin_k d2 ;  min_k d2
#
# into one Pallas kernel: the (rows, k) distance tile lives only in VMEM and
# the kernel's outputs are the (rows,) argmin/min vectors.  (The wrapper does
# pad X to lane-aligned feature width first, which costs one HBM copy of X
# when d % 128 != 0 — acceptable for the inference path this kernel serves.)
#
# Where it is used: KMeansModel.predict / transform
# (ops/kmeans.py:kmeans_predict_kernel), routed by regime — see
# min_dist_argmin() for the measured crossover.  The Lloyd *training* loop
# deliberately keeps the XLA formulation: its assignment step feeds a
# one-hot-matmul stats accumulation that wants the same X block anyway, and
# hardware A/Bs on a v5e (2026-07-29 default precision, 2026-07-30 HIGHEST)
# showed XLA's fusion of this pattern wins whenever FLOPs dominate
# (n=32768 d=3000 k=1000: pallas 13.5 ms vs XLA 10.0 ms at HIGHEST), while
# the fused kernel wins the memory-bound low-d/large-k regime
# (n=131072 d=32 k=16384: 27.4 vs 34.5 ms).  Hardware-exactness record at
# HIGHEST precision: argmin mismatch 0, max |min_d2| diff 4.9e-4 on the
# d=3000 shape.
#
# Grid layout: (row_tiles, center_tiles), center tiles innermost.  The row
# block of X stays resident in VMEM across the inner sweep (its index map
# ignores j), a running (min, argmin) pair persists in VMEM scratch, and the
# final j step writes the result block.  Tile sizes are chosen per feature
# width by the scoped-VMEM model at _pick_tiles (2x double-buffered X/C
# blocks + the f32 distance tile, against the _VMEM_BUDGET slice of the
# ~16 MB/core).
#
# CPU fallback: everything routes through min_dist_argmin(), which uses the
# plain XLA formulation off-TPU (tests exercise the kernel itself in
# interpreter mode).
#

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DISABLE_ENV = "SRML_DISABLE_PALLAS"

# Scoped-VMEM model for tile selection (bytes).  The estimate below charges
# 2x the X/C input blocks (Mosaic double-buffers them, and the
# HIGHEST-precision f32 dot keeps extra scratch) plus the (TILE_N, TILE_K)
# f32 distance tile itself; 15 MB leaves margin under the ~16 MB/core scoped
# limit.  Calibrated on v5e 2026-07-30: (256,256)@d_pad=3072 est 19.1 MB
# really OOMs at 18.35 MB allocated; (1024,2048)@d_pad=128 est 13.2 MB
# compiles; (2048,2048)@d_pad=128 est 22.2 MB OOMs.
_VMEM_BUDGET = 15 * 1024 * 1024


def pallas_enabled() -> bool:
    """Pallas kernels run on real TPU backends unless explicitly disabled."""
    if os.environ.get(DISABLE_ENV) == "1":
        return False
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:  # pragma: no cover - no backend at all
        return False


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# Candidate (TILE_N, TILE_K) shapes, best-first.  Large center tiles win in
# the low-d regime this kernel targets (fewer inner sweeps, d2 tile still
# VMEM-resident); (2048, 1024) is deliberately absent — it fits the model
# but fails Mosaic compilation on v5e.
_TILE_CANDIDATES = (
    (1024, 2048),
    (1024, 1024),
    (512, 1024),
    (512, 512),
    (512, 256),
    (256, 256),
    (256, 128),
    (128, 128),
)


def _pick_tiles(d_pad: int, itemsize: int) -> Optional[Tuple[int, int]]:
    """Largest candidate (TILE_N, TILE_K) whose modeled scoped-VMEM use
    (2x double-buffered X/C blocks + the f32 distance tile) fits the budget;
    None if the feature dim is too wide for this kernel."""
    for tile_n, tile_k in _TILE_CANDIDATES:
        est = 2 * (tile_n + 2 * tile_k) * d_pad * itemsize + tile_n * tile_k * 4
        if est <= _VMEM_BUDGET:
            return tile_n, tile_k
    return None


def _min_dist_kernel(xn_ref, x_ref, c_ref, cn_ref, min_ref, arg_ref, mins, args):
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    tile_k = c_ref.shape[0]

    @pl.when(j == 0)
    def _():
        mins[:] = jnp.full_like(mins, jnp.inf)
        args[:] = jnp.zeros_like(args)

    # (TILE_N, TILE_K) distance tile — exists only in VMEM.  HIGHEST keeps
    # the MXU multiply at full f32 (matching cuML's exact-f32 distances);
    # the norm-expansion form cancels catastrophically, so single-pass bf16
    # products can flip argmins between nearly-equidistant centers.
    cross = jnp.dot(
        x_ref[:],
        c_ref[:].T,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = xn_ref[:] - 2.0 * cross + cn_ref[:]
    local_min = jnp.min(d2, axis=1, keepdims=True)
    local_arg = (
        jnp.argmin(d2, axis=1).astype(jnp.int32).reshape(-1, 1) + j * tile_k
    )
    better = local_min < mins[:]
    args[:] = jnp.where(better, local_arg, args[:])
    mins[:] = jnp.minimum(local_min, mins[:])

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        min_ref[:] = mins[:]
        arg_ref[:] = args[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _min_dist_argmin_pallas(
    X: jax.Array,       # (N, D) f32/bf16
    centers: jax.Array,  # (k, D) same dtype
    x_norm: jax.Array,   # (N,) f32
    c_norm: jax.Array,   # (k,) f32
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = X.shape
    k = centers.shape[0]
    d_pad = _round_up(d, 128)
    tiles = _pick_tiles(d_pad, X.dtype.itemsize)
    assert tiles is not None, "feature dim too wide for pallas kernel"
    tile_n, tile_k = tiles
    n_pad = _round_up(n, tile_n)
    k_pad = _round_up(k, tile_k)

    Xp = jnp.pad(X, ((0, n_pad - n), (0, d_pad - d)))
    Cp = jnp.pad(centers, ((0, k_pad - k), (0, d_pad - d)))
    xnp = jnp.pad(x_norm, (0, n_pad - n)).reshape(n_pad, 1).astype(jnp.float32)
    # padded center slots must never win the argmin
    cnp = jnp.pad(c_norm, (0, k_pad - k), constant_values=jnp.inf)
    cnp = cnp.reshape(1, k_pad).astype(jnp.float32)

    grid = (n_pad // tile_n, k_pad // tile_k)
    mins, args = pl.pallas_call(
        _min_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, d_pad), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_k, d_pad), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_k), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_n, 1), jnp.float32),
            pltpu.VMEM((tile_n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xnp, Xp, Cp, cnp)
    return mins[:n, 0], args[:n, 0]


def _min_dist_argmin_xla(
    X: jax.Array, centers: jax.Array, x_norm: jax.Array, c_norm: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    cross = jnp.matmul(
        X,
        centers.T,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = x_norm[:, None] - 2.0 * cross + c_norm[None, :]
    return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Fused feature binning: (N, D) f32 + (D, B-1) edges -> (D, N) int8 bins.
#
# The XLA compare-accumulate (ops/forest.bin_features_feature_major) re-reads
# each X chunk from HBM once per edge — 127 x 4.8 GB ~ 700 GB of HBM traffic
# (2.9 s) at the 400k x 3000 128-bin benchmark shape.  Here each (TN, TD)
# X tile is read into VMEM ONCE and all B-1 compares run on the resident
# tile: HBM traffic drops to X + edges + the int8 output (~6 GB).
# ---------------------------------------------------------------------------

_BIN_TILE_N = 512
_BIN_TILE_D = 512


def _bin_kernel(x_ref, e_ref, out_ref, *, n_edges: int, n_true: int, tile_n: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(1)  # row-tile index (minor grid dim)
    xt = x_ref[:].T  # (TD, TN) — transpose once in VMEM
    # accumulate in int32 (Mosaic vector adds support i16/i32 only) and
    # cast to int8 at the single output store
    acc = jnp.zeros(xt.shape, jnp.int32)
    for b in range(n_edges):
        acc += (xt > e_ref[:, b][:, None]).astype(jnp.int32)
    # rows past the true count carry garbage X (OOB block reads): force
    # bin 0 so padded rows look like the zero-padding the XLA path emits
    col = i * tile_n + jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
    out_ref[:] = jnp.where(col < n_true, acc, 0).astype(jnp.int8)


def bin_features_fm_pallas(
    X: jax.Array,          # (N, D) f32
    edges: jax.Array,      # (D, B-1) f32, B-1 <= 127
    n_pad: int,            # output row padding target (>= N)
    interpret: bool = False,
    tiled: bool = False,
) -> jax.Array:
    """(D, n_pad) int8 feature-major bins — pallas drop-in for
    ops/forest.bin_features_feature_major on TPU; with `tiled`, a feature a
    slice of whole tiles (forest_hist.tile_feature_rows), laid out by the
    copy that cuts the kernel's padded output to size: no second table.

    Mesh-sharded inputs (NamedSharding, even over ONE device — what
    DataFrame.from_device / core ingest produce) are re-committed to the
    plain single-device sharding first: jit-of-pallas under a NamedSharding
    operand lowers through the partitioner, which at the 400k x 3000
    benchmark shape exhausted HBM / left the device in a failed state.
    Same-device re-commit is copy-free."""
    if (
        isinstance(X, jax.Array)
        and not interpret
        and hasattr(X.sharding, "mesh")
        and len(X.sharding.device_set) == 1
    ):
        (dev,) = X.sharding.device_set
        X = jax.device_put(X, dev)
    return _bin_features_fm_pallas(X, edges, n_pad, interpret, tiled)


@functools.partial(jax.jit, static_argnames=("n_pad", "interpret", "tiled"))
def _bin_features_fm_pallas(
    X: jax.Array,
    edges: jax.Array,
    n_pad: int,
    interpret: bool = False,
    tiled: bool = False,
) -> jax.Array:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = X.shape
    n_edges = edges.shape[1]
    tn, td = _BIN_TILE_N, _BIN_TILE_D
    grid = (pl.cdiv(d, td), pl.cdiv(n_pad, tn))
    # NO out-of-bounds block reads: OOB DMA past an input's HBM extent is
    # not a safe pad-with-garbage on real hardware — a ~17 MB overread (the
    # RF row-tile padding target) left the device in a failed state where
    # a ~5 MB one happened to survive.  Pad X/edges to tile multiples (one
    # ~12 ms HBM copy of X) and clamp row-block indices past the X extent
    # (those tiles are pure padding output; the kernel masks them to 0).
    n_x = _round_up(n, tn)
    d_x = _round_up(d, td)
    Xp = (
        X
        if (n_x, d_x) == X.shape
        else jnp.pad(X, ((0, n_x - n), (0, d_x - d)))
    )
    max_row_blk = n_x // tn - 1
    # lane-pad the edge block; padded edge slots hold +inf so they never
    # count ((x > inf) == 0), keeping the compare loop branch-free
    e_pad = jnp.pad(
        edges.astype(jnp.float32),
        (
            (0, d_x - edges.shape[0]),
            (0, _round_up(max(n_edges, 1), 128) - n_edges),
        ),
        constant_values=jnp.inf,
    )
    out = pl.pallas_call(
        functools.partial(
            _bin_kernel, n_edges=n_edges, n_true=n, tile_n=tn
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (tn, td),
                lambda j, i: (jnp.minimum(i, max_row_blk), j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (td, e_pad.shape[1]), lambda j, i: (j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (td, tn), lambda j, i: (j, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct(
            (d_x, _round_up(n_pad, tn)), jnp.int8
        ),
        interpret=interpret,
        name="forest_bin",
    )(Xp, e_pad)
    out = out[:d, :n_pad]
    if tiled:
        from .forest_hist import tile_feature_rows

        out = tile_feature_rows(out)
    return out


def min_dist_route(
    n: int, d: int, k: int, itemsize: int, interpret: bool = False
) -> str:
    """'pallas' or 'xla': the route min_dist_argmin takes at this shape.

    Routing (v5e A/B, HIGHEST precision, 2026-07-30): the fused kernel wins
    only when the (n, k) distance matrix dominates HBM traffic — low d,
    large k (d=32/k=16384: 27.4 ms vs XLA 34.5; d=64/k=8192: 15.3 vs 17.8).
    When FLOPs dominate (d=3000/k=1000: 13.5 vs 10.0) or the batch pads up
    to one row tile (single-row predict), XLA's own fusion is the better
    program.  interpret mode bypasses the heuristic so tests always hit the
    kernel."""
    if not (interpret or pallas_enabled()):
        return "xla"
    d_pad = _round_up(d, 128)
    tiles = _pick_tiles(d_pad, itemsize)
    if tiles is None:
        return "xla"
    worthwhile = d_pad <= 256 and k >= 1024 and n >= tiles[0]
    return "pallas" if interpret or worthwhile else "xla"


def min_dist_argmin(
    X: jax.Array,
    centers: jax.Array,
    x_norm: Optional[jax.Array] = None,
    c_norm: Optional[jax.Array] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused nearest-center search: returns (min_d2 (N,), argmin (N,)).

    Uses the Pallas TPU kernel where min_dist_route says so; the
    identical-math XLA formulation otherwise.  min_d2 is clamped below at 0
    by neither path (callers clamp if needed).
    """
    if x_norm is None:
        x_norm = (X.astype(jnp.float32) ** 2).sum(axis=1)
    if c_norm is None:
        c_norm = (centers.astype(jnp.float32) ** 2).sum(axis=1)
    route = min_dist_route(
        X.shape[0], X.shape[1], centers.shape[0], X.dtype.itemsize, interpret
    )
    if route == "pallas":
        return _min_dist_argmin_pallas(
            X, centers, x_norm, c_norm, interpret=interpret
        )
    return _min_dist_argmin_xla(X, centers, x_norm, c_norm)
