#
# Sparse feature support: ELL (padded row-major) layout + mesh-aware kernels.
#
# TPU-native replacement for the sparse-input path of cuML's qn solvers
# (the reference fits CSR batches without densification for
# LogisticRegression — classification.py:1206-1218 handles the sparse
# coefficient layout, and BASELINE.json's logreg config is "1B x 100
# sparse").  There is no sparse unit on a TPU, so CSR itself is the wrong
# device format: variable-length rows mean dynamic shapes, which XLA cannot
# tile.  The TPU-shaped formulation used here:
#
#   - ELL layout: every row padded to the max row-nnz P, giving two dense
#     (N, P) arrays (column indices, values).  Static shapes, row-shardable
#     over the data mesh axis exactly like a dense (N, D) block, and the
#     memory is O(nnz * N/avg_nnz * P) ~ O(nnz) for the near-uniform row
#     occupancies of ML feature matrices (vs O(N*D) densified).
#   - iterative objectives (L-BFGS / OWL-QN): the forward model term
#     X @ W.T becomes a gather of W rows by the (N, P) index table plus a
#     VPU multiply-reduce.  jax.grad transposes the gather into the
#     scatter-add X.T @ r automatically — the backward pass needs no
#     hand-written sparse kernel.
#   - one-pass sufficient statistics (OLS/Ridge/CD): the Gram matrix is
#     dense (D, D) regardless of input sparsity, so each row chunk is
#     densified on device (a tiny C*P-element scatter) and hit with a dense
#     (D, C) @ (C, D) MXU contraction.  FLOPs on the MXU are ~free relative
#     to scatter throughput on this hardware (memory: tens of TF vs ~50M
#     scalar scatter updates/s), so "densify the chunk, matmul" beats any
#     nnz^2 scatter formulation while HBM never holds more than one
#     (chunk, D) tile.
#

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class EllMatrix:
    """Row-sharded ELL sparse matrix: ``idx`` (N, P) int32 column ids,
    ``val`` (N, P) values; padding slots have idx == 0 and val == 0 (exact:
    they contribute 0 to every product).  ``n_cols`` is static (part of the
    pytree structure) so kernels can shape outputs at trace time."""

    __slots__ = ("idx", "val", "n_cols")

    def __init__(self, idx, val, n_cols: int):
        self.idx = idx
        self.val = val
        self.n_cols = int(n_cols)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.idx.shape[0], self.n_cols)

    @property
    def dtype(self):
        return self.val.dtype

    def tree_flatten(self):
        return (self.idx, self.val), self.n_cols

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        obj.idx, obj.val = children
        obj.n_cols = aux
        return obj


def ell_from_csr(
    indptr: np.ndarray,
    indices: np.ndarray,
    data: np.ndarray,
    n_cols: int,
    dtype=np.float32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side CSR -> ELL conversion (vectorized, no per-row Python loop).

    Returns (idx (N, P) int32, val (N, P) dtype) with P = max row nnz
    (>= 1 so downstream shapes stay non-degenerate)."""
    indptr = np.asarray(indptr, dtype=np.int64)
    n = indptr.size - 1
    counts = np.diff(indptr)
    P = int(max(1, counts.max() if n else 1))
    idx = np.zeros((n, P), dtype=np.int32)
    val = np.zeros((n, P), dtype=dtype)
    # position of each nnz within its row: global arange minus row start
    pos = np.arange(indptr[-1], dtype=np.int64) - np.repeat(indptr[:-1], counts)
    row = np.repeat(np.arange(n, dtype=np.int64), counts)
    idx[row, pos] = np.asarray(indices, dtype=np.int32)
    val[row, pos] = np.asarray(data, dtype=dtype)
    return idx, val


def ell_device_from_scipy(X, dtype=np.float32, mesh=None) -> EllMatrix:
    """scipy sparse -> device EllMatrix.  With a mesh, idx/val are row-sharded
    over the data axis (zero-padded rows are exact no-ops: idx 0 / val 0)."""
    csr = X.tocsr()
    idx, val = ell_from_csr(csr.indptr, csr.indices, csr.data, csr.shape[1], dtype)
    if mesh is not None:
        from ..parallel.mesh import shard_rows

        idx_s, _ = shard_rows(idx, mesh)
        val_s, _ = shard_rows(val, mesh)
        return EllMatrix(idx_s, val_s, csr.shape[1])
    return EllMatrix(jax.device_put(idx), jax.device_put(val), csr.shape[1])


def ell_matvec(ell: EllMatrix, b: jax.Array) -> jax.Array:
    """X @ b for b (D,) -> (N,).  Gather + multiply-reduce; the autodiff
    transpose is the scatter-add X.T @ r."""
    return (ell.val * b[ell.idx]).sum(axis=1)


def ell_matmat(ell: EllMatrix, B: jax.Array) -> jax.Array:
    """X @ B for B (D, K) -> (N, K)."""
    return (ell.val[:, :, None] * B[ell.idx]).sum(axis=1)


def ell_densify_chunk(idx: jax.Array, val: jax.Array, n_cols: int) -> jax.Array:
    """(C, P) ELL chunk -> dense (C, n_cols).  Padding slots write val 0 at
    column 0 — .add keeps that exact even when real nnz live at column 0."""
    C = idx.shape[0]
    out = jnp.zeros((C, n_cols), val.dtype)
    return out.at[jnp.arange(C)[:, None], idx].add(val)


def _ell_local_moments(
    idx: jax.Array,
    val: jax.Array,
    w_loc: jax.Array,
    n_cols: int,
    chunk: int,
    y_loc: jax.Array,
):
    """Per-shard chunk-scanned sufficient statistics from ELL rows; the
    sparse twin of linalg._local_moments (same outputs, same scan shape:
    compile time independent of N)."""
    n_loc = idx.shape[0]
    if n_loc == 0:
        z = jnp.zeros((), val.dtype)
        zd = jnp.zeros((n_cols,), val.dtype)
        return z, zd, jnp.zeros((n_cols, n_cols), val.dtype), z, zd, z
    chunk = max(1, min(chunk, n_loc))
    n_chunks = -(-n_loc // chunk)
    pad = n_chunks * chunk - n_loc
    if pad:
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        val = jnp.pad(val, ((0, pad), (0, 0)))
        w_loc = jnp.pad(w_loc, (0, pad))
        y_loc = jnp.pad(y_loc, (0, pad))

    def body(carry, args):
        wsum, xwsum, G, ywsum, c, y2 = carry
        ic, vc, wc, yc = args
        Xc = ell_densify_chunk(ic, vc, n_cols)
        Xw = Xc * wc[:, None]
        return (
            wsum + wc.sum(),
            xwsum + Xw.sum(axis=0),
            G + Xw.T @ Xc,
            ywsum + (yc * wc).sum(),
            c + Xw.T @ yc,
            y2 + (yc * yc * wc).sum(),
        ), None

    z = jnp.zeros((), val.dtype)
    zd = jnp.zeros((n_cols,), val.dtype)
    init = (z, zd, jnp.zeros((n_cols, n_cols), val.dtype), z, zd, z)
    (wsum, xwsum, G, ywsum, c, y2), _ = jax.lax.scan(
        body,
        init,
        (
            idx.reshape(n_chunks, chunk, -1),
            val.reshape(n_chunks, chunk, -1),
            w_loc.reshape(n_chunks, chunk),
            y_loc.reshape(n_chunks, chunk),
        ),
    )
    return wsum, xwsum, G, ywsum, c, y2


@partial(jax.jit, static_argnames=("mesh", "chunk"))
def ell_sufficient_stats(
    ell: EllMatrix, y: jax.Array, w: jax.Array, mesh=None, chunk: int = 8192
):
    """Sparse twin of glm.linreg_sufficient_stats: one fused pass over the
    row-sharded ELL arrays; outputs replicated (psum over the data axis)."""
    from ..parallel.mesh import DATA_AXIS
    from .glm import LinregStats

    if mesh is None:
        wsum, xwsum, G, ywsum, c, y2 = _ell_local_moments(
            ell.idx, ell.val, w, ell.n_cols, chunk, y
        )
        return LinregStats(wsum, xwsum / wsum, ywsum / wsum, G, c, y2)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_cols = ell.n_cols

    def per_device(idx_loc, val_loc, y_loc, w_loc):
        return tuple(
            jax.lax.psum(v, DATA_AXIS)
            for v in _ell_local_moments(idx_loc, val_loc, w_loc, n_cols, chunk, y_loc)
        )

    wsum, xwsum, G, ywsum, c, y2 = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(),) * 6,
        check_vma=False,
    )(ell.idx, ell.val, y, w)
    return LinregStats(wsum, xwsum / wsum, ywsum / wsum, G, c, y2)
