#
# A dense table of many rows and few columns, FEATURE-MAJOR.
#
# A TPU array's minor dimension is tiled to 128 lanes and the one before it to
# 8 sublanes, so the row-major (N, D) float32 table of a narrow data set pays
# for 128 columns whatever D is: 25,000,000 x 30 is 3.0 GB of values and
# 12.8 GB on the device, and every pass over it reads 4.27 bytes for each byte
# of the table.  Held the other way round, (D_pad, N) with the ROWS ON THE
# LANES and D rounded up to a sublane group, the same table is 32 x 25M x 4 B
# = 3.2 GB: 93.75% of what the device holds is the table.  It is the dense
# sibling of ops/sparse.py's slot-major EllMatrix, and it reaches an estimator
# the same way: DataFrame.from_device(TallMatrix(...)), FitInputs.X carries it,
# and the estimators with a pass written for it (KMeans: ops/kmeans.py's
# lloyd_tall, whose update passes read a tile of it once through the Pallas
# kernel of ops/lloyd_tall_pass.py) fit it where it lies.  Every other
# estimator refuses it at entry
# (core._build_fit_inputs_device): nothing densifies or transposes a whole
# table behind the user's back.  The package does not import this module
# (core.is_tall_table asks for the type without it): whoever does is about to
# build such a table, and the module imports Pallas ahead of the first fit on a
# thread of its own (_import_pallas_ahead).
#

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Iterable, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# a table's feature rows are rounded up to a multiple of this: a sublane group
FEATURE_MULTIPLE = 8


def _import_pallas_ahead() -> None:
    """Whoever imports this module is about to build a feature-major table, and
    the passes over one are Pallas kernels (ops/lloyd_tall_pass.py).  Importing
    Pallas takes 0.4 s of the chip's host, a seventh of a tall fit's whole
    set-up, and nothing needs it before the first fit is traced: so it is
    imported here on a thread of its own, which ends with the import, while the
    caller builds or uploads the table and the host waits for the device.  (The
    package does not import this module: core.is_tall_table asks for the type
    without it.)"""
    from .lloyd_tall_pass import _pallas

    threading.Thread(target=_pallas, name="srml-pallas-import", daemon=True).start()


_import_pallas_ahead()


def padded_features(n_cols: int) -> int:
    """Feature rows a table of `n_cols` columns is stored with."""
    return -(-int(n_cols) // FEATURE_MULTIPLE) * FEATURE_MULTIPLE


@jax.tree_util.register_pytree_node_class
class TallMatrix:
    """Row-sharded dense table, feature-major: ``xt`` (D_pad, N_pad), the rows
    on the minor (lane) axis (sharded along axis 1 on a mesh), D_pad =
    padded_features(n_cols).  The padding feature rows are ZERO (exact: they
    add 0 to every product, norm and difference); rows past a frame's
    `n_rows` are padding that the fit's weight vector masks, as ever.
    ``n_cols`` is static (part of the pytree structure).

    TallMatrix(xt, n_cols) takes a device array that is already in this form
    and trusts its padding rows; TallMatrix.from_row_blocks builds one from
    host row blocks."""

    __slots__ = ("xt", "n_cols")

    def __init__(self, xt: Any, n_cols: int):
        n_cols = int(n_cols)
        if xt.ndim != 2 or n_cols < 1 or xt.shape[0] != padded_features(n_cols):
            raise ValueError(
                f"TallMatrix wants xt of (padded_features(n_cols), rows) = "
                f"({padded_features(n_cols)}, N) for n_cols={n_cols}, got {tuple(xt.shape)}"
            )
        if not jnp.issubdtype(xt.dtype, jnp.floating):
            raise ValueError(f"TallMatrix wants a floating table, got {xt.dtype}")
        self.xt = xt
        self.n_cols = n_cols

    @property
    def shape(self) -> Tuple[int, int]:
        """(rows, columns) of the table it stands for, as a row-major array's."""
        return (self.xt.shape[1], self.n_cols)

    @property
    def dtype(self):
        return self.xt.dtype

    @property
    def table_bytes(self) -> int:
        """The table's own bytes: rows x columns x itemsize."""
        return self.xt.shape[1] * self.n_cols * self.xt.dtype.itemsize

    @property
    def resident_bytes(self) -> int:
        """What the stored form holds for it: the padding feature rows too."""
        return self.xt.shape[0] * self.xt.shape[1] * self.xt.dtype.itemsize

    def tree_flatten(self):
        return (self.xt,), self.n_cols

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)
        (obj.xt,) = children
        obj.n_cols = aux
        return obj

    @classmethod
    def from_row_blocks(
        cls, blocks: Iterable[np.ndarray], n_rows: int, mesh: Any = None
    ) -> "TallMatrix":
        """The float32 table from host (n_i, D) row blocks taken one at a time, in
        order (numpy or Arrow-made batches, as core.stage_dense_batches
        receives them): `n_rows` rows in all, zero-padded to a multiple of
        the mesh's devices (`mesh` None: the default device).  A block is cut
        as stage_dense_batches cuts one (core._pieces: where a device's rows
        end, and into pieces of at most core._STAGE_PIECE_BYTES); each piece
        is transposed ON THE HOST into (D_pad, rows) and written
        into its device's shard, allocated once as zeros, in place.  The
        device never holds a row-major array of the table: only the shards
        and two pieces."""
        from ..core import _pieces

        devices = list(mesh.devices.flat) if mesh is not None else [jax.devices()[0]]
        share = -(-int(n_rows) // len(devices))
        dtype = np.dtype(np.float32)
        shards, row, n_cols, d_pad, waiting = [None] * len(devices), 0, 0, 0, None
        for block in blocks:
            block = np.asarray(block)
            if block.ndim != 2:
                raise ValueError(f"a row block is 2-D (rows, columns), got {block.shape}")
            if block.shape[0] == 0:
                continue
            if not n_cols:
                n_cols, d_pad = block.shape[1], padded_features(block.shape[1])
            elif block.shape[1] != n_cols:
                raise ValueError(f"row blocks disagree on width: {block.shape[1]} after {n_cols}")
            if row + block.shape[0] > n_rows:
                raise ValueError(f"the row blocks hold more than the {n_rows} rows stated")
            for lo, hi in _pieces(row, row + block.shape[0], share, d_pad * dtype.itemsize):
                s, at = divmod(lo, share)
                host = np.zeros((d_pad, hi - lo), dtype)
                host[:n_cols] = block[lo - row : hi - row].T
                if shards[s] is None:
                    shards[s] = jnp.zeros((d_pad, share), dtype, device=devices[s])
                up = jax.device_put(host, devices[s])
                shards[s] = _place_cols(shards[s], up, np.int32(at))
                if waiting is not None:     # one piece behind the newest: the device holds two
                    waiting.block_until_ready()
                waiting = up
            row += block.shape[0]
        if row != n_rows or not n_cols:
            raise ValueError(f"the row blocks hold {row} rows of the {n_rows} stated")
        for s, dev in enumerate(devices):
            if shards[s] is None:           # more devices than rows: all padding
                shards[s] = jnp.zeros((d_pad, share), dtype, device=dev)
        if mesh is None:
            return cls(shards[0], n_cols)
        from ..parallel.mesh import col_sharding

        xt = jax.make_array_from_single_device_arrays(
            (d_pad, share * len(devices)), col_sharding(mesh), shards
        )
        return cls(xt, n_cols)


@partial(jax.jit, donate_argnums=0)
def _place_cols(shard: jax.Array, piece: jax.Array, start: Any) -> jax.Array:
    """`piece` over columns [start, start + width) of `shard`, in the shard's
    own buffer (donated).  One executable a (shard, piece) shape."""
    return jax.lax.dynamic_update_slice(shard, piece, (np.int32(0), start))
