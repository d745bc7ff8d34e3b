"""What the plain references share: the seed's key words, the precisions a
product can be computed in, and generation of per-device row blocks."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PRECISIONS = ("highest", "bf16", "fp8")


def seed_words(seed: int) -> np.ndarray:
    """--seed (up to a little over 2**31) as the two words of a threefry key: a
    runtime array, so no seed ever enters a compile key."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def mm(a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    """a @ b with float32 accumulation, operands held in `prec`: "highest" is
    the reference; "bf16" (one pass) and "fp8" (e4m3) are the controls."""
    if prec == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if prec == "bf16":
        lo = lambda x: x.astype(jnp.bfloat16)
    elif prec == "fp8":
        # e4m3 values are exact in bfloat16, so the product runs on any backend
        lo = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    else:
        raise ValueError(f"precision {prec!r} not in {PRECISIONS}")
    return jnp.matmul(lo(a), lo(b), preferred_element_type=jnp.float32)


def block_rows(rows: int, target: int = 16000) -> int:
    """Largest divisor of `rows` that is at most `target`."""
    for b in range(min(rows, target), 0, -1):
        if rows % b == 0:
            return b
    return rows


def sharded_rows(mesh, axis: str, rows_per_device: int, gen_block, n_out: int):
    """jit(words) -> n_out arrays of rows_per_device * n_devices rows, sharded over
    `axis`: each device makes its own rows, block by block, from the seed's key
    folded with its position, so no host array and no gather is involved.
    gen_block(key, shared_key, block) -> tuple of n_out (block, ...) arrays."""
    block = block_rows(rows_per_device)
    n_blocks = rows_per_device // block

    def per_device(words):
        key = jax.random.wrap_key_data(words)
        shared, mine = jax.random.split(key)
        mine = jax.random.fold_in(mine, jax.lax.axis_index(axis))
        outs = jax.lax.map(
            lambda i: gen_block(jax.random.fold_in(mine, i), shared, block),
            jnp.arange(n_blocks),
        )
        return tuple(o.reshape((rows_per_device,) + o.shape[2:]) for o in outs)

    return jax.jit(
        jax.shard_map(
            per_device, mesh=mesh, in_specs=P(), out_specs=(P(axis),) * n_out,
            check_vma=False,
        )
    )


def local_shards(x: jax.Array):
    """A row-sharded array's addressable shards, in row order."""
    return [
        s.data
        for s in sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
    ]
