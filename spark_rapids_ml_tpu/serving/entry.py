#
# The model <-> serving-engine contract.
#
# A ServingEntry is what a fitted model hands the online inference engine:
# a `call` that runs ONE padded device batch end to end (upload -> cached
# executable -> host fetch -> output columns) and a `warm` that submits
# ahead-of-time compilations for every row bucket the engine will ever
# dispatch.  Models implement `_serving_entry(mesh)` (core._TpuModel hook);
# most build theirs through `kernel_entry` below, which wires a single
# jitted kernel into the process-wide AOT executable cache
# (ops/precompile.cached_kernel) exactly the way the batch transform paths
# of PRs 2-4 do — serving rides the same executables.
#
# The ONE bucketing rule: every flushed micro-batch is zero-padded to
# `bucket_rows(n)` — a power of two between SRML_SERVE_MIN_BUCKET and the
# batcher's max batch — so the steady state touches a handful of compiled
# geometries (all warmed at model-load time) instead of one compile per
# distinct batch length.
#

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Sequence

import numpy as np

MIN_BUCKET_ENV = "SRML_SERVE_MIN_BUCKET"
_DEFAULT_MIN_BUCKET = 16


def min_bucket() -> int:
    """Smallest serving row bucket (power of two enforced by bucket_rows'
    doubling walk; a non-pow2 setting rounds up implicitly)."""
    return max(1, int(os.environ.get(MIN_BUCKET_ENV, str(_DEFAULT_MIN_BUCKET))))


def bucket_rows(n: int, max_batch: int) -> int:
    """Power-of-two row bucket for a flushed batch of `n` valid rows —
    shared by the dispatch path and the warm path so a warmed executable is
    the exact entry the later dispatch looks up (the same contract
    ops/precompile.shape_bucket gives the batch transform paths)."""
    from ..ops.precompile import shape_bucket

    return shape_bucket(n, lo=min_bucket(), hi=max(min_bucket(), max_batch))


def serve_buckets(max_batch: int) -> List[int]:
    """Every bucket the engine can dispatch at `max_batch`: the doubling
    ladder min_bucket, 2*min_bucket, ..., bucket_rows(max_batch).  This is
    the warm set — steady state never meets a geometry outside it."""
    out, b = [], min_bucket()
    top = bucket_rows(max_batch, max_batch)
    while b < top:
        out.append(b)
        b *= 2
    out.append(top)
    return out


@dataclass
class ServingEntry:
    """One model's online-inference surface.

    `call` receives the PADDED (bucket, n_cols) float batch (pad rows are
    zeros) and returns {output column: host np array of bucket rows} — the
    engine slices to the valid row count and scatters per request.  `warm`
    submits AOT compilations for the given bucket sizes on the precompile
    worker pool and returns the submitted cache keys (possibly empty when a
    route has nothing soundly warmable — the engine then warms by
    dispatching one synthetic batch per bucket instead)."""

    name: str                 # stable kernel-cache namespace, e.g. "serve.kmeans"
    n_cols: int
    dtype: np.dtype
    out_cols: List[str]
    call: Callable[[np.ndarray], Dict[str, np.ndarray]]
    warm: Callable[[Sequence[int]], list]
    # optional extras a model wants surfaced in server stats
    info: Dict[str, Any] = field(default_factory=dict)


def kernel_entry(
    name: str,
    fn: Any,
    consts: tuple,
    statics: Dict[str, Any],
    postprocess: Callable[[Any], Dict[str, np.ndarray]],
    *,
    dtype: Any,
    n_cols: int,
    out_cols: List[str],
    info: Dict[str, Any] = None,
    mesh: Any = None,
) -> ServingEntry:
    """ServingEntry for the common single-kernel models (kmeans/pca/linreg/
    logreg/forest): `fn` is a jitted kernel (X, *consts, **statics) -> device
    outputs, dispatched through the process-wide AOT executable cache under
    `name`; `postprocess` maps the HOST-fetched outputs to output columns
    (still at padded length — the engine slices).

    `mesh` is the replica's device slice (serving/slicepool lease): the
    constants and every batch are placed ON it — one program per device of
    the slice, no collectives — so each replica of a router computes on its
    own chip(s), and the slice rides the cache key so replicas never share
    an executable compiled for another device.  None keeps jax's default
    device (a standalone ModelServer)."""
    import jax

    from ..ops.precompile import global_precompiler, kernel_cache_key

    np_dtype = np.dtype(dtype)
    consts = tuple(consts)
    placement = None
    if mesh is not None:
        from ..parallel.mesh import replicated_sharding

        # a one-device slice is that device (plain single-device programs);
        # a wider slice replicates
        placement = (
            jax.sharding.SingleDeviceSharding(mesh.devices.flat[0])
            if mesh.devices.size == 1
            else replicated_sharding(mesh)
        )
        consts = tuple(jax.device_put(c, placement) for c in consts)

    def call(batch: np.ndarray) -> Dict[str, np.ndarray]:
        args = (
            jax.device_put(
                np.ascontiguousarray(batch, dtype=np_dtype), placement
            ),
        ) + consts
        key = kernel_cache_key(name, args, mesh, statics)
        out = global_precompiler().cached_call(key, fn, *args, **statics)
        return postprocess(jax.device_get(out))

    def warm(buckets: Sequence[int]) -> list:
        pc = global_precompiler()
        keys = []
        for b in buckets:
            args = (
                jax.ShapeDtypeStruct(
                    (int(b), n_cols), np_dtype, sharding=placement
                ),
            ) + consts
            key = kernel_cache_key(name, args, mesh, statics)
            pc.submit(key, fn, *args, **statics)
            keys.append(key)
        return keys

    return ServingEntry(
        name=name,
        n_cols=int(n_cols),
        dtype=np_dtype,
        out_cols=list(out_cols),
        call=call,
        warm=warm,
        info=dict(info or {}),
    )


def entry_signature(entry: "ServingEntry") -> tuple:
    """The client-visible serving contract of an entry: feature width,
    dtype, and output columns.  Two models with equal signatures are
    hot-swappable — every in-flight and future request that was valid
    against one is valid against the other."""
    return (
        int(entry.n_cols),
        str(np.dtype(entry.dtype)),
        tuple(sorted(entry.out_cols)),
    )


def check_swap_compatible(
    old: "ServingEntry", new: "ServingEntry", name: str
) -> None:
    """Raise ValueError naming every signature mismatch — the registry/
    router swap() guard.  A width or dtype change would make already-
    admitted requests dispatch garbage; an output-column change would break
    every client parsing the result dict.  Incompatible model upgrades are
    a REGISTER-under-a-new-name event, not a swap."""
    mismatches = []
    if int(old.n_cols) != int(new.n_cols):
        mismatches.append(f"n_cols {old.n_cols} -> {new.n_cols}")
    if np.dtype(old.dtype) != np.dtype(new.dtype):
        mismatches.append(f"dtype {np.dtype(old.dtype)} -> {np.dtype(new.dtype)}")
    if sorted(old.out_cols) != sorted(new.out_cols):
        mismatches.append(
            f"out_cols {sorted(old.out_cols)} -> {sorted(new.out_cols)}"
        )
    if mismatches:
        raise ValueError(
            f"swap({name!r}): incoming model is not serving-compatible "
            f"({'; '.join(mismatches)}); register it under a new name "
            "instead"
        )


def entry_for(model: Any, mesh: Any = None) -> ServingEntry:
    """The model's serving entry via its `_serving_entry` hook, with a
    uniform error for models that have no online-inference path."""
    hook = getattr(model, "_serving_entry", None)
    if hook is None:
        raise TypeError(
            f"{type(model).__name__} is not a servable model (no "
            "_serving_entry hook)"
        )
    entry = hook(mesh)
    if not isinstance(entry, ServingEntry):
        raise TypeError(
            f"{type(model).__name__}._serving_entry returned "
            f"{type(entry).__name__}, expected ServingEntry"
        )
    return entry
