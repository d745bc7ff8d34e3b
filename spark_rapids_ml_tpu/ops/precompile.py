#
# Parallel ahead-of-time kernel compilation.
#
# A cold estimator fit dispatches dozens of jit geometries (the MXU forest
# builder's level/class/chunk variants are the extreme case: ~480 XLA
# compilations at a 200k x 500 depth-10 shape).  XLA compilation runs
# outside the Python interpreter lock, so a fit that knows its kernel
# geometries up front can overlap the compiles by lowering+compiling every
# geometry on a thread pool and dispatching through the resulting AOT
# executables.
#
# The reference hides the analogous cost inside cuML's precompiled fatbins
# (its kernels ship compiled; only tiny JIT specializations happen at run
# time) — on XLA the compile is unavoidable, but it does not have to be
# serial.
#
# Beyond the per-fit thread pool this module is the process's ONE executable
# cache: `cached_call` dispatches any jit through an AOT executable keyed on
# (shape-bucket, dtype, mesh fingerprint, donation, statics) — first call
# compiles (counted in profiling as precompile.compile / aot_miss), repeats
# run the cached executable (aot_hit) with zero new compilations — and
# `ensure_compile_cache` owns the rule for jax's on-disk compilation cache.
# Users: the kNN query engine (ops/knn.py), the MXU forest builder
# (ops/forest_mxu.py), the serving entries (serving/entry.py), and the
# benchmarks.
#

from __future__ import annotations

import logging
import os
import queue
import threading
from collections import OrderedDict
from typing import Any, Hashable, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import profiling

logger = logging.getLogger("spark_rapids_ml_tpu.precompile")

_POOL_WORKERS = 16
# executable-cache bound: far above any one fit's geometry count (the MXU
# forest's worst case is ~480), small enough that a long-lived process
# cycling through many distinct fit shapes cannot grow without bound
_MAX_CACHED = 1024


def aval(shape: Tuple[int, ...], dtype: Any) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def shape_bucket(n: int, lo: int = 64, hi: int = 1 << 30) -> int:
    """Power-of-two bucket for a dynamic row count — the ONE bucketing rule
    shared by cache keys and the callers that pad their blocks to it, so a
    warm-path submit and the later dispatch always agree on the shape."""
    b = lo
    while b < min(n, hi):
        b *= 2
    return b


def mesh_fingerprint(mesh: Any) -> Tuple:
    """Value identity of a mesh for cache keys: axis layout + device ids.
    get_mesh() builds a FRESH Mesh object per call, so keying on id(mesh)
    would miss on every repeat search; two meshes over the same devices and
    axes produce identical executables."""
    if mesh is None:
        return ()
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


# -- persistent on-disk compilation cache ------------------------------------
# ONE rule, owned here.  Where JAX_COMPILATION_CACHE_DIR is set, jax keeps
# its cache there (it reads the variable itself) and no code sets another
# directory.  Where it is not, the cache lives in ONE fixed directory inside
# the checkout, <repo>/.jax_cache: the path is part of jax's cache key, so a
# directory named from a pid, a time or mktemp would never hit.  Every fit
# and every server start calls ensure_compile_cache(), so a FRESH PROCESS at
# a seen kernel geometry pays a disk read instead of an XLA compile.

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)
# cache every executable: a compile-time floor would let timing jitter
# decide whether a kernel is stored, so a second run of the same program
# could still add files
_CACHE_MIN_COMPILE_SECS = 0.0
_persist_lock = threading.Lock()
_persist_dir: Optional[str] = None


def ensure_compile_cache() -> str:
    """Apply the compile-cache rule above (idempotent) and return the
    active cache directory."""
    global _persist_dir
    with _persist_lock:
        if _persist_dir is None:
            path = os.environ.get(CACHE_DIR_ENV)
            if not path:
                path = CHECKOUT_CACHE_DIR
                os.makedirs(path, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", path)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                _CACHE_MIN_COMPILE_SECS,
            )
            # every fit, transform and server start passes here before it
            # compiles: the compile account listens from now on
            profiling.watch_compiles()
            _persist_dir = path
        return _persist_dir


class _Job:
    """A one-shot future: holds either the compiled executable or the
    compile-time exception."""

    __slots__ = ("done", "result", "error", "key")

    def __init__(self, key=None):
        self.done = threading.Event()
        self.key = key
        self.result = None
        self.error: Optional[BaseException] = None

    def wait(self):
        self.done.wait()
        if self.error is not None:
            raise self.error
        return self.result


class Precompiler:
    """Submit jit lowerings for background compilation; `call` dispatches
    through the compiled executable (waiting for it if needed) and falls
    back to the plain jit call when COMPILATION failed.  Runtime errors from
    the compiled executable propagate unchanged — a device OOM must surface
    at its true site, not be retried on the jit path minutes later.

    Workers are daemon threads: an interrupted fit never blocks interpreter
    exit on a half-finished kernel compile (XLA compiles cannot be
    cancelled, only abandoned).  Compiled executables are cached per
    (fn, key) for the life of the instance, so repeated fits at one
    geometry skip compilation the same way jax's own jit cache would; the
    cache is bounded by the number of distinct fit geometries a process
    sees, the same growth jax's jit cache has."""

    def __init__(self, max_workers: int = _POOL_WORKERS):
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._jobs: "OrderedDict[Hashable, _Job]" = OrderedDict()
        self._lock = threading.Lock()
        self._workers = []
        for i in range(max_workers):
            t = threading.Thread(
                target=self._worker, name=f"srml-precompile-{i}", daemon=True
            )
            t.start()
            self._workers.append(t)

    def _worker(self):
        import contextlib

        while True:
            job, fn, avals, static_kwargs = self._q.get()
            try:
                # x64 is a THREAD-LOCAL scope: a float64 fit submits 64-bit
                # avals from inside its enable_x64 context, but this worker
                # thread is outside it — lowering here would silently
                # canonicalize the avals to 32-bit and build an executable
                # that rejects the fit's actual arguments.  Re-enter the
                # scope whenever the avals carry 8-byte dtypes.
                wide = any(
                    jnp.dtype(a.dtype).itemsize == 8
                    for a in jax.tree_util.tree_leaves(avals)
                    if hasattr(a, "dtype")
                )
                ctx = jax.enable_x64(True) if wide else contextlib.nullcontext()
                # the compile span carries the kernel name (first key
                # element) so pool compile time is attributable per kernel
                # in traces without string-ifying the full geometry key
                kname = (
                    job.key[0]
                    if isinstance(job.key, tuple) and job.key
                    else str(job.key)[:64]
                )
                with ctx, profiling.span(
                    "precompile.compile", kernel=str(kname)
                ):
                    job.result = fn.lower(*avals, **static_kwargs).compile()
                profiling.incr_counter("precompile.compile")
            except BaseException as exc:  # noqa: BLE001 - relayed to waiter
                job.error = exc
            finally:
                job.done.set()

    def submit(self, key: Hashable, fn, *avals, **static_kwargs) -> None:
        """Queue `fn.lower(*avals, **static_kwargs).compile()` if this key
        has not been queued already.  avals are ShapeDtypeStructs (or
        concrete arrays) matching the future call EXACTLY."""
        with self._lock:
            if key in self._jobs:
                return
            job = _Job(key)
            self._jobs[key] = job
            # LRU bound: evict the oldest FINISHED executables (an in-flight
            # job must stay — its waiter holds a reference to the key)
            while len(self._jobs) > _MAX_CACHED:
                stale = next(
                    (k for k, j in self._jobs.items() if j.done.is_set()),
                    None,
                )
                if stale is None:
                    break
                del self._jobs[stale]
        self._q.put((job, fn, avals, static_kwargs))

    def clear(self) -> None:
        """Drop every finished executable (an in-flight job stays: its
        waiter holds the key).  Later same-key calls compile again."""
        with self._lock:
            for key in [k for k, j in self._jobs.items() if j.done.is_set()]:
                del self._jobs[key]

    def wait(self, keys) -> None:
        """Block until every submitted key in `keys` has finished compiling
        (compile FAILURES are swallowed — the dispatch path's jit fallback
        owns them).  Lets warm-path callers (and the zero-recompile tests)
        draw a line between 'warm compiles in flight' and 'steady state'."""
        for key in keys:
            with self._lock:
                job = self._jobs.get(key)
            if job is None:
                continue
            try:
                job.wait()
            except Exception:  # noqa: BLE001 - surfaced at dispatch instead
                pass

    def cache_stats(self) -> dict:
        """Executable-cache introspection for the srml-watch health plane:
        entry/in-flight counts, per-kernel entry counts and (bounded) the
        set of leading-argument bucket geometries, plus a best-effort
        estimated code footprint from XLA's memory analysis.  Read-only and
        cheap enough for gauge scrapes; estimation failures degrade to
        None, never raise."""
        with self._lock:
            jobs = list(self._jobs.items())
        per_kernel: dict = {}
        in_flight = 0
        est_bytes: Optional[float] = 0.0
        for key, job in jobs:
            name = (
                str(key[0])
                if isinstance(key, tuple) and key
                else str(key)[:64]
            )
            entry = per_kernel.setdefault(
                name, {"entries": 0, "bucket_geometries": []}
            )
            entry["entries"] += 1
            # bucket geometry: the first argument's shape in the cache key
            # (kernel_cache_key layout) — the pow2 row bucket callers pad to
            if (
                isinstance(key, tuple)
                and len(key) > 1
                and isinstance(key[1], tuple)
                and key[1]
                and isinstance(key[1][0], tuple)
            ):
                geo = list(key[1][0][0]) if key[1][0] else []
                if geo not in entry["bucket_geometries"] and len(
                    entry["bucket_geometries"]
                ) < 16:
                    entry["bucket_geometries"].append(geo)
            if not job.done.is_set():
                in_flight += 1
                continue
            if est_bytes is not None and job.result is not None:
                try:
                    ma = job.result.memory_analysis()
                    est_bytes += float(
                        getattr(ma, "generated_code_size_in_bytes", 0)
                    ) + float(getattr(ma, "temp_size_in_bytes", 0))
                except Exception:  # noqa: BLE001 - backend-dependent surface
                    est_bytes = None
        return {
            "entries": len(jobs),
            "in_flight": in_flight,
            "est_code_bytes": est_bytes,
            "kernels": dict(sorted(per_kernel.items())),
        }

    def cached_call(self, key: Hashable, fn, *args, **static_kwargs):
        """Executable-cache dispatch: run `fn` through the AOT executable for
        `key`, COMPILING IT ON MISS (lowered from the concrete args, so their
        shardings are captured exactly) and caching it for every later
        same-key call.  The profiling counters make the contract observable:
        a repeat call at a cached key moves `precompile.aot_hit` and leaves
        `precompile.compile` untouched — zero new compilations."""
        with self._lock:
            missing = key not in self._jobs
        if missing:
            profiling.incr_counter("precompile.aot_miss")
            self.submit(key, fn, *args, **static_kwargs)
        else:
            profiling.incr_counter("precompile.aot_hit")
        return self._dispatch(key, fn, args, static_kwargs)

    def call(self, key: Hashable, fn, *args, **static_kwargs):
        """Run the precompiled executable for `key` (blocking on its
        compilation if still in flight).  Unsubmitted keys and COMPILE
        failures fall back to the plain jit call — correctness never
        depends on the precompiler.  Errors raised while RUNNING the
        executable propagate to the caller."""
        with self._lock:
            known = key in self._jobs
        if not known:
            profiling.incr_counter("precompile.aot_miss")
            return fn(*args, **static_kwargs)
        profiling.incr_counter("precompile.aot_hit")
        return self._dispatch(key, fn, args, static_kwargs)

    def _dispatch(self, key: Hashable, fn, args, static_kwargs):
        """Wait for `key`'s executable and run it; fall back to the plain jit
        call on compile failure or input incompatibility (counted)."""
        with self._lock:
            job = self._jobs.get(key)
            if job is not None:
                self._jobs.move_to_end(key)  # LRU recency
        if job is None:  # evicted between the caller's check and now
            return fn(*args, **static_kwargs)
        try:
            compiled = job.wait()
        except Exception as exc:
            logger.warning("AOT compile for %r failed (%s); jit fallback", key, exc)
            profiling.incr_counter("precompile.fallback")
            with self._lock:
                self._jobs.pop(key, None)
            return fn(*args, **static_kwargs)
        try:
            return compiled(*args)
        except Exception as exc:
            # AOT executables are lowered from bare ShapeDtypeStructs
            # (default placement).  An argument arriving committed to
            # another device or carrying a non-default sharding is an INPUT
            # incompatibility, not a kernel failure: drop the executable and
            # fall back to the plain jit call, which re-specializes.  All
            # other runtime errors (OOM and friends) propagate unchanged —
            # they must surface at their true site.
            msg = str(exc).lower()
            if any(
                s in msg
                for s in (
                    "sharding",
                    "placement",
                    "compiled for input",
                    "types differ",  # aval/dtype drift (e.g. x64-scope skew)
                )
            ):
                logger.warning(
                    "AOT executable for %r rejected its inputs (%s); "
                    "jit fallback",
                    key,
                    exc,
                )
                profiling.incr_counter("precompile.fallback")
                with self._lock:
                    self._jobs.pop(key, None)
                return fn(*args, **static_kwargs)
            raise


_global: Optional[Precompiler] = None


def global_precompiler() -> Precompiler:
    """Process-wide instance: compiled geometries persist across fits."""
    global _global
    if _global is None:
        _global = Precompiler()
    return _global


def executable_cache_stats() -> dict:
    """cache_stats() of the process-wide precompiler WITHOUT constructing
    it (a gauge scrape must not spin up 16 worker threads in a process that
    never compiled anything)."""
    if _global is None:
        return {
            "entries": 0, "in_flight": 0, "est_code_bytes": 0.0, "kernels": {},
        }
    return _global.cache_stats()


def kernel_cache_key(name: str, args, mesh, statics: dict):
    """The ONE key derivation shared by dispatch-time cached_kernel and the
    AOT warm paths (e.g. knn.warm_search_kernels) — a warmed executable must
    be the exact entry the later dispatch looks up.  Args may be pytrees
    (the sweep kernels pass stacked stats NamedTuples); leaves key on
    shape/dtype, so the derivation is unchanged for plain array args."""
    return (
        name,
        tuple(
            (tuple(a.shape), str(a.dtype))
            for a in jax.tree_util.tree_leaves(args)
        ),
        mesh_fingerprint(mesh),
        tuple(sorted(statics.items())),
    )


def cached_kernel(name: str, fn, *args, mesh=None, **statics):
    """Dispatch a jitted kernel through the process-wide AOT executable
    cache: keyed on (kernel name, per-arg shape/dtype, mesh fingerprint,
    statics), compiled once per key — from the concrete args, so shardings
    are captured — and reused by every later same-shape call (repeat
    searches and fits, benchmarks, other models' queries).  The mesh rides
    the key by VALUE (get_mesh builds fresh Mesh objects per call).  Shared
    by the kNN query engine (ops/knn.py) and the sharded UMAP layout engine
    (ops/umap.py)."""
    key = kernel_cache_key(name, args, mesh, statics)
    if mesh is not None:
        statics["mesh"] = mesh
    if not hasattr(fn, "lower"):
        # plain callable (tests monkeypatch the jitted phases with spies):
        # nothing to AOT-compile, call through
        return fn(*args, **statics)
    return global_precompiler().cached_call(key, fn, *args, **statics)
