#
# srml-scope: the runtime observability layer.
#
# TPU-native equivalent of the reference's observability surface (SURVEY.md
# §5): the Scala path wraps phases in NVTX ranges
# (/root/reference/jvm/src/main/scala/org/apache/spark/ml/linalg/distributed/RapidsRowMatrix.scala:62,70)
# and the Python path logs coarse phase lines inside the fit UDF
# (/root/reference/python/src/spark_rapids_ml/core.py:583,617) with wall-clock
# timers in the benchmark harness
# (/root/reference/python/benchmark/benchmark/utils.py:42-50).  Those ideas
# grew here into three pillars:
#
#   1. HIERARCHICAL SPANS — span(name, **attrs) nests: each span records its
#      parent span (per-thread stack), thread id/name, monotonic start/end
#      timestamps, and any attached counters (bytes=, rows=, block=...).
#      phase(name) is the same function (API-compatible shim) — every
#      existing phase site in the engines is a span site.  Alongside the
#      host-side record, every span still emits a jax.profiler
#      TraceAnnotation so xprof captures carry the same names.  Span records
#      are collected ONLY while a trace session is active: spans off means
#      no allocation, no buffer append, no thread-local stack — the disabled
#      path is the old flat phase timer, nothing more (guarded by
#      tests/test_profiling.py).
#   2. TRACE EXPORT — trace_session(tag) (active when SRML_TRACE_DIR is set)
#      collects every span completed during the session and writes a Chrome
#      trace-event JSON file (load it in Perfetto / chrome://tracing).  Fit,
#      kneighbors, and serving sessions open one automatically.
#   3. MERGEABLE TELEMETRY — TelemetrySnapshot rolls up phase stats,
#      counters, and duration digests into a JSON-safe dict with associative
#      commutative merge rules (mirroring metrics/binary.py partials), so
#      executor-side fit telemetry crosses the Spark wire and merges on the
#      driver: model.fit_telemetry() works on live Spark, not just local
#      mode.  export_metrics() / render_prometheus() are the pull surface
#      (stable JSON + Prometheus text exposition).
#
# The flat primitives underneath are unchanged:
#   - incr_counter/counters: PROCESS-wide monotonic counters (precompile's
#     compile/hit/miss accounting; worker threads report into the registry
#     the main thread reads).
#   - record_event/events: a per-thread ORDERED event log for asserting
#     pipeline interleavings without timing-dependent tests.
#   - record_duration/percentiles: PROCESS-wide duration samples with
#     p50/p95/p99 summaries (the serving SLO surface).
#   - maybe_trace(): opt-in whole-program xprof capture (SRML_PROFILE=<dir>).
#   - watch_compiles/compile_events/compile_summary: the compile account —
#     what the process traced, lowered, and compiled or loaded, by
#     executable, from jax's own monitoring events (compile.* counters and a
#     bounded journal; always on, called only when jax builds something).
#   - LandingJournal: [opened, landed] of asynchronous host→device copies,
#     the landing stamped by the process's one watcher thread (srml-link-watch)
#     and not where the sender next looks; core.stage_dense_batches keeps one a
#     staging inside a fit job (counters ingest.link_*, span srml.link.h2d).
#   - now(): the ONE monotonic clock.  Engine/serving modules must take
#     timestamps through it (or through span()) — graftlint R6 rejects raw
#     time.perf_counter()/time.time() outside this module, so every timing
#     source srml-scope reports from is the same clock.
#

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import queue
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

_log = logging.getLogger("spark_rapids_ml_tpu.profiling")

PROFILE_ENV = "SRML_PROFILE"
TRACE_ENV = "SRML_TRACE_DIR"
METRIC_TTL_ENV = "SRML_METRIC_TTL_S"

_tls = threading.local()

# srml-watch flight-recorder hook (watch.install sets this to the process
# FlightRecorder).  Unlike trace sessions the recorder is ALWAYS on: span()
# and incr_counter() feed it bounded O(1) ring events so the last moments
# before a hang/crash are reconstructable without any session open.  None
# (SRML_WATCH=0) restores the exact pre-watch code path.
_flight: Optional[Any] = None


def now() -> float:
    """The process's ONE monotonic clock (time.perf_counter).  All timing in
    engine/serving modules goes through here or span() — graftlint R6."""
    return time.perf_counter()


# perf_counter value at import: trace-event timestamps are exported relative
# to it so a Perfetto timeline starts near zero instead of at host uptime
_EPOCH = time.perf_counter()


def _registry() -> Dict[str, float]:
    reg = getattr(_tls, "phases", None)
    if reg is None:
        reg = {}
        _tls.phases = reg
    return reg


def _count_registry() -> Dict[str, int]:
    reg = getattr(_tls, "phase_counts", None)
    if reg is None:
        reg = {}
        _tls.phase_counts = reg
    return reg


def reset_phase_times() -> None:
    """Clear the current thread's phase registry (called at fit entry)."""
    _registry().clear()
    _count_registry().clear()


def phase_times(prefix: str = "") -> Dict[str, float]:
    """Seconds per named phase recorded on this thread since the last reset
    (optionally filtered by name prefix — the benchmark idiom for reporting
    one subsystem's phase set, e.g. "forest." or "knn.")."""
    reg = _registry()
    if not prefix:
        return dict(reg)
    return {k: v for k, v in reg.items() if k.startswith(prefix)}


def phase_stats(prefix: str = "") -> Dict[str, Dict[str, float]]:
    """{name: {"count", "total_s"}} for this thread's phases since the last
    reset — the span rollup a TelemetrySnapshot carries (counts travel with
    totals so merged snapshots can still average per-invocation cost)."""
    reg = _registry()
    cnt = _count_registry()
    return {
        k: {"count": int(cnt.get(k, 0)), "total_s": float(v)}
        for k, v in reg.items()
        if k.startswith(prefix)
    }


# -- process-wide counters ---------------------------------------------------
# Unlike the phase registry these are NOT thread-local: the precompile worker
# pool compiles on daemon threads while fits read the counters from the main
# thread, so one locked registry is the only consistent view.

_counters_lock = threading.Lock()
_counters: Dict[str, int] = {}


def incr_counter(name: str, amount: int = 1) -> None:
    """Add `amount` to the process-wide counter `name` (created at 0)."""
    with _counters_lock:
        total = _counters.get(name, 0) + amount
        _counters[name] = total
    fr = _flight
    if fr is not None:
        fr.on_counter(name, amount, total)


def counter(name: str) -> int:
    with _counters_lock:
        return _counters.get(name, 0)


def counters(prefix: str = "") -> Dict[str, int]:
    """Snapshot of all counters (optionally filtered by name prefix)."""
    with _counters_lock:
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def counter_deltas(before: Dict[str, int], prefix: str = "") -> Dict[str, int]:
    """Nonzero differences of the current counters vs a `counters(prefix)`
    snapshot — the benchmark/test idiom for "what moved during this fit"
    without resetting the monotonic registry."""
    now_ = counters(prefix)
    keys = set(now_) | set(before)
    return {
        k: now_.get(k, 0) - before.get(k, 0)
        for k in sorted(keys)
        if now_.get(k, 0) != before.get(k, 0)
    }


def reset_counters(prefix: str = "") -> None:
    """Zero counters matching `prefix` (tests; production code never resets —
    the counters are monotonic so deltas are always well-defined)."""
    with _counters_lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            del _counters[k]


# -- process-wide duration samples -------------------------------------------
# Like the counters (and unlike the phase registry) these are NOT thread-
# local: the serving engine records request latencies on its dispatch worker
# thread while stats()/tests read the percentiles from the main thread.
# Bounded per name so a long-lived server cannot grow the sample list without
# limit; past the cap new samples overwrite the oldest (ring buffer), keeping
# the percentiles a sliding window over the most recent traffic.

_DURATION_CAP = 65536
# TTL sweeps run at most once per _TTL_SWEEP_EVERY records so the eviction
# scan cost amortizes to ~zero on hot serving paths
_TTL_SWEEP_EVERY = 256

_durations_lock = threading.Lock()
_durations: Dict[str, list] = {}
_duration_next: Dict[str, int] = {}  # ring-buffer write cursor past the cap
# lifetime [count, sum, min, max] per series: unlike the capped ring these
# are MONOTONIC (evicted samples stay counted), so duration_digests deltas
# between two snapshots are exact no matter how busy the series is
_duration_stats: Dict[str, list] = {}
# last-touch clock per series (only maintained while SRML_METRIC_TTL_S > 0)
_duration_touched: Dict[str, float] = {}
_ttl_record_count = 0


def metric_ttl_s() -> float:
    """SRML_METRIC_TTL_S: seconds a duration series may go untouched before
    eviction (0, the default, disables eviction).  The per-series sample
    ring is bounded, but the NUMBER of series is not — a long-lived serving
    process cycling through model names would otherwise leak series."""
    try:
        return float(os.environ.get(METRIC_TTL_ENV, "") or 0.0)
    except ValueError:
        return 0.0


def _evict_stale_series_locked(ttl: float, now_t: float, keep: str) -> None:
    """Drop every series untouched for `ttl` seconds (except `keep`, the
    series being written).  A series recorded before TTL was enabled has no
    touch stamp — it is stamped now and given a full TTL."""
    for k in list(_durations):
        if k == keep:
            continue
        touched = _duration_touched.get(k)
        if touched is None:
            _duration_touched[k] = now_t
        elif now_t - touched > ttl:
            del _durations[k]
            _duration_next.pop(k, None)
            _duration_stats.pop(k, None)
            _duration_touched.pop(k, None)


def record_duration(name: str, seconds: float) -> None:
    """Append one duration sample (seconds) to the process-wide series
    `name`.  Cheap enough for per-request recording; capped per name (ring
    buffer) so recording is observability, never a leak.  With
    SRML_METRIC_TTL_S set, series untouched for the TTL are evicted here
    (amortized: one sweep per _TTL_SWEEP_EVERY records)."""
    global _ttl_record_count
    s = float(seconds)
    ttl = metric_ttl_s()  # env read outside the lock: the hot serving path
    # records several series per batch and must not serialize on it
    with _durations_lock:
        series = _durations.get(name)
        if series is None:
            series = []
            _durations[name] = series
        if len(series) < _DURATION_CAP:
            series.append(s)
        else:
            cur = _duration_next.get(name, 0)
            series[cur] = s
            _duration_next[name] = (cur + 1) % _DURATION_CAP
        stats = _duration_stats.get(name)
        if stats is None:
            _duration_stats[name] = [1, s, s, s]
        else:
            stats[0] += 1
            stats[1] += s
            if s < stats[2]:
                stats[2] = s
            if s > stats[3]:
                stats[3] = s
        if ttl > 0:
            now_t = time.perf_counter()
            _duration_touched[name] = now_t
            _ttl_record_count += 1
            if _ttl_record_count % _TTL_SWEEP_EVERY == 0:
                _evict_stale_series_locked(ttl, now_t, keep=name)


def series_stats() -> Dict[str, Any]:
    """Self-description of the duration registry — series count, total ring
    samples, estimated resident bytes, and per-series lifetime counts +
    last-touch age — so a long-lived serving process can watch its own
    metric footprint (the leak this surface exists to catch)."""
    now_t = time.perf_counter()
    with _durations_lock:
        per = {}
        total_samples = 0
        for k, v in _durations.items():
            total_samples += len(v)
            stats = _duration_stats.get(k) or [len(v), 0.0, 0.0, 0.0]
            touched = _duration_touched.get(k)
            per[k] = {
                "ring_samples": len(v),
                "lifetime_count": int(stats[0]),
                "age_s": (
                    round(now_t - touched, 3) if touched is not None else None
                ),
            }
        return {
            "series_count": len(per),
            "ring_samples": total_samples,
            "est_bytes": total_samples * 8,
            "ttl_s": metric_ttl_s(),
            "series": per,
        }


def durations(prefix: str = "") -> Dict[str, list]:
    """Copy of every duration series whose name starts with `prefix`."""
    with _durations_lock:
        return {k: list(v) for k, v in _durations.items() if k.startswith(prefix)}


def reset_durations(prefix: str = "") -> None:
    with _durations_lock:
        for k in [k for k in _durations if k.startswith(prefix)]:
            del _durations[k]
            _duration_next.pop(k, None)
            _duration_stats.pop(k, None)
            _duration_touched.pop(k, None)


def percentiles(prefix: str = "") -> Dict[str, float]:
    """p50/p95/p99 (plus count/mean/max) over every duration sample recorded
    under names starting with `prefix`, merged into ONE distribution — pass
    an exact series name for a single series, or a subsystem prefix (e.g.
    "serve.kmeans.") for its whole latency surface.  Returns {} when nothing
    was recorded.  Linear interpolation between order statistics, the numpy
    default, so tiny test samples get deterministic values."""
    merged: list = []
    with _durations_lock:
        for k, v in _durations.items():
            if k.startswith(prefix):
                merged.extend(v)
    return _percentile_digest(merged)


def _percentile_digest(samples: list) -> Dict[str, float]:
    if not samples:
        return {}
    import numpy as np

    arr = np.asarray(samples, dtype=np.float64)
    p50, p95, p99 = np.percentile(arr, [50.0, 95.0, 99.0])
    return {
        "count": int(arr.size),
        "mean": float(arr.mean()),
        "p50": float(p50),
        "p95": float(p95),
        "p99": float(p99),
        "max": float(arr.max()),
    }


def duration_digests(prefix: str = "") -> Dict[str, Dict[str, float]]:
    """Mergeable per-series digests {name: {count, sum_s, min_s, max_s}} —
    the duration form a TelemetrySnapshot carries: unlike percentiles these
    merge associatively across executors, so a driver-side rollup is exact
    regardless of merge order.  Built from LIFETIME running totals, not the
    capped sample ring, so count/sum stay monotonic past the ring's
    eviction point and snapshot deltas (registry.telemetry(since=...)) are
    exact on arbitrarily busy series (percentiles over the raw ring remain
    a most-recent-traffic view; see docs/observability.md)."""
    out: Dict[str, Dict[str, float]] = {}
    with _durations_lock:
        for k, s in _duration_stats.items():
            if k.startswith(prefix):
                out[k] = {
                    "count": s[0],
                    "sum_s": s[1],
                    "min_s": s[2],
                    "max_s": s[3],
                }
    return out


# -- per-thread ordered event log --------------------------------------------
# Bounded so a long-lived process that never drains the log cannot grow it
# without limit; the cap is far above any one search's dispatch/collect count.

_EVENT_CAP = 4096


def _event_log() -> list:
    log = getattr(_tls, "events", None)
    if log is None:
        log = []
        _tls.events = log
    return log


def record_event(name: str, **meta: Any) -> None:
    """Append (name, meta) to this thread's ordered event log (dropped
    silently past the cap — the log is observability, never control flow)."""
    log = _event_log()
    if len(log) < _EVENT_CAP:
        log.append((name, meta))


def events(prefix: str = "") -> list:
    """This thread's events in record order, optionally prefix-filtered."""
    return [(n, m) for n, m in _event_log() if n.startswith(prefix)]


def reset_events() -> None:
    _event_log().clear()


# -- the compile account -------------------------------------------------------
# What a process traces, lowers, and compiles or loads, by executable: jax
# times each of the three where it happens and reports it through
# jax.monitoring with the function's name: its start when it opens, and its
# start and end on time.time() when it closes.  watch_compiles() (called by
# ops/precompile's ensure_compile_cache, which every fit, transform and
# server start reaches before anything of the program compiles) registers
# the listeners for the life of the process, whether or not a trace session
# is on: jax calls them only when it traces, lowers or compiles, which a
# warm call never does, so a steady state pays nothing and any event there
# is itself a finding (a job that built something again).
#
# Nested events count once.  jax reports the trace of `matmul` or `tanh`
# INSIDE the trace of the function that calls them (hundreds a solver), so
# the account keeps a thread's OUTERMOST event of each kind and drops what
# opens inside it: the seconds are the union of a thread's intervals per
# kind, not the sum of the durations.
#
#   - counters (integer microseconds and counts, so they ride the counter
#     delta into fit_telemetry() and export_metrics()): compile.trace_us,
#     compile.lower_us, compile.backend_us, compile.executables (backend
#     events: on a persistent-cache hit the retrieval and load, on a miss
#     XLA's compile), compile.cache_hits, compile.cache_misses.  They are
#     THREAD seconds: the precompile pool lowers several executables at once.
#   - compile_summary(): the same seconds by function name and kind, which
#     the journal's cap does not truncate.
#   - compile_events(): a journal of (kind, fun_name, start, end, thread
#     name), bounded like the event log (new events are dropped past the
#     cap).  start/end are time.time() readings as jax took them: the
#     journal can be laid over any wall-clock record of the process (a
#     phase clock, an xprof capture) to partition WALL time, which is the
#     reader's to do.

_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_COMPILE_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_compile_journal: List[tuple] = []
_compile_by_name: Dict[str, Dict[str, Dict[str, float]]] = {}
_compile_watching = False


def _bump_locked(name: str, amount: int = 1) -> None:
    """incr_counter for a caller that holds _counters_lock (one acquisition
    an event for its counters, roll-up and journal; no flight-ring event)."""
    _counters[name] = _counters.get(name, 0) + amount


def _compile_depths() -> Dict[str, int]:
    depths = getattr(_tls, "compile_depths", None)
    if depths is None:
        depths = _tls.compile_depths = dict.fromkeys(_COMPILE_KINDS.values(), 0)
    return depths


def _on_compile_open(event: str, _start_time: float, **_kw: Any) -> None:
    kind = _COMPILE_KINDS.get(event)
    if kind is not None:
        _compile_depths()[kind] += 1


def _on_compile_span(
    event: str, start_time: float, end_time: float, **kw: Any
) -> None:
    kind = _COMPILE_KINDS.get(event)
    if kind is None:
        return
    depths = _compile_depths()
    # an event whose opening was not seen (the listeners were registered
    # inside it) closes at depth 0 and counts as outermost
    depths[kind] = max(depths[kind] - 1, 0)
    if depths[kind]:
        return
    name = str(kw.get("fun_name", ""))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]  # lowering and backend events wrap the trace's name
    seconds = end_time - start_time
    with _counters_lock:
        agg = _compile_by_name.setdefault(name, {}).setdefault(
            kind, {"count": 0, "total_s": 0.0}
        )
        agg["count"] += 1
        agg["total_s"] += seconds
        _bump_locked("compile." + kind + "_us", int(round(1e6 * seconds)))
        if kind == "backend":
            _bump_locked("compile.executables")
        if len(_compile_journal) < _EVENT_CAP:
            _compile_journal.append(
                (kind, name, start_time, end_time,
                 threading.current_thread().name)
            )


def _on_compile_event(event: str, **_kw: Any) -> None:
    name = _COMPILE_CACHE_COUNTERS.get(event)
    if name is not None:
        with _counters_lock:
            _bump_locked(name)


def watch_compiles() -> None:
    """Register the compile account's jax.monitoring listeners (idempotent;
    jax is imported here and not at this module's import)."""
    global _compile_watching
    with _counters_lock:
        if _compile_watching:
            return
        _compile_watching = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_compile_open)
    jax.monitoring.register_event_time_span_listener(_on_compile_span)
    jax.monitoring.register_event_listener(_on_compile_event)


def compile_events() -> List[tuple]:
    """Copy of the journal: (kind, fun_name, start, end, thread name) per
    outermost event, kind one of "trace" / "lower" / "backend", start and
    end on time.time()."""
    with _counters_lock:
        return list(_compile_journal)


def compile_summary() -> Dict[str, Dict[str, Dict[str, float]]]:
    """{fun_name: {kind: {"count", "total_s"}}} over the process's life:
    outermost events, thread seconds."""
    with _counters_lock:
        return {
            name: {kind: dict(agg) for kind, agg in kinds.items()}
            for name, kinds in _compile_by_name.items()
        }


def reset_compile_account() -> None:
    """Clear the journal, the by-name roll-up and the compile.* counters
    (tests; the listeners stay registered)."""
    with _counters_lock:
        _compile_journal.clear()
        _compile_by_name.clear()
        for k in [k for k in _counters if k.startswith("compile.")]:
            del _counters[k]


# -- hierarchical spans -------------------------------------------------------
# A span is the phase timer grown a parent: while a trace session is active,
# every completed span appends ONE record (name, t0, t1, thread, span id,
# parent id, attrs) to a process-wide bounded buffer under a lock.  The
# per-thread parent stack exists only while collecting, so the disabled path
# is byte-for-byte the old flat timer: TraceAnnotation + two thread-local
# dict updates, no allocation, no lock (asserted by the zero-overhead guard
# in tests/test_profiling.py).

_TRACE_CAP = 131072

_trace_lock = threading.Lock()
_trace_records: List[tuple] = []
_collect_depth = 0  # active trace sessions / collection scopes
_span_ids = itertools.count(1)
_session_seq = itertools.count(1)


class _SpanHandle:
    """Yielded by span(): set(**kv) attaches counters (bytes=, rows=...) to
    the span record mid-flight.  The module-level null handle is what the
    disabled path yields — set() is a no-op there, so call sites never
    branch on whether tracing is on."""

    __slots__ = ("attrs",)

    def __init__(self, attrs: Optional[Dict[str, Any]]):
        self.attrs = attrs

    def set(self, **kv: Any) -> None:
        if self.attrs is not None:
            self.attrs.update(kv)


_NULL_SPAN = _SpanHandle(None)


def _span_stack() -> list:
    stack = getattr(_tls, "span_stack", None)
    if stack is None:
        stack = []
        _tls.span_stack = stack
    return stack


# jax.profiler.TraceAnnotation, resolved on the first span (profiling itself
# never pulls jax in at import): the import machinery is not on every span's
# path
_annotation: Optional[Callable[[str], contextlib.AbstractContextManager]] = None


def _resolve_annotation() -> Callable[[str], contextlib.AbstractContextManager]:
    global _annotation
    try:
        import jax.profiler

        _annotation = jax.profiler.TraceAnnotation
    except Exception:  # pragma: no cover - profiler always importable with jax
        _annotation = lambda _name: contextlib.nullcontext()  # noqa: E731
    return _annotation


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[_SpanHandle]:
    """Named range: xprof TraceAnnotation + wall-clock accounting + (while a
    trace session is active) one hierarchical span record.

    The TraceAnnotation shows up in a tensorboard/xprof capture exactly
    where NVTX ranges show up in nsys for the reference's Scala path; the
    span record is what the Chrome-trace export and TelemetrySnapshot
    rollups are built from.  `attrs` become the trace event's args
    (bytes=, rows=, block=...); they are ignored — never allocated — when
    no session is collecting."""
    # the annotation opens first and closes last: the span's own bookkeeping
    # lies inside it, so spans that tile a thread leave only the context
    # managers' plumbing between their ranges in a profiler trace
    with (_annotation or _resolve_annotation())(name):
        collecting = _collect_depth > 0
        if collecting:
            sid = next(_span_ids)
            stack = _span_stack()
            parent = stack[-1] if stack else 0
            stack.append(sid)
            handle = _SpanHandle(dict(attrs))
        else:
            handle = _NULL_SPAN
        # flight recorder (srml-watch): ALWAYS on when installed — one
        # bounded ring event per span close plus the open-span stack a hang
        # dump and the stall watchdog read.  Overhead is gated <2% of a warm
        # fit by tests/test_watch.py.
        fr = _flight
        if fr is not None:
            fr.on_span_open(name)
        t0 = time.perf_counter()
        try:
            yield handle
        finally:
            t1 = time.perf_counter()
            dt = t1 - t0
            reg = _registry()
            reg[name] = reg.get(name, 0.0) + dt
            cnt = _count_registry()
            cnt[name] = cnt.get(name, 0) + 1
            if collecting:
                stack.pop()
                th = threading.current_thread()
                with _trace_lock:
                    if len(_trace_records) < _TRACE_CAP:
                        _trace_records.append(
                            (name, t0, t1, th.ident, th.name, sid, parent,
                             handle.attrs)
                        )
            if fr is not None:
                fr.on_span_close(name, t0, t1, sys.exc_info()[0] is not None)
            _log.debug("phase %s: %.3fs", name, dt)


# API-compatible shim: every existing phase site is a span site
phase = span


def span_records() -> List[tuple]:
    """Copy of the collected span records (name, t0, t1, thread_ident,
    thread_name, span_id, parent_id, attrs) — test/introspection surface."""
    with _trace_lock:
        return list(_trace_records)


@contextlib.contextmanager
def collect_spans() -> Iterator[None]:
    """Enable span-record collection for the enclosing scope WITHOUT writing
    a trace file (trace_session composes this with the Chrome-trace writer;
    tests use it directly).  Reentrant; the shared buffer clears when the
    last scope exits."""
    global _collect_depth
    with _trace_lock:
        _collect_depth += 1
    try:
        yield
    finally:
        with _trace_lock:
            _collect_depth -= 1
            if _collect_depth == 0:
                _trace_records.clear()


def _safe_tag(tag: str) -> str:
    return "".join(c if (c.isalnum() or c in "-_.") else "-" for c in tag)


def _clock_pair() -> Dict[str, float]:
    """The span clock beside the wall clock, read back to back."""
    return {"perf_counter_s": time.perf_counter(), "unix_s": time.time()}


def _write_chrome_trace(
    path: str, records: List[tuple], clock: Optional[Dict[str, Any]] = None
) -> None:
    """Write span records as Chrome trace-event JSON (the `traceEvents`
    array format Perfetto and chrome://tracing load): one complete ("X")
    event per span with microsecond ts/dur relative to the process epoch,
    plus thread_name metadata events so worker threads are labeled.
    `clock` lands under the document's `metadata` key (trace viewers keep
    unknown top-level keys as metadata): the span clock's epoch and its
    reading beside the wall clock at the session's start and end, so the
    file can be laid over an xprof capture of the same run, whose host
    events carry wall-clock times."""
    pid = os.getpid()
    tid_of: Dict[int, int] = {}
    names: Dict[int, str] = {}
    events_out: List[Dict[str, Any]] = []
    for name, t0, t1, ident, tname, sid, parent, attrs in records:
        tid = tid_of.setdefault(ident, len(tid_of) + 1)
        names.setdefault(tid, tname)
        args: Dict[str, Any] = {"span_id": sid}
        if parent:
            args["parent_id"] = parent
        if attrs:
            args.update(attrs)
        events_out.append(
            {
                "name": name,
                "cat": "srml",
                "ph": "X",
                "ts": (t0 - _EPOCH) * 1e6,
                "dur": (t1 - t0) * 1e6,
                "pid": pid,
                "tid": tid,
                "args": args,
            }
        )
    meta = [
        {
            "name": "thread_name",
            "ph": "M",
            "pid": pid,
            "tid": tid,
            "args": {"name": tname},
        }
        for tid, tname in sorted(names.items())
    ]
    doc: Dict[str, Any] = {"traceEvents": meta + events_out, "displayTimeUnit": "ms"}
    if clock is not None:
        doc["metadata"] = {"clock": {"epoch_perf_counter_s": _EPOCH, **clock}}
    tmp = f"{path}.tmp{pid}"
    try:
        with open(tmp, "w") as f:
            # default=str: span attrs are an open kwargs surface (numpy
            # scalars, dtypes, ...) and a non-JSON attr must degrade to its
            # repr, never fail the export
            json.dump(doc, f, default=str)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise


@contextlib.contextmanager
def trace_session(tag: str = "session") -> Iterator[Optional[str]]:
    """Collect spans for the enclosed region and write them as ONE Chrome
    trace-event JSON file under $SRML_TRACE_DIR (yielding the target path).
    No-op — zero overhead, yields None — when the env var is unset.  Opened
    automatically around every top-level fit (core.fit_job),
    kneighbors search, and serving engine lifetime; overlapping sessions
    each export their own window of the shared buffer."""
    out_dir = os.environ.get(TRACE_ENV)
    if not out_dir:
        yield None
        return
    try:
        os.makedirs(out_dir, exist_ok=True)
    except OSError as exc:
        # a bad observability env var must never fail the fit/search/server
        # it wraps — degrade to the disabled path with one warning
        _log.warning(
            "%s=%r is not writable (%s); tracing disabled for %r",
            TRACE_ENV, out_dir, exc, tag,
        )
        yield None
        return
    path = os.path.join(
        out_dir,
        f"{_safe_tag(tag)}-{os.getpid()}-{next(_session_seq):04d}.trace.json",
    )
    global _collect_depth
    with _trace_lock:
        _collect_depth += 1
    clock = {"start": _clock_pair()}
    t_start = clock["start"]["perf_counter_s"]
    try:
        yield path
    finally:
        clock["end"] = _clock_pair()
        with _trace_lock:
            records = [r for r in _trace_records if r[1] >= t_start]
            _collect_depth -= 1
            if _collect_depth == 0:
                _trace_records.clear()
        try:
            _write_chrome_trace(path, records, clock)
            _log.info(
                "srml-scope trace for %r: %d span(s) -> %s",
                tag, len(records), path,
            )
        except Exception as exc:  # disk-full, serialization drift, ...
            # the export is best-effort by design: it runs in a finally
            # around successful fits/searches and must never replace their
            # result with a telemetry crash
            _log.warning("trace export for %r failed: %s", tag, exc)


# -- the landing journal -------------------------------------------------------
# jax's host→device copies are asynchronous, and the thread that sends one sees
# it land only when it next blocks on it: on time while it has nothing else to
# do (the link is fed, the sender waits), late while it works (the link stands
# still behind it), which is the case a measurement of the link is for.  So a
# landing is stamped by a thread that does nothing else: the process's one
# watcher takes the journaled copies in the order they were sent, blocks on each
# (block_until_ready releases the interpreter lock) and stamps now() when it
# returns.  The stamp waits for the interpreter lock like any other line of
# Python: at most one switch interval (5 ms) late while another thread runs
# bytecode without a pause, and at once while the others are blocked themselves.

# how long a journal's close waits for the watcher to reach its end; the sender
# has seen every copy land by then, so the watcher is one wake-up behind
_LANDING_DRAIN_S = 10.0

_landing_queue: "queue.SimpleQueue[tuple]" = queue.SimpleQueue()
_landing_lock = threading.Lock()
_landing_thread: Optional[threading.Thread] = None


def interval_measures(intervals: List[Tuple[float, float]]) -> Tuple[float, float, float]:
    """(fed, starved, flight) of the closed intervals [(start, end), ...]: the
    measure of their union (at least one under way), what is missing of it from
    the first start to the last end (none under way), and the sum of their
    lengths (flight / fed is how many were under way at once while any was)."""
    fed, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            fed += end - max(start, reach)
            reach = end
    first = min(start for start, _ in intervals)
    return fed, (reach - first) - fed, sum(end - start for start, end in intervals)


def _stamp_landing(journal: "LandingJournal", piece: int, array: Any) -> None:
    """The watcher's one step, a function of its own so that the thread holds no
    reference to a copy while it waits for the next."""
    if array is None:       # a journal's end: every copy before it has been stamped
        journal._drained.set()
        return
    try:
        with (_annotation or _resolve_annotation())(journal.name):
            array.block_until_ready()
        journal.pieces[piece][1] = time.perf_counter()
    except Exception as exc:  # noqa: BLE001 - the sender's own wait raises it; the journal is void
        _log.debug("landing %d of %s was not seen: %s", piece, journal.name, exc)


def _watch_landings() -> None:
    while True:
        _stamp_landing(*_landing_queue.get())


class LandingJournal:
    """One sender's record of the asynchronous copies it enqueues, a piece a
    copy: [opened, landed, bytes], `opened` the sender's stamp and `landed` the
    watcher's.  sent() a copy, close() when the sender has seen the last one
    land.  A sender that raises simply lets go of it: the watcher stamps what is
    still under way (nothing blocks on a copy for longer than the copy takes),
    drops each array as it lands, and the journal goes with the last."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.pieces: List[list] = []
        self._drained = threading.Event()

    def sent(self, opened: float, nbytes: int, array: Any) -> None:
        """`array` (anything with block_until_ready) was enqueued at `opened`."""
        global _landing_thread
        if _landing_thread is None:
            with _landing_lock:
                if _landing_thread is None:
                    _landing_thread = threading.Thread(
                        target=_watch_landings, name="srml-link-watch", daemon=True
                    )
                    _landing_thread.start()
        self.pieces.append([opened, None, int(nbytes)])
        _landing_queue.put((self, len(self.pieces) - 1, array))

    def close(self) -> Optional[List[list]]:
        """The pieces as [opened, landed, bytes], once the watcher has stamped
        the last; while a trace session collects, each is also one span record
        on the watcher's thread (a lane of its own in the Chrome-trace export)
        under the caller's open span.  None where nothing was sent, or where a
        landing went unseen (a copy that raised): no journal rather than one
        with a guess in it."""
        if not self.pieces:
            return None
        _landing_queue.put((self, -1, None))
        if not self._drained.wait(_LANDING_DRAIN_S) or any(p[1] is None for p in self.pieces):
            _log.warning("the landing journal %s is void: a copy's landing was not seen", self.name)
            return None
        if _collect_depth > 0:
            stack = _span_stack()
            parent = stack[-1] if stack else 0
            th = _landing_thread
            with _trace_lock:
                for i, (t0, t1, nbytes) in enumerate(self.pieces):
                    if len(_trace_records) < _TRACE_CAP:
                        _trace_records.append(
                            (self.name, t0, t1, th.ident, th.name, next(_span_ids), parent,
                             {"bytes": nbytes, "piece": i})
                        )
        return self.pieces


# -- mergeable telemetry snapshots -------------------------------------------


class TelemetrySnapshot:
    """Serializable rollup of one session's observability: span/phase stats,
    counter deltas, and duration digests.

    Merge rules are associative AND commutative (sums, mins, maxes — the
    same algebra as metrics/binary.py partials), so executor-side snapshots
    captured at fit-task exit can cross the Spark wire as JSON and merge on
    the driver in any order: merge(a, b) == merge(b, a) and
    merge(merge(a, b), c) == merge(a, merge(b, c)) on every rollup field."""

    __slots__ = ("phases", "counters", "durations", "memory", "meta")

    def __init__(
        self,
        phases: Optional[Dict[str, Dict[str, float]]] = None,
        counters: Optional[Dict[str, int]] = None,
        durations: Optional[Dict[str, Dict[str, float]]] = None,
        memory: Optional[Dict[str, Dict[str, float]]] = None,
        meta: Optional[Dict[str, Any]] = None,
    ):
        self.phases = dict(phases or {})
        self.counters = dict(counters or {})
        self.durations = dict(durations or {})
        self.memory = dict(memory or {})
        self.meta = dict(meta or {})
        self.meta.setdefault("ranks", [])

    @classmethod
    def capture(
        cls,
        counters_before: Optional[Dict[str, int]] = None,
        counter_prefix: str = "",
        duration_prefix: Optional[str] = None,
        rank: Optional[int] = None,
    ) -> "TelemetrySnapshot":
        """Snapshot THIS thread's phase stats plus the process counters
        (delta vs `counters_before` when given, so a fit reports what IT
        moved, not process history), optionally duration digests under
        `duration_prefix`, and — when the srml-watch recorder is installed —
        the memory section (per-phase peak-delta attribution + HBM/host
        watermarks; empty on backends without device memory stats)."""
        ctr = (
            counter_deltas(counters_before, counter_prefix)
            if counters_before is not None
            else counters(counter_prefix)
        )
        dur = (
            duration_digests(duration_prefix)
            if duration_prefix is not None
            else {}
        )
        mem: Dict[str, Dict[str, float]] = {}
        fr = _flight
        if fr is not None:
            try:
                mem = fr.telemetry_memory()
            except Exception:  # noqa: BLE001 - observability never fails fits
                mem = {}
        meta: Dict[str, Any] = {"ranks": [int(rank)] if rank is not None else []}
        return cls(
            phases=phase_stats(), counters=ctr, durations=dur, memory=mem,
            meta=meta,
        )

    def merge(self, other: "TelemetrySnapshot") -> "TelemetrySnapshot":
        phases: Dict[str, Dict[str, float]] = {}
        for src in (self.phases, other.phases):
            for k, v in src.items():
                agg = phases.setdefault(k, {"count": 0, "total_s": 0.0})
                agg["count"] += int(v.get("count", 0))
                agg["total_s"] += float(v.get("total_s", 0.0))
        ctr: Dict[str, int] = dict(self.counters)
        for k, v in other.counters.items():
            ctr[k] = ctr.get(k, 0) + v
        dur: Dict[str, Dict[str, float]] = {}
        for src in (self.durations, other.durations):
            for k, v in src.items():
                agg = dur.get(k)
                if agg is None:
                    dur[k] = dict(v)
                else:
                    agg["count"] += v["count"]
                    agg["sum_s"] += v["sum_s"]
                    agg["min_s"] = min(agg["min_s"], v["min_s"])
                    agg["max_s"] = max(agg["max_s"], v["max_s"])
        # memory watermarks: counts sum, peaks MAX (a watermark across ranks
        # is the worst rank's), deltas sum — still associative+commutative
        mem: Dict[str, Dict[str, float]] = {}
        for src in (self.memory, other.memory):
            for k, v in src.items():
                agg = mem.get(k)
                if agg is None:
                    mem[k] = dict(v)
                else:
                    agg["count"] += v.get("count", 0)
                    agg["peak_bytes"] = max(
                        agg.get("peak_bytes", 0.0), v.get("peak_bytes", 0.0)
                    )
                    agg["sum_delta_bytes"] = agg.get(
                        "sum_delta_bytes", 0.0
                    ) + v.get("sum_delta_bytes", 0.0)
        meta = {
            "ranks": sorted(
                set(self.meta.get("ranks", [])) | set(other.meta.get("ranks", []))
            )
        }
        return TelemetrySnapshot(
            phases=phases, counters=ctr, durations=dur, memory=mem, meta=meta
        )

    def delta(self, since: "TelemetrySnapshot") -> "TelemetrySnapshot":
        """What moved between `since` and this snapshot: counter
        differences (zero-change keys dropped) and count/sum duration
        deltas.  min/max cannot be un-merged, so the window keeps the
        current extremes (documented in docs/observability.md).  The ONE
        delta rule behind every scrape-loop surface
        (ModelRegistry.telemetry(since=), Router.telemetry(since=))."""
        ctr = {
            k: v - since.counters.get(k, 0)
            for k, v in self.counters.items()
            if v != since.counters.get(k, 0)
        }
        dur: Dict[str, Dict[str, float]] = {}
        for k, d in self.durations.items():
            prev = since.durations.get(k)
            if prev is None:
                dur[k] = dict(d)
                continue
            dc = d["count"] - prev["count"]
            if dc > 0:
                dur[k] = {
                    "count": dc,
                    "sum_s": d["sum_s"] - prev["sum_s"],
                    "min_s": d["min_s"],
                    "max_s": d["max_s"],
                }
        return TelemetrySnapshot(counters=ctr, durations=dur)

    def phase_seconds(self, prefix: str = "") -> Dict[str, float]:
        """{phase name: total seconds} — the phase_times() view of a merged
        snapshot (what the driver prints for a live-Spark fit)."""
        return {
            k: float(v.get("total_s", 0.0))
            for k, v in self.phases.items()
            if k.startswith(prefix)
        }

    def to_dict(self) -> Dict[str, Any]:
        return {
            "schema": "srml-scope/v1",
            "phases": self.phases,
            "counters": self.counters,
            "durations": self.durations,
            "memory": self.memory,
            "meta": self.meta,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TelemetrySnapshot":
        return cls(
            phases=d.get("phases"),
            counters=d.get("counters"),
            durations=d.get("durations"),
            memory=d.get("memory"),
            meta=d.get("meta"),
        )

    def __eq__(self, other: Any) -> bool:
        return (
            isinstance(other, TelemetrySnapshot)
            and self.to_dict() == other.to_dict()
        )

    def __repr__(self) -> str:
        return (
            f"TelemetrySnapshot(phases={len(self.phases)}, "
            f"counters={len(self.counters)}, durations={len(self.durations)}, "
            f"ranks={self.meta.get('ranks', [])})"
        )


# -- export surface -----------------------------------------------------------

# Gauge providers: named callables returning {gauge name: float} sampled at
# export time (unlike counters, gauges describe CURRENT state — memory
# watermarks, serving health, cache sizes).  srml-watch registers the
# memory/cache provider; each ModelRegistry registers its health provider;
# sanitize registers lockdep.{locks,edges,violations} when armed (gauges,
# not counters, because the counter path's flight-recorder hook takes the
# watch ring lock — itself lockdep-wrapped when armed).
_gauges_lock = threading.Lock()
_gauge_providers: Dict[str, Callable[[], Dict[str, float]]] = {}


def register_gauges(key: str, fn: Callable[[], Dict[str, float]]) -> None:
    """Register (or replace) gauge provider `key`; its dict is merged into
    export_metrics()['gauges'] at every export."""
    with _gauges_lock:
        _gauge_providers[key] = fn


def unregister_gauges(key: str) -> None:
    with _gauges_lock:
        _gauge_providers.pop(key, None)


def collect_gauges(prefix: str = "") -> Dict[str, float]:
    """Sample every registered gauge provider (best-effort: a provider that
    raises is skipped — export must never fail on a sick subsystem, that is
    exactly when it is needed)."""
    with _gauges_lock:
        providers = list(_gauge_providers.values())
    out: Dict[str, float] = {}
    for fn in providers:
        try:
            sampled = fn()
        except Exception:  # noqa: BLE001 - export over failure
            continue
        for k, v in sampled.items():
            if k.startswith(prefix):
                try:
                    out[k] = float(v)
                except (TypeError, ValueError):
                    continue
    return dict(sorted(out.items()))


def export_metrics(prefix: str = "") -> Dict[str, Any]:
    """One stable JSON document of the process's observability state:
    counters, per-series duration percentile summaries, this thread's
    phase stats, and sampled gauges (memory watermarks, serving health,
    executable-cache size — whatever providers are registered), all
    optionally prefix-filtered.  Round-trippable through json.dumps/loads
    (tests/test_profiling.py)."""
    dur: Dict[str, Dict[str, float]] = {}
    with _durations_lock:
        series = {
            k: list(v) for k, v in _durations.items() if k.startswith(prefix)
        }
    for k, v in series.items():
        dur[k] = _percentile_digest(v)
    return {
        "schema": "srml-scope/v1",
        "counters": counters(prefix),
        "durations": dur,
        "phases": phase_stats(prefix),
        "gauges": collect_gauges(prefix),
    }


def _prom_escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"')


def render_prometheus(metrics: Optional[Dict[str, Any]] = None) -> str:
    """Prometheus text exposition of export_metrics(): counters as
    `srml_counter{name="..."}`, phases as seconds/count pairs, duration
    series as quantile summaries.  Names ride a label (srml counter names
    carry dots, which Prometheus metric names cannot)."""
    m = metrics if metrics is not None else export_metrics()
    lines = ["# TYPE srml_counter counter"]
    for k, v in sorted(m.get("counters", {}).items()):
        lines.append(f'srml_counter{{name="{_prom_escape(k)}"}} {v}')
    lines.append("# TYPE srml_phase_seconds_total counter")
    lines.append("# TYPE srml_phase_count_total counter")
    for k, v in sorted(m.get("phases", {}).items()):
        n = _prom_escape(k)
        lines.append(f'srml_phase_seconds_total{{name="{n}"}} {v["total_s"]}')
        lines.append(f'srml_phase_count_total{{name="{n}"}} {v["count"]}')
    lines.append("# TYPE srml_duration_seconds summary")
    for k, d in sorted(m.get("durations", {}).items()):
        if not d:
            continue
        n = _prom_escape(k)
        for q_label, q_key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            lines.append(
                f'srml_duration_seconds{{name="{n}",quantile="{q_label}"}} '
                f"{d[q_key]}"
            )
        lines.append(
            f'srml_duration_seconds_sum{{name="{n}"}} '
            f"{d['mean'] * d['count']}"
        )
        lines.append(f'srml_duration_seconds_count{{name="{n}"}} {d["count"]}')
    # gauges (srml-watch health plane) split into the families dashboards
    # alert on: memory watermarks, serving health (per server/replica),
    # router capacity (srml-router), and the rest
    gauges = m.get("gauges", {})
    if gauges:
        fams = {
            "srml_memory_bytes": [],
            "srml_health": [],
            "srml_router": [],
            "srml_elastic": [],
            "srml_gauge": [],
        }
        # exchange link pressure gets its own family with a `link` label
        # (ici|dcn) — the dashboard dimension is the physical link class,
        # not the dotted counter name
        link_entries = []
        for k, v in sorted(gauges.items()):
            if k.startswith("exchange.link."):
                link = k[len("exchange.link."):].removesuffix("_bytes")
                link_entries.append((link, v))
            elif k.startswith("mem."):
                fams["srml_memory_bytes"].append((k, v))
            elif k.startswith("health."):
                fams["srml_health"].append((k, v))
            elif k.startswith("router."):
                fams["srml_router"].append((k, v))
            elif k.startswith(("slicepool.", "autoscale.")):
                # srml-elastic capacity plane: pool ledger + policy loop
                fams["srml_elastic"].append((k, v))
            else:
                fams["srml_gauge"].append((k, v))
        if link_entries:
            lines.append("# TYPE srml_exchange_bytes gauge")
            for link, v in link_entries:
                lines.append(
                    f'srml_exchange_bytes{{link="{_prom_escape(link)}"}} {v}'
                )
        for fam, entries in fams.items():
            if not entries:
                continue
            lines.append(f"# TYPE {fam} gauge")
            for k, v in entries:
                lines.append(f'{fam}{{name="{_prom_escape(k)}"}} {v}')
    return "\n".join(lines) + "\n"


# -- xprof capture -------------------------------------------------------------


@contextlib.contextmanager
def maybe_trace(tag: str = "fit") -> Iterator[None]:
    """If SRML_PROFILE=<dir> is set, capture an xprof trace of the enclosed
    region into <dir>/<tag>.  No-op (zero overhead) otherwise."""
    out_dir = os.environ.get(PROFILE_ENV)
    if not out_dir:
        yield
        return
    import jax.profiler

    target = os.path.join(out_dir, tag)
    os.makedirs(target, exist_ok=True)
    with jax.profiler.trace(target):
        yield
    _log.info("xprof trace for %r written to %s", tag, target)


# -- srml-watch bootstrap ------------------------------------------------------
# The flight recorder is ALWAYS on (SRML_WATCH=0 opts out): installed here,
# at the bottom of the module, so watch's own `from . import profiling` sees
# a fully-initialized namespace.  watch.install() sets _flight and registers
# the memory/cache gauge provider.

def _bootstrap_watch() -> None:
    if os.environ.get("SRML_WATCH", "1") == "0":
        return
    try:
        from . import watch

        watch.install()
    except Exception as exc:  # pragma: no cover - never fail the import
        _log.warning("srml-watch flight recorder unavailable: %s", exc)


_bootstrap_watch()
