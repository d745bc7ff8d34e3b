"""What the always-on instrumentation costs, measured where it runs:

    chiprun -- python3 tools/span_cost.py            (and --chips 4)

Prints one JSON line: microseconds per profiling.span() open+close with no
trace session (nested, and outermost: the one that samples device memory),
the same with the flight recorder detached, microseconds per
watch._device_mem() call, memory samples and memory_stats() calls per warm
fit job of a device-resident frame, and the host time of such a fit with a
trace session on (SRML_TRACE_DIR set) against off; and for the compile
account (profiling.watch_compiles, never off): microseconds per outermost
event (its opening and its close), and the calls jax made to the three
listeners in a cold and in a warm process's first fit of rf_clf_fit's
estimator (50 trees x depth 13 x 128 bins on 400,000 x 3000 rows: two
children, one after the other on one compile cache that starts empty,
BEFORE this process touches the chip).
docs/observability.md §7 and PERF.md quote its numbers; run it again after
adding a span to a hot path.  On the CPU it runs (no device memory stats
there; the children's table is small) and proves nothing.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _us_per_call(fn, n: int) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return 1e6 * (time.perf_counter() - t0) / n


def first_forest_fit() -> None:
    """A child: one process's first fit of rf_clf_fit's estimator, with counting
    listeners beside the program's; prints one JSON line."""
    import jax
    import jax.monitoring
    import numpy as np

    from spark_rapids_ml_tpu import RandomForestClassifier, profiling
    from spark_rapids_ml_tpu.dataframe import DataFrame

    calls = {"scalar": 0, "span": 0, "event": 0}

    def counting(key):
        def listener(*_a, **_k):
            calls[key] += 1
        return listener

    jax.monitoring.register_scalar_listener(counting("scalar"))
    jax.monitoring.register_event_time_span_listener(counting("span"))
    jax.monitoring.register_event_listener(counting("event"))
    on_chip = jax.devices()[0].platform == "tpu"
    rows, cols = (400_000, 3000) if on_chip else (4096, 64)
    X = jax.random.normal(jax.random.PRNGKey(34), (rows, cols), jax.numpy.float32)
    y = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(35), 0.5, (rows,)), np.float64)
    est = RandomForestClassifier(seed=34, num_workers=1, numTrees=50, maxBins=128, maxDepth=13 if on_chip else 4)
    t0 = time.perf_counter()
    model = est.fit(DataFrame.from_device(X, y=y, n_rows=rows))
    fit_s = time.perf_counter() - t0
    counters = model.fit_telemetry().counters
    journal_events = len(profiling.compile_events())
    n = 20000
    event = "/jax/core/compile/jaxpr_trace_duration"

    def one_event():       # as jax reports an outermost one: its opening, then its close
        profiling._on_compile_open(event, 1.0, fun_name="cost.listener")
        profiling._on_compile_span(event, 1.0, 2.0, fun_name="cost.listener")

    us = _us_per_call(one_event, n)
    print(json.dumps({
        "first_fit_s": fit_s, "listener_calls": calls, "journal_events": journal_events,
        "compile": {k: v for k, v in counters.items() if k.startswith("compile.")},
        "import_us": profiling.counter("import.us"), "listener_us_per_outermost_event": us,
    }))


def compile_account() -> dict:
    """The two children's lines: a cold process and a warm one."""
    out = {}
    with tempfile.TemporaryDirectory() as cache_dir:
        env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": cache_dir}
        for name in ("cold", "warm"):
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--first-forest-fit"],
                env=env, capture_output=True, text=True, check=True,
            )
            out[name] = json.loads(done.stdout.strip().splitlines()[-1])
    return out


def main() -> None:
    os.environ.pop("SRML_TRACE_DIR", None)
    if "--first-forest-fit" in sys.argv:
        first_forest_fit()
        return
    account = compile_account()     # children first: a process that has touched jax holds the chip
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu import KMeans, profiling, watch
    from spark_rapids_ml_tpu.dataframe import DataFrame

    devices = jax.local_devices()
    real_device_mem = watch._device_mem
    calls = []

    def counted_device_mem():
        calls.append(1)
        return real_device_mem()

    def one_span():
        with profiling.span("cost.span"):
            pass

    def nested_spans(n: int) -> float:
        with profiling.span("cost.outer"):
            return _us_per_call(one_span, n)

    out = {
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)},
        "compile_account": account,
        "device_mem_us": _us_per_call(real_device_mem, 2000),
        "span_us": {"nested": nested_spans(20000), "outermost": _us_per_call(one_span, 5000)},
    }
    watch.disable()
    out["span_us"]["recorder_off"] = _us_per_call(one_span, 20000)
    rec = watch.enable()
    if real_device_mem() is not None:       # the sampler the recorder would pick, counted
        watch._device_mem = counted_device_mem
        rec.set_memory_sampler(counted_device_mem)

    # a warm fit job of a device-resident frame, as the benchmark's fit cells run it
    n_dev = len(devices)
    X = jax.random.normal(jax.random.PRNGKey(0), (8192 * n_dev, 64), jnp.float32)
    est = KMeans(k=16, maxIter=5, tol=0.0, initMode="random", seed=1, num_workers=n_dev)
    frame = DataFrame.from_device(X, n_rows=X.shape[0])
    est.fit(frame)
    before, spans_before = len(calls), profiling.counter("watch.mem_samples")
    model = est.fit(frame)
    out["per_fit_job"] = {
        "device_mem_calls": len(calls) - before,
        "memory_stats_calls": (len(calls) - before) * n_dev,
        "watch_mem_samples": profiling.counter("watch.mem_samples") - spans_before,
        "spans": sum(int(v["count"]) for v in model.fit_telemetry().phases.values()),
    }

    def fit_ms(n: int = 30) -> float:
        est.fit(frame)
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            est.fit(frame)
            times.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(times)

    off = fit_ms()
    with tempfile.TemporaryDirectory() as trace_dir:
        os.environ["SRML_TRACE_DIR"] = trace_dir
        on = fit_ms()
        del os.environ["SRML_TRACE_DIR"]
    out["fit_ms"] = {"trace_session_off": off, "trace_session_on": on, "off_again": fit_ms()}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
