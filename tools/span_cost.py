"""What the always-on instrumentation costs, measured where it runs:

    chiprun -- python3 tools/span_cost.py            (and --chips 4)

Prints one JSON line: microseconds per profiling.span() open+close with no
trace session (nested, and outermost: the one that samples device memory),
the same with the flight recorder detached, microseconds per
watch._device_mem() call, memory samples and memory_stats() calls per warm
fit job of a device-resident frame, and the host time of such a fit with a
trace session on (SRML_TRACE_DIR set) against off.  docs/observability.md
§7 and PERF.md quote its numbers; run it again after adding a span to a hot
path.  On the CPU it runs (no device memory stats there) and proves nothing.
"""
from __future__ import annotations

import json
import os
import statistics
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


def main() -> None:
    os.environ.pop("SRML_TRACE_DIR", None)
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
