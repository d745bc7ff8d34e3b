"""Records chipbench/tests/data/small.xplane.pb on the chip (run once, by hand):
three short "job" spans with an idle "between-jobs" span after each, inside a
"window" span, so test_trace_reduce.py knows what the reduction has to find.

    chiprun -- python3 chipbench/tests/record_trace.py chiprun_out/small_trace
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import trace_reduce
    from chipbench.harness import _trace_options

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, 64, lambda _i, a: jnp.tanh(a @ a), x)   # some ms a job

    x = jnp.ones((2048, 2048), jnp.float32)
    work(x).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out, profiler_options=_trace_options())
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("job"):
                work(x).block_until_ready()
            with jax.profiler.TraceAnnotation("between-jobs"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    profile = trace_reduce.load(path)
    print("\n".join(trace_reduce.describe(profile)))
    s = trace_reduce.summarize(profile, 1)
    print({k: s[k] for k in ("window_s", "busy_s", "modules", "idle_gaps")}, {n: len(x) for n, x in s["spans"].items()})
    print("xplane bytes", os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
