"""Records chipbench/tests/data/steps.xplane.pb on the chip (run once, by hand):
a few whole public KMeans fits at a test's size, through the harness's rehearsal
hook, the fit_loop driver and the kmeans subject, so that the trace carries the
driver's "window" and "job" spans, the program's step spans inside each job
(srml.prepare, srml.ingest, srml.fit with its init, solve, wait, fetch and pack,
srml.finish) and the device's operations.  test_step_spans.py knows what the
readers have to find in it.

    chiprun -- python3 chipbench/tests/record_step_trace.py chiprun_out/step_trace
"""
import glob
import os
import shutil
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# conftest.small("kmeans_fit"), not imported: conftest pins the CPU backend
SMALL = {
    "data": {"rows_per_chip": 4096, "cols": 64, "k_true": 16, "ridges": 2, "ridge_share": 0.2, "ridge_scale": 2.0},
    "params": {"k": 16, "maxIter": 5},
    "config": {"expected_iters": 5},
    "limits": {"fixed_point_gap_p75": 0.5, "fixed_point_gap_p90": 0.5, "fixed_point_gap_p95": 0.5, "fixed_point_gap_worst": 0.5},
}


def main(out: str) -> None:
    from chipbench import harness, step_spans, trace_reduce
    from chipbench.clock import PhaseClock

    os.makedirs(out, exist_ok=True)
    os.environ["CHIPBENCH_OUT"] = os.path.join(out, "run")
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, "kmeans_fit")
    clock = PhaseClock()
    clock.mark("process_start")
    clock.mark("main")
    result = harness.run_cell(bench, cell, 24, 0.01, True, clock, rehearsal=SMALL)
    # a rehearsal's trace is left where it was written, unread
    path = glob.glob(os.path.join(harness.out_dir("kmeans_fit"), "trace", "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out, "steps.xplane.pb"))
    summary = trace_reduce.summarize(trace_reduce.load(path), 1)
    ctx = SimpleNamespace(trace=summary, detail={})
    print(result["device"], result["attempted"], {n: len(x) for n, x in summary["spans"].items()})
    print("host_ms_per_job", harness.load_reader("fit.host_ms_per_job").read(ctx))
    for step in ("api", "ingest", "launch", "result"):
        print(step, harness.load_reader(f"fit.{step}_idle_ms_per_job").read(ctx))
    print("device lead bounds ms", step_spans.device_lead_bounds_ms(summary))
    print("idle_gaps", summary["idle_gaps"][:12])
    print("xplane bytes", os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
