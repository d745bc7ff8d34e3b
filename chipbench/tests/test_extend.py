"""Adding a cell, a configuration, a traffic mix, a driver with its subject, and a
per-layer metric needs only new files and new entries: a throwaway example of each,
run through the unchanged harness in a copy of the tree.  No file that is there is
edited: the new driver's comparison and limits for an EXISTING configuration come
in files of their own (subjects/<family>.<driver>.py and .json)."""
import json
import os
import shutil

from chipbench import harness
from chipbench.clock import PhaseClock

from .conftest import small

DRIVER = '''"""fit-once: exactly one whole job a window (a throwaway driver)."""
from chipbench.drivers import fit_loop

setup, check, tally = fit_loop.setup, fit_loop.check, fit_loop.tally


def window(ctx, seconds):
    fit_loop.window(ctx, 0.0)


def metrics(ctx):
    return {"fit_once_s": {"value": ctx.t_end - ctx.t_start, "unit": "s"}}
'''

SUBJECT = '''"""KMeans under fit_once: the same table and job as under fit_loop, a check of its own."""
from chipbench.harness import load_part

_fit_loop = load_part("subjects", "kmeans.fit_loop")
stage, job = _fit_loop.stage, _fit_loop.job


def check(ctx, jobs):
    return [{"name": "jobs_beyond_one", "value": len(jobs) - 1}]
'''


def _write(root, rel, text):
    with open(os.path.join(root, rel), "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text))


def _run(bench, name, root, spec):
    result = None
    for trace in (False, True):
        clock = PhaseClock()
        clock.mark("process_start")
        clock.mark("main")
        result = harness.run_cell(bench, harness.find_cell(bench, name), 11, 0.3, trace, clock, rehearsal=spec, root=root)
        assert result["correct"]
    return result


def test_new_files_and_entries_are_enough(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {os.path.join(b, f): os.path.getmtime(os.path.join(b, f)) for b, _d, fs in os.walk(root) for f in fs}
    bench = harness.load_benchmark()

    # a configuration of a family that is there: BASELINE.json's k=20
    config = harness.load_json(harness.ROOT, "chipbench/configs/kmeans-k1000-d3000.json")
    config["name"] = "kmeans-k20-d3000"
    config["estimator"]["params"]["k"] = 20
    _write(root, "chipbench/configs/kmeans-k20-d3000.json", config)
    # a mix: another file for a driver that is there
    mix = harness.load_json(harness.ROOT, "chipbench/traffic/fit-loop.json")
    _write(root, "chipbench/traffic/fit-loop-again.json", {**mix, "name": "fit-loop-again"})
    # a per-layer metric: one small reader
    _write(root, "chipbench/layer_metrics/fit.jobs_in_window.py", "def read(ctx):\n    return len(ctx.jobs)\n")
    bench["configs"].append({"name": "kmeans-k20-d3000", "source": "BASELINE.json", "file": "chipbench/configs/kmeans-k20-d3000.json", "reduced": ["rows"], "why": "throwaway"})
    bench["workloads"].append({"name": "kmeans_k20_fit", "config": "kmeans-k20-d3000", "traffic": "fit-loop-again", "chips": 1, "why": "throwaway"})
    for m in bench["end_to_end"]:
        if m["name"] == "fit_throughput":
            m["workloads"].append("kmeans_k20_fit")
    bench["per_layer"].append({"name": "fit.jobs_in_window", "unit": "count", "better": "higher", "source": "program_counter", "layer": "L4 solvers", "moves": "fit_throughput", "workloads": ["kmeans_k20_fit"]})

    # a driver, its mix, and its subject and limits for the configuration that is there
    _write(root, "chipbench/drivers/fit_once.py", DRIVER)
    _write(root, "chipbench/traffic/fit-once.json", {"name": "fit-once", "driver": "fit_once"})
    _write(root, "chipbench/subjects/kmeans.fit_once.py", SUBJECT)
    _write(root, "chipbench/subjects/kmeans.fit_once.json", {"limits": {"jobs_beyond_one": 0}})
    bench["workloads"].append({"name": "kmeans_fit_once", "config": "kmeans-k1000-d3000", "traffic": "fit-once", "chips": 1, "why": "throwaway"})
    bench["end_to_end"].append({"name": "fit_once_s", "unit": "s", "better": "lower", "bound": 0.05, "source": "host_clock", "workloads": ["kmeans_fit_once"]})
    bench["per_layer"].append({"name": "fit.jobs_in_window", "unit": "count", "better": "higher", "source": "program_counter", "layer": "L4 solvers", "moves": "fit_once_s", "workloads": ["kmeans_fit_once"]})

    spec = small("kmeans_k20_fit")
    result = _run(bench, "kmeans_k20_fit", root, {**spec, "params": {"k": 8, "maxIter": 5}})
    assert result["metrics"]["fit.jobs_in_window"]["value"] >= 1
    assert "fit.iters_per_job" not in result["metrics"], "metrics that do not list the new cell stay out"

    spec.pop("limits")      # the rehearsal's limits are for fit_loop's numbers
    result = _run(bench, "kmeans_fit_once", root, spec)
    assert result["metrics"]["fit.jobs_in_window"]["value"] == 1 and result["attempted"] == 1

    assert all(os.path.getmtime(p) == t for p, t in before.items()), "no file that was there is edited"
