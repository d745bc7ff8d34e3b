"""The harness: finds a cell in BENCHMARK.json, its configuration and traffic files
by name, the driver the traffic file names, the subject of that driver for the
configuration's family (subjects/<family>.<driver>.py with its limits beside it
in .json), and every per-layer metric that lists the cell; runs set-up, the
measured window and the check; prints the phase clock, each number compared
beside its limit, and the result line.

A later PR adds cells, configurations, mixes, drivers, subjects and per-layer
metrics as new files and new entries of BENCHMARK.json: nothing here names one,
and none needs an edit to a file that is there."""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from .clock import PhaseClock, process_start_epoch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# setup_s runs from here to the window's start: everything the program does in a
# fresh process (its imports, staging, warm-up).  What comes before is the
# interpreter, `import jax` and the runtime's claim on the chip, which the set-up
# study (PERF.md) found to swing by seconds from run to run on one machine.
SETUP_FROM = "devices"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for: no result is printed."""


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, rel: str) -> Dict[str, Any]:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_files(bench: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, str]:
    """Relative paths of everything a cell resolves to, by name."""
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "config": config["file"],
        "traffic": os.path.join("chipbench", "traffic", cell["traffic"] + ".json"),
    }


def metrics_for(bench: Dict[str, Any], cell: Dict[str, Any], kind: str) -> List[Dict[str, Any]]:
    """The end_to_end or per_layer entries a cell reports: those that list it, or,
    without a `workloads` key, every cell (per-layer: every cell reporting `moves`)."""
    e2e = [
        m for m in bench["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if cell["name"] in m.get("workloads", [cell["name"]]) and m["moves"] in names
    ]


def load_part(kind: str, name: str, root: str = ROOT):
    """chipbench/<kind>/<name>.py of the checkout at `root`, by path: a driver,
    a subject or a per-layer reader is found by its file's name, dots and all."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: str = ROOT):
    return load_part("layer_metrics", name, root)


def out_dir(workload: str) -> str:
    """Traces and full records: never inside the checkout (CHIPBENCH_OUT, else TMPDIR)."""
    base = os.environ.get("CHIPBENCH_OUT") or os.path.join(tempfile.gettempdir(), "chipbench_out")
    path = os.path.join(base, workload)
    os.makedirs(path, exist_ok=True)
    return path


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # TraceAnnotations only: no Python call events
    opts.host_tracer_level = 2
    return opts


def prepare(
    bench: Dict[str, Any],
    cell: Dict[str, Any],
    seed: int,
    seconds: float,
    clock: PhaseClock,
    rehearsal: Optional[Dict[str, Any]] = None,
    root: str = ROOT,
):
    """Resolve a cell to its files, claim the chip, import its driver and subject;
    returns (ctx, driver).  `rehearsal` (tests only; no command-line form) shrinks
    the configuration's sizes and lifts the need for a chip."""
    files = cell_files(bench, cell)
    config = load_json(root, files["config"])
    mix = load_json(root, files["traffic"])
    if rehearsal:
        config["data"].update(rehearsal.get("data", {}))
        config["estimator"]["params"].update(rehearsal.get("params", {}))
        config.update(rehearsal.get("config", {}))

    import jax

    clock.mark("jax_imported")
    devices = jax.devices()      # the runtime's claim on the chip: the machine's time, not the program's
    clock.mark("devices")
    chips = int(cell["chips"])
    if not rehearsal and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell {cell['name']} needs {chips} TPU chip(s); jax sees {len(devices)} {devices[0].platform} device(s)")

    from .cachewatch import CacheWatch
    from .peaks import peaks_for
    from .references.common import seed_words

    watch = CacheWatch()
    pair = config["family"] + "." + mix["driver"]
    driver = load_part("drivers", mix["driver"], root)
    subject = load_part("subjects", pair, root)     # imports the program
    check = load_json(root, os.path.join("chipbench", "subjects", pair + ".json"))
    # limits that hold in one cell only (its size decides them) lie beside the pair's
    cell_limits = os.path.join("chipbench", "subjects", f"{pair}.{cell['name']}.json")
    if os.path.isfile(os.path.join(root, cell_limits)):
        check["limits"].update(load_json(root, cell_limits)["limits"])
    if rehearsal:
        check["limits"].update(rehearsal.get("limits", {}))
        check.update(rehearsal.get("check", {}))
    clock.mark("imports_done")
    ctx = SimpleNamespace(
        cell=cell, config=config, traffic=mix, seed=int(seed), words=seed_words(seed),
        seconds=float(seconds), chips=chips, clock=clock, subject=subject, jitted={},
        reference_precision="highest", trace=None, detail=None,
        peaks=None if rehearsal else peaks_for(devices[0].device_kind),
        check=check, limits=check["limits"], devices=devices, watch=watch,
    )
    return ctx, driver


def compare(ctx, driver) -> List[Dict[str, Any]]:
    """The driver's check: each number that this cell holds, beside its limit."""
    produced = driver.check(ctx)
    unread = set(ctx.limits) - {c["name"] for c in produced}
    if unread:
        raise KeyError(f"limits with no number to hold: {sorted(unread)}")
    comparisons = [c for c in produced if c["name"] in ctx.limits]
    for c in comparisons:
        c["limit"] = ctx.limits[c["name"]]
        c["ok"] = bool(c["value"] <= c["limit"])
    return comparisons


def run_cell(
    bench: Dict[str, Any],
    cell: Dict[str, Any],
    seed: int,
    seconds: float,
    trace: bool,
    clock: PhaseClock,
    rehearsal: Optional[Dict[str, Any]] = None,
    root: str = ROOT,
) -> Dict[str, Any]:
    """One run of one cell.  A rehearsal's result is returned to the caller and
    never printed as a result line."""
    import jax

    from . import program
    from .cachewatch import cache_files

    ctx, driver = prepare(bench, cell, seed, seconds, clock, rehearsal, root)
    devices, chips, watch = ctx.devices, ctx.chips, ctx.watch
    cache_dir = program.ensure_compile_cache()    # the program's one rule; nothing set here
    files_before = cache_files(cache_dir)
    counters_start = program.counters()

    driver.setup(ctx)
    setup_cache = watch.snapshot()
    files_after = cache_files(cache_dir)
    counters_setup = program.counters()
    ctx.cache = {
        **setup_cache,
        "dir": cache_dir,
        "files_before": len(files_before),
        "files_added": sorted(set(files_after) - set(files_before)),
        "precompile": {
            k: counters_setup.get(k, 0) - counters_start.get(k, 0)
            for k in ("precompile.compile", "precompile.aot_hit", "precompile.aot_miss", "precompile.fallback")
        },
    }

    out = out_dir(cell["name"])
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    clock.mark("window_start")
    try:
        driver.window(ctx, seconds)
    finally:
        clock.mark("window_end")
        if trace:
            jax.profiler.stop_trace()
    ctx.window_cache_misses = watch.snapshot()["misses"] - setup_cache["misses"]
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)

    comparisons = compare(ctx, driver)
    clock.mark("check_end")
    correct = all(c["ok"] for c in comparisons)

    if trace and not rehearsal:
        from . import trace_reduce

        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        ctx.trace = trace_reduce.summarize(trace_reduce.load(paths[0]), chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    wanted = metrics_for(bench, cell, "per_layer" if trace else "end_to_end")
    since = clock.since_start()
    # a configuration may report a quantity under a name of its own (`report_as`),
    # so that BENCHMARK.json can bound it apart from other configurations' cells
    alias = ctx.config.get("report_as", {})
    quantity = {v: k for k, v in alias.items()}
    if trace:
        metrics = {}
        for m in wanted:
            value = load_reader(quantity.get(m["name"], m["name"]), root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        produced = {alias.get(k, k): v for k, v in driver.metrics(ctx).items()}
        produced["setup_s"] = {"value": since["window_start"] - since[SETUP_FROM], "unit": "s"}
        metrics = {m["name"]: produced[m["name"]] for m in wanted}

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": chips if rehearsal else len(devices),
        "memory_peak_bytes": int(memory_peak),
    }
    result = {"correct": bool(correct), **driver.tally(ctx), "metrics": metrics, "device": device}
    if trace and ctx.trace is not None:
        device["busy_s"], device["window_s"] = ctx.trace["busy_s"], ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"][:10],
            "idle_gaps": ctx.trace["idle_gaps"][:10],
        }
    record = {
        "workload": cell["name"], "seed": int(seed), "seconds": seconds, "trace": bool(trace),
        "phases_s": since, "setup_from": SETUP_FROM, "cache": ctx.cache,
        "window_cache_misses": ctx.window_cache_misses,
        "comparisons": comparisons, "result": result, "detail": ctx.detail,
    }
    print("PHASES " + json.dumps({"phases_s": since, "setup_from": SETUP_FROM, "cache": {k: v for k, v in ctx.cache.items() if k != "files_added"}, "cache_files_added": ctx.cache["files_added"][:40], "window_cache_misses": ctx.window_cache_misses}))
    for c in comparisons:
        print("COMPARED " + json.dumps(c))
    if ctx.detail:
        print("DETAIL " + json.dumps(ctx.detail))
    with open(os.path.join(out, f"record-seed{seed}-trace{int(trace)}.json"), "w") as f:
        json.dump(record, f)
    return result


def main(argv=None, t_main: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    clock = PhaseClock()
    clock.mark("process_start", process_start_epoch())
    clock.mark("main", t_main if t_main is not None else time.time())
    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    try:
        result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace), clock)
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0
