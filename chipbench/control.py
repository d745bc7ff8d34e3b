"""Readings a limit is set from ("How `correct` is decided", steps 3 to 5): for
each seed, one process stages the cell at its own size, drives the timed path for
a short window, and reads every number compared twice: for the program's output
(a sound run) and for the reference computed in the lower precision and put in
the program's place (the control).  Run on the chip, by hand:

    chiprun -- python3 -m chipbench.control --workload kmeans_fit --control fp8 --seeds 11,12,13 --seconds 3
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from . import harness
from .clock import PhaseClock


def readings(bench, cell, seed: int, seconds: float, control: str, rehearsal=None):
    clock = PhaseClock()
    clock.mark("process_start")
    ctx, driver = harness.prepare(bench, cell, seed, seconds, clock, rehearsal)
    driver.setup(ctx)
    driver.window(ctx, seconds)
    t0 = time.perf_counter()
    sound = harness.compare(ctx, driver)
    t1 = time.perf_counter()
    sound_detail = ctx.detail
    ctx.reference_precision = control
    ctrl = harness.compare(ctx, driver)
    return {"seed": seed, "sound": sound, "sound_detail": sound_detail, "control": ctrl,
            "control_detail": ctx.detail, "tally": driver.tally(ctx),
            "check_s": t1 - t0, "control_check_s": time.perf_counter() - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, help="the lower precision: bf16 or fp8")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print("READING " + json.dumps(readings(bench, cell, seed, args.seconds, args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
