"""fit-loop: back-to-back whole fits through the public API on a device-resident
frame.  A job is est.fit(frame), which ends in the fetch of the model's arrays."""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax


def _run_job(ctx) -> Dict[str, Any]:
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("job"):
        out = ctx.job()
    out["t0"], out["t1"] = t0, time.perf_counter()
    return out


def setup(ctx) -> None:
    ctx.subject.stage(ctx)
    jax.block_until_ready(ctx.X)
    ctx.clock.mark("data_staged")
    ctx.job = ctx.subject.job(ctx)
    ctx.clock.mark("ready")
    with jax.profiler.TraceAnnotation("warm"):
        ctx.warm_job = _run_job(ctx)       # one whole job: every shape the window uses
    ctx.clock.mark("warm_done")


def window(ctx, seconds: float) -> None:
    """Whole jobs until `seconds` have passed; the job under way then is finished."""
    jobs: List[Dict[str, Any]] = []
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            jobs.append(_run_job(ctx))
            with jax.profiler.TraceAnnotation("between-jobs"):
                if time.perf_counter() - t_start >= seconds:
                    break
    ctx.jobs, ctx.t_start, ctx.t_end = jobs, t_start, jobs[-1]["t1"]


def check(ctx) -> List[Dict[str, Any]]:
    return ctx.subject.check(ctx, ctx.jobs)


def tally(ctx) -> Dict[str, int]:
    return {"attempted": len(ctx.jobs), "failed": sum(not j.get("ok", False) for j in ctx.jobs)}


def metrics(ctx) -> Dict[str, Any]:
    """fit_throughput: rows of every job of the window over the window's whole
    time (first job's start to last job's end), over the chips."""
    span = ctx.t_end - ctx.t_start
    return {"fit_throughput": {"value": ctx.rows * len(ctx.jobs) / span / ctx.chips, "unit": "rows/s/chip"}}
