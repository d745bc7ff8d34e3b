"""What the program's step spans say on the device trace's clock.  A fit job is
tiled by srml.prepare, srml.ingest, srml.fit and srml.finish, and srml.fit by
its init, solve, wait, fetch and pack (the program's core.py); they reach the
profiler as TraceAnnotations, so trace_reduce.summarize() has their intervals
under `spans`, on the clock of the device's busy intervals.

All functions return None where the trace has no such span: a program from
before the step spans, or a run without a trace."""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from chipbench.trace_reduce import Interval, busy_inside


def idle_ms_per_job(trace: Optional[Dict[str, Any]], names: Iterable[str]) -> Optional[float]:
    """Over the spans called `names`: their length minus the time an operation
    ran on the device inside them, summed, over the number of jobs."""
    if trace is None:
        return None
    jobs = trace["spans"].get("job", [])
    spans = [iv for name in names for iv in trace["spans"].get(name, [])]
    if not jobs or not spans:
        return None
    idle = sum((e - s) - busy_inside(trace["busy_intervals"], s, e) for s, e in spans)
    return 1e3 * idle / len(jobs)


def _inside(spans: List[Interval], s: float, e: float) -> List[Interval]:
    return [iv for iv in spans if s <= iv[0] and iv[1] <= e]


def device_lead_bounds_ms(trace: Optional[Dict[str, Any]]) -> Optional[Tuple[float, float]]:
    """(lower, upper) bound, over the window's jobs, on how far the device's
    clock runs ahead of the host's in this trace (negative: behind).  The
    device idles between two jobs' work, from the one's last operation to
    the other's first.  srml.fit.wait cannot end before that last operation
    does, so its end minus the wait's end is at most the lead (lower: the
    largest over the jobs).  The first operation cannot start before the
    next job's srml.fit.init opens, so its start minus that opening is at
    least the lead (upper: the smallest).

    Which idle stretch lies between which jobs is told by order and not by
    time, which is what the offset spoils: the n - 1 longest idle stretches
    of a window of n whole jobs are the ones between them (the host's
    fetch, pack, finish, prepare and ingest lie in each)."""
    if trace is None:
        return None
    jobs = sorted(trace["spans"].get("job", []))
    waits = [_inside(trace["spans"].get("srml.fit.wait", []), s, e) for s, e in jobs]
    inits = [_inside(trace["spans"].get("srml.fit.init", []), s, e) for s, e in jobs]
    busy = trace["busy_intervals"]
    idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    if len(jobs) < 2 or len(idle) < len(jobs) - 1 or not all(waits) or not all(inits):
        return None
    between = sorted(sorted(idle, key=lambda g: g[1] - g[0])[-(len(jobs) - 1):])
    lower = max(g[0] - w[-1][1] for g, w in zip(between, waits))
    upper = min(g[1] - i[0][0] for g, i in zip(between, inits[1:]))
    return 1e3 * lower, 1e3 * upper
