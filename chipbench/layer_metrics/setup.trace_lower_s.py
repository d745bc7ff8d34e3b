"""Wall seconds of set-up (`devices` to `window_start` on the phase clock) in
which some thread of the process traced or lowered: what the persistent cache
does not save.  From the program's compile journal (profiling.compile_events():
jax's own trace, lowering and backend events with their start and end on
time.time(), the phase clock's clock; a thread's outermost event of each kind),
as the union over threads of the `trace` and `lower` intervals.  setup.backend_s,
setup.executables and fit.retraces_per_job load this file for the journal and the
arithmetic.

On the run's DETAIL line under "compile": the wall seconds beside the thread
seconds (per kind, summed over threads: set-up's part of the program's
compile.*_us counters), the number of events in the journal (the program bounds
it: at its cap the seconds are lower bounds), and the eight (name, kind) with
most seconds.

Reads nothing where the program keeps no journal (a parent from before it)."""
from collections import defaultdict

from chipbench import program
from chipbench.trace_reduce import clip, merge, total

FRONT = ("trace", "lower")      # the host's own work; "backend" is the third kind


def journal():
    events = getattr(program.profiling, "compile_events", None)
    return None if events is None else events()


def setup_span(ctx):
    return ctx.clock.at("devices"), ctx.clock.at("window_start")


def wall_seconds(events, t0, t1):
    """(trace or lower, backend and neither) wall seconds inside [t0, t1]: the two
    are disjoint, so they add up to at most t1 - t0."""
    front = merge(clip([(s, e) for kind, _n, s, e, _t in events if kind in FRONT], t0, t1))
    every = merge(clip([(s, e) for _k, _n, s, e, _t in events], t0, t1))
    return total(front), total(every) - total(front)


def by_name_and_kind(events, t0, t1):
    """Per (name, kind): [count, seconds] of the journal's events inside [t0, t1],
    and per kind their sum: thread seconds (the journal holds a thread's outermost
    events of each kind, so nothing in it is counted twice)."""
    by_name, by_kind = defaultdict(lambda: [0, 0.0]), defaultdict(float)
    for kind, name, s, e, _thread in events:
        seconds = min(e, t1) - max(s, t0)
        if seconds > 0:
            by_name[(name, kind)][0] += 1
            by_name[(name, kind)][1] += seconds
            by_kind[kind] += seconds
    return by_name, dict(by_kind)


def read(ctx):
    events = journal()
    if events is None:
        return None
    t0, t1 = setup_span(ctx)
    front, backend = wall_seconds(events, t0, t1)
    if isinstance(ctx.detail, dict):
        by_name, by_kind = by_name_and_kind(events, t0, t1)
        top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:8]
        ctx.detail["compile"] = {
            "wall_s": {"trace_lower": front, "backend": backend},
            "thread_s": by_kind,
            "events": len(events),
            "top": [[name, kind, count, seconds] for (name, kind), (count, seconds) in top],
        }
    return front
