"""From the profiler's xplane to numbers: device busy and idle time, time per
operation (self time, so a loop does not count its body twice), time per XLA
module, and the longest idle gaps, each named by what the host was doing.

All times are seconds.  The traced window is the host's "window" annotation
(drivers put it around the measured loop); without one it is the whole trace."""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


_HLO = re.compile(r"^%?(?P<name>[^ =]+) = (?P<shape>\(?[a-z0-9]+\[[^\]]*\])?.*?\)? (?P<op>[a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An HLO instruction's text -> 'name op shape' (the trace names a device
    operation by its whole instruction, operands and layouts included)."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    shape = (m.group("shape") or "").lstrip("(")
    return f"{m.group('name')} {m.group('op')} {shape}".strip()[:80]


def _events(line, shorten: bool = False) -> List[Tuple[str, float, float]]:
    return [
        (short_name(e.name) if shorten else e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
    ]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_inside(merged: List[Interval], t0: float, t1: float) -> float:
    return total(clip(merged, t0, t1))


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per name, the time of its events minus the events nested inside them."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List[Any]] = []          # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out[name] += own

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return dict(out)


def _host_label(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost host span of the window's thread that covers time t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return (best[0] if best else "unattributed")[:64]


def summarize(profile, chips: int) -> Dict[str, Any]:
    planes = list(profile.planes)
    device_planes = sorted((p for p in planes if DEVICE_PLANE.match(p.name)), key=lambda p: p.name)[:chips]
    if not device_planes:
        raise ValueError(f"no device plane among {[p.name for p in planes]}")

    # the host thread that carries the "window" annotation
    window, host_spans = None, []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            ev = _events(line)
            w = [x for x in ev if x[0] == "window"]
            if w:
                window, host_spans = (w[0][1], w[0][2]), ev
                break
        if window:
            break

    per_device = []
    for p in device_planes:
        lines = {l.name: l for l in p.lines}
        ops = _events(lines[OPS_LINE], shorten=True) if OPS_LINE in lines else []
        mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        per_device.append((ops, mods))
    if window is None:
        every = [x for ops, _ in per_device for x in ops]
        window = (min(x[1] for x in every), max(x[2] for x in every))
    t0, t1 = window

    n = len(per_device)
    busy, op_time, mod_time = 0.0, defaultdict(float), defaultdict(float)
    merged0: List[Interval] = []
    for i, (ops, mods) in enumerate(per_device):
        inside = [(nm, max(s, t0), min(e, t1)) for nm, s, e in ops if e > t0 and s < t1]
        merged = merge((s, e) for _, s, e in inside)
        if i == 0:
            merged0 = merged
        busy += total(merged) / n
        for nm, sec in self_times(inside).items():
            op_time[nm] += sec / n
        for nm, s, e in mods:
            if e > t0 and s < t1:
                mod_time[nm] += (min(e, t1) - max(s, t0)) / n

    gaps, prev = [], t0
    for s, e in merged0 + [(t1, t1)]:
        if s - prev > 1e-6:      # back-to-back operations leave nanoseconds between them
            gaps.append((_host_label(host_spans, 0.5 * (prev + s)), s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])

    spans: Dict[str, List[Interval]] = defaultdict(list)
    for nm, s, e in host_spans:
        if s >= t0 and e <= t1 + 1e-6:
            spans[nm].append((s, e))
    collective = sum(sec for nm, sec in op_time.items() if COLLECTIVE.match(nm))
    return {
        "window_s": t1 - t0,
        "busy_s": busy,
        "device_ops": [[nm, sec] for nm, sec in sorted(op_time.items(), key=lambda kv: -kv[1])],
        "modules": dict(mod_time),
        "collective_s": collective,
        "idle_gaps": [[nm, sec] for nm, sec in gaps],
        "busy_intervals": merged0,
        "spans": dict(spans),
        "planes": [p.name for p in planes],
    }


def idle_share(trace: Dict[str, Any]) -> float:
    """1 - busy / window of a summary, in percent."""
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def module_seconds(trace: Dict[str, Any], pattern: str) -> float:
    """Device time, per chip, of the XLA modules whose name holds `pattern`."""
    return sum(sec for name, sec in trace["modules"].items() if pattern in name)


def describe(profile) -> List[str]:
    """Planes, lines and a few event names: what to look at by hand first."""
    out = []
    for p in profile.planes:
        out.append(f"PLANE {p.name}")
        for line in p.lines:
            ev = list(line.events)
            names = sorted({e.name for e in ev[:2000]})[:12]
            out.append(f"  LINE {line.name!r} events={len(ev)} e.g. {names}")
    return out
