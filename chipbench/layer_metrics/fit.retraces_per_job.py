"""Events of ANY kind in the program's compile journal that start inside the
window (`window_start` to `window_end` on the phase clock), over its jobs: a job
that traced, lowered, loaded or compiled again.  0 is the contract: the warm job
has built every shape the window uses.  What was built again goes on the DETAIL
line under "retraced": per kind and name, its events and their seconds."""
from chipbench.harness import load_reader

_account = load_reader("setup.trace_lower_s")


def read(ctx):
    events = _account.journal()
    if events is None or not ctx.jobs:
        return None
    t0, t1 = ctx.clock.at("window_start"), ctx.clock.at("window_end")
    again = [ev for ev in events if t0 <= ev[2] < t1]
    if again and isinstance(ctx.detail, dict):
        by_name, _ = _account.by_name_and_kind(again, t0, float("inf"))
        worst = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:16]
        ctx.detail["retraced"] = {f"{kind} {name}": [count, seconds] for (name, kind), (count, seconds) in worst}
    return len(again) / len(ctx.jobs)
