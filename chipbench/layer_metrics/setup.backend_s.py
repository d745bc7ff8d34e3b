"""Wall seconds of set-up in which some thread of the process was in a backend
event of the program's compile journal (on a persistent-cache hit the retrieval
and load of the executable, on a miss XLA's compile) and none traced or lowered:
with setup.trace_lower_s it adds up to at most the set-up's time."""
from chipbench.harness import load_reader

_account = load_reader("setup.trace_lower_s")


def read(ctx):
    events = _account.journal()
    if events is None:
        return None
    return _account.wall_seconds(events, *_account.setup_span(ctx))[1]
