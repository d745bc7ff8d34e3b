"""Backend events of the program's compile journal that end inside set-up: how
many executables the process builds or loads before its first window job."""
from chipbench.harness import load_reader

_account = load_reader("setup.trace_lower_s")


def read(ctx):
    events = _account.journal()
    if events is None:
        return None
    t0, t1 = _account.setup_span(ctx)
    return sum(1 for kind, _n, s, e, _t in events if kind == "backend" and t0 <= s and e <= t1)
