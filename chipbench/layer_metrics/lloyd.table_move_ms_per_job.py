"""Device time, per chip and per job, of the window's operations that only move
the table: a `pad`, `copy` or `convert` whose result has the configuration's
`cols` columns and at least `rows_per_chip` rows (self time, from the trace
summary's `device_ops` names: "<name> <op> <dtype>[<rows>,<cols>]").  A fusion
of that shape does work on the rows and is not counted."""
import re

MOVES = ("pad", "copy", "convert")
SHAPE = re.compile(r"^[a-z0-9]+\[(\d+),(\d+)\]$")


def moves_table(name: str, rows: int, cols: int) -> bool:
    parts = name.split(" ")
    if len(parts) != 3 or parts[1] not in MOVES:
        return False
    m = SHAPE.match(parts[2])
    return bool(m) and int(m.group(1)) >= rows and int(m.group(2)) == cols


def read(ctx):
    if ctx.trace is None or not getattr(ctx, "jobs", None):
        return None
    d = ctx.config["data"]
    moved = sum(sec for name, sec in ctx.trace["device_ops"] if moves_table(name, d["rows_per_chip"], d["cols"]))
    return 1e3 * moved / len(ctx.jobs)
