"""Seconds the program spent importing itself through its public names (its
counter import.us): inside set-up, before anything of it compiles."""
from chipbench import program


def read(ctx):
    us = program.counters().get("import.us")
    return None if us is None else 1e-6 * us
