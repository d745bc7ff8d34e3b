"""Persistent-cache misses during set-up (jax's own monitoring events): 0 on every
run of a cell after its first in a checkout."""


def read(ctx):
    return ctx.cache["misses"]
