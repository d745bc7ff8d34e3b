"""Phase clock: data staged to warm call returned (executables loaded or compiled,
one whole job run)."""


def read(ctx):
    return ctx.clock.span("data_staged", "warm_done")
