"""Device time of the collective operations over the device's busy time."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2 or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * ctx.trace["collective_s"] / ctx.trace["busy_s"]
