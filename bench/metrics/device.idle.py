"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window), averaged over chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_share * 100
