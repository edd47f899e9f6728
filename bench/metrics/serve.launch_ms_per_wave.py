"""Host milliseconds inside the jitted gather calls (trace, lower and
compile of a wave shape not seen before, then the enqueue), per read wave
dispatched in the window, from ``CheckoutStats.launch_s``."""


def read(ctx):
    waves = ctx.stats.get("waves", 0)
    if not waves or "launch_s" not in ctx.stats:
        return None
    return ctx.stats["launch_s"] / waves * 1e3
