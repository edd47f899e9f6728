"""Host milliseconds of the device->host copies of the packed gathers and
their per-version split (``CheckoutStats.d2h_s``), per read wave delivered
in the window."""


def read(ctx):
    waves = ctx.stats.get("waves_delivered", 0)
    if not waves or "d2h_s" not in ctx.stats:
        return None
    return ctx.stats["d2h_s"] / waves * 1e3
