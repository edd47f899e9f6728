"""Host milliseconds spent planning gathers (``plan_wave_cached``: the memo
lookup, and ``plan_wave`` on a miss), per read wave dispatched in the
window, from ``CheckoutStats.plan_s``."""


def read(ctx):
    waves = ctx.stats.get("waves", 0)
    if not waves or "plan_s" not in ctx.stats:
        return None
    return ctx.stats["plan_s"] / waves * 1e3
