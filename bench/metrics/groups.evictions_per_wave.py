"""Group superblocks the budget evicted per group wave in the window, from
``CheckoutStats``."""


def read(ctx):
    waves = ctx.stats.get("group_waves", 0)
    return ctx.stats["group_evictions"] / waves if waves else None
