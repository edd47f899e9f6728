"""Checkout requests per dispatched wave in the window, from the server's
``CheckoutStats`` counters."""


def read(ctx):
    waves = ctx.stats.get("waves", 0)
    return ctx.stats["requests"] / waves if waves else None
