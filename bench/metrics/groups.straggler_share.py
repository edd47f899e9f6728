"""Share of checkout requests in the window that the partition-group layer
served per partition (stragglers), from ``CheckoutStats``."""


def read(ctx):
    if not ctx.stats.get("group_waves") or not ctx.stats.get("requests"):
        return None
    return ctx.stats["straggler_requests"] / ctx.stats["requests"] * 100
