"""Host milliseconds of the per-partition straggler batches (partition
upload, plan, kernel and copy, all synchronous), per group wave in the
window, from ``CheckoutStats.straggler_s``."""


def read(ctx):
    waves = ctx.stats.get("group_waves", 0)
    if not waves or "straggler_s" not in ctx.stats:
        return None
    return ctx.stats["straggler_s"] / waves * 1e3
