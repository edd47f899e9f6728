"""Host milliseconds pinning the partition groups a wave touches (building
a group's superblock on the host, the LRU evictions that make room, its
first upload), per group wave in the window, from
``CheckoutStats.pin_s``."""


def read(ctx):
    waves = ctx.stats.get("group_waves", 0)
    if not waves or "pin_s" not in ctx.stats:
        return None
    return ctx.stats["pin_s"] / waves * 1e3
