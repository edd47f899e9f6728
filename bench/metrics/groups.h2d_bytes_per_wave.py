"""Host->device bytes read waves caused (group superblock uploads and
straggler partition uploads, ``CheckoutStats.h2d_bytes``), per group wave
in the window."""


def read(ctx):
    waves = ctx.stats.get("group_waves", 0)
    if not waves or "h2d_bytes" not in ctx.stats:
        return None
    return ctx.stats["h2d_bytes"] / waves
