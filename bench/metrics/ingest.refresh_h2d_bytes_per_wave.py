"""Host->device bytes the post-commit superblock refresh uploaded (the
delta tiles ``segment_append`` takes), per commit wave in the window,
from ``CheckoutStats.refresh_h2d_bytes``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "refresh_h2d_bytes" not in ctx.stats:
        return None
    return ctx.stats["refresh_h2d_bytes"] / waves
