"""Host milliseconds of the post-commit superblock refresh (touched
segment rebuild, delta upload, ``segment_append`` trace, compile and
launch), per commit wave in the window, from ``CheckoutStats.refresh_s``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "refresh_s" not in ctx.stats:
        return None
    return ctx.stats["refresh_s"] / waves * 1e3
