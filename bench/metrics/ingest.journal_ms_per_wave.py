"""Host milliseconds of each commit wave's ``commit.batch`` journal append,
encode to fsync, per commit wave in the window, from
``CheckoutStats.journal_s``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "journal_s" not in ctx.stats:
        return None
    return ctx.stats["journal_s"] / waves * 1e3
