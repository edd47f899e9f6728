"""Commits landed per ingest wave in the window, from ``CheckoutStats``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    return ctx.stats["commits_ingested"] / waves if waves else None
