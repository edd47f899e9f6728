"""Merges (commits naming two or more parents) landed per ingest wave in
the window, from ``CheckoutStats.merges_ingested``; nothing where the
program counts no merges or no commit wave landed."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "merges_ingested" not in ctx.stats:
        return None
    return ctx.stats["merges_ingested"] / waves
