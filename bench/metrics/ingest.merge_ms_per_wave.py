"""Host milliseconds of the merges' union and match inside ``commit_many``'s
staging (a part of ``ingest.stage_ms_per_wave``, not a stage of its own),
per commit wave in the window, from ``CheckoutStats.merge_s``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "merge_s" not in ctx.stats:
        return None
    return ctx.stats["merge_s"] / waves * 1e3
