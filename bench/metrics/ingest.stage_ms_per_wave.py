"""Host milliseconds of ``commit_many``'s staging (delta extraction, CSR
and data concatenation, partition rebuilds, up to the journal append), per
commit wave in the window, from ``CheckoutStats.ingest_stage_s``."""


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if not waves or "ingest_stage_s" not in ctx.stats:
        return None
    return ctx.stats["ingest_stage_s"] / waves * 1e3
