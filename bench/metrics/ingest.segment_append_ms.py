"""Device milliseconds of the ``jit_segment_append`` programs (superblocks
extended in place after a commit) in the traced window, per ingest wave."""

MODULE = "jit_segment_append"


def read(ctx):
    waves = ctx.stats.get("commit_waves", 0)
    if ctx.trace is None or not waves or MODULE not in ctx.trace.module_s:
        return None
    return ctx.trace.module_s[MODULE] / waves * 1e3
