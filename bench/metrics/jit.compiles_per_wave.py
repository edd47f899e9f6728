"""Backend compiles (``jax.monitoring`` compile events, persistent cache
off) in the window, per wave dispatched there, reads and commits alike.
The program compiles its gather for every wave of a tile count it has not
run, and its append for every write wave; set-up warms a fixed stream, so
this counts what the window's own traffic makes the program compile."""


def read(ctx):
    waves = ctx.stats.get("waves", 0) + ctx.stats.get("commit_waves", 0)
    return ctx.compiles / waves if waves else None
