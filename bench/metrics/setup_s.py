"""Process start to window start: runtime start-up, data generation,
LyreSplit, store and superblock build and upload, and the warm-up that
compiles every shape the window uses (host clock)."""


def read(ctx):
    return ctx.setup_s
