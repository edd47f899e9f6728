"""Rows of every checkout delivered to clients in the window, over the
window's seconds (host clock)."""


def read(ctx):
    if not ctx.reads:
        return None
    return sum(rows for _, _, _, rows in ctx.reads) / ctx.seconds
