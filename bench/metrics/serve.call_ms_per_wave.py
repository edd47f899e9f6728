"""Host milliseconds the clients spent inside calls into the server
(submit, submit_commit, flush, deliver, result), per read wave delivered in
the window: the serve layer's share of the host thread."""


def read(ctx):
    waves = ctx.stats.get("waves_delivered", 0)
    return ctx.call_s / waves * 1e3 if waves else None
