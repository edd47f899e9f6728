"""Host milliseconds the delivery spent blocked on the device before
copying each gather (``CheckoutStats.device_wait_s``), per read wave
delivered in the window: the kernel time the pipeline did not hide."""


def read(ctx):
    waves = ctx.stats.get("waves_delivered", 0)
    if not waves or "device_wait_s" not in ctx.stats:
        return None
    return ctx.stats["device_wait_s"] / waves * 1e3
