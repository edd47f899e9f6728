"""Bytes copied device->host (the packed, lane-padded gathers,
``CheckoutStats.d2h_bytes``) per row delivered to clients in the window.
A record row is ``n_attrs`` x itemsize bytes: lane and tile padding raise
the ratio; requests that share a version (gathered once) and stragglers
(copied inside their own per-partition batch) lower it."""


def read(ctx):
    rows = ctx.stats.get("rows_served", 0)
    if not rows or "d2h_bytes" not in ctx.stats:
        return None
    return ctx.stats["d2h_bytes"] / rows
