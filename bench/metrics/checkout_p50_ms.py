"""Median checkout latency over every checkout finished in the window, from
the client's submit to the return of ``result()`` (host clock)."""
import numpy as np


def read(ctx):
    if not ctx.reads:
        return None
    return float(np.percentile([t1 - t0 for t0, t1, _, _ in ctx.reads], 50)) * 1e3
