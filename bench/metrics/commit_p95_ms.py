"""95th percentile of commit latency over every commit acknowledged in the
window, from ``submit_commit`` to the acknowledged vid returned by
``result()``; the server acknowledges a commit only after its journal
record is fsynced."""
import numpy as np


def read(ctx):
    if not ctx.writes:
        return None
    return float(np.percentile([t1 - t0 for t0, t1, _ in ctx.writes], 95)) * 1e3
