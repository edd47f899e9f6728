import os

# Tests must see the real host device count (the dry-run fakes 512 devices in
# its own process only).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def capped_launches(monkeypatch):
    """``run(kernel, call, plan_bytes)`` calls ``call()`` with at most
    ``plan_bytes`` of prefetched plan per launch (``PLAN_SMEM_BYTES`` is read
    at trace time, so the jitted ``kernel``'s cache is cleared around it) and
    returns (output, pallas_calls traced)."""
    from repro.kernels import plan_launch

    def run(kernel, call, plan_bytes):
        calls = []
        real = plan_launch.pl.pallas_call

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        with monkeypatch.context() as m:
            m.setattr(plan_launch, "PLAN_SMEM_BYTES", plan_bytes)
            m.setattr(plan_launch.pl, "pallas_call", counting)
            kernel.clear_cache()
            try:
                out = call()
            finally:
                kernel.clear_cache()          # drop the capped trace
        return out, len(calls)

    return run


def canon_rows(x):
    """Row-set canonical form for set-equality of record tables."""
    x = np.ascontiguousarray(x)
    return x[np.lexsort(x.T[::-1])]
