"""Batched checkout: K-launch per-version loop vs the fused single-launch
engine, across wave sizes K ∈ {1, 4, 16, 64}.

Two tiers per K:
  * kernel tier — K × ``checkout_gather`` gathers vs ONE ``checkout_batched``
    gather (interpret mode off-TPU; on TPU the gap is the K-1 saved
    pipeline spin-ups plus the fused DMA stream);
  * host tier — K separate ``data[rl]`` takes vs one take over the
    concatenated rlists (the numpy fallback the serve layer uses off-device).
    Expect ~parity here: numpy pays no per-launch overhead, so fusing buys
    nothing on host — which is precisely why the kernel tier is where the
    batched engine earns its keep.

Emits CSV lines (benchmarks/run.py convention) and writes
``BENCH_batched_checkout.json`` next to the repo root for the perf
trajectory.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core.checkout import _fused_host_gather, checkout_versions_loop
from repro.core.graph import BipartiteGraph
from repro.kernels import ops

from .common import emit, timeit

KS = (1, 4, 16, 64)
R, D = 4096, 128
ROWS_PER_VERSION = 256
SEED = 0


def _make_workload(rng, k):
    """k rlists, half dense runs (post-LYRESPLIT) / half scattered."""
    rls = []
    for i in range(k):
        if i % 2 == 0:
            s = int(rng.integers(0, R - ROWS_PER_VERSION))
            rls.append(np.arange(s, s + ROWS_PER_VERSION, dtype=np.int64))
        else:
            rls.append(np.sort(rng.choice(
                R, ROWS_PER_VERSION, replace=False)).astype(np.int64))
    return rls


def _per_version_kernel(data, rls):
    return [np.asarray(ops.checkout_gather(data, rl)) for rl in rls]


def _fused_kernel(data, rls):
    outs, _ = ops.checkout_batched(data, rls)
    return outs


def main() -> None:
    rng = np.random.default_rng(SEED)
    data = rng.integers(0, 1 << 20, (R, D)).astype(np.int32)
    results = []
    for k in KS:
        rls = _make_workload(rng, k)
        graph = BipartiteGraph.from_rlists(rls, n_records=R)

        # warm both jit caches so compile time stays out of the measurement
        _per_version_kernel(data, rls)
        _fused_kernel(data, rls)

        t_loop_k, out_loop = timeit(_per_version_kernel, data, rls, repeat=5)
        t_fused_k, out_fused = timeit(_fused_kernel, data, rls, repeat=5)
        for a, b in zip(out_loop, out_fused):
            np.testing.assert_array_equal(a, np.asarray(b))

        t_loop_h, _ = timeit(checkout_versions_loop, graph, data,
                             list(range(k)), repeat=5)
        t_fused_h, _ = timeit(_fused_host_gather, data, rls, repeat=5)

        row = {"k": k, "rows": int(sum(len(r) for r in rls)),
               "kernel_loop_s": t_loop_k, "kernel_fused_s": t_fused_k,
               "kernel_speedup": t_loop_k / max(t_fused_k, 1e-12),
               "host_loop_s": t_loop_h, "host_fused_s": t_fused_h,
               "host_speedup": t_loop_h / max(t_fused_h, 1e-12)}
        results.append(row)
        emit(f"batched_checkout_k{k}_kernel", t_fused_k * 1e6,
             f"loop_us={t_loop_k * 1e6:.1f} speedup={row['kernel_speedup']:.2f}")
        emit(f"batched_checkout_k{k}_host", t_fused_h * 1e6,
             f"loop_us={t_loop_h * 1e6:.1f} speedup={row['host_speedup']:.2f}")

    out_path = pathlib.Path(__file__).resolve().parent.parent / \
        "BENCH_batched_checkout.json"
    out_path.write_text(json.dumps(
        {"config": {"R": R, "D": D, "rows_per_version": ROWS_PER_VERSION},
         "results": results}, indent=2))
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
