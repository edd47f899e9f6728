"""Run one cell of the benchmark once, on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``<config>.<mix>`` as ``BENCHMARK.json`` names it.  The run
builds the configuration's store from ``--seed``, warms up every shape the
window will use, drives the mix's closed-loop clients through
``serve.BatchedCheckoutServer`` for ``--seconds``, then compares the
answers with the plain reference.  The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit, also printed as the last lines of stderr.

On any backend but a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.  JAX's persistent compilation
cache lives in ``JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``bench/.jax_cache``; the journal and traces go to ``bench/.run``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness
    watch = harness.CompileWatch()
    cfg = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"])
    out = harness.run_cell(
        cell, cfg, mix, seed=args.seed, seconds=args.seconds,
        work_dir=BENCH / ".run", trace=bool(args.trace), t_start=T_START,
        watch=watch, emit=lambda s: print(s, flush=True))
    ctx, checks = out["ctx"], out["checks"]
    metrics = {}
    for m in spec.metrics_for(bench, cell["name"], bool(args.trace)):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = out["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": out["peak"]}
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
    result = {"correct": checks.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if out["breakdown"] is not None:
        result["breakdown"] = out["breakdown"]
    result["checks"] = checks.report()
    for line in checks.lines():
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
