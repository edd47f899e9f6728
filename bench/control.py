"""The control: the served path with one guarantee of the configuration
broken, which the comparison has to catch.  Not run by ``run.py``.

* ``exact``: every delivered checkout has one value changed (the last
  attribute of its last row), its shape kept, so only the byte-for-byte
  comparison can catch it: the slip a change to the lane packing or the
  host split could make;
* ``durable``: commits are acknowledged with no journal record and no
  fsync, the step an asynchronous journal would take.

Run it at a cell's own size on the chip, several seeds in one process:

    python3 bench/control.py --workload <cell> --guarantee exact \\
        --seconds 10 --seeds 11 12 13

It prints one line per seed with the numbers compared and whether the run
came out correct (it must not).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def control_server(guarantee: str):
    """A ``BatchedCheckoutServer`` subclass that breaks ``guarantee``."""
    from repro.core.journal import attach_journal
    from repro.serve import BatchedCheckoutServer

    class Control(BatchedCheckoutServer):
        def __init__(self, store, **kw):
            super().__init__(store, **kw)
            if guarantee == "durable":
                attach_journal(store, None)

        def result(self, ticket):
            out = super().result(ticket)
            if guarantee == "exact" and getattr(out, "ndim", 0) == 2 \
                    and len(out):
                out = np.array(out)
                out[-1, -1] += 1
            return out

    if guarantee not in ("exact", "durable"):
        raise ValueError(f"unknown guarantee {guarantee!r}")
    return Control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--guarantee", choices=("exact", "durable"),
                    required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    bench = spec.load_benchmark(ROOT)
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"], ROOT)
    mix = spec.traffic(cell["traffic"])
    watch = harness.CompileWatch()
    for seed in args.seeds:
        out = harness.run_cell(
            cell, cfg, mix, seed=seed, seconds=args.seconds,
            work_dir=BENCH / ".run", t_start=time.perf_counter(),
            watch=watch, server_factory=control_server(args.guarantee),
            emit=lambda s: print(s, flush=True))
        print(json.dumps({"control": args.guarantee, "workload": cell["name"],
                          "seed": seed, "correct": out["checks"].correct,
                          "checks": out["checks"].report()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
