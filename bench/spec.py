"""Find a cell's configuration, traffic mix and metrics by name, from files
alone.

``BENCHMARK.json`` names everything; each piece lives in a file of its own
under ``bench/``, which a later change may add without editing any file
that is already there:

* a configuration: the file its ``configs`` entry names;
* a traffic mix: ``bench/traffic/<mix>.json``, the parameters that
  ``driver``'s one closed loop reads (``driver.MIX_KEYS``);
* a metric: ``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns the
  value, or None where the run holds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration named {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    """The mix with every parameter the driver reads; a mix the driver
    cannot run is a ValueError."""
    from .driver import parse_mix
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return parse_mix(json.load(f))


def metrics_for(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell_name`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced.  A metric without a
    ``workloads`` list belongs to every cell."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, bench_dir: Path = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, bench_dir: Path = BENCH) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind the table
    does not hold is an error."""
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["chips"][device_kind]
