"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What the trace holds on a TPU (seen on a v5e with jax 0.9): one plane per
chip named ``/device:TPU:<n>`` whose line ``XLA Modules`` has one event per
executed jitted program, named ``jit_<function>(<hash>)``, and whose line
``XLA Ops`` has one event per operation inside it; and a ``/host:CPU``
plane whose threads carry the ``TraceAnnotation`` spans the benchmark
opens (``bench.*``).  Device and host events share one clock.

All times come back in seconds.  Only the part of the trace inside the
benchmark's ``bench.window`` span counts.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the trace clock
    dur: float        # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """The raw events the reduction needs."""
    modules: dict      # device plane name -> [Event] (XLA Modules line)
    ops: dict          # device plane name -> [Event] (XLA Ops line)
    spans: list        # [Event] host spans named bench.*


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # device busy, averaged over chips
    module_s: dict                    # module base name -> device seconds
    module_n: dict                    # module base name -> executions
    top_ops: list                     # [[op name, seconds]] largest first
    idle_gaps: list                   # [[host activity, seconds]] longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read the events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: dict = {}
    ops: dict = {}
    spans: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules[plane.name] = [_event(e) for e in line.events]
                elif line.name == OPS_LINE:
                    ops[plane.name] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_event(e) for e in line.events
                             if e.name.startswith("bench."))
    return Trace(modules=modules, ops=ops, spans=spans)


def _event(e) -> Event:
    return Event(name=e.name, start=e.start_ns * 1e-9,
                 dur=e.duration_ns * 1e-9)


def module_base(name: str) -> str:
    """``jit_checkout_wave(6986730289551645697)`` -> ``jit_checkout_wave``."""
    return name.split("(", 1)[0]


def op_base(name: str) -> str:
    """``%checkout_wave.4 = s32[...] custom-call(...)`` -> ``checkout_wave``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(trace: Trace) -> tuple:
    ws = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    w = max(ws, key=lambda s: s.dur)
    return w.start, w.end


def summarize(trace: Trace, extra_spans=(), top: int = 10) -> Summary:
    """Device busy time (the union of op intervals), device time per XLA
    module and per op, and the longest idle gaps labelled by what the
    host was doing: the innermost benchmark span (or ``extra_spans``
    entry, such as a compile) that covers the gap's middle."""
    lo, hi = window_of(trace)
    planes = sorted(trace.ops) or sorted(trace.modules)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy_per_plane = []
    gaps: list = []
    for p in planes:
        evs = trace.ops.get(p) or trace.modules.get(p, [])
        merged = union(clip([(e.start, e.end) for e in evs], lo, hi))
        busy_per_plane.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    module_s: dict = {}
    module_n: dict = {}
    for p in planes:
        for e in trace.modules.get(p, []):
            if lo <= e.start < hi:
                k = module_base(e.name)
                module_s[k] = module_s.get(k, 0.0) + e.dur
                module_n[k] = module_n.get(k, 0) + 1
    op_s: dict = {}
    for p in planes:
        for e in trace.ops.get(p, []):
            if lo <= e.start < hi:
                k = op_base(e.name)
                op_s[k] = op_s.get(k, 0.0) + e.dur
    labels = [s for s in trace.spans if s.name != WINDOW_SPAN]
    labels += list(extra_spans)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    idle = [[_label(labels, (s + e) / 2), e - s] for s, e in longest]
    n = len(planes)
    return Summary(
        window_s=hi - lo,
        busy_s=sum(busy_per_plane) / n,
        module_s={k: v / n for k, v in module_s.items()},
        module_n=module_n,
        top_ops=[[k, v / n] for k, v in
                 sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=idle)


def _label(spans, t: float) -> str:
    inside = [s for s in spans if s.start <= t <= s.end]
    if not inside:
        return "bench.client"
    return min(inside, key=lambda s: s.dur).name
