"""Label the device's idle gaps in a traced run by the program's own stages.

    python3 bench/program_spans.py <run.xplane.pb | directory holding one>

A ``--trace 1`` run leaves its trace under ``bench/.run/trace``.  This
loads the benchmark's events from it (``bench/trace.py``) and every host
event the program names in ``repro.obs.SPAN_NAMES`` (``serve.flush``,
``checkout.launch``, ``ingest.stage``, ...), then reduces it with
``trace.summarize(extra_spans=...)``: each of the longest idle gaps is
labelled by the innermost span covering its middle, a program stage where
the host was inside a call into the server, the benchmark's own span
(``bench.*``, or ``bench.client`` outside every call) where it was not.

Prints one JSON object: ``window_s``, ``busy_s``, the device's
``modules`` (executions per XLA module) and ``device_ops``, ``idle_gaps``
(each ``[program label, seconds, benchmark label]``, longest first), and
per program span the seconds (``span_s``) and count (``span_n``) that
start inside the window.  A program without ``repro.obs`` has no program spans:
every gap then keeps its benchmark label.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def span_names() -> tuple:
    """The program's span names, or () for a program that has none."""
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        from repro.obs import SPAN_NAMES
    except ImportError:
        return ()
    return tuple(SPAN_NAMES)


def load_program_spans(path: str, names) -> list:
    """The host events of ``path`` whose name is one of ``names``."""
    from jax.profiler import ProfileData

    from bench import trace as tr
    names = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(tr.Event(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                           for e in line.events if e.name in names)
    return out


def relabel(trace, program_spans, top: int = 10) -> dict:
    """The longest idle gaps of ``trace``, each labelled by the innermost
    program span and by the innermost benchmark span over its middle."""
    from bench import trace as tr
    plain = tr.summarize(trace, top=top)
    ours = tr.summarize(trace, extra_spans=program_spans, top=top)
    lo, hi = tr.window_of(trace)
    span_s: dict = {}
    span_n: dict = {}
    for e in program_spans:
        if lo <= e.start < hi:
            span_s[e.name] = span_s.get(e.name, 0.0) + e.dur
            span_n[e.name] = span_n.get(e.name, 0) + 1
    return {"window_s": ours.window_s, "busy_s": ours.busy_s,
            "modules": ours.module_n, "device_ops": ours.top_ops,
            "idle_gaps": [[label, dur, bench_label] for (label, dur),
                          (bench_label, _) in zip(ours.idle_gaps,
                                                  plain.idle_gaps)],
            "span_s": span_s, "span_n": span_n}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.splitlines()[2].strip(), file=sys.stderr)
        return 2
    span_names()                      # puts the repo root on sys.path
    from bench import trace as tr
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    names = span_names()
    if not names:
        print("program_spans: the program names no spans (no repro.obs)",
              file=sys.stderr)
    result = relabel(tr.load(path), load_program_spans(path, names))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
