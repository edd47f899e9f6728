"""Stage counters and profiler spans of the serve, group and ingest paths.

Every read wave a ``BatchedCheckoutServer`` serves adds its host seconds
by stage to ``CheckoutStats`` (plan, launch, pin, stragglers at dispatch;
device wait, device->host copy at delivery), every commit wave its ingest
stages (staging, journal, superblock refresh), and each wave kind raises
exactly its own stages.  The
byte counters equal what moved: the packed arrays copied to the host, the
superblock and straggler partitions uploaded.  The spans land on a
``jax.profiler`` trace nested under ``serve.flush`` with shared wave ids,
and every span the program opens is one of ``obs.SPAN_NAMES``.
"""
import ast
import dataclasses
import glob
import importlib
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core.checkout import (WaveStages, get_superblock_groups,
                                 partition_segment_bytes)
from repro.core.graph import BipartiteGraph
from repro.core.journal import Journal, attach_journal
from repro.core.partition import PartitionedCVD
from repro.serve.checkout import BatchedCheckoutServer, CheckoutStats

_ops = importlib.import_module("repro.kernels.ops")
SRC = Path(__file__).resolve().parents[1] / "src"

READ = ("plan_s", "launch_s", "device_wait_s", "d2h_s")
GROUP = ("pin_s", "straggler_s")
INGEST = ("ingest_stage_s", "journal_s", "refresh_s")
BYTES = ("h2d_bytes", "d2h_bytes")
STAGE_S = READ + GROUP + INGEST


def _store(rng, p=4, n_versions=16, r=512, rows=24, d=12, big=None):
    """Partitions v -> v % p, half dense runs, half scattered; partition
    ``big`` holds versions of 200 scattered rows, so its segment outgrows
    the other partitions' together."""
    rls = []
    for v in range(n_versions):
        if v % p == big:
            rls.append(np.sort(rng.choice(r, 200, replace=False))
                       .astype(np.int64))
        elif v % 2 == 0:
            s = int(rng.integers(0, r - rows))
            rls.append(np.arange(s, s + rows, dtype=np.int64))
        else:
            rls.append(np.sort(rng.choice(r, rows, replace=False))
                       .astype(np.int64))
    graph = BipartiteGraph.from_rlists(rls, n_records=r)
    data = rng.integers(0, 1 << 20, (r, d)).astype(np.int32)
    return PartitionedCVD(graph, data, np.arange(n_versions) % p)


def _snap(srv) -> dict:
    return {f.name: getattr(srv.stats, f.name)
            for f in dataclasses.fields(CheckoutStats)
            if f.name in STAGE_S + BYTES}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _raised(d: dict) -> set:
    return {k for k, v in d.items() if v > 0}


class _Timed:
    """Host seconds spent inside calls into the server."""

    def __init__(self, srv):
        self.srv, self.s = srv, 0.0

    def __call__(self, name, *args):
        t = time.perf_counter()
        out = getattr(self.srv, name)(*args)
        self.s += time.perf_counter() - t
        return out


def _spy_packed(monkeypatch, sizes):
    real = _ops.checkout_wave

    def spied(*a, **kw):
        out = real(*a, **kw)
        sizes.append(int(out.nbytes))
        return out
    monkeypatch.setattr(_ops, "checkout_wave", spied)


# ---------------------------------------------------------------- counters --
def test_whole_store_wave_raises_only_read_stages(rng, monkeypatch):
    store = _store(rng)
    srv = BatchedCheckoutServer(store, use_kernel=True)
    srv.warmup()                           # whole superblock built and pinned
    sizes: list = []
    _spy_packed(monkeypatch, sizes)
    call = _Timed(srv)
    before = _snap(srv)
    tickets = call("submit_many", [0, 1, 2, 5, 5])
    call("flush")
    blocks = [call("result", t) for t in tickets]
    d = _delta(before, _snap(srv))
    assert _raised(d) == set(READ) | {"d2h_bytes"}
    assert d["d2h_bytes"] == sum(sizes) and len(sizes) == 1
    assert sum(d[k] for k in STAGE_S) <= call.s
    assert sum(len(b) for b in blocks) == srv.stats.rows_served


def test_group_wave_with_a_straggler_raises_pin_and_straggler_stages(
        rng, monkeypatch):
    big = 3
    store = _store(rng, big=big)
    seg = partition_segment_bytes(store)
    rest = int(seg.sum() - seg[big])
    assert rest < seg[big]
    store.superblock_max_bytes = rest        # the rest co-pin, ``big`` never
    srv = BatchedCheckoutServer(store, use_kernel=True)
    sizes: list = []
    _spy_packed(monkeypatch, sizes)
    uploads: list = []
    real_lane_rows = _ops._lane_rows

    def lane_rows(*a, **kw):
        out = real_lane_rows(*a, **kw)
        uploads.append(int(out[0].nbytes))
        return out
    monkeypatch.setattr(_ops, "_lane_rows", lane_rows)
    call = _Timed(srv)
    before = _snap(srv)
    vids = [0, 1, 2, big]
    tickets = call("submit_many", vids)
    call("flush")
    for t in tickets:
        call("result", t)
    d = _delta(before, _snap(srv))
    mgr = get_superblock_groups(store)
    assert mgr.last_wave.straggler_vids == 1 and mgr.last_wave.pinned >= 1
    assert _raised(d) == set(READ) | set(GROUP) | set(BYTES)
    group_up = sum(sb.bytes_uploaded for sb in mgr.groups.values())
    assert uploads and d["h2d_bytes"] == group_up + sum(uploads)
    assert d["d2h_bytes"] == sum(sizes)
    assert sum(d[k] for k in STAGE_S) <= call.s
    # a second wave over the same pinned groups pins and uploads nothing
    before = _snap(srv)
    srv.serve([v for v in vids if v != big])
    d = _delta(before, _snap(srv))
    assert d["pin_s"] == 0 and d["h2d_bytes"] == 0 and d["straggler_s"] == 0


def test_commit_wave_raises_only_ingest_stages(rng, tmp_path):
    store = _store(rng)
    journal = Journal(str(tmp_path / "j.wal"))
    attach_journal(store, journal)
    srv = BatchedCheckoutServer(store, use_kernel=True)
    srv.warmup()
    write_s: list = []
    real_commit_many = store.commit_many

    def commit_many(commits, **kw):
        w0 = journal.write_s
        out = real_commit_many(commits, **kw)
        write_s.append(journal.write_s - w0)
        return out
    store.commit_many = commit_many
    call = _Timed(srv)
    before = _snap(srv)
    new_rows = np.arange(3 * 12, dtype=np.int32).reshape(3, 12)
    rid0 = store.graph.n_records
    tickets = call("submit_commit", [
        {"parent": 0, "rlist": np.r_[store.graph.rlist(0), rid0:rid0 + 3],
         "new_rows": new_rows}])
    call("flush")
    vid = int(call("result", tickets[0]))
    d = _delta(before, _snap(srv))
    assert _raised(d) == set(INGEST)
    assert d["journal_s"] == write_s[0] > 0
    assert store.last_ingest.commits == 1
    assert sum(d[k] for k in STAGE_S) <= call.s
    # the commit is not a checkout: it leaves the checkout latencies alone
    assert len(srv.stats.ticket_latency_s) == 0
    np.testing.assert_array_equal(srv.serve([vid])[0][-3:], new_rows)
    assert len(srv.stats.ticket_latency_s) == 1
    srv.close()
    attach_journal(store, None)
    journal.close()


def test_wave_stages_are_the_server_counters(rng):
    """Every field of ``WaveStages`` is a ``CheckoutStats`` field of the
    same name: the server sums them field by field, each in exactly one
    half of the wave (dispatch or delivery)."""
    names = {f.name for f in dataclasses.fields(CheckoutStats)}
    fields = {f.name for f in dataclasses.fields(WaveStages)}
    assert fields <= names
    assert set(STAGE_S + BYTES) <= names
    assert set(WaveStages.DISPATCH) | set(WaveStages.DELIVERY) == fields
    assert not set(WaveStages.DISPATCH) & set(WaveStages.DELIVERY)


def test_dispatch_stages_count_at_dispatch_and_delivery_ones_at_delivery(
        rng):
    """A wave left in flight has its plan and launch counted already and
    its wait and copy not yet, so a window's counters cover the calls its
    clock covers."""
    srv = BatchedCheckoutServer(_store(rng), use_kernel=True)
    srv.warmup()
    before = _snap(srv)
    srv.submit_many([0, 1, 2])
    assert srv.flush() == []               # dispatched, still in flight
    d = _delta(before, _snap(srv))
    assert _raised(d) == {"plan_s", "launch_s"}
    srv.deliver()
    d = _delta(before, _snap(srv))
    assert _raised(d) == set(READ) | {"d2h_bytes"}


# ------------------------------------------------------------------- spans --
def _span_literals():
    """(path, line, name) of every ``obs.span("...")`` call in src/."""
    out = []
    for path in sorted(SRC.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "span"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "obs"):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), (path, node.lineno)
                out.append((path, node.lineno, arg.value))
    return out


def test_every_span_the_program_opens_is_named():
    used = {name for _, _, name in _span_literals()}
    assert used == set(obs.SPAN_NAMES)
    assert len(obs.SPAN_NAMES) == len(set(obs.SPAN_NAMES))


def test_core_stays_free_of_jax_and_spans_are_a_shared_noop():
    code = ("import sys\n"
            "import repro.core.journal, repro.core.partition, repro.obs\n"
            "assert 'jax' not in sys.modules\n"
            "from repro.obs import span\n"
            "assert span('serve.flush') is span('ingest.stage', wave=1)\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)


def test_span_ids_pass_to_nested_spans(monkeypatch):
    import jax
    seen = []

    class Ann:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    with obs.span("serve.flush", wave=4, commit_wave=1):
        with obs.span("serve.deliver", wave=3):
            with obs.span("checkout.d2h", bytes=8):
                pass
        with obs.span("checkout.plan", vids=2):
            pass
    with obs.span("journal.append", kind="ticket"):
        pass
    assert seen == [
        ("serve.flush", {"wave": 4, "commit_wave": 1}),
        ("serve.deliver", {"wave": 3, "commit_wave": 1}),
        ("checkout.d2h", {"wave": 3, "commit_wave": 1, "bytes": 8}),
        ("checkout.plan", {"wave": 4, "commit_wave": 1, "vids": 2}),
        ("journal.append", {"kind": "ticket"})]


def _host_spans(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in obs.SPAN_NAMES:
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def test_a_cpu_trace_holds_the_spans_nested_under_flush(rng, tmp_path):
    import jax
    store = _store(rng)
    journal = Journal(str(tmp_path / "j.wal"))
    attach_journal(store, journal)
    srv = BatchedCheckoutServer(store, use_kernel=True)
    srv.warmup()
    srv.serve([0, 1, 2])                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        srv.submit_many([1, 2, 3])
        srv.flush()                        # dispatches wave 1
        srv.submit_commit([{"parent": 0, "rlist": store.graph.rlist(0)},
                           {"parents": [1, 2],
                            "table": store.checkout(1)}])
        srv.submit_many([0, 3])
        srv.flush()                        # lands commit wave 0, delivers
        srv.deliver()                      # wave 1, dispatches wave 2
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path / "trace"))
    names = {n for n, *_ in spans}
    assert names == set(obs.SPAN_NAMES) - {"checkout.pin",
                                           "checkout.stragglers"}
    flushes = [s for s in spans if s[0] == "serve.flush"]
    assert [f[3]["wave"] for f in flushes] == [1, 2]
    assert flushes[1][3]["commit_wave"] == 0

    def inside(s, outer):
        return outer[1] <= s[1] and s[2] <= outer[2]
    for s in spans:
        if s[0] == "serve.flush":
            continue
        if s[0] in ("serve.deliver", "checkout.device_wait", "checkout.d2h") \
                and not any(inside(s, f) for f in flushes):
            assert s[3]["wave"] == 2     # the explicit deliver() call
            continue
        (f,) = [f for f in flushes if inside(s, f)]
        if s[0].startswith(("ingest.", "journal.")) and s[3].get(
                "kind") != "ticket":
            assert s[3]["commit_wave"] == 0
        elif s[0] in ("serve.deliver", "checkout.device_wait",
                      "checkout.d2h"):
            assert s[3]["wave"] == f[3]["wave"] - 1
        elif s[0] in ("serve.dispatch", "checkout.plan", "checkout.launch"):
            assert s[3]["wave"] == f[3]["wave"]
    srv.close()
    attach_journal(store, None)
    journal.close()
