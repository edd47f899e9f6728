"""One run of one cell: set up the store, warm up, measure a window of
closed-loop traffic, then check every answer against the plain reference.

Set-up builds the deployment (the SCI history, LyreSplit, the store, the
server with its superblock or partition groups pinned) and warms it up on
a fixed stream, the same for every seed: ``warmup_waves`` read waves and,
with writers, ``warmup_commit_waves`` write waves.  That compiles what
every wave runs, and a first set of wave shapes, which the persistent
compilation cache then holds for the next run.  The window carries on
from there on the same store and server, with every reader drawing its
ranks from ``--seed``.  The program compiles its gather anew for every
wave of a new tile count, and its append for every write wave, since the
superblock grows: those compiles are the program's own work on traffic it
has not seen, so the window pays them.  Persistent caching is switched off
before the window opens, so every run pays them alike, and they are
counted (``jit.compiles_per_wave``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable

import numpy as np

from . import check, scigen
from .driver import ClosedLoop, Reader, Record, Writer
from .reference import HostReference, Ranks

WARMUP_SEED = 0          # the warm-up's reads: the same for every seed
DRAIN_S = 60.0           # an answer may come this long after the window

# The configuration keys the harness acts on.  Any other deployment key, or
# any other server key that is set, is refused: a setting the run would
# not act on must not be measured as if it had been.
DEPLOYMENT_KEYS = ("kind", "n_versions", "inserts", "n_branches", "n_attrs",
                   "update_frac", "delete_frac", "dtype", "gamma",
                   "history_seed", "budget_frac")
SERVER_KEYS = ("use_kernel", "pipeline", "max_wave", "deadline_s")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_COMPILE = _COMPILE_EVENTS[0]


class CompileWatch:
    """Counts backend compiles and the seconds spent tracing, lowering and
    compiling, through ``jax.monitoring``.  Register once per process."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.spans: list = []          # (start, end) wall-clock seconds
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_time_span_listener(self._span)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.count += event == _BACKEND_COMPILE

    def _span(self, event, start, end, **_):
        if event == _BACKEND_COMPILE:
            self.spans.append((start, end))


def check_config(cfg: dict) -> None:
    """Raises ValueError naming a deployment key the harness does not
    drive, or a server key it does not drive that is set (not null).  The
    records it generates are int32, so another ``dtype`` is refused too."""
    d = cfg["deployment"]
    unknown = [f"deployment.{k}" for k in d if k not in DEPLOYMENT_KEYS]
    unknown += [f"server.{k}" for k, v in cfg["server"].items()
                if k not in SERVER_KEYS and v is not None]
    if d.get("dtype") != "int32":
        unknown.append(f"deployment.dtype {d.get('dtype')!r}")
    if unknown:
        raise ValueError(f"configuration {cfg.get('name')!r}: the benchmark "
                         f"does not act on {', '.join(unknown)}")


@dataclasses.dataclass
class Deployment:
    """The generated history and what every store of the run is built from."""
    hist: scigen.History
    data: np.ndarray
    indptr: np.ndarray
    indices: np.ndarray
    assignment: np.ndarray


def make_deployment(cfg: dict, seed: int) -> Deployment:
    """The SCI history of the configuration (its shape fixed by the
    configuration's ``history_seed``, so every run holds the same sizes)
    with record values drawn from ``seed``, partitioned by LyreSplit."""
    from repro.core import BipartiteGraph, VersionGraph, to_tree
    from repro.core import lyresplit_for_budget
    d = cfg["deployment"]
    hist = scigen.generate(d["kind"], n_versions=d["n_versions"],
                           inserts=d["inserts"], n_branches=d["n_branches"],
                           n_attrs=d["n_attrs"], seed=d["history_seed"],
                           update_frac=d["update_frac"],
                           delete_frac=d["delete_frac"])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    data = rng.integers(0, 1000, size=hist.data.shape, dtype=np.int32)
    data[:, 0] = np.arange(len(data), dtype=np.int32)
    data[:, 1] = rng.integers(0, 1 << 20, size=len(data), dtype=np.int32)
    hist.data = data
    indptr = np.zeros(hist.n_versions + 1, np.int64)
    np.cumsum([len(r) for r in hist.rlists], out=indptr[1:])
    indices = np.concatenate(hist.rlists)
    graph = BipartiteGraph(indptr=indptr, indices=indices,
                           n_records=hist.n_records)
    vg = VersionGraph()
    for v, ps in enumerate(hist.parents):
        vg.add_version(parents=ps, commit_t=float(v),
                       checkout_t=v - 0.5 if v else None)
    tree, _ = to_tree(graph, vg)
    split = lyresplit_for_budget(tree, gamma=d["gamma"] * hist.n_records)
    return Deployment(hist=hist, data=data, indptr=indptr, indices=indices,
                      assignment=np.asarray(split.best.assignment))


def build_store(dep: Deployment, cfg: dict):
    """A fresh store over copies of the deployment (a store grows its graph
    and data in place), with the configuration's pinned-byte budget."""
    from repro.core import BipartiteGraph, PartitionedCVD
    from repro.core import estimate_superblock_bytes
    graph = BipartiteGraph(indptr=dep.indptr.copy(),
                           indices=dep.indices.copy(),
                           n_records=dep.hist.n_records)
    store = PartitionedCVD(graph, dep.data.copy(), dep.assignment.copy())
    frac = cfg["deployment"].get("budget_frac")
    if frac is not None:
        store.superblock_max_bytes = int(estimate_superblock_bytes(store)
                                         * frac)
    return store


def start_server(store, cfg: dict, factory=None):
    """The served path: ``BatchedCheckoutServer`` on the kernel tier, with
    the whole superblock or the partition groups pinned and uploaded."""
    import jax
    from repro.core.checkout import get_superblock_groups, peek_superblock
    from repro.serve import BatchedCheckoutServer
    s = cfg["server"]
    srv = (factory or BatchedCheckoutServer)(
        store, use_kernel=s["use_kernel"], pipeline=s["pipeline"],
        max_wave=s["max_wave"], deadline_s=s["deadline_s"])
    srv.warmup()
    sb = peek_superblock(store)
    mgr = get_superblock_groups(store)
    pinned = ([sb.device()] if sb is not None else
              [g.device() for g in mgr.groups.values()] if mgr else [])
    jax.block_until_ready(pinned)
    return srv


def make_clients(dep: Deployment, mix: dict, seed: int):
    """Readers holding their warm-up streams, the readers' window streams,
    and the writers.

    The warm-up streams come from ``WARMUP_SEED`` and are the same for
    every seed, so set-up sends the same waves in every run.  ``seed``
    draws the window's streams (each request's rank, and whether its block
    is compared byte for byte), the record values and the writers' edits.
    """
    warm = np.random.SeedSequence(WARMUP_SEED).spawn(mix["readers"])
    r_ss, w_ss = np.random.SeedSequence([seed, 2]).spawn(2)
    readers = [Reader(idx=i, rng=np.random.default_rng(s))
               for i, s in enumerate(warm)]
    window = [np.random.default_rng(s) for s in r_ss.spawn(mix["readers"])]
    tips = dep.hist.tips
    writers = []
    for i, s in enumerate(w_ss.spawn(mix["writers"])):
        tip = tips[i % len(tips)]
        rlist = np.asarray(dep.hist.rlists[tip], dtype=np.int64)
        writers.append(Writer(idx=i, rng=np.random.default_rng(s),
                              table=dep.data[rlist], parent=tip, ids=rlist))
    return readers, window, writers


def _journal(path: Path):
    from repro.core.journal import Journal
    path.parent.mkdir(parents=True, exist_ok=True)
    if path.exists():
        path.unlink()
    return Journal(str(path))


def _stats(srv) -> dict:
    return {k: v for k, v in dataclasses.asdict(srv.stats).items()
            if isinstance(v, (int, float))}


def run_cell(cell: dict, cfg: dict, mix: dict, *, seed: int, seconds: float,
             work_dir: Path, trace: bool = False, t_start: float,
             watch: CompileWatch, server_factory=None,
             emit: Callable[[str], None] = print) -> dict:
    """Run one cell once; returns the result object (see ``run.py``)."""
    import jax
    from repro.core.journal import attach_journal
    check_config(cfg)
    clock = time.perf_counter
    dev = jax.devices()[0]
    phases = [("start", clock() - t_start)]
    dep = make_deployment(cfg, seed)
    phases.append(("deployment", clock() - t_start))
    readers, window_rngs, writers = make_clients(dep, mix, seed)
    n0 = dep.hist.n_versions

    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: contextlib.nullcontext()))
    store = build_store(dep, cfg)
    journal = _journal(work_dir / "journal" / "run.wal") if writers else None
    attach_journal(store, journal)
    srv = start_server(store, cfg, server_factory)
    phases.append(("store", clock() - t_start))
    loop = ClosedLoop(srv, readers, writers, newest=n0 - 1,
                      ranks=Ranks(mix["ranks"], n0),
                      check_share=mix["check_share"],
                      commit_edit=mix["commit"],
                      merge_every=mix["merge_every"], journal=journal,
                      clock=clock, span=span)
    # -- warm-up on the fixed stream, then the window's streams -------------
    loop.start()
    while (len(loop.rec.waves) < mix["warmup_waves"]
           or loop.rec.commit_waves < mix["warmup_commit_waves"]):
        loop.step()
    warm, loop.rec = loop.rec, Record()
    for r, rng in zip(readers, window_rngs):
        r.rng = rng
    phases.append(("warm", clock() - t_start))
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    trace_dir = work_dir / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles0, spans0 = watch.count, len(watch.spans)
    s0 = _stats(srv)
    t0 = clock()
    setup_s = t0 - t_start
    wall0 = time.time()
    with span("bench.window"):
        while clock() - t0 < seconds:
            loop.step()
        t_close = clock()
    call_s = loop.rec.call_s
    s1 = _stats(srv)
    compiles = watch.count - compiles0
    compile_spans = watch.spans[spans0:]
    if trace:
        jax.profiler.stop_trace()
    attempted = loop.rec.submitted
    unanswered = loop.drain(DRAIN_S)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # -- after the window: read back, then free the program's state ---------
    rec = loop.rec
    acks = warm.acks + rec.acks      # warm-up commits are in the store too
    rb_rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    readback = check.readback_sample(acks, mix["readback"], rb_rng)
    got_back = check.read_back(srv, readback)
    srv.close()
    jrecs = None
    if journal is not None:
        attach_journal(store, None)
        journal.close()
        jrecs = check.journal_commits(journal.path)
    del srv, store
    gc.collect()

    # -- the reference -------------------------------------------------------
    ref = HostReference(dep.hist.rlists, dep.data)
    w_by_idx = {w.idx: w for w in writers}
    commit_parts = {
        "order": int(not check.replay_commits(ref, acks, w_by_idx, n0)),
        "readback": check.wrong_readback(ref, got_back),
        "journal": (check.journal_mismatches(ref, acks, w_by_idx, jrecs)
                    if jrecs is not None else 0)}
    results = check.Checks()
    results.add("unanswered", unanswered + warm.lost + rec.lost)
    results.add("wrong_blocks", check.wrong_blocks(ref, rec.reads,
                                                   rec.samples))
    results.add("wrong_commits", sum(commit_parts.values()))
    results.add("acks_without_fsync",
                warm.unsynced_acks + rec.unsynced_acks)

    ctx = SimpleNamespace(
        seconds=t_close - t0, setup_s=setup_s,
        reads=[r for r in rec.reads if r[1] <= t_close],
        writes=[w for w in rec.writes if w[1] <= t_close],
        waves=[v for t, v in rec.waves if t <= t_close],
        commit_waves=sum(1 for t, _ in rec.commit_times if t <= t_close),
        stats={k: s1[k] - s0[k] for k in s1}, compiles=compiles,
        call_s=call_s, size=ref.size, n_attrs=cfg["deployment"]["n_attrs"],
        itemsize=np.dtype(cfg["deployment"]["dtype"]).itemsize,
        device_kind=dev.device_kind, trace=None)
    breakdown = None
    if trace:
        from . import trace as tr
        events = tr.load(tr.find_xplane(str(trace_dir)))
        # the window span opened at wall0 on the wall clock: compile spans
        # (wall clock) move onto the trace's clock by that offset
        offset = wall0 - tr.window_of(events)[0]
        compile_ev = [tr.Event("compile", s - offset, e - s)
                      for s, e in compile_spans]
        summary = tr.summarize(events, extra_spans=compile_ev)
        ctx.trace = summary
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_gaps}
    emit(f"run: cell={cell['name']} seed={seed} setup_s={setup_s} "
         f"window_s={ctx.seconds} warmup_waves={len(warm.waves)} "
         f"window_waves={len(ctx.waves)} compiles_in_window={compiles} "
         f"forced_flushes={rec.forced} commit_waves={rec.commit_waves} "
         f"acks={len(rec.acks)} "
         f"merges={sum(len(a[1]) > 1 for a in rec.acks)} "
         f"samples={len(rec.samples)} "
         f"readback={len(got_back)} phases_s="
         + ",".join(f"{k}:{v:.2f}" for k, v in phases))
    return {"ctx": ctx, "checks": results, "attempted": attempted,
            "failed": unanswered + warm.lost + rec.lost, "peak": peak,
            "breakdown": breakdown, "device": dev, "rec": rec, "acks": acks,
            "commit_parts": commit_parts}

