"""Smoke run of the versioned store's main path on one TPU chip.

Builds the paper's SCI versioning benchmark at SCI_1M scale (1,000
versions, ~945K records of 20 int32 attributes), partitions it with
LyreSplit at gamma = 2, loads it as a ``PartitionedCVD`` and drives it
through ``serve.BatchedCheckoutServer`` on the kernel path:

  (a) whole store pinned: Zipf-skewed checkout waves over the newest
      versions, served off ONE uploaded superblock;
  (b) partition groups: one wave scattered over many partitions, then
      the same stream, with the pinned budget at a quarter of the
      superblock, so group launches and per-partition stragglers run;
  (c) ingest: one ``commit_many`` write wave of 16 commits with the
      write-ahead journal attached, extended in place on the device
      (``segment_append``), then read back;
  (d) migration: one ``apply_migration`` that splits the new commits into
      their own partition, migrated on the device (``segment_move``),
      then read back.

Every delivered block is compared with a plain host reference of the same
semantics (``data[rlist(v)]``, with its own numpy appends for the commits).
A phase fails the run unless the counters show that the kernels did the
work: no degraded or retried wave, no lazy rebuild, no append eviction,
exactly one upload per pinned superblock, group superblocks grown or
migrated on the device with only their delta uploaded, and read-backs
served by group kernel launches.

Usage: ``python chip_smoke.py [--seed N]``.  Needs a TPU: on any other
backend it exits non-zero before doing any work.  The last line of stdout
is ``{"ok": true, "device": {...}}``.  The JAX compile cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` next to this
file; the journal is written to ``.smoke_journal/`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


class SmokeFailure(RuntimeError):
    """A phase produced a wrong block or its counters show a fallback."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The deployment and the traffic.  The defaults are SCI_1M: the
    paper's smallest SCI scale (``core.bench_gen`` implements the
    benchmark)."""
    n_versions: int = 1000
    inserts: int = 630
    n_branches: int = 10
    n_attrs: int = 20
    gamma: float = 2.0            # LyreSplit storage budget, x |R|
    hot: int = 64                 # the stream reads the newest `hot` versions
    zipf_a: float = 1.2           # rank skew of the stream
    waves: tuple = (8, 16, 8, 16, 16)
    commits: int = 16             # commits in the ingest wave
    budget_frac: float = 0.25     # phase (b)'s pinned budget, x superblock
    scatter: int = 16             # partitions phase (b)'s last wave spans
    seed: int = 0


class HostReference:
    """Plain reference of the store's semantics: version v is
    ``data[rlists[v]]``; a commit appends its new rows and its rlist."""

    def __init__(self, graph, data):
        self.data = np.array(data, copy=True)
        self.rlists = [graph.rlist(v).copy() for v in range(graph.n_versions)]

    def checkout(self, vid: int) -> np.ndarray:
        return self.data[self.rlists[vid]]

    def commit(self, rlist: np.ndarray, new_rows: np.ndarray) -> int:
        self.data = np.concatenate([self.data, new_rows])
        self.rlists.append(rlist)
        return len(self.rlists) - 1


def zipf_waves(rng, newest: np.ndarray, sizes, a: float) -> list[list[int]]:
    """Waves of vids drawn Zipf-skewed over ``newest`` (newest first)."""
    p = np.arange(1, len(newest) + 1, dtype=np.float64) ** -a
    p /= p.sum()
    return [[int(newest[i]) for i in rng.choice(len(newest), n, p=p)]
            for n in sizes]


def serve_stream(srv, waves, ref: HostReference) -> int:
    """Submit and flush each wave through the pipelined server, then claim
    every ticket and compare it with the reference.  Returns the number of
    blocks checked."""
    tickets = []
    for vids in waves:
        tickets.append((srv.submit_many(vids), vids))
        srv.flush()
    srv.deliver()
    n = 0
    for ts, vids in tickets:
        for t, v in zip(ts, vids):
            got = srv.result(t)
            want = ref.checkout(v)
            _check(got.shape == want.shape and np.array_equal(got, want),
                   f"version {v}: delivered block differs from the host "
                   f"reference ({got.shape} vs {want.shape})")
            n += 1
    return n


def _check_clean(srv, phase: str) -> None:
    st = srv.stats
    _check(st.degraded_waves == 0 and st.retries == 0 and st.requeues == 0,
           f"{phase}: degraded={st.degraded_waves} retries={st.retries} "
           f"requeues={st.requeues}")


def _check_device_built(sbs, counted: int, phase: str, verb: str) -> None:
    """The group superblocks ``sbs`` were built by a device kernel from
    their old device copies (the manager counted every one of them), and
    only their delta crossed the host link."""
    _check(len(sbs) > 0 and counted == len(sbs),
           f"{phase}: {counted} groups {verb} on the device, "
           f"{len(sbs)} new group superblocks pinned")
    for g in sbs:
        _check(g._device is not None and g.uploads <= 1
               and g.bytes_uploaded < g.host.nbytes,
               f"{phase}: group {tuple(g.pids)} uploads={g.uploads} "
               f"bytes_uploaded={g.bytes_uploaded} of {g.host.nbytes}")


def _make_commits(rng, ref: HostReference, parents, k: int, inserts: int,
                  n_attrs: int) -> list[dict]:
    """k SCI-style commits: each derives from its parent by ~2% deletes,
    inserts/2 updates and `inserts` inserts (new rows get fresh rids).
    Commit i >= len(parents) chains onto commit i - len(parents) of the
    same wave.  The reference applies each commit as it is made."""
    vid0 = len(ref.rlists)
    n_cur = len(ref.data)
    commits = []
    for i in range(k):
        parent = (int(parents[i]) if i < len(parents)
                  else vid0 + i - len(parents))
        base = ref.rlists[parent]
        keep = np.delete(base, rng.choice(len(base),
                                          max(1, len(base) // 50) + inserts // 2,
                                          replace=False))
        n_new = inserts + inserts // 2
        rows = rng.integers(0, 1000, (n_new, n_attrs), dtype=np.int32)
        rows[:, 0] = np.arange(n_cur, n_cur + n_new, dtype=np.int32)
        rlist = np.union1d(keep, np.arange(n_cur, n_cur + n_new))
        n_cur += n_new
        commits.append({"parent": parent, "rlist": rlist, "new_rows": rows})
        ref.commit(rlist, rows)
    return commits


def run(cfg: SmokeConfig, journal_dir: Path, emit=print) -> dict:
    """Run phases (a)-(d); raise ``SmokeFailure`` on any wrong block or
    fallback.  ``emit`` receives one labelled line per measurement."""
    import jax

    from repro.core import (PartitionedCVD, estimate_superblock_bytes,
                            evict_superblocks, generate,
                            lyresplit_for_budget, plan_migration, to_tree)
    from repro.core.checkout import get_superblock_groups, peek_superblock
    from repro.core.journal import Journal, attach_journal, read_records
    from repro.serve import BatchedCheckoutServer

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind}]"
    rng = np.random.default_rng(cfg.seed)
    summary: dict = {}

    t0 = time.perf_counter()
    w = generate("SCI", n_versions=cfg.n_versions, inserts=cfg.inserts,
                 n_branches=cfg.n_branches, n_attrs=cfg.n_attrs,
                 seed=cfg.seed)
    ref = HostReference(w.graph, w.data)
    tree, _ = to_tree(w.graph, w.vgraph)
    split = lyresplit_for_budget(tree, gamma=cfg.gamma * w.n_records)
    store = PartitionedCVD(w.graph, w.data, split.best.assignment)
    need = estimate_superblock_bytes(store)
    emit(f"store: |V|={w.n_versions} |R|={w.n_records} |E|={w.n_edges} "
         f"partitions={len(store.partitions)} "
         f"storage_rows={store.storage_cost()} superblock_bytes={need} "
         f"setup_s={time.perf_counter() - t0:.3f} (host) {tag}")
    n_versions = w.n_versions       # the store grows w.graph in place
    newest = np.arange(n_versions - 1, n_versions - 1 - cfg.hot, -1)
    waves = zipf_waves(rng, newest, cfg.waves, cfg.zipf_a)

    # (a) whole store pinned -------------------------------------------------
    t0 = time.perf_counter()
    with BatchedCheckoutServer(store, use_kernel=True) as srv:
        srv.warmup()
        sb = peek_superblock(store)
        _check(sb is not None and sb.uploads == 1,
               "(a): warmup did not pin the whole-store superblock")
        n = serve_stream(srv, waves, ref)
        _check_clean(srv, "(a)")
    _check(peek_superblock(store) is sb and sb.uploads == 1,
           f"(a): superblock uploads={sb.uploads}, expected exactly 1")
    _check(sb.launches == len(waves),
           f"(a): {sb.launches} kernel gathers for {len(waves)} waves")
    emit(f"phase a (whole store pinned): waves={len(waves)} blocks={n} "
         f"bit_identical=yes kernel_launches={sb.launches} "
         f"uploads={sb.uploads} degraded=0 retries=0 "
         f"wall_s={time.perf_counter() - t0:.3f} (host clock, compiles "
         f"included) {tag}")
    summary["a"] = n

    # (b) partition groups under a quarter of the superblock -----------------
    t0 = time.perf_counter()
    evict_superblocks(store)
    store.superblock_max_bytes = int(need * cfg.budget_frac)
    pids = np.linspace(0, len(store.partitions) - 1,
                       min(cfg.scatter, len(store.partitions))).astype(int)
    scatter = [int(store.partitions[q].vids[-1]) for q in np.unique(pids)]
    with BatchedCheckoutServer(store, use_kernel=True) as srv:
        srv.warmup()
        n = serve_stream(srv, [scatter] + waves, ref)
        _check_clean(srv, "(b)")
        st = srv.stats
    mgr = get_superblock_groups(store)
    _check(mgr is not None and peek_superblock(store) is None,
           "(b): the store did not serve through the group layer")
    _check(st.group_waves == len(waves) + 1 and st.group_launches > 0,
           f"(b): group_waves={st.group_waves} "
           f"group_launches={st.group_launches}")
    _check(st.straggler_requests > 0,
           "(b): no per-partition straggler ran")
    _check(all(g.uploads == 1 for g in mgr.groups.values()),
           "(b): a pinned group was uploaded more than once")
    _check(mgr.pinned_bytes <= mgr.budget
           and mgr.pins - mgr.evictions == len(mgr.groups),
           "(b): group accounting out of balance")
    emit(f"phase b (groups, budget={mgr.budget} bytes): waves="
         f"{len(waves) + 1} blocks={n} bit_identical=yes "
         f"group_launches={st.group_launches} "
         f"groups_touched={st.groups_touched} "
         f"stragglers={st.straggler_requests} pins={mgr.pins} "
         f"evictions={mgr.evictions} degraded=0 retries=0 "
         f"wall_s={time.perf_counter() - t0:.3f} (host clock, compiles "
         f"included) {tag}")
    summary["b"] = n

    # (c) one ingest wave of K commits, journaled ----------------------------
    t0 = time.perf_counter()
    shutil.rmtree(journal_dir, ignore_errors=True)
    journal_dir.mkdir(parents=True)
    journal = Journal(str(journal_dir / "journal.wal"))
    attach_journal(store, journal)
    pinned = set().union(*mgr.groups) if mgr.groups else set()
    parents = [v for v in range(n_versions - 1, -1, -1)
               if int(store.vid_to_pid[v]) in pinned][:cfg.commits // 2]
    _check(len(parents) > 0, "(c): no pinned group to ingest into")
    commits = _make_commits(rng, ref, parents, cfg.commits, cfg.inserts,
                            cfg.n_attrs)
    ext0, ev0 = mgr.extended, mgr.append_evictions
    before = dict(mgr.groups)
    with BatchedCheckoutServer(store, use_kernel=True) as srv:
        wt = srv.submit_commit(commits)
        srv.flush()
        new_vids = [int(srv.result(t)) for t in wt]
        _check(new_vids == list(range(n_versions, n_versions + cfg.commits)),
               f"(c): commit_many assigned vids {new_vids}")
        grown = [g for k, g in mgr.groups.items() if before.get(k) is not g]
        _check_device_built(grown, mgr.extended - ext0, "(c)", "extended")
        n = serve_stream(srv, [new_vids], ref)
        _check_clean(srv, "(c)")
        st = srv.stats
    _check(st.group_launches > 0 and st.straggler_requests == 0,
           f"(c): read-back group_launches={st.group_launches} "
           f"stragglers={st.straggler_requests}")
    recs, bad = read_records(journal.path)
    batches = [r for r in recs if r.kind == "commit.batch"]
    _check(bad is None and len(batches) == 1
           and len(batches[0].payload["commits"]) == cfg.commits,
           f"(c): journal holds {[r.kind for r in recs]} (bad at {bad})")
    _check(st.commit_waves == 1 and st.commits_ingested == cfg.commits,
           f"(c): commit_waves={st.commit_waves}")
    _check(mgr.append_evictions == ev0,
           "(c): an in-place append fell back to eviction")
    _check(mgr.extended > ext0, "(c): no pinned group was extended in place")
    emit(f"phase c (ingest, K={cfg.commits}): commit_waves=1 "
         f"journal_batches=1 fsyncs={journal.synced} "
         f"groups_extended={mgr.extended - ext0} append_evictions=0 "
         f"bytes_uploaded={sum(g.bytes_uploaded for g in grown)} "
         f"of_group_bytes={sum(g.host.nbytes for g in grown)} "
         f"read_back_blocks={n} group_launches={st.group_launches} "
         f"bit_identical=yes "
         f"wall_s={time.perf_counter() - t0:.3f} (host clock, compiles "
         f"included) {tag}")
    summary["c"] = n

    # (d) one migration on the device ----------------------------------------
    t0 = time.perf_counter()
    assignment = store.assignment.copy()
    assignment[new_vids] = assignment.max() + 1
    plan = plan_migration(store, assignment)
    mig0, lr0 = mgr.migrated, mgr.lazy_rebuilds
    store.apply_migration(plan)
    _check(mgr.lazy_rebuilds == lr0,
           "(d): a group migration fell back to a lazy rebuild")
    migrated = mgr.migrated - mig0
    # the only groups pinned right after the migration are the migrated ones
    moved = list(mgr.groups.values())
    _check_device_built(moved, migrated, "(d)", "migrated")
    newest_all = np.arange(len(ref.rlists) - 1,
                           len(ref.rlists) - 1 - cfg.hot, -1)
    with BatchedCheckoutServer(store, use_kernel=True) as srv:
        n = serve_stream(srv, zipf_waves(rng, newest_all, cfg.waves,
                                         cfg.zipf_a), ref)
        _check_clean(srv, "(d)")
        st = srv.stats
    _check(st.group_launches > 0,
           f"(d): read-back group_launches={st.group_launches}")
    attach_journal(store, None)
    journal.close()
    emit(f"phase d (migration): partitions={len(store.partitions)} "
         f"groups_migrated={migrated} lazy_rebuilds=0 "
         f"rows_moved={plan.rows_moved} rows_loaded={plan.rows_loaded} "
         f"bytes_uploaded={sum(g.bytes_uploaded for g in moved)} "
         f"of_group_bytes={sum(g.host.nbytes for g in moved)} "
         f"read_back_blocks={n} group_launches={st.group_launches} "
         f"bit_identical=yes "
         f"wall_s={time.perf_counter() - t0:.3f} (host clock, compiles "
         f"included) {tag}")
    summary["d"] = n

    mem = dev.memory_stats() or {}
    emit(f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'not reported')} "
         f"{tag}")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated store and traffic")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    run(SmokeConfig(seed=args.seed), ROOT / ".smoke_journal",
        emit=lambda line: print(line, flush=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
