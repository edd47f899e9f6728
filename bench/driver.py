"""Closed-loop clients driving ``serve.BatchedCheckoutServer`` through its
public calls, on one thread.

Every reader holds at most one checkout in flight and submits its next one
as soon as ``result()`` has returned the last; every writer does the same
with one commit.  A reader draws each request's rank afresh from its own
stream (``Ranks``), with no cycle.  The server's count trigger
(``max_wave``) forms the waves, so which requests share a wave is a
function of the streams alone: the loop never polls a clock to decide
what to send.  A client learns that its
answer is there from the server's public counters (``stats.waves``,
``waves_delivered``, ``commit_waves``): a flush dispatches every pending
read as one wave, and waves deliver in the order they were dispatched.

A writer edits its newest acknowledged version (``reference.edit_table``)
and commits the table, ``{"parent", "table"}``.  With ``merge_every`` m,
its commit k is a merge when ``k % m == m - 1``: it draws a partner among
the other writers, edits the union by record of its own and the
partner's newest acknowledged versions (a record both hold once, value-
equal rows of two records both), and commits ``{"parents": [own,
partner], "table"}``.  A writer names its rows' records by identity
(``reference.minted_ids``), kept in checkout order, so its table stays in
the store's checkout order after a merge.

Latencies are taken by the clients' own clock, from just before the submit
call to just after ``result()`` returns.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, NamedTuple, Optional

import numpy as np

from .reference import Ranks, edit_table, minted_ids

WRITER_PK_BASE = 1 << 30     # writers' new keys: clear of the generated pool
WRITER_PK_SPAN = 1 << 24     # keys each writer may mint

# A traffic mix (``bench/traffic/<mix>.json``): every key this loop reads,
# with its default; a key it does not know, or a value it cannot drive, is
# an error, never a silent default.
MIX_KEYS = {
    "about": "",              # one line on who sends this traffic
    "loop": None,             # "closed": the only loop this driver runs
    "readers": None,          # closed-loop reader clients, no think time
    "ranks": None,            # {"dist": "zipf", "a": ..} or {"dist": "uniform"}
    "check_share": None,      # share of checkouts compared byte for byte
    "warmup_waves": None,     # read waves of set-up, from a fixed stream
    "writers": 0,             # closed-loop writer clients
    "commit": None,           # a writer's edit: delete_frac, updates, inserts
    "warmup_commit_waves": 0,  # write waves landed in set-up
    "readback": 0,            # acknowledged commits read back, besides tips
    "merge_every": 0,         # m > 0: writer commit k merges if k % m == m - 1
}
LOOPS = ("closed",)


def parse_mix(raw: dict) -> dict:
    """The mix with every key of ``MIX_KEYS``; raises ValueError on an
    unknown key, a missing one, or a value this driver cannot run."""
    unknown = set(raw) - set(MIX_KEYS)
    if unknown:
        raise ValueError(f"traffic mix: unknown keys {sorted(unknown)}")
    mix = {**MIX_KEYS, **raw}
    missing = [k for k, v in mix.items() if v is None and k != "commit"]
    if missing:
        raise ValueError(f"traffic mix: missing keys {missing}")
    if mix["loop"] not in LOOPS:
        raise ValueError(f"traffic mix: loop {mix['loop']!r} is not one of "
                         f"{LOOPS}")
    Ranks.check(mix["ranks"])
    if mix["readers"] < 1 or mix["writers"] < 0:
        raise ValueError("traffic mix: needs a reader, and writers >= 0")
    if not 0.0 <= mix["check_share"] <= 1.0:
        raise ValueError("traffic mix: check_share lies in [0, 1]")
    merge = mix["merge_every"]
    if isinstance(merge, bool) or not isinstance(merge, int) or merge < 0:
        raise ValueError("traffic mix: merge_every is a whole number >= 0")
    if mix["writers"]:
        edit = mix["commit"] or {}
        if set(edit) != {"delete_frac", "updates", "inserts"}:
            raise ValueError("traffic mix: writers need a commit with "
                             "delete_frac, updates and inserts")
        if merge and mix["writers"] < 2:
            raise ValueError("traffic mix: merge_every needs two writers "
                             "or more")
    elif mix["commit"] is not None or mix["warmup_commit_waves"] or merge:
        raise ValueError("traffic mix: commit settings without writers")
    return mix


@dataclasses.dataclass
class Reader:
    idx: int
    rng: np.random.Generator     # each request's rank and sample flag
    k: int = 0                   # requests submitted so far
    ticket: int = -1
    vid: int = -1
    sampled: bool = False
    t_submit: float = 0.0


class Edit(NamedTuple):
    """One commit a writer drew: its base table's rows at positions
    ``keep``, then ``new_rows``.  A merge's base is the union of its
    parents' records, so it also names them: ``kept``, the identities
    (``reference.minted_ids``) of the kept rows."""
    keep: np.ndarray
    new_rows: np.ndarray
    kept: Optional[np.ndarray] = None


@dataclasses.dataclass
class Writer:
    idx: int
    rng: np.random.Generator
    table: np.ndarray            # rows of its newest acknowledged version
    parent: int                  # that version's vid
    ids: np.ndarray              # identity of each row of ``table``
    edits: list = dataclasses.field(default_factory=list)  # Edit per commit
    k: int = 0                   # commits submitted so far
    ticket: int = -1
    t_submit: float = 0.0
    pending: Optional[np.ndarray] = None   # table of the commit in flight
    pending_ids: Optional[np.ndarray] = None  # ids of its kept rows
    parents: tuple = ()          # the vids the commit in flight names


@dataclasses.dataclass
class Record:
    """What one run of the loop saw: each entry is one finished request."""
    reads: list = dataclasses.field(default_factory=list)   # (t0, t1, vid, rows)
    writes: list = dataclasses.field(default_factory=list)  # (t0, t1, vid)
    samples: list = dataclasses.field(default_factory=list)  # (vid, block)
    waves: list = dataclasses.field(default_factory=list)   # (t, vids)
    acks: list = dataclasses.field(default_factory=list)
    # (vid, parents, writer, k): parents is (parent,) or (own, partner)
    unsynced_acks: int = 0       # write waves acknowledged with no fsync
    commit_waves: int = 0
    commit_times: list = dataclasses.field(default_factory=list)  # (t, n)
    forced: int = 0              # flushes the loop had to force
    lost: int = 0                # answers result() no longer had
    call_s: float = 0.0          # host seconds inside calls into the server
    submitted: int = 0           # requests submitted


class ClosedLoop:
    """The clients of one traffic mix over one server."""

    def __init__(self, srv, readers: list[Reader], writers: list[Writer], *,
                 newest: int, ranks: Ranks, check_share: float,
                 commit_edit: Optional[dict], merge_every: int = 0,
                 journal=None, clock: Callable[[], float] = time.perf_counter,
                 span: Callable[[str], object] = None):
        self.srv = srv
        self.readers = readers
        self.writers = writers
        self.newest = newest
        self.ranks = ranks
        self.check_share = check_share
        self.commit_edit = commit_edit
        self.merge_every = merge_every
        self.journal = journal
        self.clock = clock
        self.span = span or (lambda name: contextlib.nullcontext())
        self.rec = Record()
        self.submitting = True
        st = srv.stats
        self._seen = (st.waves, st.waves_delivered, st.commit_waves)
        self._undispatched: list[Reader] = []
        self._inflight: collections.deque = collections.deque()
        self._ready_reads: list[Reader] = []
        self._pending_writes: list[Writer] = []
        self._ready_writes: list[Writer] = []

    # -- calls into the server ------------------------------------------------
    def _call(self, name: str, fn, *args):
        synced = self.journal.synced if self.journal is not None else 0
        t = self.clock()
        with self.span(name):
            out = fn(*args)
        self.rec.call_s += self.clock() - t
        self._observe(synced)
        return out

    def _observe(self, synced_before: int) -> None:
        st = self.srv.stats
        waves, delivered, commits = self._seen
        if st.waves > waves:
            if st.waves != waves + 1:
                raise RuntimeError(f"{st.waves - waves} waves in one call")
            wave, self._undispatched = self._undispatched, []
            self._inflight.append(wave)
            self.rec.waves.append((self.clock(), [r.vid for r in wave]))
        for _ in range(st.waves_delivered - delivered):
            self._ready_reads.extend(self._inflight.popleft())
        if st.commit_waves > commits:
            if st.commit_waves != commits + 1:
                raise RuntimeError("two write waves in one call")
            self.rec.commit_waves += 1
            self.rec.commit_times.append((self.clock(),
                                          len(self._pending_writes)))
            if self.journal is not None and self.journal.synced <= synced_before:
                self.rec.unsynced_acks += 1
            self._ready_writes.extend(self._pending_writes)
            self._pending_writes = []
        self._seen = (st.waves, st.waves_delivered, st.commit_waves)

    # -- clients ----------------------------------------------------------------
    def _submit_read(self, r: Reader) -> None:
        r.vid = self.newest - self.ranks.draw(r.rng)
        r.sampled = bool(r.rng.random() < self.check_share)
        r.k += 1
        self.rec.submitted += 1
        self._undispatched.append(r)
        r.t_submit = self.clock()
        (r.ticket,) = self._call("bench.submit", self.srv.submit_many, [r.vid])

    def _base(self, w: Writer):
        """The table commit ``w.k`` edits, its rows' identities, and the
        parents it names.  A merge draws its partner from the writer's own
        stream, uniformly among the other writers, and edits the union of
        both newest acknowledged versions by record, in checkout order."""
        m = self.merge_every
        if not m or w.k % m != m - 1:
            return w.table, w.ids, (w.parent,)
        others = [o for o in self.writers if o is not w]
        p = others[int(w.rng.integers(len(others)))]
        ids, first = np.unique(np.concatenate([w.ids, p.ids]),
                               return_index=True)
        return (np.concatenate([w.table, p.table])[first], ids,
                (w.parent, p.parent))

    def _submit_write(self, w: Writer) -> None:
        base, ids, parents = self._base(w)
        e = self.commit_edit
        keep, new_rows, w.pending = edit_table(
            w.rng, base, delete_frac=e["delete_frac"], updates=e["updates"],
            inserts=e["inserts"], next_pk=WRITER_PK_BASE
            + w.idx * WRITER_PK_SPAN + w.k * e["inserts"])
        w.pending_ids, w.parents = ids[keep], parents
        w.edits.append(Edit(keep, new_rows,
                            w.pending_ids if len(parents) > 1 else None))
        w.k += 1
        self.rec.submitted += 1
        self._pending_writes.append(w)
        w.t_submit = self.clock()
        commit = ({"parent": w.parent} if len(parents) == 1
                  else {"parents": list(parents)})
        commit["table"] = w.pending
        (w.ticket,) = self._call("bench.submit_commit", self.srv.submit_commit,
                                 [commit])

    def _claim_read(self, r: Reader) -> None:
        try:
            block = self._call("bench.result", self.srv.result, r.ticket)
        except KeyError:            # the server lost the answer
            self.rec.lost += 1
            return
        t1 = self.clock()
        self.rec.reads.append((r.t_submit, t1, r.vid, len(block)))
        if r.sampled:
            self.rec.samples.append((r.vid, np.array(block)))

    def _claim_write(self, w: Writer) -> bool:
        """False when the acknowledgement is lost: the writer stops, as
        its next commit would name a parent it never learned."""
        try:
            vid = int(self._call("bench.result", self.srv.result, w.ticket))
        except KeyError:
            self.rec.lost += 1
            return False
        t1 = self.clock()
        self.rec.writes.append((w.t_submit, t1, vid))
        self.rec.acks.append((vid, w.parents, w.idx, w.k - 1))
        n_new = len(w.pending) - len(w.pending_ids)
        w.ids = np.concatenate([w.pending_ids, minted_ids(vid, n_new)])
        w.table, w.parent, w.pending = w.pending, vid, None
        self.newest = max(self.newest, vid)
        return True

    # -- the loop -----------------------------------------------------------------
    def start(self) -> None:
        for r in self.readers:
            self._submit_read(r)
        for w in self.writers:
            self._submit_write(w)

    def step(self) -> None:
        """Claim every answer that is there and, while submitting, send each
        such client's next request; writers first, then readers.  Where no
        answer is there, force the server on (deliver, else flush)."""
        writes, self._ready_writes = self._ready_writes, []
        reads, self._ready_reads = self._ready_reads, []
        for w in writes:
            if self._claim_write(w) and self.submitting:
                self._submit_write(w)
        for r in reads:
            self._claim_read(r)
            if self.submitting:
                self._submit_read(r)
        if not writes and not reads:
            self.rec.forced += self.submitting
            if self._inflight:
                self._call("bench.deliver", self.srv.deliver)
            else:
                self._call("bench.flush", self.srv.flush)

    def outstanding(self) -> int:
        return (len(self._undispatched) + sum(map(len, self._inflight))
                + len(self._ready_reads) + len(self._pending_writes)
                + len(self._ready_writes))

    def drain(self, deadline_s: float = 60.0) -> int:
        """Stop submitting and claim every outstanding answer; returns how
        many never came within ``deadline_s``."""
        self.submitting = False
        t_end = self.clock() + deadline_s
        while self.outstanding() and self.clock() < t_end:
            self.step()
        return self.outstanding()
