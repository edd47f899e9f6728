"""The comparison that decides ``correct``: every number here is a count
of answers that disagree with the plain reference, and its limit is 0.

* ``unanswered``: requests sent in the window whose answer never came.
* ``wrong_blocks``: delivered checkouts whose row count differs from the
  reference's, plus sampled blocks (drawn from the seed, copied when they
  were claimed) whose bytes differ.
* ``wrong_commits``: acknowledged commits that read back wrong after the
  window, are missing from the journal or stored there differently, or
  were given vids out of order.
* ``acks_without_fsync``: write waves acknowledged before the journal
  paid an fsync.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"unanswered": 0, "wrong_blocks": 0, "wrong_commits": 0,
          "acks_without_fsync": 0}


class Checks:
    def __init__(self):
        self.values: dict = {}

    def add(self, name: str, value: int) -> None:
        self.values[name] = int(value)

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.values.items())

    def report(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.values.items()}

    def lines(self) -> list[str]:
        return [f"check {k}={v} limit={LIMITS[k]}"
                for k, v in self.values.items()]


def replay_commits(ref, acks, writers: dict, n0: int) -> bool:
    """Apply every acknowledged commit, merges and single-parent commits
    alike, to the reference in vid order; False when the vids are not
    exactly ``n0, n0 + 1, ...``.  A commit whose vid or parents are out of
    that order, or whose kept records the reference cannot place, is not
    applied."""
    ok = True
    for i, (vid, parents, widx, k) in enumerate(sorted(acks)):
        e = writers[widx].edits[k]
        if vid != n0 + i or not all(0 <= p < len(ref.rlists)
                                    for p in parents):
            ok = False
            continue
        if len(parents) == 1:
            ref.commit(parents[0], e.keep, e.new_rows)
            continue
        try:
            ref.merge(parents, e.kept, e.new_rows)
        except (KeyError, ValueError):
            ok = False
    return bool(ok)


def _differs(ref, vid: int, block) -> bool:
    """True unless ``block`` is exactly version ``vid`` of the reference;
    a vid the reference does not hold, or no block, differs."""
    if block is None or not 0 <= vid < len(ref.rlists):
        return True
    want = ref.checkout(vid)
    return not (block.shape == want.shape and np.array_equal(block, want))


def wrong_blocks(ref, reads, samples) -> int:
    n = len(ref.rlists)
    bad = sum(not 0 <= vid < n or rows != ref.size(vid)
              for _, _, vid, rows in reads)
    bad += sum(_differs(ref, vid, block) for vid, block in samples)
    return int(bad)


def readback_sample(acks, n: int, rng) -> list[int]:
    """Each writer's newest acknowledged commit, and ``n`` more drawn from
    the seed."""
    if not acks:
        return []
    newest: dict = {}
    for vid, _, widx, _ in acks:
        newest[widx] = max(vid, newest.get(widx, -1))
    rest = sorted(set(a[0] for a in acks) - set(newest.values()))
    extra = rng.choice(rest, size=min(n, len(rest)), replace=False) \
        if rest else []
    return sorted(set(newest.values()) | {int(v) for v in extra})


def read_back(srv, vids: list[int]) -> list:
    """Check the versions out through the server: [(vid, block)], the
    block None where the server has lost the answer."""
    if not vids:
        return []
    tickets = srv.submit_many(vids)
    srv.flush()
    srv.deliver()
    got = []
    for v, t in zip(vids, tickets):
        try:
            got.append((v, np.array(srv.result(t))))
        except KeyError:
            got.append((v, None))
    return got


def wrong_readback(ref, got) -> int:
    return int(sum(_differs(ref, vid, block) for vid, block in got))


def journal_commits(path: str) -> dict:
    """vid -> the commit as the journal's ``commit.batch`` records hold it."""
    from repro.core.journal import read_records
    recs, _ = read_records(path)
    out = {}
    for r in recs:
        if r.kind == "commit.batch":
            for c in r.payload["commits"]:
                out[int(c["vid"])] = c
    return out


def _array(x):
    if x is None:
        return None
    if isinstance(x, dict):          # the journal's (bytes, dtype, shape)
        return np.frombuffer(x["b"], dtype=x["dt"]).reshape(x["sh"])
    return np.asarray(x)


def journal_mismatches(ref, acks, writers: dict, jrecs: dict) -> int:
    """Acknowledged commits the journal misses or holds with other
    parents, another row count or other new rows.  A single-parent commit
    is journaled under ``parent``, a merge under ``parents``, in the order
    the writer named them (its own version first)."""
    bad = 0
    for vid, parents, widx, k in acks:
        c = jrecs.get(vid)
        if c is None:
            bad += 1
            continue
        new_rows = writers[widx].edits[k].new_rows
        got = _array(c.get("new_rows"))
        rlist = _array(c.get("rlist"))
        named = ([c.get("parent")] if len(parents) == 1
                 else c.get("parents"))
        bad += not (named is not None and list(named) == list(parents)
                    and 0 <= vid < len(ref.rlists)
                    and rlist is not None and len(rlist) == ref.size(vid)
                    and got is not None and np.array_equal(got, new_rows))
    return int(bad)
