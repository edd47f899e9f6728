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
    """Apply every acknowledged commit to the reference in vid order;
    False when the vids are not exactly ``n0, n0 + 1, ...``.  A commit
    whose vid or parent is out of that order is not applied."""
    ok = True
    for i, (vid, parent, widx, k) in enumerate(sorted(acks)):
        keep, new_rows = writers[widx].edits[k]
        if vid != n0 + i or not 0 <= parent < len(ref.rlists):
            ok = False
            continue
        ref.commit(parent, keep, new_rows)
    return bool(ok)


def _differs(ref, vid: int, block) -> bool:
    """True unless ``block`` is exactly version ``vid`` of the reference;
    a vid the reference does not hold differs."""
    if not 0 <= vid < len(ref.rlists):
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
    """Check the versions out through the server: [(vid, block)]."""
    if not vids:
        return []
    tickets = srv.submit_many(vids)
    srv.flush()
    srv.deliver()
    return [(v, np.array(srv.result(t))) for v, t in zip(vids, tickets)]


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
    """Acknowledged commits the journal misses or holds with another
    parent, another row count or other new rows."""
    bad = 0
    for vid, parent, widx, k in acks:
        c = jrecs.get(vid)
        if c is None:
            bad += 1
            continue
        _, new_rows = writers[widx].edits[k]
        got = _array(c.get("new_rows"))
        rlist = _array(c.get("rlist"))
        bad += not (c.get("parent") == parent and 0 <= vid < len(ref.rlists)
                    and rlist is not None and len(rlist) == ref.size(vid)
                    and got is not None and np.array_equal(got, new_rows))
    return int(bad)
