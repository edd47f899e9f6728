"""The SCI / CUR versioning-benchmark generator (Maddox et al., as used by
OrpheusDB §5.1), kept with the benchmark so that the data a cell measures
does not move when the program's own generator changes.

Same algorithm and random stream as the program's ``core.bench_gen``: the
same seed gives the same record pool and the same version history.  The
result is plain numpy (no program types): each version's sorted rid list,
its parents, the record pool, and the branch tips.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class History:
    rlists: list[np.ndarray]        # per version: sorted int64 rids
    parents: list[tuple[int, ...]]  # per version: parent vids
    data: np.ndarray                # (n_records, n_attrs) int32 record pool
    tips: list[int]                 # mainline and branch tips, newest first

    @property
    def n_versions(self) -> int:
        return len(self.rlists)

    @property
    def n_records(self) -> int:
        return len(self.data)

    @property
    def n_edges(self) -> int:
        return int(sum(len(r) for r in self.rlists))


def _new_rows(rng: np.random.Generator, count: int, n_attrs: int,
              start_pk: int) -> np.ndarray:
    rows = rng.integers(0, 1000, size=(count, n_attrs), dtype=np.int32)
    rows[:, 0] = np.arange(start_pk, start_pk + count, dtype=np.int32)
    rows[:, 1] = rng.integers(0, 1 << 20, size=count, dtype=np.int32)
    return rows


def generate(kind: str = "SCI", n_versions: int = 100, inserts: int = 100,
             n_branches: int = 10, n_attrs: int = 20, seed: int = 0,
             update_frac: float = 0.5, delete_frac: float = 0.02,
             merge_every: int = 8) -> History:
    """SCI: a mainline with branches forked from it or from other branches
    (a tree).  CUR: branches also merge back into the mainline (a DAG).
    Each version derives from its parent by ``inserts`` inserts,
    ``inserts * update_frac`` updates (a fresh rid replacing an old one)
    and ``delete_frac`` of its parent's rows deleted."""
    if kind not in ("SCI", "CUR"):
        raise ValueError(f"unknown versioning benchmark {kind!r}")
    rng = np.random.default_rng(seed)
    chunks: list[np.ndarray] = []
    rlists: list[np.ndarray] = []
    parents: list[tuple[int, ...]] = []
    next_rid = 0

    def alloc(count: int) -> np.ndarray:
        nonlocal next_rid
        chunks.append(_new_rows(rng, count, n_attrs, next_rid))
        rids = np.arange(next_rid, next_rid + count, dtype=np.int64)
        next_rid += count
        return rids

    rlists.append(alloc(max(inserts, 1)))
    parents.append(())
    mainline = 0
    branch_tips: list[int] = []
    for step in range(1, n_versions):
        u = rng.random()
        want_branch = (len(branch_tips) < n_branches
                       and u < (n_branches / max(n_versions, 1)) * 2.0)
        if kind == "CUR" and branch_tips and step % merge_every == 0:
            tip = branch_tips.pop(int(rng.integers(0, len(branch_tips))))
            merged = np.union1d(rlists[mainline], rlists[tip])
            rlists.append(np.union1d(merged, alloc(max(1, inserts // 4))))
            parents.append((mainline, tip))
            mainline = len(rlists) - 1
            continue
        bi = -1
        if want_branch:
            src = mainline if (not branch_tips or rng.random() < 0.7) \
                else branch_tips[int(rng.integers(0, len(branch_tips)))]
        elif branch_tips and rng.random() < 0.5:
            bi = int(rng.integers(0, len(branch_tips)))
            src = branch_tips[bi]
        else:
            src = mainline
        base = rlists[src]
        n_upd = int(inserts * update_frac)
        n_del = max(0, int(len(base) * delete_frac))
        keep = base
        if n_del and len(base) > n_del:
            keep = np.delete(base, rng.choice(len(base), size=n_del,
                                              replace=False))
        if n_upd and len(keep) > n_upd:
            keep = np.delete(keep, rng.choice(len(keep), size=n_upd,
                                              replace=False))
            upd = alloc(n_upd)
        else:
            upd = np.zeros(0, dtype=np.int64)
        ins = alloc(inserts)
        rlists.append(np.union1d(np.union1d(keep, upd), ins))
        parents.append((src,))
        vid = len(rlists) - 1
        if want_branch:
            branch_tips.append(vid)
        elif src == mainline:
            mainline = vid
        else:
            branch_tips[bi] = vid
    data = (np.concatenate(chunks, axis=0) if chunks
            else np.zeros((0, n_attrs), np.int32))
    tips = sorted({mainline, *branch_tips}, reverse=True)
    return History(rlists=rlists, parents=parents, data=data, tips=tips)
