"""The plain reference the benchmark holds the store to, and the seeded
draws its clients make.

``HostReference`` is the store's semantics written out directly: version v
is ``data[rlists[v]]`` (rows in rid order), and a commit appends its new
rows to the record pool and its rlist to the history.  It imports nothing
of the program and takes nothing the program made.

A client names the records of a version by identity, not rid: a record of
the generated pool by its rid, a row a commit added by (the commit's vid,
its position among that commit's new rows), ``minted_ids``.  Rids are
handed out in vid order and, within a commit, in the order of its new
rows, so ordering identities orders rids; the reference alone turns
identities into rids (``rids_of``), on replay.
"""
from __future__ import annotations

import numpy as np

MINTED = 1 << 40     # identities from here on name rows commits added
MINT_SPAN = 1 << 20  # new rows one commit may add


def minted_ids(vid: int, n: int) -> np.ndarray:
    """The identities of the ``n`` new rows of commit ``vid``."""
    if n > MINT_SPAN:
        raise ValueError(f"a commit of {n} new rows: at most {MINT_SPAN}")
    return MINTED + vid * MINT_SPAN + np.arange(n, dtype=np.int64)


class HostReference:
    """version v = ``data[rlists[v]]``; a commit appends its new rows (fresh
    rids, in the order given) and its rlist."""

    def __init__(self, rlists, data: np.ndarray):
        self.rlists = [np.asarray(r, dtype=np.int64) for r in rlists]
        self._chunks = [np.asarray(data)]
        self._data = self._chunks[0]
        self.n_records = len(data)
        self.first_rid: dict[int, int] = {}   # vid -> rid of its 1st new row

    @property
    def data(self) -> np.ndarray:
        if len(self._chunks) > 1:
            self._chunks = [np.concatenate(self._chunks, axis=0)]
        self._data = self._chunks[0]
        return self._data

    def size(self, vid: int) -> int:
        return len(self.rlists[vid])

    def checkout(self, vid: int) -> np.ndarray:
        return self.data[self.rlists[vid]]

    def commit(self, parent: int, keep: np.ndarray,
               new_rows: np.ndarray) -> int:
        """Version = the parent's rows at positions ``keep`` (in the
        parent's order) followed by ``new_rows``."""
        return self._append(self.rlists[parent][keep], new_rows)

    def merge(self, parents, kept: np.ndarray, new_rows: np.ndarray) -> int:
        """Version = the records ``kept`` (identities, in checkout order)
        of the union of the parents' records, followed by ``new_rows``.
        A record both parents hold is one record; value-equal rows of two
        records are two.  Raises ValueError where a kept record is in no
        parent, or the identities are not in rid order; KeyError where one
        names a commit not replayed."""
        rids = self.rids_of(kept)
        union = np.unique(np.concatenate([self.rlists[p] for p in parents]))
        if not np.isin(rids, union).all():
            raise ValueError("merge keeps a record no parent holds")
        if np.any(np.diff(rids) <= 0):
            raise ValueError("merge's kept identities are not in rid order")
        return self._append(rids, new_rows)

    def rids_of(self, ids: np.ndarray) -> np.ndarray:
        """The rids of records named by identity (``minted_ids``)."""
        ids = np.asarray(ids, dtype=np.int64)
        out = ids.copy()
        new = ids >= MINTED
        vid, pos = np.divmod(ids[new] - MINTED, MINT_SPAN)
        vids, inv = np.unique(vid, return_inverse=True)
        first = np.array([self.first_rid[int(v)] for v in vids], np.int64)
        out[new] = first[inv] + pos
        return out

    def _append(self, kept_rids: np.ndarray, new_rows: np.ndarray) -> int:
        vid = len(self.rlists)
        self.first_rid[vid] = self.n_records
        rids = np.arange(self.n_records, self.n_records + len(new_rows),
                         dtype=np.int64)
        self._chunks.append(np.asarray(new_rows))
        self.n_records += len(new_rows)
        self.rlists.append(np.concatenate([kept_rids, rids]))
        return vid


class Ranks:
    """A rank distribution over ``support`` versions, rank 0 the newest,
    drawn one request at a time: ``{"dist": "zipf", "a": a}`` gives
    P(rank r) proportional to ``(r + 1) ** -a``, ``{"dist": "uniform"}``
    every rank alike."""

    DISTS = ("zipf", "uniform")

    def __init__(self, params: dict, support: int):
        self.check(params)
        r = np.arange(1, support + 1, dtype=np.float64)
        w = r ** -params["a"] if params["dist"] == "zipf" else np.ones(support)
        self.cdf = np.cumsum(w / w.sum())

    @classmethod
    def check(cls, params: dict) -> None:
        dist = params.get("dist") if isinstance(params, dict) else None
        keys = {"dist", "a"} if dist == "zipf" else {"dist"}
        if dist not in cls.DISTS or set(params) != keys:
            raise ValueError(f"ranks {params!r}: give {{'dist': 'zipf', "
                             f"'a': ..}} or {{'dist': 'uniform'}}")

    def draw(self, rng: np.random.Generator) -> int:
        i = int(np.searchsorted(self.cdf, rng.random(), side="right"))
        return min(i, len(self.cdf) - 1)


def edit_table(rng: np.random.Generator, table: np.ndarray, *,
               delete_frac: float, updates: int, inserts: int,
               next_pk: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One SCI-style edit of ``table`` (a version's rows in checkout order):
    ``delete_frac`` of its rows deleted, ``updates`` rows changed in a
    non-key attribute (a new row replacing the old one) and ``inserts``
    new rows with fresh keys from ``next_pk`` on.

    Returns (keep, new_rows, new_table): the positions of ``table`` that
    survive unchanged, the updated rows followed by the inserted ones, and
    the new version's rows in checkout order (kept rows, then new rows).
    Every new row differs from every row of ``table``: an inserted row has
    a new key, and an updated row's third attribute moves on past the
    value of any row of ``table`` under its key.  Where ``table`` holds
    value-equal rows (two records, as a merge can bring together), the
    ones kept are the first of them in checkout order.  Neither rule draws
    anything, and neither changes an edit of a table whose keys are
    distinct."""
    n = len(table)
    n_del = int(n * delete_frac)
    gone = rng.choice(n, size=n_del + updates, replace=False)
    keep = np.ones(n, bool)
    keep[gone] = False
    upd = table[np.sort(gone[n_del:])].copy()
    upd[:, 2] = (upd[:, 2] + rng.integers(1, 1000, size=updates,
                                          dtype=np.int32)) % 1000
    ins = rng.integers(0, 1000, size=(inserts, table.shape[1]),
                       dtype=np.int32)
    ins[:, 0] = np.arange(next_pk, next_pk + inserts, dtype=np.int32)
    ins[:, 1] = rng.integers(0, 1 << 20, size=inserts, dtype=np.int32)
    _fresh(table, upd)
    keep = np.flatnonzero(_first_of_equals(table, keep))
    new_rows = np.concatenate([upd, ins])
    return keep, new_rows, np.concatenate([table[keep], new_rows])


def _fresh(table: np.ndarray, upd: np.ndarray) -> None:
    """Move each updated row's third attribute on (mod 1000) until the row
    equals no row of ``table``; only rows under the same key can."""
    same_key = table[np.isin(table[:, 0], upd[:, 0])]
    if len(same_key) == len(np.unique(same_key[:, 0])):
        return                   # one row a key: the update already differs
    rows = {r.tobytes() for r in same_key}
    for r in upd:
        while r.tobytes() in rows:
            r[2] = (r[2] + 1) % 1000


def _first_of_equals(table: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """``keep`` (a mask over ``table``) with each class of value-equal rows
    keeping as many rows as before, the first ones in table order."""
    keys, counts = np.unique(table[:, 0], return_counts=True)
    dup = np.flatnonzero(np.isin(table[:, 0], keys[counts > 1]))
    if not len(dup):
        return keep
    keep = keep.copy()
    classes: dict = {}
    for i in dup:
        classes.setdefault(table[i].tobytes(), []).append(i)
    for pos in classes.values():
        kept = int(keep[pos].sum())
        keep[pos] = False
        keep[pos[:kept]] = True
    return keep
