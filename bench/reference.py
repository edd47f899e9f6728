"""The plain reference the benchmark holds the store to, and the seeded
draws its clients make.

``HostReference`` is the store's semantics written out directly: version v
is ``data[rlists[v]]`` (rows in rid order), and a commit appends its new
rows to the record pool and its rlist to the history.  It imports nothing
of the program and takes nothing the program made.
"""
from __future__ import annotations

import numpy as np


class HostReference:
    """version v = ``data[rlists[v]]``; a commit appends its new rows (fresh
    rids, in the order given) and its rlist."""

    def __init__(self, rlists, data: np.ndarray):
        self.rlists = [np.asarray(r, dtype=np.int64) for r in rlists]
        self._chunks = [np.asarray(data)]
        self._data = self._chunks[0]
        self.n_records = len(data)

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
        rids = np.arange(self.n_records, self.n_records + len(new_rows),
                         dtype=np.int64)
        self._chunks.append(np.asarray(new_rows))
        self.n_records += len(new_rows)
        self.rlists.append(np.concatenate([self.rlists[parent][keep], rids]))
        return len(self.rlists) - 1


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
    Every new row differs from every row of ``table``: an updated row keeps
    its key but not its third attribute, an inserted row has a new key."""
    n = len(table)
    n_del = int(n * delete_frac)
    gone = rng.choice(n, size=n_del + updates, replace=False)
    keep = np.ones(n, bool)
    keep[gone] = False
    keep = np.flatnonzero(keep)
    upd = table[np.sort(gone[n_del:])].copy()
    upd[:, 2] = (upd[:, 2] + rng.integers(1, 1000, size=updates,
                                          dtype=np.int32)) % 1000
    ins = rng.integers(0, 1000, size=(inserts, table.shape[1]),
                       dtype=np.int32)
    ins[:, 0] = np.arange(next_pk, next_pk + inserts, dtype=np.int32)
    ins[:, 1] = rng.integers(0, 1 << 20, size=inserts, dtype=np.int32)
    new_rows = np.concatenate([upd, ins])
    return keep, new_rows, np.concatenate([table[keep], new_rows])
