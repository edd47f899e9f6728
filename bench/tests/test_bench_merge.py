"""Merge commits in the benchmark's traffic: the mix key, the draws of the
mixes that have no merges, the configuration keys the harness refuses,
and whole tiny CUR runs on the CPU through server adapters that land a
merge in the store's explicit commit form (``rlist`` and ``new_rows``)."""
import hashlib
import json

import numpy as np
import pytest

from bench import harness, spec
from bench.driver import ClosedLoop, parse_mix
from bench.reference import Ranks
from bench.tests.test_bench_run import tiny


@pytest.fixture(scope="module")
def watch():
    return harness.CompileWatch()


def host_tier(kind="SCI"):
    """The tiny configuration of ``test_bench_run`` on the host tier."""
    cfg, _ = tiny("sci_1m_pinned", "read_commit")
    cfg["deployment"]["kind"] = kind
    return cfg


# -- the mix key ------------------------------------------------------------
WRITE = {"writers": 4, "commit": {"delete_frac": 0.02, "updates": 5,
                                  "inserts": 10}}


@pytest.mark.parametrize("change,ok", [
    (dict(WRITE, merge_every=2), True),
    (dict(WRITE, merge_every=0), True),
    (dict(WRITE, merge_every=-1), False),          # negative
    (dict(WRITE, merge_every=1.5), False),         # not a whole number
    (dict(WRITE, merge_every="2"), False),
    (dict(WRITE, merge_every=True), False),
    (dict(WRITE, writers=1, merge_every=2), False),  # one writer
    ({"merge_every": 2}, False),                   # no writers at all
])
def test_parse_mix_takes_merge_every_only_as_it_can_drive_it(change, ok):
    raw = json.loads((spec.BENCH / "traffic" / "zipf_read.json").read_text())
    if ok:
        assert parse_mix(raw | change)["merge_every"] == change["merge_every"]
    else:
        with pytest.raises(ValueError):
            parse_mix(raw | change)


# -- today's mixes draw what they drew --------------------------------------
# sha256 over the first 64 requests the loop submits (reader vids; each
# commit's parent and table bytes, so its kept rows and new rows) on the
# commit before merges existed, at the size below, seed 2**31 + 5
PARENT_DIGESTS = {
    "zipf_read":
        "7ca5e051a7338bc03a96747d98aac3b635c57d57d277d90d3057f1ea34c9b8d9",
    "read_commit":
        "6c8b55596d40153f1a3a3167d53f6fa8153ac64b05e3a7726bde15258a1d658c",
}


def first_requests(mix_name, seed=2**31 + 5, n=64):
    from repro.serve import BatchedCheckoutServer
    cfg, (_, mix) = host_tier(), tiny("sci_1m_pinned", mix_name)
    seen = []

    class Recording(BatchedCheckoutServer):
        def submit_many(self, vids):
            seen.append(("r", [int(v) for v in vids]))
            return super().submit_many(vids)

        def submit_commit(self, commits):
            for c in commits:
                seen.append(("w", int(c["parent"]), hashlib.sha256(
                    np.ascontiguousarray(c["table"]).tobytes()).hexdigest()))
            return super().submit_commit(commits)

    dep = harness.make_deployment(cfg, seed)
    readers, window, writers = harness.make_clients(dep, mix, seed)
    srv = harness.start_server(harness.build_store(dep, cfg), cfg, Recording)
    loop = ClosedLoop(srv, readers, writers, newest=dep.hist.n_versions - 1,
                      ranks=Ranks(mix["ranks"], dep.hist.n_versions),
                      check_share=mix["check_share"],
                      commit_edit=mix["commit"],
                      merge_every=mix["merge_every"])
    loop.start()
    while (len(loop.rec.waves) < mix["warmup_waves"]
           or loop.rec.commit_waves < mix["warmup_commit_waves"]):
        loop.step()
    for r, rng in zip(readers, window):
        r.rng = rng
    while len(seen) < n:
        loop.step()
    srv.close()
    return seen[:n]


@pytest.mark.parametrize("mix", sorted(PARENT_DIGESTS))
def test_todays_mixes_submit_what_they_submitted_before_merges(mix):
    seen = first_requests(mix)
    h = hashlib.sha256()
    for s in seen:
        h.update(repr(s).encode())
    assert h.hexdigest() == PARENT_DIGESTS[mix]
    assert any(s[0] == "w" for s in seen) == (mix == "read_commit")


# -- configuration keys the harness does not act on ------------------------
@pytest.mark.parametrize("section,key,value", [
    ("server", "retry", {"attempts": 3}),
    ("server", "repartition_trigger", {"density": 0.5}),
    ("server", "autoscale", True),
    ("deployment", "key_skew", 1.1),
    ("deployment", "dtype", "int64"),
])
def test_a_setting_the_harness_does_not_act_on_is_refused_by_name(
        watch, tmp_path, section, key, value):
    cfg, mix = tiny("sci_1m_pinned", "zipf_read")
    cfg[section][key] = value
    with pytest.raises(ValueError, match=f"{section}.{key}"):
        harness.run_cell({"name": "sci_1m_pinned.zipf_read"}, cfg,
                         mix, seed=3, seconds=0.1,
                         work_dir=tmp_path, t_start=0.0, watch=watch,
                         emit=lambda s: None)


def test_the_benchmarks_configurations_set_only_what_is_acted_on():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        cfg = spec.config(bench, c["name"])
        harness.check_config(cfg)
        assert cfg["server"]["retry"] is None
        assert cfg["server"]["repartition_trigger"] is None


# -- merges through adapters in the explicit commit form -------------------
def _keys(rows):
    rows = np.ascontiguousarray(rows)
    return rows.view(np.dtype((np.void, rows.dtype.itemsize
                               * rows.shape[1]))).ravel()


def multiset_match(table, rows, rids):
    """The contract: ``table``'s rows matched by value against the records
    ``rids`` (rows ``rows``, in rid order), each record used at most once,
    equal values taking the lowest rids first; returns the matched rids
    and the unmatched rows, in table order."""
    table = np.asarray(table, dtype=rows.dtype)
    rk, tk = _keys(rows), _keys(table)
    ro = np.argsort(rk, kind="stable")
    to = np.argsort(tk, kind="stable")
    rs, ts = rk[ro], tk[to]
    occ = np.arange(len(ts)) - np.searchsorted(ts, ts, side="left")
    lo = np.searchsorted(rs, ts, side="left")
    hit = occ < np.searchsorted(rs, ts, side="right") - lo
    new = np.ones(len(table), bool)
    new[to[hit]] = False
    return np.sort(rids[ro[lo[hit] + occ[hit]]]), table[new]


def last_wins(table, rows, rids):
    """Today's table-form match: value-equal records collapse onto one."""
    from repro.core.datamodels import diff_against_parents
    return diff_against_parents(np.asarray(table, dtype=rows.dtype), rows,
                                rids)


def explicit(match):
    """A server that lands every commit, a merge too, in the explicit form:
    its table matched by ``match`` against the union of its parents'
    records, the unmatched rows new records, and ``parent`` the parent
    that shares more records with it."""
    from repro.serve import BatchedCheckoutServer

    class Explicit(BatchedCheckoutServer):
        def _commit(self, commits):
            st = self.store
            n = int(st.graph.n_records)
            out = []
            for c in commits:
                parents = c.get("parents") or [c["parent"]]
                held = [st.graph.rlist(p) for p in parents]
                union = np.unique(np.concatenate(held))
                matched, new_rows = match(c["table"], st.data[union], union)
                shared = [len(np.intersect1d(h, matched)) for h in held]
                out.append({"parent": parents[int(np.argmax(shared))],
                            "rlist": np.concatenate(
                                [matched, n + np.arange(len(new_rows))]),
                            "new_rows": new_rows})
                n += len(new_rows)
            return super()._commit(out)
    return Explicit


def twin_records(make):
    """``harness.make_deployment`` with value-equal records under different
    rids: a record only the first writer's tip holds (of the four tips the
    writers start from) copied onto one only the second's holds, so only
    a merge brings both into one version."""
    def deployment(cfg, seed):
        dep = make(cfg, seed)
        held = [set(dep.hist.rlists[t]) for t in dep.hist.tips[:4]]
        only = [sorted(h - set().union(*(o for o in held if o is not h)))
                for h in held[:2]]
        n = min(len(only[0]), len(only[1]), 20)
        assert n >= 5
        dep.data[only[1][:n]] = dep.data[only[0][:n]]
        return dep
    return deployment


def merge_run(watch, tmp_path, match, twins, monkeypatch, seed=2**31 + 77):
    if twins:
        monkeypatch.setattr(harness, "make_deployment",
                            twin_records(harness.make_deployment))
    _, mix = tiny("sci_1m_pinned", "read_commit")
    mix["merge_every"] = 2
    out = harness.run_cell({"name": "cur_tiny.merge_commit"},
                           host_tier("CUR"), mix, seed=seed, seconds=0.5,
                           work_dir=tmp_path,
                           t_start=0.0, watch=watch,
                           server_factory=explicit(match),
                           emit=lambda s: None)
    acks = out["acks"]
    newest: dict = {}        # each commit names its writer's last one first
    for vid, parents, widx, _ in sorted(acks, key=lambda a: a[2:]):
        assert parents[0] == newest.get(widx, parents[0])
        newest[widx] = vid
    merges = sum(len(parents) == 2 for _, parents, _, _ in acks)
    return out["checks"].values, out["commit_parts"], merges


def test_the_journal_must_name_a_merges_parents_own_first():
    from types import SimpleNamespace
    from bench import check
    from bench.driver import Edit
    from bench.reference import HostReference
    data = np.arange(40, dtype=np.int32).reshape(10, 4)
    ref = HostReference([np.array([1, 2]), np.array([2, 3])], data)
    new = np.full((1, 4), -1, np.int32)
    ref.merge([0, 1], np.array([1, 2, 3]), new)
    ref.commit(2, np.array([0, 3]), new + 1)
    writers = {0: SimpleNamespace(edits=[
        Edit(np.arange(3), new, np.array([1, 2, 3])),
        Edit(np.array([0, 3]), new + 1)])}
    acks = [(2, (0, 1), 0, 0), (3, (2,), 0, 1)]

    def entry(size, rows, **named):
        return dict(named, rlist=np.arange(size), new_rows=rows)
    good = {2: entry(4, new, parents=[0, 1]), 3: entry(3, new + 1, parent=2)}
    assert check.journal_mismatches(ref, acks, writers, good) == 0
    for vid, bad in [(2, entry(4, new, parents=[1, 0])),
                     (2, entry(4, new, parent=0)),
                     (2, entry(3, new, parents=[0, 1])),
                     (3, entry(3, new + 1, parents=[2])),
                     (3, entry(3, new, parent=2))]:
        assert check.journal_mismatches(ref, acks, writers,
                                        good | {vid: bad}) == 1


@pytest.mark.parametrize("twins", [False, True])
def test_a_sound_merge_run_is_wrong_only_where_the_journal_drops_a_parent(
        watch, tmp_path, monkeypatch, twins):
    """Today's journal keeps one ``parent``, so each acknowledged merge is
    one wrong commit, and nothing else is wrong: every checkout, every
    read-back and every single-parent commit agree with the reference."""
    values, parts, merges = merge_run(watch, tmp_path, multiset_match,
                                      twins, monkeypatch)
    assert merges >= 4
    assert values["unanswered"] == 0 and values["wrong_blocks"] == 0
    assert values["acks_without_fsync"] == 0
    assert parts == {"order": 0, "readback": 0, "journal": merges}
    assert values["wrong_commits"] == merges


def test_a_last_wins_match_of_value_equal_records_is_not_correct(
        watch, tmp_path, monkeypatch):
    values, parts, merges = merge_run(watch, tmp_path, last_wins, True,
                                      monkeypatch)
    assert merges >= 4
    assert (values["wrong_blocks"] + parts["readback"] + parts["order"]
            + parts["journal"] - merges) > 0, (values, parts)
