"""The CUR cell, ``cur_1m_pinned.merge_commit``: its configuration and mix
are what the harness drives, a tiny run of it through the real
``BatchedCheckoutServer`` (merges in the table form, no adapter) comes
out correct, twins included, and the readers of its merge and refresh
counters report them per commit wave."""
from types import SimpleNamespace

import pytest

from bench import harness, spec
from bench.tests.test_bench_merge import twin_records
from bench.tests.test_bench_run import tiny

CELL = "cur_1m_pinned.merge_commit"


@pytest.fixture(scope="module")
def watch():
    return harness.CompileWatch()


def test_the_cur_configuration_is_one_the_harness_drives():
    bench = spec.load_benchmark()
    cfg = spec.config(bench, "cur_1m_pinned")
    harness.check_config(cfg)
    assert cfg["deployment"]["kind"] == "CUR"
    assert cfg["deployment"]["budget_frac"] is None
    assert cfg["reduced"] == []
    assert spec.cell(bench, CELL)["chips"] == 1


def test_the_merge_mix_is_read_commit_merging_every_other_commit():
    mix, base = spec.traffic("merge_commit"), spec.traffic("read_commit")
    assert mix["merge_every"] == 2
    assert {k: v for k, v in mix.items() if k not in ("about",
                                                      "merge_every")} \
        == {k: v for k, v in base.items() if k not in ("about",
                                                       "merge_every")}


@pytest.mark.parametrize("twins", [False, True])
def test_a_tiny_cur_run_through_the_server_is_correct(watch, tmp_path,
                                                      monkeypatch, twins):
    if twins:
        monkeypatch.setattr(harness, "make_deployment",
                            twin_records(harness.make_deployment))
    cfg, mix = tiny("cur_1m_pinned", "merge_commit")
    assert mix["merge_every"] == 2 and not cfg["server"]["use_kernel"]
    out = harness.run_cell({"name": CELL}, cfg, mix, seed=2**31 + 91,
                           seconds=0.5, work_dir=tmp_path, t_start=0.0,
                           watch=watch, emit=lambda s: None)
    values = out["checks"].values
    assert values == {"unanswered": 0, "wrong_blocks": 0,
                      "wrong_commits": 0, "acks_without_fsync": 0}
    assert out["checks"].correct
    assert out["commit_parts"] == {"order": 0, "readback": 0, "journal": 0}
    merges = sum(len(parents) > 1 for _, parents, _, _ in out["acks"])
    assert merges >= 4
    assert out["ctx"].stats["merges_ingested"] > 0


def ctx(**stats):
    return SimpleNamespace(stats=stats, call_s=0.0, trace=None)


@pytest.mark.parametrize("name,counter,scale", [
    ("ingest.merges_per_wave", "merges_ingested", 1),
    ("ingest.merge_ms_per_wave", "merge_s", 1e3),
    ("ingest.refresh_h2d_bytes_per_wave", "refresh_h2d_bytes", 1),
])
def test_the_merge_and_refresh_readers(name, counter, scale):
    read = spec.reader(name)
    assert read(ctx(commit_waves=4, **{counter: 6})) \
        == pytest.approx(6 / 4 * scale)
    assert read(ctx(commit_waves=0, **{counter: 6})) is None
    assert read(ctx(commit_waves=4)) is None        # a program without it
