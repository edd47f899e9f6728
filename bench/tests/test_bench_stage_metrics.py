"""The readers of the program's stage counters, on hand-made runs, and
``program_spans``'s relabelling of idle gaps by the program's own spans.

A program that has no stage counters (the fields are missing from its
``CheckoutStats``) reports nothing, as does a run with a zero base."""
from types import SimpleNamespace

import pytest

from bench import program_spans, spec
from bench import trace as tr

STAGE_METRICS = {
    # metric: (counter, base, scale)
    "serve.plan_ms_per_wave": ("plan_s", "waves", 1e3),
    "serve.launch_ms_per_wave": ("launch_s", "waves", 1e3),
    "serve.device_wait_ms_per_wave": ("device_wait_s", "waves_delivered",
                                      1e3),
    "serve.d2h_ms_per_wave": ("d2h_s", "waves_delivered", 1e3),
    "serve.d2h_bytes_per_row": ("d2h_bytes", "rows_served", 1),
    "groups.pin_ms_per_wave": ("pin_s", "group_waves", 1e3),
    "groups.straggler_ms_per_wave": ("straggler_s", "group_waves", 1e3),
    "groups.h2d_bytes_per_wave": ("h2d_bytes", "group_waves", 1),
    "ingest.stage_ms_per_wave": ("ingest_stage_s", "commit_waves", 1e3),
    "ingest.journal_ms_per_wave": ("journal_s", "commit_waves", 1e3),
    "ingest.refresh_ms_per_wave": ("refresh_s", "commit_waves", 1e3),
}
STAGES = ("plan_s", "launch_s", "pin_s", "straggler_s", "device_wait_s",
          "d2h_s", "ingest_stage_s", "journal_s", "refresh_s")
BASES = dict(waves=10, waves_delivered=8, rows_served=4000, group_waves=5,
             commit_waves=2)


def ctx(stats, call_s=0.0):
    return SimpleNamespace(stats=stats, call_s=call_s, trace=None)


def read(name, c):
    return spec.reader(name)(c)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_a_stage_counter_over_its_base(name):
    counter, base, scale = STAGE_METRICS[name]
    stats = dict(BASES, **{counter: 0.8})
    assert read(name, ctx(stats)) == pytest.approx(0.8 / BASES[base] * scale)


@pytest.mark.parametrize("name", sorted(STAGE_METRICS)
                         + ["serve.self_ms_per_wave"])
def test_no_base_or_no_counter_reports_nothing(name):
    full = dict(BASES, **{k: 0.5 for k in STAGES}, h2d_bytes=1, d2h_bytes=1)
    counter, base, _ = STAGE_METRICS.get(
        name, ("plan_s", "waves_delivered", 1))
    assert read(name, ctx(dict(full, **{base: 0}), call_s=9.0)) is None
    parent = {k: v for k, v in full.items() if k != counter}
    assert read(name, ctx(parent, call_s=9.0)) is None


def test_self_time_is_call_time_less_every_stage():
    stats = dict(BASES, **{k: 0.1 * (i + 1) for i, k in enumerate(STAGES)})
    staged = sum(0.1 * (i + 1) for i in range(len(STAGES)))
    got = read("serve.self_ms_per_wave", ctx(stats, call_s=6.0))
    assert got == pytest.approx((6.0 - staged) / 8 * 1e3)


def test_every_new_metric_is_declared_for_the_cells_that_count_it():
    bench = spec.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in list(STAGE_METRICS) + ["serve.self_ms_per_wave"]:
        m = per_layer[name]
        assert m["source"] == "program_counter"
        cells = set(m["workloads"])
        if name.startswith("groups."):
            assert cells == {"sci_1m_quarter.zipf_read",
                             "sci_1m_quarter.read_commit"}
        elif name.startswith("ingest."):
            assert cells == {"sci_1m_pinned.read_commit",
                             "sci_1m_quarter.read_commit"}
        else:
            assert cells == {w["name"] for w in bench["workloads"]}


def _trace():
    plane = "/device:TPU:0"
    ops = [(0.5, 0.5), (4.0, 1.0), (8.0, 0.5)]
    return tr.Trace(
        modules={plane: [tr.Event("jit_f(1)", s, d) for s, d in ops]},
        ops={plane: [tr.Event("%f.1 = x", s, d) for s, d in ops]},
        spans=[tr.Event("bench.window", 0.0, 10.0),
               tr.Event("bench.submit", 1.0, 2.9),
               tr.Event("bench.submit_commit", 5.0, 2.9)])


def test_program_spans_relabel_the_gaps_inside_calls():
    prog = [tr.Event("serve.flush", 1.0, 2.9),
            tr.Event("checkout.launch", 1.2, 2.5),
            tr.Event("serve.flush", 5.0, 2.9),
            tr.Event("ingest.commit_many", 5.0, 2.9),
            tr.Event("ingest.stage", 5.1, 2.0),
            tr.Event("ingest.refresh", 7.2, 0.6)]
    out = program_spans.relabel(_trace(), prog, top=4)
    # gaps: [1.0, 4.0) mid 2.5, [5.0, 8.0) mid 6.5, [0, 0.5), [8.5, 10)
    assert out["idle_gaps"] == [
        ["checkout.launch", pytest.approx(3.0), "bench.submit"],
        ["ingest.stage", pytest.approx(3.0), "bench.submit_commit"],
        ["bench.client", pytest.approx(1.5), "bench.client"],
        ["bench.client", pytest.approx(0.5), "bench.client"]]
    assert out["span_n"]["serve.flush"] == 2
    assert out["span_s"]["ingest.stage"] == pytest.approx(2.0)
    assert out["busy_s"] == pytest.approx(2.0)


def test_program_spans_names_come_from_the_program():
    names = program_spans.span_names()
    assert "serve.flush" in names and "ingest.refresh" in names
    out = program_spans.relabel(_trace(), [], top=2)
    assert [g[0] for g in out["idle_gaps"]] == [g[2] for g in
                                                out["idle_gaps"]]
