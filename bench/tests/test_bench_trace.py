"""The trace reduction, on a trace recorded on one TPU v5e: a 10 s window
of sci_1m_pinned.zipf_read (124 gathers), and on hand-made intervals."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    with gzip.open(DATA / "pinned_zipf_read.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load(str(path))


def test_recorded_window_busy_and_modules(recorded):
    s = tr.summarize(recorded)
    assert s.window_s == pytest.approx(10.062346571, abs=1e-6)
    assert s.busy_s == pytest.approx(2.060893325, abs=1e-6)
    assert s.module_n == {"jit_checkout_wave": 124}
    assert s.module_s["jit_checkout_wave"] == pytest.approx(2.0608954, abs=1e-6)
    assert s.top_ops[0][0] == "checkout_wave"
    assert 0.79 < s.idle_share < 0.80


def test_recorded_idle_gaps_are_labelled_and_longest_first(recorded):
    s = tr.summarize(recorded)
    assert len(s.idle_gaps) == 10
    lengths = [g for _, g in s.idle_gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert all(name.startswith("bench.") for name, _ in s.idle_gaps)
    extra = [tr.Event("compile", s0, 1e-3) for s0 in
             (tr.window_of(recorded)[0] + 1.0,)]
    assert tr.summarize(recorded, extra_spans=extra).busy_s == s.busy_s


def test_names():
    assert tr.module_base("jit_checkout_wave(6986730289551645697)") == \
        "jit_checkout_wave"
    assert tr.op_base("%checkout_wave.4 = s32[340424,128]{1,0} custom-call("
                      "s32[104856] %a)") == "checkout_wave"
    assert tr.op_base("%fusion = (s32[8]) fusion(s32[8] %b)") == "fusion"


def test_union_and_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]
    assert tr.clip([(0, 2), (3, 4), (5, 6)], 1, 3.5) == [(1, 2), (3, 3.5)]


def _trace(ops, window=(0.0, 10.0)):
    plane = "/device:TPU:0"
    return tr.Trace(modules={plane: [tr.Event("jit_f(1)", s, d)
                                     for s, d in ops]},
                    ops={plane: [tr.Event("%f.1 = x", s, d) for s, d in ops]},
                    spans=[tr.Event("bench.window", window[0],
                                    window[1] - window[0]),
                           tr.Event("bench.flush", 2.0, 6.0)])


def test_busy_counts_overlaps_once_and_only_inside_the_window():
    s = tr.summarize(_trace([(-1.0, 2.0), (0.5, 1.0), (9.0, 3.0)]))
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(1.5 + 1.0)
    assert s.idle_share == pytest.approx(0.75)
    # modules that start before the window are not counted
    assert s.module_n == {"jit_f": 2}
    assert s.idle_gaps[0] == ["bench.flush", pytest.approx(9.0 - 1.5)]


def test_a_trace_without_the_window_span_is_refused():
    t = _trace([(1.0, 1.0)])
    t.spans = []
    with pytest.raises(ValueError):
        tr.summarize(t)
