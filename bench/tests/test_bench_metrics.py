"""The arithmetic of each metric reader, on hand-made runs: rates over all
of the window, percentiles over all requests, the semantic byte count of
the gather's roofline, and silence where a run holds nothing to read."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spec

ALL = ["checkout_rows_per_s", "checkout_p50_ms", "checkout_p95_ms",
       "commit_p95_ms", "setup_s", "serve.requests_per_wave",
       "serve.call_ms_per_wave", "jit.compiles_per_wave",
       "checkout_wave_roofline", "device.idle", "groups.straggler_share",
       "groups.evictions_per_wave", "ingest.commits_per_wave",
       "ingest.segment_append_ms"]


def ctx(**kw):
    base = dict(seconds=4.0, setup_s=30.0, reads=[], writes=[], waves=[],
                stats={}, compiles=0, call_s=0.0, size=lambda v: 0,
                n_attrs=20, itemsize=4, device_kind="TPU v5 lite",
                trace=None)
    base.update(kw)
    return SimpleNamespace(**base)


def read(name, c):
    return spec.reader(name)(c)


def test_rate_is_every_row_over_the_whole_window():
    reads = [(0.0, 0.1, 7, 100), (0.0, 3.9, 8, 300), (1.0, 2.0, 7, 100)]
    assert read("checkout_rows_per_s", ctx(reads=reads)) == 500 / 4.0


def test_percentiles_take_every_request():
    lat = np.arange(1, 101) / 1000.0          # 1..100 ms
    reads = [(1.0, 1.0 + d, 0, 1) for d in lat]
    c = ctx(reads=reads)
    assert read("checkout_p50_ms", c) == pytest.approx(50.5)
    assert read("checkout_p95_ms", c) == pytest.approx(95.05)
    writes = [(0.0, d, 1) for d in lat[::-1]]
    assert read("commit_p95_ms", ctx(writes=writes)) == pytest.approx(95.05)


def test_counter_ratios():
    st = dict(waves=10, waves_delivered=8, requests=160, commit_waves=2,
              commits_ingested=32, group_waves=10, group_evictions=15,
              straggler_requests=40)
    c = ctx(stats=st, call_s=0.8, compiles=3)
    assert read("serve.requests_per_wave", c) == 16
    assert read("serve.call_ms_per_wave", c) == pytest.approx(100.0)
    assert read("jit.compiles_per_wave", c) == pytest.approx(3 / 12)
    assert read("groups.straggler_share", c) == pytest.approx(25.0)
    assert read("groups.evictions_per_wave", c) == pytest.approx(1.5)
    assert read("ingest.commits_per_wave", c) == 16


def test_roofline_counts_each_unique_version_once_from_the_semantics():
    sizes = {3: 1000, 4: 500}
    waves = [[3, 3, 4], [4]]
    semantic_bytes = spec.reader("checkout_wave_roofline").__globals__[
        "semantic_bytes"]
    nbytes = semantic_bytes(waves, sizes.get, 20, 4)
    assert nbytes == 2 * (1500 + 500) * 20 * 4
    trace = SimpleNamespace(module_s={"jit_checkout_wave": 0.001})
    c = ctx(waves=waves, size=sizes.get, trace=trace)
    assert read("checkout_wave_roofline", c) == pytest.approx(
        nbytes / 819e9 / 0.001 * 100)


def test_device_metrics_read_the_trace():
    trace = SimpleNamespace(idle_share=0.75,
                            module_s={"jit_segment_append": 0.3})
    c = ctx(trace=trace, stats=dict(commit_waves=3))
    assert read("device.idle", c) == 75.0
    assert read("ingest.segment_append_ms", c) == pytest.approx(100.0)


@pytest.mark.parametrize("name", ALL)
def test_a_run_with_nothing_to_read_reports_nothing(name):
    value = read(name, ctx())
    assert value is None or name == "setup_s"


def test_an_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("cpu")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
