"""Everything a cell needs is found by name from files alone, so a later
change adds a configuration, a traffic mix or a metric with new files and
new BENCHMARK.json entries, and edits nothing; and BENCHMARK.json keeps to
the shape the benchmark's contract sets."""
import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_named_piece_has_its_file(bench):
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cfg = spec.config(bench, w["config"])
        assert cfg["name"] == w["config"]
        mix = spec.traffic(w["traffic"])
        assert mix["readers"] > 0 and mix["loop"] == "closed"
        for traced in (False, True):
            for m in spec.metrics_for(bench, w["name"], traced):
                assert callable(spec.reader(m["name"]))


def test_benchmark_json_shape(bench):
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in bench["workloads"]}
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        reach = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reach
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert any(m["name"] != "setup_s" for m in bench["end_to_end"]
                   if w["name"] in m.get("workloads", cells))
        assert spec.metrics_for(bench, w["name"], True)
    for c in bench["configs"]:
        assert len(c["source"]) <= 200 and c["file"].startswith("bench/")
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_new_cell_needs_only_new_files(tmp_path, bench):
    """A configuration, a traffic mix and a per-layer metric, all new, are
    found from their files and the entries that name them."""
    root = tmp_path
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "metrics").mkdir()
    cfg = spec.config(bench, bench["configs"][0]["name"])
    cfg["name"] = "sci_5m_pinned"
    cfg["deployment"]["n_versions"] = 5000
    (root / "bench" / "configs" / "sci_5m_pinned.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "uniform_read.json").write_text(
        json.dumps({"loop": "closed", "readers": 4,
                    "ranks": {"dist": "uniform"}, "check_share": 1.0,
                    "warmup_waves": 1}))
    (root / "bench" / "metrics" / "serve.new_ratio.py").write_text(
        "def read(ctx):\n    return ctx.stats.get('waves')\n")
    new = {
        "configs": [{"name": "sci_5m_pinned", "source": "x",
                     "file": "bench/configs/sci_5m_pinned.json",
                     "reduced": [], "why": "x"}],
        "workloads": [{"name": "sci_5m_pinned.uniform_read",
                       "config": "sci_5m_pinned", "traffic": "uniform_read",
                       "chips": 1, "why": "x"}],
        "end_to_end": [], "per_layer": [
            {"name": "serve.new_ratio", "unit": "x", "better": "higher",
             "source": "program_counter", "layer": "serve",
             "moves": "checkout_rows_per_s",
             "workloads": ["sci_5m_pinned.uniform_read"]}]}
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    b = spec.load_benchmark(root)
    w = spec.cell(b, "sci_5m_pinned.uniform_read")
    assert spec.config(b, w["config"], root)["deployment"]["n_versions"] \
        == 5000
    assert spec.traffic(w["traffic"], root / "bench")["readers"] == 4
    (m,) = spec.metrics_for(b, w["name"], traced=True)
    read = spec.reader(m["name"], root / "bench")
    assert read(type("C", (), {"stats": {"waves": 7}})) == 7
    with pytest.raises(KeyError):
        spec.cell(b, "sci_5m_pinned.zipf_read")


def test_every_file_of_the_benchmark_sits_under_its_path(tmp_path):
    src = spec.BENCH
    shutil.copytree(src, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".run", ".jax_cache",
                                                  "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    b = spec.load_benchmark(tmp_path)
    for w in b["workloads"]:
        spec.config(b, w["config"], tmp_path)
        spec.traffic(w["traffic"], tmp_path / "bench")


@pytest.mark.parametrize("change", [
    {"loop": "open"},                       # a loop the driver has not got
    {"cycle": 8},                           # a key it does not read
    {"ranks": {"dist": "pareto", "a": 1}},  # a distribution it cannot draw
    {"writers": 2},                         # writers with no edit to send
])
def test_a_mix_the_driver_cannot_run_is_refused(tmp_path, change):
    raw = json.loads((spec.BENCH / "traffic" / "zipf_read.json").read_text())
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(json.dumps(raw | change))
    with pytest.raises(ValueError):
        spec.traffic("bad", tmp_path)
