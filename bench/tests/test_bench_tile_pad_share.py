"""The reader of ``serve.tile_pad_share`` on hand-made runs: the ladder's
pad tiles over every tile gathered, nothing where the program has no such
counters or gathered nothing, and the metric declared for every cell."""
from types import SimpleNamespace

import pytest

from bench import spec

NAME = "serve.tile_pad_share"


def read(stats):
    return spec.reader(NAME)(SimpleNamespace(stats=stats, trace=None))


@pytest.mark.parametrize("tiles,pad,want", [
    (900, 100, 10.0), (1000, 0, 0.0), (7, 1, 12.5)])
def test_pad_tiles_over_every_tile_gathered(tiles, pad, want):
    assert read({"waves": 3, "tiles": tiles,
                 "pad_tiles": pad}) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    {"waves": 3},                                   # a program without
    {"waves": 3, "tiles": 40},                      # the ladder's counters
    {"waves": 0, "tiles": 0, "pad_tiles": 0},       # nothing gathered
])
def test_nothing_to_read_reports_nothing(stats):
    assert read(stats) is None


def test_declared_for_every_cell_under_the_launch_layer():
    bench = spec.load_benchmark()
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    launch = {m["name"]: m for m in bench["per_layer"]}["jit.compiles_per_wave"]
    assert m["layer"] == launch["layer"]
    assert m["source"] == "program_counter" and m["unit"] == "%"
    assert set(m["workloads"]) == {w["name"] for w in bench["workloads"]}
