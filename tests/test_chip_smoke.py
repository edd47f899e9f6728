"""``chip_smoke.py`` at a tiny size on the CPU (kernels interpreted): every
phase's checks pass against the host reference, and the script refuses to
run — printing no result — on a backend that is not a TPU."""
import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve it by name
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def test_phases_pass_at_tiny_size(smoke, tmp_path):
    lines = []
    cfg = smoke.SmokeConfig(n_versions=40, inserts=6, n_branches=4, hot=12,
                            waves=(4, 6), commits=4, scatter=6, seed=1)
    out = smoke.run(cfg, tmp_path / "journal", emit=lines.append)
    assert set(out) == {"a", "b", "c", "d"} and all(out.values())
    assert any(line.startswith("phase d") for line in lines)


def test_refuses_a_non_tpu_backend(smoke, capsys):
    assert smoke.main([]) == 2
    assert capsys.readouterr().out == ""
