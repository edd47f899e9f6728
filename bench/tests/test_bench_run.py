"""Whole runs at a tiny size on the CPU, through the same harness the
chip runs take, with the served path sound and then broken: every fault
the cells can have, and the control, must come out as not correct.  And
``run.py`` refuses a backend that is not a TPU."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import control, harness, spec


@pytest.fixture(scope="module")
def watch():
    return harness.CompileWatch()


def tiny(config: str, mix: str):
    with open(spec.BENCH / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    cfg["deployment"].update(n_versions=30, inserts=20)
    m = spec.traffic(mix)
    # the kernel tier runs in interpret mode here; a commit mix grows the
    # store on every wave, so every wave compiles anew: those runs take the
    # host tier, whose semantics are the same
    cfg["server"].update(max_wave=4, use_kernel=not m["writers"])
    m.update(readers=8, writers=4 if m["writers"] else 0, check_share=1.0,
             warmup_waves=2, readback=2)
    if m["writers"]:
        m["commit"].update(updates=5, inserts=10)
    return cfg, m


def run(watch, tmp_path, config, mix, factory=None, seed=2**31 + 5):
    cfg, m = tiny(config, mix)
    out = harness.run_cell({"name": f"{config}.{mix}"}, cfg, m, seed=seed,
                           seconds=0.3, work_dir=tmp_path, t_start=0.0,
                           watch=watch, server_factory=factory,
                           emit=lambda s: None)
    return out["checks"], out


def broken(method):
    from repro.serve import BatchedCheckoutServer

    class Broken(BatchedCheckoutServer):
        def result(self, ticket):
            return method(self, super().result(ticket), ticket)
    return Broken


def _stale(self, out, ticket):
    """Reads: the answer of the request before, as if nothing moved."""
    prev = getattr(self, "_prev", None)
    if getattr(out, "ndim", 0) == 2:
        self._prev = out
        return out if prev is None else prev
    return out


def _lose_half(self, out, ticket):
    if ticket % 2:
        raise KeyError(ticket)
    return out


def _alter(self, out, ticket):
    if getattr(out, "ndim", 0) == 2 and len(out):
        out = np.array(out)
        out[len(out) // 2, 1] += 1
    return out


def _unapplied_commit(self, out, ticket):
    """Writes: acknowledged, but the store is as before."""
    return out - 1 if getattr(out, "ndim", 2) == 0 else out


def test_a_sound_read_run_is_correct(watch, tmp_path):
    checks, out = run(watch, tmp_path, "sci_1m_pinned", "zipf_read")
    assert checks.correct, checks.report()
    assert out["attempted"] > 0 and out["failed"] == 0
    ctx = out["ctx"]
    assert ctx.reads and ctx.waves
    assert spec.reader("jit.compiles_per_wave")(ctx) >= 0
    assert spec.reader("checkout_rows_per_s")(ctx) > 0


def test_a_sound_commit_run_is_correct(watch, tmp_path):
    checks, out = run(watch, tmp_path, "sci_1m_quarter", "read_commit")
    assert checks.correct, checks.report()
    assert out["rec"].acks and out["ctx"].writes


@pytest.mark.parametrize("fault,mix,number", [
    (_stale, "zipf_read", "wrong_blocks"),
    (_lose_half, "zipf_read", "unanswered"),
    (_alter, "zipf_read", "wrong_blocks"),
    (_unapplied_commit, "read_commit", "wrong_commits"),
])
def test_each_fault_comes_out_not_correct(watch, tmp_path, fault, mix,
                                          number):
    checks, _ = run(watch, tmp_path, "sci_1m_pinned", mix, broken(fault))
    assert not checks.correct
    assert checks.values[number] > 0, checks.report()


@pytest.mark.parametrize("fault,number", [
    (_stale, "wrong_blocks"),
    (_lose_half, "unanswered"),
    (_alter, "wrong_blocks"),
    (_unapplied_commit, "wrong_commits"),
])
def test_each_fault_of_the_quarter_commit_cell_comes_out_not_correct(
        watch, tmp_path, fault, number):
    """``sci_1m_quarter.read_commit``: reads and commits over groups that
    commit waves extend and evict."""
    checks, _ = run(watch, tmp_path, "sci_1m_quarter", "read_commit",
                    broken(fault))
    assert not checks.correct
    assert checks.values[number] > 0, checks.report()


@pytest.mark.parametrize("guarantee,mix,numbers", [
    ("exact", "zipf_read", ["wrong_blocks"]),
    ("durable", "read_commit", ["wrong_commits", "acks_without_fsync"]),
])
def test_the_control_comes_out_not_correct(watch, tmp_path, guarantee, mix,
                                           numbers):
    checks, _ = run(watch, tmp_path, "sci_1m_pinned", mix,
                    control.control_server(guarantee))
    assert not checks.correct
    assert all(checks.values[n] > 0 for n in numbers), checks.report()


def test_a_new_mix_runs_from_its_file_alone(watch, tmp_path):
    """A mix added as one data file is driven by the same loop: here
    uniform ranks, which reach versions a Zipf mix seldom reads."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "uniform_read.json").write_text(json.dumps({
        "loop": "closed", "readers": 8, "ranks": {"dist": "uniform"},
        "check_share": 1.0, "warmup_waves": 2}))
    cfg, _ = tiny("sci_1m_pinned", "zipf_read")
    mix = spec.traffic("uniform_read", tmp_path)
    out = harness.run_cell({"name": "sci_1m_pinned.uniform_read"}, cfg, mix,
                           seed=2**31 + 9, seconds=0.3, work_dir=tmp_path,
                           t_start=0.0, watch=watch, emit=lambda s: None)
    assert out["checks"].correct, out["checks"].report()
    vids = {vid for _, _, vid, _ in out["ctx"].reads}
    assert len(out["ctx"].reads) >= 4 and len(vids) >= 3


def test_run_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.monotonic()
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "sci_1m_pinned.zipf_read", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == "" and "TPU" in p.stderr
    assert time.monotonic() - t < 300


def test_each_seed_draws_its_own_window_and_one_warm_up():
    """The warm-up streams are the same for every seed, so set-up compiles
    the same programs; the window's ranks and samples come from the seed,
    afresh for every request, with no cycle."""
    from types import SimpleNamespace
    from bench.reference import Ranks
    dep = SimpleNamespace(hist=SimpleNamespace(n_versions=200, tips=[]))
    mix = dict(spec.traffic("zipf_read"), readers=4)
    ranks = Ranks(mix["ranks"], 200)

    def draws(seed):
        readers, window, _ = harness.make_clients(dep, mix, seed)
        warm = [[ranks.draw(r.rng) for _ in range(64)] for r in readers]
        return warm, [[ranks.draw(g) for _ in range(64)] for g in window]

    (w1, a), (w2, b), (_, a2) = draws(7), draws(2**31 + 11), draws(7)
    assert w1 == w2 and a == a2 and a != b
    for stream in a:
        assert stream[:32] != stream[32:]      # no cycle