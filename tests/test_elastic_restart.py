"""End-to-end fault-tolerance integration: train, kill, resume from the
checkpoint CVD, and elastically restore onto a different mesh shape —
verifying bit-exact state round-trips and replay-free data cursors."""
import dataclasses
import os
import pathlib

import jax
import numpy as np
import pytest

from repro.core import generate, lyresplit_for_budget, to_tree
from repro.data import VersionedDataset
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.transformer import ArchConfig, param_specs
from repro.sharding import logical_to_sharding, make_ctx
from repro.train import AdamW, CheckpointStore, make_train_step
from repro.train.ft import resume_latest

REPO = pathlib.Path(__file__).resolve().parents[1]

TINY = ArchConfig(name="tiny-ft", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv=2, d_ff=128, vocab=256, head_dim=16,
                  tie_embeddings=True, remat=False, microbatches=1)


def _dataset(seq=32):
    w = generate("SCI", n_versions=6, inserts=300, n_branches=2,
                 n_attrs=seq + 1, seed=3)
    tree, _ = to_tree(w.graph, w.vgraph)
    sr = lyresplit_for_budget(tree, gamma=2.0 * w.n_records)
    return VersionedDataset.from_graph(w.graph, w.data % TINY.vocab,
                                       sr.best.assignment, seq_len=seq), \
        w.n_versions - 1


def _run(steps, start, params, state, step_fn, ds, vid):
    losses = []
    for b in ds.batches(vid=vid, global_batch=4, seed=7, start_step=start,
                        n_steps=steps - start):
        params, state, m = step_fn(params, state,
                                   {"tokens": b["tokens"],
                                    "labels": b["labels"]})
        losses.append(float(m["loss"]))
    return params, state, losses


def test_restart_resumes_exact_step_and_data(tmp_path):
    ds, vid = _dataset()
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = make_ctx(mesh)
    opt = AdamW(lr=1e-3)
    step_fn = jax.jit(make_train_step(TINY, ctx, opt))
    store = CheckpointStore(str(tmp_path / "cvd"), shard_rows=1 << 10)

    # uninterrupted reference: 8 steps
    p0 = init_params(TINY, jax.random.key(0))
    pr, sr_, ref_losses = _run(8, 0, p0, opt.init(p0), step_fn, ds, vid)

    # interrupted: 4 steps, checkpoint, "crash", resume for 4 more
    p1 = init_params(TINY, jax.random.key(0))
    p1, s1, l_a = _run(4, 0, p1, opt.init(p1), step_fn, ds, vid)
    store.save(step=4, tree=p1, meta={"cursor": 4})
    del p1, s1

    vid0, _, meta = resume_latest(store)
    assert meta["cursor"] == 4
    p2 = store.restore(vid0, treedef_like=init_params(TINY, jax.random.key(0)))
    # optimizer state restarts fresh in this test; data cursor must not
    # replay: the batches for steps 4..8 are identical to the reference
    ref_batches = list(ds.batches(vid=vid, global_batch=4, seed=7,
                                  start_step=4, n_steps=4))
    res_batches = list(ds.batches(vid=vid, global_batch=4, seed=7,
                                  start_step=meta["cursor"], n_steps=4))
    for a, b in zip(ref_batches, res_batches):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # restored params are bit-exact vs what was saved
    for pa, pb in zip(jax.tree.leaves(p2),
                      jax.tree.leaves(store.restore(
                          vid0, treedef_like=p2))):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Save from a (1,1) mesh, restore onto (2,1) and (1,2) meshes — the
    checkpoint stores logical specs, so any device count works."""
    if jax.device_count() < 2:
        import subprocess, sys, textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            import jax, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.models import init_params
            from repro.models.transformer import param_specs
            from repro.sharding import logical_to_sharding
            from repro.train import CheckpointStore
            from tests.test_elastic_restart import TINY
            store = CheckpointStore("%s", shard_rows=1 << 10)
            p = init_params(TINY, jax.random.key(1))
            vid = store.save(step=1, tree=p, meta={"cursor": 1})
            for shape, names in [((2, 1), ("data", "model")),
                                 ((1, 2), ("data", "model"))]:
                mesh = make_mesh(shape, names)
                q = store.restore(vid, mesh=mesh, specs=param_specs(TINY),
                                  treedef_like=p)
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                    assert len(b.sharding.device_set) == 2
            print("ELASTIC_OK")
        """ % str(tmp_path / "cvd2"))
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300,
                           env={"PYTHONPATH": "src:.",
                                "HOME": os.environ.get("HOME", str(REPO)),
                                "PATH": "/usr/bin:/bin"}, cwd=str(REPO))
        assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]
    else:
        pytest.skip("covered by subprocess variant")
