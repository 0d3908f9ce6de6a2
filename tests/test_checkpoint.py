"""Checkpoint manager: atomicity, resume, async, retention, elastic
restore onto a different sharding layout."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save


def tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "blocks": (jnp.ones((2, 2)), jnp.zeros((2,)))},
            "step": jnp.int32(7)}


def test_save_restore_roundtrip(tmp_path):
    t = tree()
    save(str(tmp_path), 5, t)
    assert latest_step(str(tmp_path)) == 5
    got = restore(str(tmp_path), 5, jax.tree.map(jnp.zeros_like, t))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_partial_write_is_invisible(tmp_path):
    """A crash mid-save (leftover .tmp) must not surface as latest."""
    save(str(tmp_path), 1, tree())
    os.makedirs(tmp_path / "step_00000002.tmp")
    (tmp_path / "step_00000002.tmp" / "garbage").write_text("x")
    assert latest_step(str(tmp_path)) == 1
    # an empty committed dir without metadata is also ignored
    os.makedirs(tmp_path / "step_00000003")
    assert latest_step(str(tmp_path)) == 1


def test_shape_mismatch_rejected(tmp_path):
    save(str(tmp_path), 1, {"w": jnp.ones((3, 3))})
    with pytest.raises(ValueError):
        restore(str(tmp_path), 1, {"w": jnp.ones((4, 4))})


def test_async_manager_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        mgr.save_async(s, tree())
    mgr.wait()
    steps = sorted(int(n[5:]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [20, 30]


def test_resume_training(tmp_path):
    """Kill/restart: a fresh run resumes from the committed step and
    reaches the same final state as an uninterrupted run."""
    from repro.launch.train import train
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    # uninterrupted
    full = train("qwen3-1.7b", steps=8, batch=2, seq=16, ckpt_dir=d1,
                 ckpt_every=4)
    # interrupted at step 6 (after ckpt at 4) then resumed
    with pytest.raises(RuntimeError):
        train("qwen3-1.7b", steps=8, batch=2, seq=16, ckpt_dir=d2,
              ckpt_every=4, fail_at=6)
    assert latest_step(d2) == 4
    resumed = train("qwen3-1.7b", steps=8, batch=2, seq=16, ckpt_dir=d2,
                    ckpt_every=4)
    for a, b in zip(jax.tree.leaves(full["params"]),
                    jax.tree.leaves(resumed["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_restore_onto_different_sharding(tmp_path):
    """Elastic restore: checkpoint written unsharded restores onto an
    explicit (1-device here) NamedSharding target."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    save(str(tmp_path), 2, t)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got = restore(str(tmp_path), 2, t, sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(t["w"]))


def test_metadata_contents(tmp_path):
    save(str(tmp_path), 3, tree(), extra_meta={"arch": "x"})
    with open(tmp_path / "step_00000003" / "metadata.json") as f:
        meta = json.load(f)
    assert meta["step"] == 3 and meta["arch"] == "x"
    assert meta["num_leaves"] == len(jax.tree.leaves(tree()))
