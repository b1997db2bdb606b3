"""Checkpoint store: atomicity, keep-k GC, auto-resume, manifest."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 8)), "b": jnp.zeros(8)},
        "opt": {"mu": jnp.ones((8, 8)), "step": jnp.asarray(7, jnp.int32)},
    }


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t)
    assert latest_step(str(tmp_path)) == 3
    r = restore_checkpoint(str(tmp_path), 3, jax.eval_shape(lambda: t))
    _assert_tree_equal(t, r)


def test_atomicity_tmp_dirs_ignored(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    # simulate a crashed save: a stale .tmp dir and an incomplete manifest
    os.makedirs(tmp_path / "step_000000002.tmp")
    os.makedirs(tmp_path / "step_000000005")
    with open(tmp_path / "step_000000005" / "manifest.json", "w") as f:
        json.dump({"step": 5, "complete": False}, f)
    assert latest_step(str(tmp_path)) == 1


def test_corrupt_manifest_ignored(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    os.makedirs(tmp_path / "step_000000009")
    with open(tmp_path / "step_000000009" / "manifest.json", "w") as f:
        f.write("{not json")
    assert latest_step(str(tmp_path)) == 1


def test_keep_k_gc(tmp_path):
    t = _tree()
    for s in range(6):
        save_checkpoint(str(tmp_path), s, t, keep=3)
    steps = sorted(
        int(d.split("_")[1]) for d in os.listdir(tmp_path) if d.startswith("step_")
    )
    assert steps == [3, 4, 5]


def test_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((4, 4))})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((8, 8))})


def test_missing_leaf_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((4, 4))})
    with pytest.raises(KeyError):
        restore_checkpoint(
            str(tmp_path), 1, {"w": jnp.zeros((4, 4)), "extra": jnp.zeros(2)}
        )


def test_manager_auto_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    assert mgr.restore_latest(_tree()) is None
    mgr.save(10, _tree(1))
    mgr.save(20, _tree(2))
    step, tree = mgr.restore_latest(jax.eval_shape(lambda: _tree()))
    assert step == 20
    _assert_tree_equal(tree, _tree(2))


def test_manifest_carries_mesh(tmp_path):
    mesh = make_mesh((1,), ("data",))
    save_checkpoint(str(tmp_path), 1, _tree(), mesh=mesh)
    with open(tmp_path / "step_000000001" / "manifest.json") as f:
        m = json.load(f)
    assert m["mesh"]["axes"] == ["data"]
    assert m["mesh"]["shape"] == [1]
