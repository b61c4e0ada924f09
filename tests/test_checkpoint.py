"""Checkpointer: roundtrip, async, atomicity, keep-k GC, crc32 integrity,
elastic restore."""

import os
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.checkpoint import Checkpointer


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "w": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(8,)), jnp.bfloat16),
        },
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    tree = _tree()
    ckpt.save(7, tree, blocking=True)
    restored = ckpt.restore(tree)
    for a, b in zip(
        jnp.asarray(tree["params"]["w"]).flatten(),
        jnp.asarray(restored["params"]["w"]).flatten(),
    ):
        assert float(a) == float(b)
    assert restored["params"]["b"].dtype == jnp.bfloat16
    assert int(restored["opt"]["step"]) == 7


def test_async_save_then_wait(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    ckpt.save(1, _tree())  # non-blocking
    ckpt.wait()
    assert ckpt.latest_step() == 1


def test_keep_k_garbage_collection(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ckpt.save(s, _tree(s), blocking=True)
    assert ckpt.all_steps() == [3, 4]


def test_no_partial_checkpoints_visible(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=3)
    ckpt.save(5, _tree(), blocking=True)
    names = os.listdir(str(tmp_path))
    assert not any(n.endswith(".tmp") for n in names)


def test_crc_corruption_detected(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    tree = _tree()
    ckpt.save(3, tree, blocking=True)
    # corrupt the npz payload
    path = os.path.join(str(tmp_path), "step_00000003", "proc_00000.npz")
    data = np.load(path)
    arrs = {k: data[k].copy() for k in data.files}
    key = [k for k in arrs if k.endswith("w")][0]
    arrs[key][0, 0] += 1.0
    np.savez(path, **arrs)
    with pytest.raises(IOError, match="corruption"):
        ckpt.restore(tree)


def test_restore_latest_of_many(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=5)
    for s in (10, 20, 30):
        ckpt.save(s, _tree(s), blocking=True)
    restored = ckpt.restore(_tree())
    expected = _tree(30)
    np.testing.assert_allclose(
        np.asarray(restored["params"]["w"]), np.asarray(expected["params"]["w"])
    )


def test_elastic_restore_with_shardings(tmp_path):
    """Restore with explicit (single-device) shardings — the elastic path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ckpt = Checkpointer(str(tmp_path), keep=2)
    tree = _tree()
    ckpt.save(1, tree, blocking=True)
    mesh = make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P())
    shardings = {
        "params": {"w": sh, "b": sh},
        "opt": {"step": sh},
    }
    restored = ckpt.restore(tree, shardings=shardings)
    assert restored["params"]["w"].sharding == sh
