"""Checkpointer: roundtrip, atomicity, retention, corruption detection."""

import json
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, restore_pytree, save_pytree


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)), "b": jnp.zeros((4,))},
        "step": jnp.int32(7),
        "nested": [jnp.arange(3), {"x": jnp.float32(2.5)}],
    }


def _assert_tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    t = _tree()
    save_pytree(t, tmp_path / "c")
    got = restore_pytree(t, tmp_path / "c")
    _assert_tree_equal(t, got)


def test_checkpointer_latest_and_resume(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (10, 20, 30):
        ck.save(s, _tree(s))
    assert ck.latest_step() == 30
    got, step = ck.restore(_tree())
    assert step == 30
    _assert_tree_equal(got, _tree(30))


def test_keep_k_retention(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in range(1, 6):
        ck.save(s, _tree(s))
    assert ck.steps() == [4, 5]


def test_no_tmp_dirs_visible(tmp_path):
    ck = Checkpointer(tmp_path, keep=3)
    ck.save(1, _tree())
    names = [p.name for p in tmp_path.iterdir()]
    assert all(not n.endswith(".tmp") for n in names)


def test_corruption_detected(tmp_path):
    t = _tree()
    save_pytree(t, tmp_path / "c")
    # flip bytes in one leaf file
    f = next((tmp_path / "c").glob("params__w.npy"))
    raw = bytearray(f.read_bytes())
    raw[-4] ^= 0xFF
    f.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="checksum|corrupt"):
        restore_pytree(t, tmp_path / "c")


def test_structure_mismatch_detected(tmp_path):
    t = _tree()
    save_pytree(t, tmp_path / "c")
    t2 = dict(t)
    t2["extra"] = jnp.zeros((2,))
    with pytest.raises(KeyError):
        restore_pytree(t2, tmp_path / "c")


def test_async_save_durable_and_ordered(tmp_path):
    ck = Checkpointer(tmp_path, keep=5)
    for s in (1, 2, 3):
        ck.save_async(s, _tree(s))
    ck.wait()
    assert ck.steps() == [1, 2, 3]
    got, step = ck.restore(_tree())
    assert step == 3
    _assert_tree_equal(got, _tree(3))


def test_async_save_snapshot_isolated_from_mutation(tmp_path):
    """The async writer must snapshot at call time — later donation/mutation
    of the live tree cannot corrupt the checkpoint."""
    import numpy as np

    ck = Checkpointer(tmp_path, keep=2)
    arr = np.ones((64,), np.float32)
    ck.save_async(1, {"w": arr})
    arr *= 0.0  # mutate the host buffer immediately
    ck.wait()
    got = ck.restore({"w": arr})[0]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.ones((64,), np.float32))


def test_mesh_agnostic_restore_onto_sharding(tmp_path):
    """Elastic path: restore with explicit shardings onto the local mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_pytree(t, tmp_path / "c")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got = restore_pytree(t, tmp_path / "c", shardings=sh)
    _assert_tree_equal(t, got)
    assert got["w"].sharding == sh["w"]
