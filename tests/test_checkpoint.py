"""Checkpoint/restart: integrity, keep-k GC, async writes, reshard restore."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint


def _tree(v=1.0):
    return {"layer": {"w": jnp.full((8, 4), v), "b": jnp.zeros((4,))},
            "step_scale": jnp.asarray(0.5)}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 3, _tree(2.0), meta={"note": "x"})
    restored, step, meta = load_checkpoint(d, _tree(0.0))
    assert step == 3 and meta["note"] == "x"
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]), 2.0)


def test_checksum_detects_corruption(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    path = os.path.join(d, "step_000000001", "arrays.npz")
    data = dict(np.load(path))
    data["layer/w"] = data["layer/w"] + 1.0
    np.savez(path, **data)
    with pytest.raises(IOError):
        load_checkpoint(d, _tree())


def test_keep_last_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_writes=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(float(s)))
    from repro.checkpoint.ckpt import list_steps
    assert list_steps(str(tmp_path)) == [3, 4]
    restored, step, _ = mgr.restore(_tree())
    assert step == 4
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]), 4.0)


def test_async_write_then_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_writes=True)
    mgr.save(7, _tree(7.0))
    mgr.wait()
    restored, step, _ = mgr.restore(_tree())
    assert step == 7


def test_restore_with_new_sharding(tmp_path):
    """Elastic restart: restore onto an explicit (different) sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree(3.0))
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("data",))
    sh = {"layer": {"w": NamedSharding(mesh, P("data")),
                    "b": NamedSharding(mesh, P())},
          "step_scale": NamedSharding(mesh, P())}
    restored, _, _ = load_checkpoint(d, _tree(), shardings=sh)
    assert restored["layer"]["w"].sharding == sh["layer"]["w"]
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]), 3.0)


def test_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    bad = {"layer": {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))},
           "step_scale": jnp.asarray(0.0)}
    with pytest.raises(ValueError):
        load_checkpoint(d, bad)
