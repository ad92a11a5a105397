"""Checkpoint manager: atomic roundtrip, async, retention, and elastic
re-shard restore."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def _state(key):
    return {"params": {"w": jax.random.normal(key, (8, 4)),
                       "b": jnp.zeros((4,))},
            "step": jnp.int32(7)}


def test_roundtrip(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path))
    st = _state(key)
    mgr.save(10, st, metric=1.5)
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    restored, meta = mgr.restore(template)
    assert meta["step"] == 10 and meta["metric"] == 1.5
    for a, b in zip(jax.tree_util.tree_leaves(st),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_and_wait(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path))
    st = _state(key)
    mgr.save_async(3, st)
    mgr.wait()
    assert mgr.latest_step() == 3


def test_retention_keeps_latest_and_best(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path), keep_latest=2, keep_best=1)
    st = _state(key)
    for step, metric in [(1, 0.5), (2, 5.0), (3, 4.0), (4, 3.0)]:
        mgr.save(step, st, metric=metric)
    steps = sorted(s for s, _ in mgr._steps())
    assert steps == [1, 3, 4]  # 3,4 newest; 1 is best-metric


def test_shape_mismatch_fails_loudly(tmp_path, key):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(key))
    bad_template = {"params": {"w": jax.ShapeDtypeStruct((9, 4), jnp.float32),
                               "b": jax.ShapeDtypeStruct((4,), jnp.float32)},
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}
    with pytest.raises(ValueError, match="shape mismatch"):
        mgr.restore(bad_template)


def test_elastic_restore_new_sharding(tmp_path, key):
    """Restore under a different mesh's shardings (1-device 'new mesh' —
    the mechanism is identical at 512 chips: device_put under the target
    NamedSharding)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    st = _state(key)
    mgr.save(1, st)
    mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None))
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), st)
    shardings = {"params": {"w": sh, "b": NamedSharding(mesh, P(None))},
                 "step": NamedSharding(mesh, P())}
    restored, _ = mgr.restore(template, shardings=shardings)
    assert restored["params"]["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(st["params"]["w"]))

