"""Checkpoint/restart fault-tolerance tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    save_checkpoint, restore_checkpoint, latest_step, AsyncCheckpointer,
    save_nmf_factors_sparse, restore_nmf_factors_sparse,
)


def test_save_restore_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
            "b": {"c": jnp.ones((2,), jnp.bfloat16)}}
    save_checkpoint(str(tmp_path), 7, tree)
    assert latest_step(str(tmp_path)) == 7
    out = restore_checkpoint(str(tmp_path), 7, tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == jnp.bfloat16


def test_latest_step_picks_newest(tmp_path):
    t = {"x": jnp.zeros(3)}
    save_checkpoint(str(tmp_path), 1, t)
    save_checkpoint(str(tmp_path), 10, t)
    save_checkpoint(str(tmp_path), 5, t)
    assert latest_step(str(tmp_path)) == 10


def test_atomicity_tmp_ignored(tmp_path):
    """A leftover .tmp dir (crash mid-write) is never picked up."""
    t = {"x": jnp.zeros(3)}
    save_checkpoint(str(tmp_path), 3, t)
    os.makedirs(tmp_path / "step_99.tmp")
    assert latest_step(str(tmp_path)) == 3


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    tree = {"w": jnp.full((4, 4), 2.0)}
    ck.save(11, tree)
    ck.wait()
    out = restore_checkpoint(str(tmp_path), 11, tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


def test_elastic_restore_resharding(tmp_path):
    """Restore with explicit shardings onto the (1-device) current mesh —
    the elastic-restart path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(8.0).reshape(4, 2)}
    save_checkpoint(str(tmp_path), 1, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out = restore_checkpoint(str(tmp_path), 1, tree, shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


def test_sparse_factor_checkpoint(tmp_path):
    """Paper Alg.2 factors stored compressed: size scales with NNZ, not n*m."""
    u = jnp.zeros((5000, 5)).at[jnp.arange(55), jnp.arange(55) % 5].set(1.5)
    v = jnp.zeros((3000, 5)).at[:40, 0].set(2.0)
    path = str(tmp_path / "factors.npz")
    sizes = save_nmf_factors_sparse(path, u, v)
    assert sum(sizes.values()) < 5000 * 5 * 4  # far below dense
    u2, v2 = restore_nmf_factors_sparse(path)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u2))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
