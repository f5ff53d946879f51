"""Data pipeline determinism + checkpoint atomicity/restart/elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.checkpoint.manager import restore_tree, save_tree
from repro.data import DataConfig, SyntheticLM, prefetch, shard_batch


def _dc(**kw):
    return DataConfig(vocab=512, seq_len=64, global_batch=4, **kw)


def test_data_deterministic_in_seed_step():
    d1 = SyntheticLM(_dc(seed=7))
    d2 = SyntheticLM(_dc(seed=7))
    b1, b2 = d1.batch_at(13), d2.batch_at(13)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(
        d1.batch_at(14)["tokens"], b1["tokens"]
    )


def test_data_seed_changes_stream():
    a = SyntheticLM(_dc(seed=0)).batch_at(0)
    b = SyntheticLM(_dc(seed=1)).batch_at(0)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_data_targets_shifted_and_docs_bounded():
    d = SyntheticLM(_dc())
    b = d.batch_at(0)
    assert b["tokens"].shape == (4, 64)
    assert b["targets"].shape == (4, 64)
    flat_t = np.concatenate(
        [b["tokens"], b["targets"][:, -1:]], axis=1
    ).reshape(-1)
    # targets are the next-token shift of the same stream
    np.testing.assert_array_equal(
        b["targets"][:, :-1], b["tokens"][:, 1:]
    )
    assert (flat_t < 512).all() and (flat_t >= 0).all()
    # EOS tokens exist somewhere in a long enough sample
    long = SyntheticLM(_dc(mean_doc_len=32)).batch_at(0)
    assert (long["tokens"] == 0).any()


def test_prefetch_preserves_order():
    it = prefetch(iter(range(20)), depth=3)
    assert list(it) == list(range(20))


def test_shard_batch_no_mesh_is_asarray():
    b = shard_batch({"x": np.ones((2, 2), np.int32)})
    assert isinstance(b["x"], jax.Array)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tree(x=1.0):
    return {
        "params": {"w": jnp.full((4, 4), x), "b": jnp.arange(3.0)},
        "step": jnp.asarray(7, jnp.int32),
        "nested": [jnp.ones((2,)), jnp.zeros((1,), jnp.bfloat16)],
    }


def test_save_restore_bitwise(tmp_path):
    path = str(tmp_path / "ck.npz")
    t = _tree(3.5)
    save_tree(path, t, extra={"step": 7})
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t
    )
    back = restore_tree(path, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_restore_shape_mismatch_raises(tmp_path):
    path = str(tmp_path / "ck.npz")
    save_tree(path, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError, match="shape"):
        restore_tree(path, {"w": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_manager_latest_keep_k_and_atomicity(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    assert mgr.latest() is None
    for s in [10, 20, 30, 40]:
        mgr.save(s, _tree(float(s)), block=True)
    assert mgr.steps() == [30, 40]  # keep-2 GC
    assert mgr.latest() == 40
    # no tmp dirs left behind (atomic rename)
    assert not [d for d in os.listdir(tmp_path) if d.startswith("tmp.")]
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _tree()
    )
    t, meta = mgr.restore(40, like)
    assert meta["step"] == 40
    assert float(t["params"]["w"][0, 0]) == 40.0


def test_manager_async_save_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(5, _tree(5.0))
    mgr.wait()
    assert mgr.latest() == 5


def test_crash_recovery_discovers_latest_valid(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(1, _tree(1.0), block=True)
    mgr.save(2, _tree(2.0), block=True)
    # simulate a crash mid-write: a stale tmp dir must be ignored
    os.makedirs(tmp_path / "tmp.3.999", exist_ok=True)
    # and a corrupt (empty) step dir must be ignored by discovery
    os.makedirs(tmp_path / "step_9", exist_ok=True)
    assert mgr.latest() == 2


def test_elastic_restore_into_mesh(tmp_path):
    """Checkpoints restore under any mesh (1-device here) via logical axes."""
    from repro.dist.partition import sharding_ctx
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1,), ("data",))
    path = str(tmp_path / "ck.npz")
    tree = {"w": jnp.ones((8, 4))}
    save_tree(path, tree)
    like = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    axes = {"w": ("embed", "mlp")}
    with sharding_ctx(mesh):
        back = restore_tree(path, like, mesh=mesh, axes=axes)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.ones((8, 4)))
    assert back["w"].sharding.mesh.shape == {"data": 1}
