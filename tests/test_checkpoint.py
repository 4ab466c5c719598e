"""Checkpoint manager: atomicity, async, keep-N, elastic resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree
from repro.launch.mesh import make_mesh


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    return {"a": jax.random.normal(k, (8, 6)),
            "nested": {"b": jnp.arange(12).reshape(3, 4).astype(jnp.float32)},
            "lst": [jnp.ones((2,)), jnp.zeros((3,))]}


def test_roundtrip(tmp_path):
    t = _tree()
    save_tree(str(tmp_path / "ck"), t)
    r = restore_tree(str(tmp_path / "ck"), jax.tree.map(jnp.zeros_like, t))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), t, r)


def test_manager_keep_n_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full((2,), float(s))})
    assert mgr.latest_step() == 4
    assert mgr.all_steps() == [3, 4]
    step, t = mgr.restore({"x": jnp.zeros((2,))})
    assert step == 4 and float(t["x"][0]) == 4.0


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=3)
    t = _tree(1)
    mgr.save_async(7, t)
    mgr.wait()
    step, r = mgr.restore(jax.tree.map(jnp.zeros_like, t))
    assert step == 7
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), t, r)


def test_atomic_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=5)
    mgr.save(1, _tree())
    for d in os.listdir(tmp_path):
        assert not d.endswith(".tmp")


def test_elastic_reshard(tmp_path):
    """Save under one sharding, restore under another (mesh change)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh1 = make_mesh((1,), ("data",))
    x = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                       NamedSharding(mesh1, P("data")))
    save_tree(str(tmp_path / "ck"), {"x": x})
    mesh2 = make_mesh((1, 1), ("data", "model"))
    tgt = NamedSharding(mesh2, P(None, "model"))
    r = restore_tree(str(tmp_path / "ck"), {"x": jnp.zeros((4, 4))},
                     shardings={"x": tgt})
    assert r["x"].sharding == tgt
    np.testing.assert_array_equal(np.asarray(r["x"]), np.asarray(x))


def test_restore_rejects_shape_mismatch(tmp_path):
    save_tree(str(tmp_path / "ck"), {"x": jnp.zeros((3,))})
    with pytest.raises(ValueError):
        restore_tree(str(tmp_path / "ck"), {"x": jnp.zeros((4,))})


# ---------------------------------------------------------------------------
# the online service's contract: versioned snapshot swap under concurrent
# readers, and recovery from a crash mid-swap
# ---------------------------------------------------------------------------

def test_concurrent_readers_see_complete_snapshots(tmp_path):
    """Readers restoring the latest step while a writer publishes new
    ones must always get an internally consistent tree: every leaf from
    the SAME version (the write-to-tmp + atomic-rename protocol makes a
    step directory visible only when complete)."""
    import threading

    mgr = CheckpointManager(str(tmp_path), keep_n=0)   # no gc: isolate swap
    mgr.save(1, {"x": jnp.full((4,), 1.0), "y": jnp.full((3,), 1.0)})
    like = {"x": jnp.zeros((4,)), "y": jnp.zeros((3,))}
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            step, t = mgr.restore(like)
            x, y = float(np.asarray(t["x"])[0]), float(np.asarray(t["y"])[0])
            if not (x == y == float(step)):
                torn.append((step, x, y))

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for th in threads:
        th.start()
    for s in range(2, 30):
        mgr.save(s, {"x": jnp.full((4,), float(s)),
                     "y": jnp.full((3,), float(s))})
    stop.set()
    for th in threads:
        th.join()
    assert torn == [], f"torn snapshot reads: {torn[:5]}"
    assert mgr.latest_step() == 29


def test_crash_mid_swap_recovers_previous_version(tmp_path):
    """A crash that leaves a partial ``.tmp`` directory (died before the
    atomic rename) must be invisible: latest_step stays on the last
    complete version, restore works, and re-saving the same step
    clobbers the debris."""
    mgr = CheckpointManager(str(tmp_path), keep_n=3)
    mgr.save(1, {"x": jnp.full((2,), 1.0)})
    # simulate the crash window: step 2's write began (tmp dir, partial
    # leaves, no index) but the rename never happened
    debris = tmp_path / "step_00000002.tmp"
    debris.mkdir()
    (debris / "leaf_00000.npy").write_bytes(b"partial")
    assert mgr.all_steps() == [1]
    assert mgr.latest_step() == 1
    step, t = mgr.restore({"x": jnp.zeros((2,))})
    assert step == 1 and float(t["x"][0]) == 1.0
    # the interrupted save can simply be retried
    mgr.save(2, {"x": jnp.full((2,), 2.0)})
    assert mgr.latest_step() == 2
    step, t = mgr.restore({"x": jnp.zeros((2,))})
    assert step == 2 and float(t["x"][0]) == 2.0
    assert not os.path.exists(debris)
