"""Checkpoint/restart + fault-tolerance machinery."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ck


def _tree():
    return {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "b": jnp.ones((3,), jnp.bfloat16)},
            "step_stuff": (jnp.asarray(3), jnp.asarray(2.5))}


def test_roundtrip(tmp_path):
    tree = _tree()
    ck.save_checkpoint(tmp_path, 7, tree)
    restored, step = ck.restore_checkpoint(tmp_path, tree)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_latest_and_keep_n(tmp_path):
    mgr = ck.CheckpointManager(tmp_path, keep=2, async_save=False)
    for s in (1, 5, 9):
        mgr.save(s, _tree())
    assert ck.latest_step(tmp_path) == 9
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir())
    assert steps == [5, 9]  # keep-2 GC


def test_async_save_and_wait(tmp_path):
    mgr = ck.CheckpointManager(tmp_path, keep=3, async_save=True)
    mgr.save(1, _tree())
    mgr.wait()
    assert ck.latest_step(tmp_path) == 1


def test_structure_mismatch_rejected(tmp_path):
    ck.save_checkpoint(tmp_path, 0, _tree())
    with pytest.raises(ValueError, match="structure mismatch"):
        ck.restore_checkpoint(tmp_path, {"other": jnp.zeros(3)})


def test_atomicity_no_partial_dirs(tmp_path):
    ck.save_checkpoint(tmp_path, 3, _tree())
    names = [p.name for p in tmp_path.iterdir()]
    assert names == ["step_0000000003"]  # no tmp.* residue


def test_elastic_reshard_restore(tmp_path):
    """Checkpoint written under one sharding restores onto another (the
    shrunk/grown-mesh restart path).  On 1 CPU device we exercise the
    device_put re-shard call with fresh shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = _tree()
    ck.save_checkpoint(tmp_path, 2, tree)
    from repro.parallel.mesh_utils import make_mesh
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)
    restored, _ = ck.restore_checkpoint(tmp_path, tree, shardings=sh)
    leaf = jax.tree_util.tree_leaves(restored)[0]
    assert leaf.sharding == NamedSharding(mesh, P())


def test_resume_continues_training(tmp_path):
    """Crash/restart: state after N steps == state after k steps + restore +
    (N-k) steps — the checkpoint path is lossless."""
    from repro.train.optim import adamw
    opt = adamw(0.1)
    params = {"x": jnp.array([4.0])}

    def step(state):
        g = jax.grad(lambda p: jnp.sum((p["x"] - 1.0) ** 2))(state["params"])
        p, o = opt.update(g, state["opt"], state["params"])
        return {"params": p, "opt": o}

    state = {"params": params, "opt": opt.init(params)}
    for i in range(5):
        state = step(state)
        if i == 2:
            ck.save_checkpoint(tmp_path, i, state)
    # restart from step 2
    state2, _ = ck.restore_checkpoint(
        tmp_path, {"params": params, "opt": opt.init(params)})
    for _ in range(2):
        state2 = step(state2)
    np.testing.assert_allclose(np.asarray(state["params"]["x"]),
                               np.asarray(state2["params"]["x"]), rtol=1e-6)


def test_step_watchdog_flags_stragglers():
    flagged = []
    wd = ck.StepWatchdog(threshold=3.0,
                         on_straggler=lambda s, dt, ema: flagged.append(s))
    for i in range(5):
        wd.start_step()
        time.sleep(0.01)
        wd.end_step(i)
    wd.start_step()
    time.sleep(0.2)  # straggler
    assert wd.end_step(99) is True
    assert flagged == [99]
    # EMA not poisoned by the outlier
    assert wd.ema < 0.05
