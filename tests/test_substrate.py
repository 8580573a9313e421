"""Substrate tests: data determinism, checkpoint roundtrip + reshard,
optimizers, collectives helpers."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, registry
from repro.configs.base import WorkloadShape


def test_data_pipeline_deterministic_and_disjoint():
    from repro.data import DataPipeline, synthetic_batch
    cfg = registry.get("yi-6b-smoke")
    shape = WorkloadShape("t", "train", 32, 8)
    # determinism: same (seed, step) -> same batch
    b1 = synthetic_batch(cfg, shape, seed=5, step=3)
    b2 = synthetic_batch(cfg, shape, seed=5, step=3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = synthetic_batch(cfg, shape, seed=5, step=4)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # host sharding: two hosts cover the global batch disjointly
    p0 = DataPipeline(cfg, shape, seed=5, host_id=0, n_hosts=2)
    p1 = DataPipeline(cfg, shape, seed=5, host_id=1, n_hosts=2)
    h0, h1 = next(p0), next(p1)
    p0.close(); p1.close()
    glob = synthetic_batch(cfg, shape, seed=5, step=0)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), glob["tokens"])
    assert h0["_step"] == 0


def test_checkpoint_roundtrip_and_manager():
    from repro.ckpt import CheckpointManager, restore_state, save_state
    state = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
             "b": {"c": jnp.ones((2,), jnp.bfloat16),
                   "d": jnp.int32(7)}}
    with tempfile.TemporaryDirectory() as d:
        save_state(state, os.path.join(d, "s"))
        tmpl = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), state)
        back = restore_state(tmpl, os.path.join(d, "s"))
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(state["a"]))
        assert back["b"]["c"].dtype == jnp.bfloat16

        mgr = CheckpointManager(d, keep=2, async_save=True)
        for step in (5, 10, 15):
            mgr.save(state, step)
        mgr.wait()
        assert mgr.latest_step() == 15
        restored, step = mgr.restore_latest(tmpl)
        assert step == 15
        # retention: only 2 kept
        kept = [x for x in os.listdir(d) if x.startswith("step_")]
        assert len(kept) == 2


def test_checkpoint_reshard_roundtrip():
    """Restore onto a different sharding layout (elastic restart)."""
    from repro.ckpt import restore_resharded, save_state
    from repro.dist.sharding import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec(None))
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    with tempfile.TemporaryDirectory() as d:
        save_state(state, os.path.join(d, "s"))
        tmpl = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
        out = restore_resharded(tmpl, {"w": sh}, os.path.join(d, "s"))
        assert out["w"].sharding == sh
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(8, dtype=np.float32))


@pytest.mark.parametrize("optname", ["adamw", "adafactor"])
def test_optimizers_reduce_quadratic_loss(optname):
    from repro.optim import make_optimizer, opt_state_defs
    from repro.models.params import PDef, init_params, abstract_params
    import dataclasses
    cfg = dataclasses.replace(registry.get("yi-6b-smoke"), optimizer=optname)
    tcfg = TrainConfig(learning_rate=0.05, warmup_steps=0,
                       total_steps=100, weight_decay=0.0, grad_clip=1e9)
    defs = {"w": PDef((4, 8), ("embed", "ff"))}
    params = init_params(defs, jax.random.PRNGKey(0))
    opt_defs = opt_state_defs(cfg, defs)
    state = init_params(opt_defs, jax.random.PRNGKey(1))
    state = jax.tree_util.tree_map(jnp.zeros_like, state)
    target = jax.random.normal(jax.random.PRNGKey(2), (4, 8))
    update = make_optimizer(cfg, tcfg)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    l0 = float(loss(params))
    for step in range(60):
        g = jax.grad(loss)(params)
        params, state, stats = update(g, state, params,
                                      jnp.int32(step))
    assert float(loss(params)) < l0 * 0.2, optname


def test_hierarchical_psum_matches_flat():
    """Reduce-scatter -> cross-pod psum -> all-gather == plain psum
    (the comm layer's core identity; tests/test_comm.py pins the full
    per-strategy and train-step variants)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs multi-device host (covered by dryrun sweep)")
    import numpy as np

    from repro import comm
    from repro.configs.base import ShardingStrategy
    from repro.dist import sharding as shd
    from repro.models.params import PDef
    mesh = shd.make_mesh((2, 2), ("pod", "data"),
                         devices=jax.devices()[:4])
    strat = ShardingStrategy(name="h", hierarchical_collectives=True)
    policy = comm.resolve_policy(strat, mesh)
    defs = {"w": PDef((6, 10), ("embed", None))}
    stacked = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 10))}
    synced, _ = comm.sync_grads(stacked, defs, mesh, policy, strat)
    np.testing.assert_allclose(np.asarray(synced["w"]),
                               np.asarray(stacked["w"].mean(0)),
                               rtol=1e-6, atol=1e-7)


def test_lr_schedule_shape():
    from repro.optim import lr_schedule
    lrs = [float(lr_schedule(s, base_lr=1.0, warmup_steps=10,
                             total_steps=100)) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0
    assert max(lrs) <= 1.0
    assert lrs[-1] < 0.2
    assert abs(lrs[10] - 1.0) < 0.01
