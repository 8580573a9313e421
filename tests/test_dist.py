"""Tests for the repro.dist execution substrate.

conftest.py forces 8 host-platform CPU devices, so these exercise real
multi-device meshes; everything also passes on a single device (the
multi-device assertions gate on the device count).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.configs import BASELINE, OPTIMIZED, TrainConfig, registry
from repro.configs.base import ModelConfig, WorkloadShape
from repro.dist import actsharding as act
from repro.dist import sharding as shd
from repro.dist import steps as dsteps

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128)


def _mesh_2x4():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them)")
    return shd.make_mesh((2, 4), ("data", "model"))


# ---------------------------------------------------------------------------
# constrain / model_axis_divides
# ---------------------------------------------------------------------------


def test_constrain_is_identity_off_mesh():
    x = jnp.ones((4, 8, 16))
    assert act.constrain(x, "act_batch", None, "act_ff") is x
    assert act.current() is None


def test_model_axis_divides_off_mesh_is_true():
    assert act.model_axis_divides(3)
    assert act.model_axis_divides(7)


def test_model_axis_divides_on_mesh():
    mesh = _mesh_2x4()
    with act.activation_sharding(mesh, BASELINE):
        assert act.model_axis_divides(8)
        assert not act.model_axis_divides(6)
    # zero3 has no tensor-parallel axis: everything divides
    from repro.configs.base import ShardingStrategy
    z3 = ShardingStrategy(name="z", tensor_parallel=False)
    with act.activation_sharding(mesh, z3):
        assert act.model_axis_divides(7)


def test_constrain_applies_sharding_under_jit():
    mesh = _mesh_2x4()

    def f(x):
        with act.activation_sharding(mesh, OPTIMIZED):
            return act.constrain(x, "act_batch", None, "act_ff")

    y = jax.jit(f)(jnp.ones((4, 8, 64)))
    assert y.sharding.spec == PartitionSpec("data", None, "model")


def test_constrain_drops_non_dividing_axes():
    mesh = _mesh_2x4()

    def f(x):
        with act.activation_sharding(mesh, BASELINE):
            # 6 heads do not divide model=4 -> that dim replicates
            return act.constrain(x, "act_batch", None, "act_heads", None)

    y = jax.jit(f)(jnp.ones((4, 8, 6, 16)))
    used = [a for s in y.sharding.spec
            for a in (s if isinstance(s, tuple) else (s,)) if s]
    assert "model" not in used


def test_constrain_rejects_rank_mismatch():
    mesh = _mesh_2x4()
    with act.activation_sharding(mesh, BASELINE):
        with pytest.raises(ValueError):
            act.constrain(jnp.ones((4, 8)), "act_batch")


# ---------------------------------------------------------------------------
# rule tables / resolution
# ---------------------------------------------------------------------------


def test_replicated_spec_is_empty():
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    assert shd.replicated(mesh).spec == PartitionSpec()


def test_resolve_spec_respects_divisibility_and_uniqueness():
    mesh = _mesh_2x4()
    rules = shd.param_rules(BASELINE)
    # heads=8 divides model=4 -> sharded; kv_heads=2 does not -> None
    assert shd.resolve_spec((64, 8), ("embed", "heads"), rules, mesh) \
        == PartitionSpec(None, "model")
    assert shd.resolve_spec((64, 2), ("embed", "kv_heads"), rules, mesh) \
        == PartitionSpec(None, None)
    # one mesh axis never appears twice: ff takes model, vocab loses it
    spec = shd.resolve_spec((64, 128), ("ff", "vocab"), rules, mesh)
    assert spec == PartitionSpec("model", None)


def test_opt_rules_shard_over_data_even_when_params_replicated():
    rules = shd.opt_rules(BASELINE)
    assert rules["embed"] == "data"
    assert shd.param_rules(BASELINE)["embed"] is None


def test_batch_sharding_replicates_odd_batches():
    mesh = _mesh_2x4()
    # batch=3 does not divide data=2 -> replicated
    assert shd.batch_sharding(mesh, 2, 3, BASELINE).spec \
        == PartitionSpec(None, None)
    assert shd.batch_sharding(mesh, 2, 4, BASELINE).spec[0] == "data"


# ---------------------------------------------------------------------------
# train step builders
# ---------------------------------------------------------------------------


def test_build_train_step_smoke_single_device_mesh():
    mesh = shd.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    tcfg = TrainConfig(learning_rate=1e-2, total_steps=10, warmup_steps=0)
    shape = WorkloadShape("t", "train", 16, 4)
    jitted, sshard, bshard = dsteps.jit_train_step(
        TINY, tcfg, BASELINE, mesh, shape)
    state = dsteps.init_train_state(TINY, tcfg, jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state, sshard)
    from repro.models import example_batch
    batch = {k: jax.device_put(v, bshard[k])
             for k, v in example_batch(TINY, shape).items()}
    l0 = None
    for _ in range(3):
        state, metrics = jitted(state, batch)
        l0 = l0 if l0 is not None else float(metrics["loss"])
    assert np.isfinite(l0)
    assert float(metrics["loss"]) < l0, "same-batch loss must drop"
    assert int(state["step"]) == 3


def test_build_train_step_shards_params_on_multi_device_mesh():
    mesh = _mesh_2x4()
    tcfg = TrainConfig(learning_rate=1e-2, total_steps=10, warmup_steps=0)
    shape = WorkloadShape("t", "train", 16, 4)
    jitted, sshard, bshard = dsteps.jit_train_step(
        TINY, tcfg, OPTIMIZED, mesh, shape)
    state = dsteps.init_train_state(TINY, tcfg, jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), state, sshard)
    from repro.models import example_batch
    batch = {k: jax.device_put(v, bshard[k])
             for k, v in example_batch(TINY, shape).items()}
    state, metrics = jitted(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    w_in = state["params"]["blocks"]["p0"]["mlp"]["w_in"]
    assert len(w_in.addressable_shards) == 8
    assert w_in.sharding.spec == PartitionSpec(None, "data", "model")


def test_abstract_state_matches_init_state():
    tcfg = TrainConfig()
    abstract = dsteps.abstract_train_state(TINY, tcfg)
    concrete = dsteps.init_train_state(TINY, tcfg, jax.random.PRNGKey(0))
    ta = jax.tree_util.tree_structure(abstract)
    tc = jax.tree_util.tree_structure(concrete)
    assert ta == tc
    for a, c in zip(jax.tree_util.tree_leaves(abstract),
                    jax.tree_util.tree_leaves(concrete)):
        assert tuple(a.shape) == tuple(jnp.shape(c))
        assert a.dtype == c.dtype


# ---------------------------------------------------------------------------
# ResourceSet -> sub-mesh bridge + the operator running real sharded steps
# ---------------------------------------------------------------------------


def test_submesh_for_maps_allocation_onto_devices():
    from repro.core.resource_graph import ResourceGraph
    g = ResourceGraph(n_pods=1, hosts_per_pod=2, chips_per_host=4)
    rset = g.match(2)
    mesh = shd.submesh_for(rset)
    if len(jax.devices()) >= 8:
        assert dict(mesh.shape) == {"data": 2, "model": 4}
        # placement follows chip ids: device i is allocation chip i
        assert [d.id for d in mesh.devices.flat] == rset.chip_ids()
    else:
        assert mesh.size <= len(jax.devices())


def test_submesh_for_degrades_when_allocation_exceeds_process():
    from repro.core.resource_graph import ResourceGraph
    g = ResourceGraph(n_pods=4, hosts_per_pod=64, chips_per_host=4)
    rset = g.match(64)
    mesh = shd.submesh_for(rset)
    assert 1 <= mesh.size <= len(jax.devices())


def test_flux_allocation_runs_sharded_step_on_its_submesh():
    """ISSUE acceptance: a FluxInstance allocation drives a real sharded
    train step on the sub-mesh its ResourceSet describes."""
    from repro.core import (FluxMiniCluster, JobSpec, JobState,
                            MiniClusterSpec, NetModel, ResourceGraph,
                            SimClock, SubmeshExecutor)
    clock = SimClock(seed=0)
    net = NetModel()
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=2, chips_per_host=4)
    executor = SubmeshExecutor(clock, net, steps=1, seq_len=16)
    mc = FluxMiniCluster(clock, net, fleet,
                         MiniClusterSpec(name="d", size=2),
                         executor=executor)
    mc.create()
    mc.wait_ready()
    job = mc.instance.submit(JobSpec(n_nodes=2, walltime=1e9,
                                     command="yi-6b-smoke"))
    clock.run(until=clock.now + 600)
    assert job.state == JobState.INACTIVE
    assert job.result == "completed"
    rec = executor.ran[job.jobid]
    assert np.isfinite(rec["loss"])
    assert rec["hosts"] == list(job.allocation.hosts) \
        if job.allocation else True
    if len(jax.devices()) >= 8:
        # 2 hosts x 4 chips -> a (data=2, model=4) sub-mesh
        assert rec["mesh_shape"] == (2, 4)
        assert rec["n_devices"] == 8


def test_submesh_executor_places_same_shape_jobs_on_their_own_devices():
    """Two same-shaped allocations on different hosts must execute on
    the devices THEIR chips name, not a cached mesh's."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them)")
    from repro.core import (FluxMiniCluster, JobSpec, MiniClusterSpec,
                            NetModel, ResourceGraph, SimClock,
                            SubmeshExecutor)
    clock = SimClock(seed=0)
    net = NetModel()
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=2, chips_per_host=4)
    executor = SubmeshExecutor(clock, net, steps=1, seq_len=16)
    mc = FluxMiniCluster(clock, net, fleet,
                         MiniClusterSpec(name="p", size=2),
                         executor=executor)
    mc.create()
    mc.wait_ready()
    j1 = mc.instance.submit(JobSpec(n_nodes=1, walltime=1e9,
                                    command="yi-6b-smoke"))
    j2 = mc.instance.submit(JobSpec(n_nodes=1, walltime=1e9,
                                    command="yi-6b-smoke"))
    clock.run(until=clock.now + 600)
    assert j1.result == "completed" and j2.result == "completed"
    ids1 = executor.ran[j1.jobid]["device_ids"]
    ids2 = executor.ran[j2.jobid]["device_ids"]
    assert ids1 == [0, 1, 2, 3]
    assert ids2 == [4, 5, 6, 7]
