"""The declarative WorkloadSpec API (ISSUE 5 acceptance).

Pins the spec-plus-reconcile contract: strict serializable round-trip,
structured submit-time rejection of bad specs (never a first-step
crash), the unified lifecycle behind ``FluxInstance.apply``, pod-local
serve packing, deprecation of the imperative ``attach_*`` entry
points, and scheduler fairness under mixed train+serve specs.
"""
import json
import warnings

try:
    import hypothesis.strategies as st
    from hypothesis import HealthCheck, given, settings
except ModuleNotFoundError:        # no extra deps in tier-1: see shim
    from _hypothesis_fallback import HealthCheck, given, settings, st

import jax
import pytest

from repro.configs.base import ModelConfig, ShardingStrategy
from repro.core import (FluxMiniCluster, JobSpec, JobState,
                        MiniClusterSpec, NetModel, ResourceGraph, SimClock)
from repro.spec import (DryRunSpec, ResourceSpec, ServeSpec, SpecError,
                        TrainSpec, WorkloadSpec)

FAST = settings(max_examples=40, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])

TINY = ModelConfig(name="tiny-spec", family="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)


def _cluster(n_pods=1, hosts_per_pod=4, size=2, max_size=4,
             chips_per_host=2, executor=None, seed=0):
    clock = SimClock(seed=seed)
    fleet = ResourceGraph(n_pods=n_pods, hosts_per_pod=hosts_per_pod,
                          chips_per_host=chips_per_host)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="spec", size=size,
                                         max_size=max_size),
                         executor=executor)
    mc.create()
    mc.wait_ready()
    return clock, mc


def _run_until(clock, cond, horizon=50_000.0):
    clock.run(until=clock.now + horizon, stop_when=cond)
    assert cond(), "sim condition not reached within horizon"


# ---------------------------------------------------------------------------
# Serialization round-trip (property-based)
# ---------------------------------------------------------------------------


@FAST
@given(t=st.tuples(
    st.sampled_from(["train", "serve", "dryrun"]),
    st.sampled_from(["yi-6b", "qwen2-72b", "lammps-proxy"]),
    st.sampled_from(["baseline", "optimized", "zero3", "custom"]),
    st.integers(1, 16),                    # n_nodes
    st.sampled_from([True, False]),        # pod_local
    st.sampled_from([True, False]),        # elastic
    st.integers(1, 6),                     # n_slots
    st.integers(1, 4),                     # pages per slot
    st.integers(1, 64),                    # total_steps
    st.integers(1, 12),                    # max_new
))
def test_workloadspec_round_trip(t):
    """from_dict(to_dict(s)) == s for every valid spec — including
    custom (non-registry-named) sharding strategies, which serialize
    as their full field dict."""
    kind, arch, strat, n_nodes, pod_local, elastic, slots, pps, steps, \
        max_new = t
    page = 8
    strategy = (ShardingStrategy(name="custom", fsdp_params=True,
                                 hierarchical_collectives=True,
                                 compress_cross_pod=True, compress_pods=3,
                                 comm_strict=True)
                if strat == "custom" else strat)
    spec = WorkloadSpec(
        kind=kind, arch=arch, name=f"rt-{kind}", strategy=strategy,
        resources=ResourceSpec(n_nodes=n_nodes, pod_local=pod_local,
                               elastic=elastic),
        train=TrainSpec(total_steps=steps, global_batch=8, seq_len=32),
        serve=ServeSpec(n_slots=slots, max_new=max_new, page_size=page,
                        max_prompt_len=page, max_seq_len=page * pps
                        if page * pps >= page else page),
        dryrun=DryRunSpec(shape="train_4k"))
    d = spec.to_dict()
    json.dumps(d)                          # the dict is JSON-clean
    assert WorkloadSpec.from_dict(d) == spec
    # validation accepts it (structural checks only)
    assert spec.errors() == []


def test_from_dict_rejects_unknown_fields():
    """Strict parsing: drifted specs fail with structured errors
    naming every unknown key (top-level AND nested)."""
    d = WorkloadSpec().to_dict()
    d["surprise"] = 1
    d["resources"]["replicas"] = 2
    d["strategy"] = {"name": "x", "warp_drive": True}
    with pytest.raises(SpecError) as ei:
        WorkloadSpec.from_dict(d)
    fields = {e["field"] for e in ei.value.errors}
    assert fields == {"surprise", "resources.replicas",
                      "strategy.warp_drive"}
    assert all(e["code"] == "unknown-field" for e in ei.value.errors)


def test_loader_checks_committed_specs(tmp_path):
    from repro.spec import check_spec, load_spec
    good = tmp_path / "good.json"
    good.write_text(json.dumps(WorkloadSpec(
        kind="serve", arch="yi-6b-smoke", name="ok").to_dict()))
    spec, errors = check_spec(str(good))
    assert errors == [] and spec.arch == "yi-6b-smoke"
    assert load_spec(str(good)).name == "ok"

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"kind": "serve", "arch": "nope",
                               "serve": {"n_slots": 99, "n_pages": 4}}))
    spec, errors = check_spec(str(bad))
    codes = {e["code"] for e in errors}
    assert "unknown-config" in codes and "pool-capacity" in codes


def test_wrong_typed_values_lint_as_structured_errors(tmp_path):
    """Drifted JSON with quoted numbers must produce bad-type lint
    errors, never a TypeError traceback."""
    from repro.spec import check_spec
    drifted = tmp_path / "drifted.json"
    drifted.write_text(json.dumps({
        "kind": "serve", "arch": "yi-6b-smoke",
        "resources": {"n_nodes": "4"},
        "serve": {"n_slots": "two", "temperature": "hot"}}))
    spec, errors = check_spec(str(drifted))
    got = {(e["field"], e["code"]) for e in errors}
    assert ("resources.n_nodes", "bad-type") in got
    assert ("serve.n_slots", "bad-type") in got
    assert ("serve.temperature", "bad-type") in got


def test_non_string_strategy_value_lints_as_structured_error():
    """\"strategy\": 42 must fail the lint with a structured bad-type
    error, never reach resolved_strategy and KeyError."""
    with pytest.raises(SpecError) as ei:
        WorkloadSpec.from_dict({"kind": "train", "arch": "lammps-proxy",
                                "strategy": 42})
    assert [(e["field"], e["code"]) for e in ei.value.errors] == \
        [("strategy", "bad-type")]
    # a hand-constructed spec with a bogus strategy object is caught by
    # errors() too
    spec = WorkloadSpec(kind="train", arch="lammps-proxy", strategy=42)
    assert [(e["field"], e["code"]) for e in spec.errors()] == \
        [("strategy", "bad-type")]


def test_serve_errors_reports_every_bad_field():
    """One SpecError lists EVERY independent bad value, not just the
    first (the collect-everything contract)."""
    spec = WorkloadSpec(kind="serve", arch="yi-6b-smoke",
                        serve=ServeSpec(n_slots=0, max_new=0,
                                        temperature=-1.0))
    fields = {e["field"] for e in spec.errors()}
    assert {"serve.n_slots", "serve.max_new",
            "serve.temperature"} <= fields


# ---------------------------------------------------------------------------
# Submit-time rejection (structured errors, acceptance cases)
# ---------------------------------------------------------------------------


def test_apply_rejects_unknown_config():
    clock, mc = _cluster()
    with pytest.raises(SpecError) as ei:
        mc.apply(WorkloadSpec(kind="train", arch="gpt-17"))
    errs = ei.value.errors
    assert [(e["field"], e["code"]) for e in errs] == \
        [("arch", "unknown-config")]
    assert mc.instance.queue.depth() == 0      # nothing reached the queue


def test_apply_rejects_comm_strict_strategy_mesh_cannot_honor():
    """A comm_strict hierarchical strategy on a single-pod cluster is
    rejected at apply time — the same resolve_policy decision the step
    builder would hit at first step, surfaced as a structured error."""
    clock, mc = _cluster(n_pods=1)
    strict = ShardingStrategy(name="strict-hier",
                              hierarchical_collectives=True,
                              comm_strict=True)
    with pytest.raises(SpecError) as ei:
        mc.apply(WorkloadSpec(kind="train", arch="tiny-spec",
                              strategy=strict,
                              resources=ResourceSpec(n_nodes=2)),
                 cfg=TINY)
    assert [(e["field"], e["code"]) for e in ei.value.errors] == \
        [("strategy", "comm-strict")]


def test_apply_accepts_comm_strict_on_pod_spanning_allocation():
    """The same strict strategy is FINE when the allocation the matcher
    would produce spans pods evenly (the mesh gains a pod tier)."""
    clock, mc = _cluster(n_pods=2, hosts_per_pod=2, size=4, max_size=4)
    strict = ShardingStrategy(name="strict-hier",
                              hierarchical_collectives=True,
                              comm_strict=True)
    h = mc.apply(WorkloadSpec(kind="train", arch="tiny-spec",
                              strategy=strict,
                              resources=ResourceSpec(n_nodes=4, elastic=True),
                              train=TrainSpec(total_steps=2,
                                              global_batch=8, seq_len=16)),
                 cfg=TINY, executor_opts=dict(sim_step_time=20.0))
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    assert h.phase == "Completed"


def test_apply_rejects_n_slots_exceeding_pool_capacity():
    clock, mc = _cluster()
    with pytest.raises(SpecError) as ei:
        mc.apply(WorkloadSpec(
            kind="serve", arch="tiny-spec",
            serve=ServeSpec(n_slots=8, n_pages=4, page_size=8,
                            max_prompt_len=8, max_seq_len=64)),
            cfg=TINY)
    codes = {(e["field"], e["code"]) for e in ei.value.errors}
    assert ("serve.n_slots", "pool-capacity") in codes
    assert ("serve.n_pages", "pool-capacity") in codes


def test_apply_rejects_over_capacity_and_collects_all_errors():
    """One SpecError carries EVERY problem, not just the first."""
    clock, mc = _cluster(max_size=4)
    with pytest.raises(SpecError) as ei:
        mc.apply(WorkloadSpec(kind="serve", arch="whisper-base",
                              resources=ResourceSpec(n_nodes=64)))
    codes = {(e["field"], e["code"]) for e in ei.value.errors}
    assert ("resources.n_nodes", "over-capacity") in codes
    assert ("arch", "not-servable") in codes   # encoder-decoder arch


def test_apply_rejects_elastic_without_minicluster():
    from repro.core import BrokerPool, FluxInstance
    clock = SimClock(seed=0)
    net = NetModel()
    graph = ResourceGraph(n_pods=1, hosts_per_pod=4)
    inst = FluxInstance(clock, net, graph, BrokerPool(clock, net, 4))
    with pytest.raises(SpecError) as ei:
        inst.apply(WorkloadSpec(kind="train", arch="lammps-proxy",
                                resources=ResourceSpec(elastic=True)))
    assert ei.value.errors[0]["code"] == "no-minicluster"


# ---------------------------------------------------------------------------
# Lifecycle + dispatch
# ---------------------------------------------------------------------------


def test_handle_lifecycle_train_elastic_resize():
    """Pending -> Bound -> Running -> Resizing -> ... -> Completed,
    observable via status()/events(), with resize detail attached."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them)")
    clock, mc = _cluster()
    h = mc.apply(WorkloadSpec(kind="train", arch="tiny-spec",
                              resources=ResourceSpec(n_nodes=2, elastic=True),
                              train=TrainSpec(total_steps=10,
                                              global_batch=8, seq_len=16)),
                 cfg=TINY, executor_opts=dict(sim_step_time=20.0))
    assert h.phase == "Pending"
    ex, job = h.executor, h.job
    _run_until(clock, lambda: job.jobid in ex.sessions
               and ex.sessions[job.jobid].step >= 2)
    assert h.phase == "Running"
    assert h.status()["hosts"] == list(job.allocation.hosts)
    mc.patch_size(4)
    assert h.phase == "Resizing"
    _run_until(clock, lambda: job.state == JobState.INACTIVE)
    assert h.phase == "Completed" and h.done
    phases = [e["phase"] for e in h.events()]
    assert phases[0] == "Pending" and phases[-1] == "Completed"
    assert "Resizing" in phases
    resize = next(e for e in h.events() if e["phase"] == "Resizing")
    assert resize["target"] == 4 and resize["source"] == "user"


def test_plain_jobspec_submissions_still_run_after_apply():
    """Jobs submitted outside apply() fall through to the instance's
    previous executor (here the sim executor) — the dispatch does not
    capture them."""
    clock, mc = _cluster()
    h = mc.apply(WorkloadSpec(kind="dryrun", arch="lammps-proxy",
                              resources=ResourceSpec(n_nodes=1)))
    plain = mc.instance.submit(JobSpec(n_nodes=1, walltime=30.0))
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE
               and plain.state == JobState.INACTIVE)
    assert h.phase == "Completed"
    assert plain.result == "completed"
    assert plain.jobid not in h.executor.ran    # sim path, not dryrun


def test_dryrun_workload_records_resolved_policy():
    clock, mc = _cluster(n_pods=2, hosts_per_pod=2, size=4, max_size=4)
    h = mc.apply(WorkloadSpec(
        kind="dryrun", arch="lammps-proxy", strategy="optimized",
        resources=ResourceSpec(n_nodes=4)))
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    rec = h.executor.ran[h.job.jobid]
    assert rec["strategy"] == "optimized"
    if len(jax.devices()) >= 8:
        assert rec["mesh_shape"] == (2, 2, 2)   # pod tier raised
        assert rec["comm"]["hierarchical"] is True


# ---------------------------------------------------------------------------
# Deprecation shims (acceptance)
# ---------------------------------------------------------------------------


def test_attach_executor_shims_warn_but_work():
    clock = SimClock(seed=0)
    net = NetModel()
    graph = ResourceGraph(n_pods=1, hosts_per_pod=4)
    from repro.core import BrokerPool, FluxInstance
    from repro.core.executor import (ElasticTrainExecutor, ServeExecutor,
                                     SubmeshExecutor)
    inst = FluxInstance(clock, net, graph, BrokerPool(clock, net, 4))
    with pytest.warns(DeprecationWarning, match="apply"):
        inst.attach_submesh_executor(steps=1)
    assert isinstance(inst.executor, SubmeshExecutor)
    with pytest.warns(DeprecationWarning, match="apply"):
        inst.attach_serve_executor()
    assert isinstance(inst.executor, ServeExecutor)
    with pytest.warns(DeprecationWarning, match="apply"):
        ex = inst.attach_elastic_executor()
    assert isinstance(ex, ElasticTrainExecutor)


def test_minicluster_attach_elastic_shim_warns():
    clock, mc = _cluster()
    with pytest.warns(DeprecationWarning, match="apply"):
        mc.attach_elastic_executor(cfg=TINY, total_steps=1)


def test_attach_after_apply_keeps_spec_dispatch():
    """An old-style attach after apply() must not clobber the spec
    dispatch: applied workloads keep their bound executors."""
    clock, mc = _cluster()
    h = mc.apply(WorkloadSpec(kind="dryrun", arch="lammps-proxy",
                              resources=ResourceSpec(n_nodes=1)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        mc.instance.attach_submesh_executor(steps=1)
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    assert h.phase == "Completed"
    assert h.job.jobid in h.executor.ran       # ran on the DRYRUN executor


# ---------------------------------------------------------------------------
# Pod-local serve packing (satellite fix)
# ---------------------------------------------------------------------------


def test_serve_allocation_packs_into_one_pod():
    """Engines pack into one pod when they fit (the rule train jobs
    already follow): with pod0 nearly full, a 2-node serve spec lands
    on two pod1 hosts — NOT scattered across the pod boundary the way
    lowest-free-id first-fit would."""
    clock, mc = _cluster(n_pods=2, hosts_per_pod=4, size=8, max_size=8,
                         chips_per_host=2)
    # blocker occupies 3 of pod0's 4 hosts for a long time
    blocker = mc.instance.submit(JobSpec(n_nodes=3, walltime=1e9))
    _run_until(clock, lambda: blocker.state == JobState.RUN, horizon=60)
    assert set(blocker.allocation.hosts) == {0, 1, 2}
    h = mc.apply(WorkloadSpec(
        kind="serve", arch="tiny-spec",
        resources=ResourceSpec(n_nodes=2),
        serve=ServeSpec(n_slots=2, max_new=2, page_size=4,
                        max_prompt_len=4, max_seq_len=8, n_requests=1)),
        cfg=TINY)
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    assert h.phase == "Completed"
    hosts = h.executor.ran[h.job.jobid]["hosts"]
    pods = {mc.instance.graph.hosts[hid].pod for hid in hosts}
    assert hosts == [4, 5] and pods == {1}, \
        "serve allocation must pack into pod 1, not span {3, 4}"


def test_pod_local_false_spec_uses_plain_first_fit():
    """resources.pod_local=false opts a workload out of pod packing:
    the matcher takes the lowest free ids even across the boundary."""
    clock, mc = _cluster(n_pods=2, hosts_per_pod=4, size=8, max_size=8,
                         chips_per_host=2)
    blocker = mc.instance.submit(JobSpec(n_nodes=3, walltime=1e9))
    _run_until(clock, lambda: blocker.state == JobState.RUN, horizon=60)
    h = mc.apply(WorkloadSpec(
        kind="dryrun", arch="lammps-proxy",
        resources=ResourceSpec(n_nodes=2, pod_local=False)))
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    assert h.executor.ran[h.job.jobid]["hosts"] == [3, 4]   # spans pods


# ---------------------------------------------------------------------------
# Scheduler fairness under mixed train+serve specs (satellite)
# ---------------------------------------------------------------------------


def test_big_train_spec_not_starved_by_serve_backfill():
    """A 4-node train spec competing with a continuous stream of 1-node
    serve specs must eventually run: smaller specs may backfill while
    the big one waits, but once it has starved past the window the
    scheduler reserves the cluster and lets it drain."""
    clock, mc = _cluster(size=4, max_size=4)
    mc.instance.starvation_window = 200.0

    def serve_spec(i):
        return WorkloadSpec(
            kind="serve", arch="tiny-spec", name=f"s{i}", user="serve",
            resources=ResourceSpec(n_nodes=1),
            serve=ServeSpec(n_slots=1, max_new=2, page_size=4,
                            max_prompt_len=4, max_seq_len=8,
                            n_requests=1))

    serve_handles = []
    # long-held 1-node serve jobs keep arriving every 40 sim-s; without
    # the reservation the 4 hosts never drain simultaneously
    opts = dict(time_scale=30.0)
    for i in range(3):
        serve_handles.append(mc.apply(serve_spec(i), cfg=TINY,
                                      executor_opts=opts))
    big = mc.apply(WorkloadSpec(
        kind="train", arch="tiny-spec", user="train",
        resources=ResourceSpec(n_nodes=4),
        train=TrainSpec(total_steps=1, global_batch=4, seq_len=8)),
        cfg=TINY)
    for i in range(3, 12):
        clock.call_at(clock.now + 40.0 * i,
                      lambda i=i: serve_handles.append(
                          mc.apply(serve_spec(i), cfg=TINY,
                                   executor_opts=opts)))
    _run_until(clock, lambda: big.job.state == JobState.INACTIVE,
               horizon=5_000.0)
    assert big.phase == "Completed"
    # backfill really happened: serve specs ran BEFORE the big one
    before = [h for h in serve_handles
              if h.job.t_run is not None and h.job.t_run < big.job.t_run]
    assert len(before) >= 3
    # and the stream continues after it (no livelock the other way)
    _run_until(clock, lambda: all(
        h.job.state == JobState.INACTIVE for h in serve_handles),
        horizon=10_000.0)
