"""Workload pipelines (repro.flow): DAG-composed WorkloadSpecs with
triggers and canary checkpoint promotion.

Contract under test (mirrors ROADMAP "Shipped contracts"):
  - PipelineSpec round-trips through to_dict/from_dict; apply-time
    validation collects EVERY problem (cycles, unknown refs, unknown
    triggers, gate/promote kind-compatibility) into one SpecError;
  - the reconciler walks the DAG event-driven off WorkloadHandle
    transitions: fan-out/fan-in, retries, failure marks descendants
    Skipped — never Failed;
  - gates read the upstream's stamped handle.result(); a failed gate
    COMPLETES, skips descendants, and leaves the serve fleet untouched;
  - canary promotion rolls new params into a LIVE fleet replica by
    replica with zero dropped requests and token-for-token identical
    prefixes for requests mid-decode on not-yet-promoted replicas;
  - cron/interval triggers are deterministic on the SimClock and a
    trigger racing a manual fire submits ONCE.
"""
import os

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core import (FluxMiniCluster, JobState, MiniClusterSpec,
                        NetModel, ResourceGraph, SimClock)
from repro.flow import (GateSpec, PipelineHandle, PipelineSpec,
                        PromoteSpec, StageSpec, TriggerSpec,
                        check_pipeline)
from repro.spec import (ResourceSpec, ServeSpec, SpecError, TrainSpec,
                        WorkloadSpec)

TINY = ModelConfig(name="tiny-flow", family="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)

MAX_NEW = 24


def _cluster(n_pods=1, hosts_per_pod=4, size=4, max_size=4,
             chips_per_host=2, seed=0):
    clock = SimClock(seed=seed)
    fleet = ResourceGraph(n_pods=n_pods, hosts_per_pod=hosts_per_pod,
                          chips_per_host=chips_per_host)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="flow", size=size,
                                         max_size=max_size))
    mc.create()
    mc.wait_ready()
    return clock, mc


def _run_until(clock, cond, horizon=100_000.0):
    clock.run(until=clock.now + horizon, stop_when=cond)
    assert cond(), "sim condition not reached within horizon"


def _dryrun(name="d", n_nodes=1):
    return WorkloadSpec(kind="dryrun", arch="lammps-proxy", name=name,
                        resources=ResourceSpec(n_nodes=n_nodes))


def _train(total_steps=4, arch="yi-6b-smoke"):
    return WorkloadSpec(
        kind="train", arch=arch, name="flow-train",
        resources=ResourceSpec(n_nodes=2, elastic=True),
        train=TrainSpec(total_steps=total_steps, global_batch=8,
                        seq_len=32, chunk_steps=2))


def _fleet(arch="yi-6b-smoke", replicas=2, n_requests=4):
    return WorkloadSpec(
        kind="serve", arch=arch, name="flow-fleet",
        resources=ResourceSpec(n_nodes=1, elastic=True),
        serve=ServeSpec(n_slots=2, page_size=8, max_prompt_len=24,
                        max_seq_len=40, max_new=MAX_NEW,
                        n_requests=n_requests, replicas=replicas,
                        tenant="canary"))


def _canary_spec(gate_value=50.0):
    return PipelineSpec(name="canary", stages=[
        StageSpec(name="fleet", kind="workload", workload=_fleet()),
        StageSpec(name="train", kind="workload", workload=_train()),
        StageSpec(name="eval-gate", kind="gate", depends_on=["train"],
                  gate=GateSpec(metric="final_loss", op="lt",
                                value=gate_value)),
        StageSpec(name="promote", kind="promote",
                  depends_on=["eval-gate"],
                  promote=PromoteSpec(from_stage="train",
                                      target="fleet")),
    ])


CANARY_OPTS = {
    # serve ticks dominate the sim timeline so the train checkpoint
    # lands while the fleet is mid-decode
    "fleet": {"cfg": TINY, "executor_opts": dict(sim_tick_time=5.0)},
    "train": {"cfg": TINY, "executor_opts": dict(sim_step_time=1.0)},
}


# ---------------------------------------------------------------------------
# Serialization + validation
# ---------------------------------------------------------------------------


def test_pipeline_round_trips_through_dict():
    p = _canary_spec()
    p.stages[1].trigger = TriggerSpec(on="cron", every=100.0,
                                      offset=10.0, count=3)
    p.stages[1].max_retries = 2
    p.stages[1].on_failure = "continue"
    q = PipelineSpec.from_dict(p.to_dict())
    assert q == p
    assert q.to_dict() == p.to_dict()


def test_committed_example_pipeline_is_valid():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "examples", "specs", "pipeline_canary.json")
    pspec, errors = check_pipeline(path)
    assert errors == []
    assert [s.kind for s in pspec.stages] == ["workload", "workload",
                                              "gate", "promote"]


def test_from_dict_rejects_unknown_keys_everywhere():
    doc = _canary_spec().to_dict()
    doc["surprise"] = 1
    doc["stages"][0]["bogus"] = 2
    doc["stages"][2]["gate"]["typo"] = 3
    with pytest.raises(SpecError) as exc:
        PipelineSpec.from_dict(doc)
    fields = {e["field"] for e in exc.value.errors}
    assert {"surprise", "stages[0].bogus",
            "stages[2].gate.typo"} <= fields


def test_errors_collects_cycles_refs_and_triggers():
    p = PipelineSpec(name="bad", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  depends_on=["b"]),
        StageSpec(name="b", kind="workload", workload=_dryrun(),
                  depends_on=["a"]),
        StageSpec(name="c", kind="workload", workload=_dryrun(),
                  depends_on=["ghost"],
                  trigger=TriggerSpec(on="hourly")),
        StageSpec(name="c", kind="mystery"),
    ])
    codes = {e["code"] for e in p.errors()}
    assert {"cycle", "unknown-ref", "unknown-trigger", "unknown-kind",
            "duplicate"} <= codes


def test_gate_and_promote_kind_compatibility():
    # a gate over a train stage cannot read a serving metric
    p = PipelineSpec(name="g", stages=[
        StageSpec(name="train", kind="workload", workload=_train()),
        StageSpec(name="gate", kind="gate", depends_on=["train"],
                  gate=GateSpec(metric="ttft_mean_s", op="lt",
                                value=1.0)),
    ])
    errs = p.errors()
    assert any(e["code"] == "kind-mismatch"
               and "gate.metric" in e["field"] for e in errs)

    # promotion needs an elastic train source and a replicated elastic
    # serve target
    p = PipelineSpec(name="p", stages=[
        StageSpec(name="d", kind="workload", workload=_dryrun()),
        StageSpec(name="solo", kind="workload",
                  workload=_fleet(replicas=1)),
        StageSpec(name="promote", kind="promote", depends_on=["d"],
                  promote=PromoteSpec(from_stage="d", target="solo")),
    ])
    fields = {e["field"] for e in p.errors() if e["code"] == "kind-mismatch"}
    assert any("promote.from_stage" in f for f in fields)
    assert any("promote.target" in f for f in fields)


def test_apply_rejects_invalid_pipeline_with_all_errors():
    clock, mc = _cluster()
    p = PipelineSpec(name="bad", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  depends_on=["a"]),
        StageSpec(name="b", kind="gate", depends_on=["a"],
                  gate=GateSpec(metric="nope")),
    ])
    with pytest.raises(SpecError) as exc:
        mc.apply_pipeline(p)
    assert len(exc.value.errors) >= 2
    assert mc.instance._pipelines.handles == {}


# ---------------------------------------------------------------------------
# DAG walk: fan-out/fan-in, retries, failure propagation
# ---------------------------------------------------------------------------


def test_dag_fan_out_fan_in_completes_in_dependency_order():
    clock, mc = _cluster()
    p = PipelineSpec(name="diamond", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun("a")),
        StageSpec(name="b", kind="workload", workload=_dryrun("b"),
                  depends_on=["a"]),
        StageSpec(name="c", kind="workload", workload=_dryrun("c"),
                  depends_on=["a"]),
        StageSpec(name="d", kind="workload", workload=_dryrun("d"),
                  depends_on=["b", "c"]),
    ])
    h = mc.apply_pipeline(p)
    assert isinstance(h, PipelineHandle)
    _run_until(clock, lambda: h.done)
    assert h.phase == "Completed"
    assert all(st.phase == "Completed" for st in h.stages.values())
    # fan-in: d starts only after BOTH b and c are done
    assert h.stages["d"].t_started >= h.stages["b"].t_done
    assert h.stages["d"].t_started >= h.stages["c"].t_done
    # one submission each; dryrun results stamped (satellite: result())
    assert all(len(st.handles) == 1 for st in h.stages.values())
    assert h.stages["a"].result["n_devices"] >= 1
    assert h.stages["a"].handle.result()["outcome"] == "completed"


class _Flaky:
    """Executor that fails the first ``n_failures`` runs."""

    def __init__(self, clock, n_failures):
        self.clock = clock
        self.n_failures = n_failures
        self.calls = 0
        self.ran = {}

    def __call__(self, job, rset, done):
        self.calls += 1
        if self.calls <= self.n_failures:
            self.clock.call_in(1.0, done, "failed", 1.0)
        else:
            self.ran[job.jobid] = {"mesh_shape": (1,), "n_devices": 1}
            self.clock.call_in(1.0, done, "completed", 1.0)


def _patched(monkeypatch, mc, n_failures):
    from repro.spec.reconcile import WorkloadReconciler
    flaky = _Flaky(mc.instance.clock, n_failures)
    monkeypatch.setattr(WorkloadReconciler, "_executor_for",
                        lambda self, *a, **k: flaky)
    return flaky


def test_failed_run_retries_up_to_max_retries(monkeypatch):
    clock, mc = _cluster()
    flaky = _patched(monkeypatch, mc, n_failures=1)
    p = PipelineSpec(name="retry", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  max_retries=1)])
    h = mc.apply_pipeline(p)
    _run_until(clock, lambda: h.done)
    assert h.phase == "Completed"
    st = h.stages["a"]
    assert st.attempts == 2 and flaky.calls == 2
    assert len(st.handles) == 2
    assert any(e["phase"] == "retry" for e in h.events())


def test_failure_marks_descendants_skipped_never_failed(monkeypatch):
    clock, mc = _cluster()
    _patched(monkeypatch, mc, n_failures=99)
    p = PipelineSpec(name="fail", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun()),
        StageSpec(name="b", kind="workload", workload=_dryrun(),
                  depends_on=["a"]),
        StageSpec(name="c", kind="workload", workload=_dryrun(),
                  depends_on=["b"]),
    ])
    h = mc.apply_pipeline(p)
    _run_until(clock, lambda: h.done)
    assert h.stages["a"].phase == "Failed"
    assert h.stages["b"].phase == "Skipped"
    assert h.stages["c"].phase == "Skipped"
    assert h.phase == "Failed"                  # on_failure="fail"


def test_on_failure_continue_keeps_pipeline_green(monkeypatch):
    clock, mc = _cluster()
    _patched(monkeypatch, mc, n_failures=99)
    p = PipelineSpec(name="soft", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  on_failure="continue")])
    h = mc.apply_pipeline(p)
    _run_until(clock, lambda: h.done)
    assert h.stages["a"].phase == "Failed"
    assert h.phase == "Completed"


# ---------------------------------------------------------------------------
# Gates read stamped results (satellite: WorkloadHandle.result())
# ---------------------------------------------------------------------------


def test_gate_reads_stamped_train_result_and_passes():
    clock, mc = _cluster()
    p = PipelineSpec(name="gated", stages=[
        StageSpec(name="train", kind="workload", workload=_train()),
        StageSpec(name="gate", kind="gate", depends_on=["train"],
                  gate=GateSpec(metric="final_loss", op="lt",
                                value=50.0)),
        StageSpec(name="after", kind="workload", workload=_dryrun()),
    ])
    p.stages[2].depends_on = ["gate"]
    h = mc.apply_pipeline(p, stage_opts={
        "train": {"cfg": TINY,
                  "executor_opts": dict(sim_step_time=1.0)}})
    _run_until(clock, lambda: h.done)
    assert h.phase == "Completed"
    # the train handle stamped steps + final loss at its terminal edge
    res = h.stages["train"].handle.result()
    assert res["kind"] == "train" and res["steps"] == 4
    assert isinstance(res["final_loss"], float)
    g = h.stages["gate"].result
    assert g["passed"] is True and g["value"] == res["final_loss"]
    assert h.stages["after"].phase == "Completed"


# ---------------------------------------------------------------------------
# Flagship: canary promotion into a LIVE fleet
# ---------------------------------------------------------------------------


def _fleet_session(handle):
    st = handle.stages["fleet"]
    return st.handle.executor.sessions[st.handle.job.jobid]


@pytest.fixture(scope="module")
def canary():
    """One control run (fleet alone, never promoted) and one full
    canary pipeline run on identical seeds/specs."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 sim devices")
    clock, mc = _cluster()
    control = mc.apply(_fleet(), cfg=TINY,
                       executor_opts=dict(sim_tick_time=5.0))
    _run_until(clock, lambda: control.job.state == JobState.INACTIVE)
    assert control.phase == "Completed"

    clock2, mc2 = _cluster()
    h = mc2.apply_pipeline(_canary_spec(), stage_opts=CANARY_OPTS)
    _run_until(clock2, lambda: h.done)
    assert h.phase == "Completed", h.status()
    return {
        "control": control.executor.ran[control.job.jobid],
        "handle": h,
        "fleet": h.stages["fleet"].handle.executor.ran[
            h.stages["fleet"].handle.job.jobid],
        "promo": h.stages["promote"].result,
        "session": _fleet_session(h),
    }


def test_canary_promotion_drops_zero_requests(canary):
    promo, rec = canary["promo"], canary["fleet"]
    assert canary["handle"].stages["promote"].phase == "Completed"
    # promotion landed mid-decode on a busy fleet...
    assert promo["in_flight_at_begin"] > 0
    assert promo["replicas"] == 2
    assert len(promo["steps"]) == 2
    assert promo["sim_promote_s"] > 0
    # ...and every request still finished with its full token budget
    assert rec["n_requests"] == 4
    assert [len(t) for t in rec["tokens"]] == [MAX_NEW] * 4
    assert rec["version"] == promo["to_version"] == 1
    assert len(rec["promotions"]) == 1


def test_canary_prefix_identity_on_unpromoted_replicas(canary):
    """Tokens generated BEFORE a request's replica was swapped came
    from the old params: they must match the never-promoted control
    run token-for-token (greedy).  Divergence is only allowed after
    the swap."""
    control = canary["control"]["tokens"]
    promoted = canary["fleet"]["tokens"]
    ses = canary["session"]
    rid_to_idx = {r.rid: i for i, r in enumerate(ses.requests)}
    checked = 0
    for step in canary["promo"]["steps"]:
        for rid, n_at_swap in step["token_progress"].items():
            i = rid_to_idx[rid]
            assert promoted[i][:n_at_swap] == control[i][:n_at_swap], \
                f"request {i} prefix diverged before its replica swap"
            assert n_at_swap < MAX_NEW      # genuinely mid-decode
            checked += 1
    assert checked > 0
    # the roll changed what the fleet serves: at least one stream
    # diverges after its swap point (same greedy prompts, new params)
    assert promoted != control


def test_failed_gate_skips_promotion_and_leaves_fleet_untouched():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 sim devices")
    clock, mc = _cluster()
    h = mc.apply_pipeline(_canary_spec(gate_value=-1.0),
                          stage_opts=CANARY_OPTS)
    _run_until(clock, lambda: h.done)
    # the gate COMPLETED (it did its job) and the pipeline is green;
    # the promote stage is Skipped — never Failed
    assert h.phase == "Completed"
    gate = h.stages["eval-gate"]
    assert gate.phase == "Completed" and gate.result["passed"] is False
    assert h.stages["promote"].phase == "Skipped"
    # the live fleet finished serving on its ORIGINAL params
    fwh = h.stages["fleet"].handle
    rec = fwh.executor.ran[fwh.job.jobid]
    assert rec["version"] == 0 and rec["promotions"] == []
    assert [len(t) for t in rec["tokens"]] == [MAX_NEW] * 4


# ---------------------------------------------------------------------------
# Triggers: deterministic on the SimClock; no double submission
# ---------------------------------------------------------------------------


def _running_times(handle, stage):
    return [e["t"] for e in handle.events()
            if e.get("stage") == stage and e["phase"] == "Running"]


def test_interval_trigger_fires_on_the_sim_grid():
    clock, mc = _cluster()
    p = PipelineSpec(name="tick", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  trigger=TriggerSpec(on="interval", every=60.0,
                                      count=2))])
    h = mc.apply_pipeline(p)
    t_armed = next(e["t"] for e in h.events()
                   if e.get("stage") == "a" and e["phase"] == "armed")
    _run_until(clock, lambda: h.done)
    assert h.phase == "Completed"
    st = h.stages["a"]
    assert st.fires == 2 and len(st.handles) == 2
    # deterministic: exactly armed-time + k*every, no drift
    assert _running_times(h, "a") == [t_armed + 60.0, t_armed + 120.0]


def test_cron_trigger_aligns_to_absolute_grid():
    clock, mc = _cluster()
    assert clock.now > 0                     # boot consumed sim time
    p = PipelineSpec(name="cron", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  trigger=TriggerSpec(on="cron", every=100.0,
                                      count=1))])
    h = mc.apply_pipeline(p)
    _run_until(clock, lambda: h.done)
    (t_fire,) = _running_times(h, "a")
    # cron is grid-ALIGNED: the fire lands on an absolute multiple of
    # the period regardless of when the pipeline was applied
    assert t_fire % 100.0 == 0.0 and t_fire >= clock.now - 100_000.0
    assert h.stages["a"].fires == 1


def test_trigger_racing_manual_fire_submits_once():
    clock, mc = _cluster()
    p = PipelineSpec(name="race", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun(),
                  trigger=TriggerSpec(on="interval", every=50.0,
                                      count=1))])
    h = mc.apply_pipeline(p)
    t_armed = next(e["t"] for e in h.events()
                   if e.get("stage") == "a" and e["phase"] == "armed")
    # a manual fire lands at EXACTLY the trigger's grid point
    clock.call_at(t_armed + 50.0, h.fire, "a")
    _run_until(clock, lambda: h.done)
    st = h.stages["a"]
    assert st.fires == 1 and len(st.handles) == 1, \
        "racing edges must submit exactly one run"
    reasons = [e.get("reason") for e in h.events()
               if e.get("stage") == "a"
               and e["phase"] == "fire_suppressed"]
    assert reasons, "the losing edge must be recorded as suppressed"


def test_manual_fire_while_running_is_suppressed():
    clock, mc = _cluster()
    p = PipelineSpec(name="live", stages=[
        StageSpec(name="a", kind="workload", workload=_train())])
    h = mc.apply_pipeline(p, stage_opts={
        "a": {"cfg": TINY, "executor_opts": dict(sim_step_time=5.0)}})
    _run_until(clock, lambda: h.stages["a"].phase == "Running")
    assert h.fire("a") is False              # run still live
    _run_until(clock, lambda: h.done)
    assert h.stages["a"].fires == 1 and len(h.stages["a"].handles) == 1


# ---------------------------------------------------------------------------
# Observability: pipeline spans
# ---------------------------------------------------------------------------


def test_spans_from_pipeline_emits_per_stage_timelines():
    from repro.obs import Tracer, spans_from_pipeline, to_chrome_trace
    clock, mc = _cluster()
    p = PipelineSpec(name="obs", stages=[
        StageSpec(name="a", kind="workload", workload=_dryrun("a")),
        StageSpec(name="b", kind="workload", workload=_dryrun("b"),
                  depends_on=["a"])])
    h = mc.apply_pipeline(p)
    _run_until(clock, lambda: h.done)
    tr = Tracer()
    spans = spans_from_pipeline(h, tr)
    traces = {sp.trace for sp in spans}
    pid = h.pid
    assert traces == {f"pipe-{pid}", f"pipe-{pid}/a", f"pipe-{pid}/b"}
    names = {sp.name for sp in spans if sp.trace == f"pipe-{pid}/a"}
    assert {"running", "completed"} <= names
    doc = to_chrome_trace(tr, meta={})
    assert any(ev.get("ph") == "X" for ev in doc["traceEvents"])
