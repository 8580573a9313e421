"""Tests for the routed multi-replica serving tier (repro.serve.fleet).

Contract under test (mirrors ROADMAP "Shipped contracts"):
  - router dispatch: pending requests go, in SLO-slack order, to the
    admissible engine with the least estimated queue wait; engines
    never hold a backlog;
  - tenant fairness: no tenant holds more than total_slots/tenants
    in-flight requests while another tenant queues;
  - prefix cache: adopting a cached page-aligned prefix skips prefill
    compute but greedy output stays token-for-token identical;
  - replica scaling: Router.desired_replicas feeds the same Autoscaler
    patch path that resizes MiniClusters (FleetDemandPolicy).
"""
from types import SimpleNamespace

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core import (Autoscaler, FleetDemandPolicy, FluxMiniCluster,
                        JobState, MiniClusterSpec, NetModel, ResourceGraph,
                        SimClock)
from repro.models.model import Model
from repro.serve import Engine, EngineConfig, Router, StreamError
from repro.spec import ResourceSpec, ServeSpec, WorkloadSpec

TINY = ModelConfig(name="tiny-fleet", family="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)

# chunked prefill (page-sized chunks) so the prefix cache is usable
ECFG = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                    max_prompt_len=8, prefill_chunk=4)


@pytest.fixture(scope="module")
def params():
    return Model(TINY).init(jax.random.PRNGKey(0))


def _engines(params, n=2, ecfg=ECFG):
    return [Engine(TINY, ecfg, params=params) for _ in range(n)]


PROMPTS = ([3, 1, 4, 1, 5], [2, 7, 1, 8], [9, 3, 11, 6, 5, 3])


# ---------------------------------------------------------------------------
# Router: identity, dispatch, fairness, SLO order
# ---------------------------------------------------------------------------


def test_router_matches_single_engine_and_spreads_load(params):
    ref = Engine(TINY, ECFG, params=params)
    want = [ref.submit(list(p), max_new_tokens=6) for p in PROMPTS]
    ref.run()

    router = Router(_engines(params))
    before = [e.stats()["n_generated"] for e in router.engines]
    got = [router.submit(list(p), max_new_tokens=6) for p in PROMPTS]
    router.run()
    assert [r.tokens for r in got] == [r.tokens for r in want]
    deltas = [e.stats()["n_generated"] - b
              for e, b in zip(router.engines, before)]
    assert all(d > 0 for d in deltas), \
        f"least-loaded dispatch must use every replica, got {deltas}"
    assert not router.pending and not router._dispatched


def test_router_rejects_unservable_request_at_submit(params):
    from repro.serve.scheduler import SubmitError
    router = Router(_engines(params))
    with pytest.raises(SubmitError):
        router.submit([1] * (ECFG.max_prompt_len + 1), max_new_tokens=2)


def test_tenant_fair_admission(params):
    """share = 4 slots / 2 tenants = 2: tenant A (6 queued) may hold at
    most 2 in-flight while tenant B still queues, so B's two requests
    are in the first dispatch wave despite arriving last."""
    router = Router(_engines(params))
    a = [router.submit(list(PROMPTS[0]), max_new_tokens=4, tenant="A")
         for _ in range(6)]
    b = [router.submit(list(PROMPTS[1]), max_new_tokens=4, tenant="B")
         for _ in range(2)]
    router.step()
    dispatched_a = [r for r in a if r.t_submit is not None]
    assert all(r.t_submit is not None for r in b), \
        "tenant B must not be starved behind tenant A's backlog"
    assert len(dispatched_a) == 2, \
        "tenant A must be capped at its share while B queues"
    router.run()
    assert all(r.finished for r in a + b)


def test_slo_slack_orders_dispatch(params):
    """Tightest ttft_slo_s deadline first: the last-submitted requests
    jump the FIFO queue when their deadline is nearer."""
    router = Router(_engines(params))
    loose = [router.submit(list(PROMPTS[0]), max_new_tokens=4)
             for _ in range(4)]
    tight = [router.submit(list(PROMPTS[1]), max_new_tokens=4,
                           ttft_slo_s=0.01) for _ in range(2)]
    router.step()                    # one wave: 4 of 6 fit the fleet
    assert all(r.t_submit is not None for r in tight), \
        "tight-SLO requests must be in the first dispatch wave"
    assert sum(r.t_submit is not None for r in loose) == 2
    router.run()


# ---------------------------------------------------------------------------
# Prefix cache: prefill skip + greedy identity
# ---------------------------------------------------------------------------


def _staggered_run(router, prompts):
    """Submit the first prompt alone so its prefix gets registered,
    then the rest (who can adopt it)."""
    reqs = [router.submit(list(prompts[0]), max_new_tokens=4)]
    for _ in range(8):
        router.step()
        if router.prefix_cache is not None \
                and router.prefix_cache.stats()["size"]:
            break
    reqs += [router.submit(list(p), max_new_tokens=4)
             for p in prompts[1:]]
    router.run()
    return [r.tokens for r in reqs]


def test_prefix_cache_skips_prefill_with_identical_greedy_output(params):
    prefix = [5, 9, 2, 6]                       # one full page
    prompts = [prefix + [10 + i, 20 + i, 3] for i in range(4)]
    engines = _engines(params)

    cold = Router(engines, prefix_cache=False)
    assert cold.prefix_cache is None
    before = sum(e.stats()["n_prefill_tokens"] for e in engines)
    want = _staggered_run(cold, prompts)
    cold_tokens = sum(e.stats()["n_prefill_tokens"]
                      for e in engines) - before

    warm = Router(engines)                      # auto-enables the cache
    assert warm.prefix_cache is not None
    before = sum(e.stats()["n_prefill_tokens"] for e in engines)
    got = _staggered_run(warm, prompts)
    warm_tokens = sum(e.stats()["n_prefill_tokens"]
                      for e in engines) - before

    assert got == want, "prefix adoption must not change greedy output"
    assert warm.prefix_cache.hits >= len(prompts) - 1
    assert warm_tokens < cold_tokens, \
        f"cache hits must skip prefill compute ({warm_tokens} vs " \
        f"{cold_tokens} prefill tokens)"


def test_prefix_cache_requires_chunked_engines(params):
    oneshot = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                           max_prompt_len=8)    # prefill_chunk=0
    engines = [Engine(TINY, oneshot, params=params) for _ in range(2)]
    assert Router(engines).prefix_cache is None
    with pytest.raises(ValueError):
        Router(engines, prefix_cache=True)


# ---------------------------------------------------------------------------
# Streaming
# ---------------------------------------------------------------------------


def test_router_stream_raises_on_foreign_request(params):
    router = Router(_engines(params))
    other = Engine(TINY, ECFG, params=params)
    req = other.submit(list(PROMPTS[0]), max_new_tokens=4)
    with pytest.raises(StreamError) as exc:
        list(router.stream(req))
    assert exc.value.errors[0]["code"] == "foreign_request"

    ours = router.submit(list(PROMPTS[1]), max_new_tokens=4)
    assert len(list(router.stream(ours))) == 4 and ours.finished


# ---------------------------------------------------------------------------
# Autoscaling: demand signal, policy, deferral
# ---------------------------------------------------------------------------


def test_desired_replicas_grows_with_backlog(params):
    router = Router(_engines(params, n=1))
    assert router.desired_replicas() == 1       # idle fleet
    for _ in range(8):
        router.submit(list(PROMPTS[0]), max_new_tokens=4)
    router.step()
    assert router.desired_replicas(target_occupancy=0.5) >= 2
    router.run()


def test_fleet_demand_policy_maps_replicas_to_hosts():
    router = SimpleNamespace(desired_replicas=lambda t: 3)
    mc = SimpleNamespace(spec=SimpleNamespace(effective_max=8))
    pol = FleetDemandPolicy(router=router, nodes_per_replica=2)
    assert pol.desired(mc) == 6
    mc.spec.effective_max = 4                   # cluster cap wins
    assert pol.desired(mc) == 4


def _mini_cluster(size, max_size, seed=0):
    clock = SimClock(seed=seed)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=8, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="fleet", size=size,
                                         max_size=max_size))
    mc.create()
    mc.wait_ready()
    return clock, mc


def test_autoscaler_defers_scale_down_in_stabilization_window():
    """A scale-down wanted inside the stabilization window is deferred
    (logged with a "deferred" tag), not dropped: a sustained drop is
    applied by the first tick past the window."""
    clock, mc = _mini_cluster(size=6, max_size=8)

    class Script:
        def __init__(self, vals):
            self.vals = list(vals)

        def desired(self, mc):
            return self.vals.pop(0) if len(self.vals) > 1 else self.vals[0]

    sc = Autoscaler(clock, mc, Script([4, 3, 3, 3, 3, 3]),
                    interval=10.0, stabilization=35.0)
    sc.start()
    clock.run(until=clock.now + 61.0)       # 6 ticks past cluster boot
    sc.stop()

    deferred = [d for d in sc.decisions if len(d) == 4 and d[3] == "deferred"]
    applied = [d for d in sc.decisions if len(d) == 3]
    assert deferred, "in-window scale-downs must be logged as deferred"
    assert all(d[2] == 3 for d in deferred)
    # first down (window long expired) applies at once; the sustained
    # drop to 3 lands on the first tick past the window, not never
    assert [(d[1], d[2]) for d in applied] == [(6, 4), (4, 3)]
    assert applied[-1][0] > deferred[-1][0]
    assert mc._desired == 3


# ---------------------------------------------------------------------------
# Spec validation + reconcile into a replicated executor
# ---------------------------------------------------------------------------


def _fleet_spec(**serve_kw):
    kw = dict(n_slots=2, max_new=4, page_size=8, max_prompt_len=8,
              max_seq_len=16, n_requests=4, prefill_chunk=8, replicas=2,
              tenant="acme", ttft_slo_s=0.5)
    kw.update(serve_kw)
    return WorkloadSpec(kind="serve", arch="yi-6b-smoke", name="fleet",
                        resources=ResourceSpec(n_nodes=1, pod_local=True),
                        serve=ServeSpec(**kw))


def test_spec_validates_fleet_fields():
    assert _fleet_spec().errors() == []
    errs = _fleet_spec(replicas=0).errors()
    assert any(e["field"] == "serve.replicas" for e in errs)
    errs = _fleet_spec(tenant="").errors()
    assert any(e["field"] == "serve.tenant" for e in errs)
    errs = _fleet_spec(ttft_slo_s=-1.0).errors()
    assert any(e["field"] == "serve.ttft_slo_s" for e in errs)
    # elastic + replicas > 1 is the live-resizable fleet (PR 10): valid
    ok = WorkloadSpec(kind="serve", arch="yi-6b-smoke",
                      resources=ResourceSpec(n_nodes=1, elastic=True),
                      serve=ServeSpec(replicas=2))
    assert ok.errors() == []


def test_apply_fleet_spec_binds_replicated_engines():
    """One serve WorkloadSpec with replicas=2 reconciles into ONE job
    holding replicas * n_nodes hosts, run by FleetServeExecutor as N
    engine bindings behind a Router."""
    clock, mc = _mini_cluster(size=4, max_size=4)
    h = mc.apply(_fleet_spec(), cfg=TINY)
    assert h.phase != "Failed", h.conditions
    assert h.job.spec.n_nodes == 2              # replicas x n_nodes
    assert h.job.spec.attributes["replicas"] == 2
    clock.run(until=clock.now + 50_000.0,
              stop_when=lambda: h.job.state == JobState.INACTIVE)
    assert h.phase == "Completed", h.conditions
    ran = h.executor.ran[h.job.jobid]
    assert ran["replicas"] == 2
    assert len(ran["mesh_shapes"]) == 2
    assert len(ran["hosts"]) == 2
    assert ran["n_requests"] == 4
    assert ran["n_tokens"] >= 4
    assert ran["desired_replicas"] >= 1


def test_fleet_demand_policy_resizes_live_fleet_end_to_end():
    """The full loop: a demand spike raises Router.desired_replicas,
    FleetDemandPolicy maps it to hosts, the Autoscaler PATCHes the
    MiniCluster, and the LIVE elastic fleet gains a replica at the next
    tick boundary — no requeue, no dropped request."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 sim devices")
    clock, mc = _mini_cluster(size=2, max_size=3)
    spec = WorkloadSpec(
        kind="serve", arch="yi-6b-smoke", name="live-fleet",
        resources=ResourceSpec(n_nodes=1, elastic=True),
        serve=ServeSpec(n_slots=2, max_new=6, page_size=8,
                        max_prompt_len=8, max_seq_len=16,
                        n_requests=2, replicas=2))
    h = mc.apply(spec, cfg=TINY, executor_opts=dict(sim_tick_time=5.0))
    ex, job = h.executor, h.job
    clock.run(until=clock.now + 50_000.0,
              stop_when=lambda: job.jobid in ex.sessions
              and ex.sessions[job.jobid].router is not None)
    ses = ex.sessions[job.jobid]
    assert len(ses.router.engines) == 2

    class LiveRouter:                  # the policy reads the CURRENT
        def desired_replicas(self, t):  # router (rebuilt on requeue)
            return ex.sessions[job.jobid].router.desired_replicas(t)

    sc = Autoscaler(clock, mc,
                    FleetDemandPolicy(router=LiveRouter(),
                                      nodes_per_replica=1,
                                      min_size=2, max_size=3),
                    interval=10.0, stabilization=100_000.0)
    sc.start()
    spike = [h.submit_request([1 + i, 2, 3], max_new_tokens=6)
             for i in range(8)]
    clock.run(until=clock.now + 50_000.0,
              stop_when=lambda: len(ses.router.engines) >= 3)
    assert len(ses.router.engines) == 3, \
        "demand spike must add a live replica via the autoscaler"
    assert h.phase in ("Resizing", "Running")
    sc.stop()
    clock.run(until=clock.now + 100_000.0,
              stop_when=lambda: job.state == JobState.INACTIVE)
    assert h.phase == "Completed", h.conditions
    assert all(r.finished and len(r.tokens) == 6 for r in spike)
    rec = ex.ran[job.jobid]
    assert rec["replicas"] == 3
    assert rec["scale_events"] and \
        rec["scale_events"][-1]["replicas"] == 3
    assert rec["n_requests"] == 10
    assert all(len(t) == 6 for t in rec["tokens"])
    # the stamped result surfaces the grown fleet (satellite: result())
    res = h.result()
    assert res["outcome"] == "completed" and res["replicas"] == 3
