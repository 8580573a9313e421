"""Elastic serving: serve workloads survive MiniCluster grow/shrink.

The invariant this suite pins (ISSUE 5 acceptance): a resize during
decode yields TOKEN-FOR-TOKEN identical outputs for every request
versus an uninterrupted run — including requests admitted mid-resize —
because the resize path parks the engine's whole decode state (paged
KV pool, block table, slot lengths, next tokens, sampling key) in the
graceful window, rebuilds the engine on the new allocation's sub-mesh,
and adopts the snapshot: the tick stream is frozen and resumed, never
replayed.
"""
import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import (FluxMiniCluster, JobState, MiniClusterSpec,
                        NetModel, ResourceGraph, SimClock)
from repro.dist.sharding import make_mesh
from repro.serve import Engine, EngineConfig
from repro.spec import ResourceSpec, ServeSpec, WorkloadSpec

TINY = ModelConfig(name="tiny-eserve", family="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)
ECFG = EngineConfig(n_slots=3, page_size=4, max_seq_len=32,
                    max_prompt_len=8)
GEN = 16
TICKS_BEFORE_RESIZE = 4

_rng = np.random.default_rng(7)
FIRST = [_rng.integers(0, TINY.vocab_size, 6).tolist() for _ in range(2)]
LATE = [_rng.integers(0, TINY.vocab_size, 5).tolist()]


def _need_8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them)")


def _run_until(clock, cond, horizon=100_000.0):
    clock.run(until=clock.now + horizon, stop_when=cond)
    assert cond(), "sim condition not reached within horizon"


def _reference_tokens(mesh_shape, temperature=0.0):
    """Uninterrupted run: same prompts, same submission tick, and the
    same weights (the executor's engines make them from seed 0 too)."""
    mesh = make_mesh(mesh_shape, ("data", "model"),
                     devices=jax.devices()[:mesh_shape[0] * mesh_shape[1]])
    eng = Engine(TINY, ECFG, mesh=mesh, seed=0)
    first = [eng.submit(p, max_new_tokens=GEN, temperature=temperature)
             for p in FIRST]
    for _ in range(TICKS_BEFORE_RESIZE):
        eng.step()
    late = [eng.submit(p, max_new_tokens=GEN, temperature=temperature)
            for p in LATE]
    eng.run()
    return [r.tokens for r in first + late]


def _elastic_run(size, max_size, patch_to, temperature=0.0,
                 sim_tick_time=40.0):
    """Operator run: resize fires after TICKS_BEFORE_RESIZE ticks, with
    the LATE requests submitted at the same tick boundary (mid-resize:
    for a shrink the engine is already parked when they arrive)."""
    clock = SimClock(seed=0)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=4, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="es", size=size,
                                         max_size=max_size))
    mc.create()
    mc.wait_ready()
    h = mc.apply(WorkloadSpec(
        kind="serve", arch="tiny-eserve",
        resources=ResourceSpec(n_nodes=size, elastic=True),
        serve=ServeSpec(n_slots=ECFG.n_slots, page_size=ECFG.page_size,
                        max_seq_len=ECFG.max_seq_len,
                        max_prompt_len=ECFG.max_prompt_len,
                        max_new=GEN, temperature=temperature,
                        n_requests=len(FIRST))),
        cfg=TINY, executor_opts=dict(sim_tick_time=sim_tick_time))
    ex, job = h.executor, h.job
    job.spec.args["prompts"] = FIRST
    job.spec.args["temperature"] = temperature
    _run_until(clock, lambda: job.jobid in ex.sessions
               and ex.sessions[job.jobid].ticks >= TICKS_BEFORE_RESIZE)
    assert ex.sessions[job.jobid].ticks == TICKS_BEFORE_RESIZE
    mc.patch_size(patch_to)
    assert h.phase == "Resizing"
    late = [h.submit_request(p, max_new_tokens=GEN,
                             temperature=temperature) for p in LATE]
    _run_until(clock, lambda: job.state == JobState.INACTIVE)
    assert h.phase == "Completed" and job.result == "completed"
    return h, ex.ran[job.jobid], late


# ---------------------------------------------------------------------------
# The elastic-serving invariant (ISSUE acceptance)
# ---------------------------------------------------------------------------


def test_grow_mid_decode_is_token_identical():
    """Grow 2 -> 4 while decoding: tokens match the uninterrupted run
    and decode genuinely CONTINUED on the grown mesh (the resume record
    proves the rebuild happened before the last tokens)."""
    _need_8()
    ref = _reference_tokens((2, 2))
    h, rec, late = _elastic_run(size=2, max_size=4, patch_to=4)
    assert rec["tokens"] == ref
    assert rec["n_resumes"] == 1
    assert rec["mesh_shape"] == (4, 2), \
        "decode must finish on the grown mesh"
    assert rec["resumes"][0]["transition"] == "2->4"
    # the mid-resize request was served in full
    assert len(late[0].tokens) == GEN


def test_shrink_mid_decode_is_token_identical():
    """Shrink 4 -> 2: the engine parks in the graceful window BEFORE
    its hosts are torn down, rides the requeue path, and resumes on the
    smaller mesh without losing a token.  The mid-resize requests are
    submitted while the engine is parked (arrival queue)."""
    _need_8()
    ref = _reference_tokens((4, 2))
    h, rec, late = _elastic_run(size=4, max_size=4, patch_to=2)
    assert rec["tokens"] == ref
    assert rec["n_resumes"] == 1
    assert rec["mesh_shape"] == (2, 2)
    assert rec["resumes"][0]["transition"] == "4->2"
    assert len(late[0].tokens) == GEN


def test_resize_token_identical_at_temperature():
    """Temperature sampling survives the resize exactly: the sampling
    key rides the parked snapshot, so the stochastic token stream is
    reproduced bit-for-bit rather than re-drawn."""
    _need_8()
    ref = _reference_tokens((2, 2), temperature=0.7)
    h, rec, late = _elastic_run(size=2, max_size=4, patch_to=4,
                                temperature=0.7)
    assert rec["tokens"] == ref
    assert rec["n_resumes"] == 1
    # a sanity check that sampling actually happened (not all-greedy):
    greedy = _reference_tokens((2, 2), temperature=0.0)
    assert rec["tokens"] != greedy


def test_lifecycle_events_cover_serve_resize():
    _need_8()
    h, rec, _ = _elastic_run(size=2, max_size=4, patch_to=4)
    phases = [e["phase"] for e in h.events()]
    assert phases[0] == "Pending" and phases[-1] == "Completed"
    assert "Resizing" in phases
    # after the resize the handle went back to Running on the new mesh
    assert phases.index("Resizing") < len(phases) - 1
    running_after = [e for e in h.events()
                     if e["phase"] == "Running" and "mesh" in e]
    assert running_after and running_after[-1]["mesh"] == [4, 2]


def test_submit_request_before_first_placement_queues():
    """The handle accepts requests as soon as apply() returns — before
    the job is even scheduled — and serves them after the declared
    batch once the engine places."""
    _need_8()
    clock = SimClock(seed=0)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=4, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="es0", size=2, max_size=2))
    mc.create()
    mc.wait_ready()
    h = mc.apply(WorkloadSpec(
        kind="serve", arch="tiny-eserve",
        resources=ResourceSpec(n_nodes=2, elastic=True),
        serve=ServeSpec(n_slots=ECFG.n_slots, page_size=ECFG.page_size,
                        max_seq_len=ECFG.max_seq_len,
                        max_prompt_len=ECFG.max_prompt_len,
                        max_new=4, n_requests=1)),
        cfg=TINY, executor_opts=dict(sim_tick_time=5.0))
    early = h.submit_request([5, 6, 7], max_new_tokens=4)
    _run_until(clock, lambda: h.job.state == JobState.INACTIVE)
    rec = h.executor.ran[h.job.jobid]
    assert rec["n_requests"] == 2      # declared batch + early arrival
    assert early.finished and len(early.tokens) == 4
    assert rec["tokens"][-1] == early.tokens   # declared batch first


def test_cluster_shrink_evicting_same_size_job_is_lossless():
    """A cluster shrink that evicts a serve job WITHOUT changing its
    own size request (its hosts are the high-index ranks the
    reconciler tears down) must still park in the graceful window:
    the job rides the requeue path and resumes token-for-token once
    hosts free up."""
    _need_8()
    from repro.core import JobSpec
    clock = SimClock(seed=0)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=4, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="es3", size=4, max_size=4))
    mc.create()
    mc.wait_ready()
    # a sim job pins hosts 0-1, pushing the serve job onto hosts 2-3 —
    # exactly the ranks a shrink to 2 tears down
    blocker = mc.instance.submit(JobSpec(n_nodes=2, walltime=300.0))
    clock.run(until=clock.now + 30,
              stop_when=lambda: blocker.state == JobState.RUN)
    h = mc.apply(WorkloadSpec(
        kind="serve", arch="tiny-eserve",
        resources=ResourceSpec(n_nodes=2, elastic=True),
        serve=ServeSpec(n_slots=ECFG.n_slots, page_size=ECFG.page_size,
                        max_seq_len=ECFG.max_seq_len,
                        max_prompt_len=ECFG.max_prompt_len,
                        max_new=GEN, n_requests=len(FIRST))),
        cfg=TINY, executor_opts=dict(sim_tick_time=40.0))
    ex, job = h.executor, h.job
    job.spec.args["prompts"] = FIRST
    _run_until(clock, lambda: job.jobid in ex.sessions
               and ex.sessions[job.jobid].ticks >= TICKS_BEFORE_RESIZE)
    assert list(job.allocation.hosts) == [2, 3]
    mc.patch_size(2)                   # evicts hosts 2-3; size req stays 2
    assert ex.sessions[job.jobid].parked is not None, \
        "the window must park the engine even though n_nodes is unchanged"
    _run_until(clock, lambda: job.state == JobState.INACTIVE)
    rec = ex.ran[job.jobid]
    assert job.requeues >= 1
    assert rec["hosts"] == [0, 1]      # re-placed after the blocker left
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    eng = Engine(TINY, ECFG, mesh=mesh, seed=0)
    reqs = [eng.submit(p, max_new_tokens=GEN) for p in FIRST]
    eng.run()
    assert rec["tokens"] == [r.tokens for r in reqs], \
        "an evicted-by-shrink serve job must not lose tokens"


def test_shrink_that_spares_the_allocation_resumes_in_place():
    """A shrink that does not touch the serve job's hosts (cluster 4 ->
    2 while the job holds 2 hosts) parks in the window, then resumes on
    the SAME allocation with zero token drift."""
    _need_8()
    clock = SimClock(seed=0)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=4, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="es2", size=4, max_size=4))
    mc.create()
    mc.wait_ready()
    h = mc.apply(WorkloadSpec(
        kind="serve", arch="tiny-eserve",
        resources=ResourceSpec(n_nodes=2, elastic=True),
        serve=ServeSpec(n_slots=ECFG.n_slots, page_size=ECFG.page_size,
                        max_seq_len=ECFG.max_seq_len,
                        max_prompt_len=ECFG.max_prompt_len,
                        max_new=GEN, n_requests=len(FIRST))),
        cfg=TINY, executor_opts=dict(sim_tick_time=40.0))
    ex, job = h.executor, h.job
    job.spec.args["prompts"] = FIRST
    _run_until(clock, lambda: job.jobid in ex.sessions
               and ex.sessions[job.jobid].ticks >= TICKS_BEFORE_RESIZE)
    held = list(job.allocation.hosts)
    mc.patch_size(2)                       # tears down hosts 2, 3 only
    _run_until(clock, lambda: job.state == JobState.INACTIVE)
    rec = ex.ran[job.jobid]
    assert rec["hosts"] == held
    assert rec["mesh_shape"] == (2, 2)
    # tokens still match the uninterrupted reference (no mid-resize
    # submissions in this scenario, so the reference skips them too)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    eng = Engine(TINY, ECFG, mesh=mesh, seed=0)
    reqs = [eng.submit(p, max_new_tokens=GEN) for p in FIRST]
    eng.run()
    assert rec["tokens"] == [r.tokens for r in reqs]
