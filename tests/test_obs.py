"""Tests for the unified observability layer (repro.obs).

Contract under test (mirrors ROADMAP "Observability contract"):
  - metrics: labelled counter/gauge/histogram registry with JSON
    snapshot (cumulative ``le`` buckets) + Prometheus text exposition;
    ``merged`` relabels each part with a ``source`` label; the legacy
    ``Engine.n_prefills``-style attributes are shims over the registry
    and survive the elastic park/restore tuple-assignment;
  - tracing: spans + instant events on one injectable clock; a
    finished request's TTFT spans (router_hold + queue_wait + prefill
    + first_decode) telescope to its stamped ``ttft_e2e`` EXACTLY,
    under the wall clock and under a virtual tick clock;
  - engine ticks: each ``Engine.step`` tick is one ``engine.step`` span
    whose six phase children nest inside it in order, as tracer spans
    and as ``jax.profiler`` annotations, without changing a token;
    ``serve_compiles_total{program}`` counts compilations inside a
    launch (warm-up ticks only);
  - clock injection: Router inherits the engines' clock, so SLO-slack
    dispatch ordering is deterministic under sim time (the fleet-bench
    clock-split fix);
  - autoscaler decisions land in the registry (scale_up / scale_down /
    deferred counted distinctly) and as why-events on the tracer;
  - exports: chrome traces refuse unclosed spans; provenance headers
    carry backend/jax_version/git_sha/timestamp.
"""
import json
from types import SimpleNamespace

import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core import (Autoscaler, FluxMiniCluster, MiniClusterSpec,
                        NetModel, ResourceGraph, SimClock)
from repro.models.model import Model
from repro.obs import (ENGINE_PHASES, MetricsRegistry, SimTime, TickClock,
                       Tracer, WallClock, compile_count, phase, provenance,
                       spans_from_handle, to_chrome_trace, ttft_breakdown)
from repro.obs.trace import TTFT_SPANS
from repro.serve import Engine, EngineConfig, Router

TINY = ModelConfig(name="tiny-obs", family="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)
ECFG = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                    max_prompt_len=8, prefill_chunk=4)


@pytest.fixture(scope="module")
def params():
    return Model(TINY).init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    m = MetricsRegistry()
    m.inc("reqs_total", tenant="a")
    m.inc("reqs_total", 2, tenant="a")
    m.inc("reqs_total", tenant="b")
    m.set("pending", 7)
    m.observe("ttft_s", 0.003)
    m.observe("ttft_s", 2.0)
    assert m.value("reqs_total", tenant="a") == 3
    assert m.value("reqs_total", tenant="b") == 1
    assert m.value("reqs_total", tenant="nope") == 0.0
    assert m.value("pending") == 7
    h = m.histogram("ttft_s")
    assert h["count"] == 2 and h["min"] == 0.003 and h["max"] == 2.0
    # put: the absolute-set path (elastic park/restore adoption)
    m.put("reqs_total", 10, tenant="a")
    assert m.value("reqs_total", tenant="a") == 10


def test_registry_snapshot_buckets_cumulative():
    m = MetricsRegistry()
    for v in (0.002, 0.002, 0.3, 100.0):
        m.observe("lat_s", v)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    [h] = snap["histograms"]
    counts = [b["count"] for b in h["buckets"]]
    assert counts == sorted(counts), "le buckets must be cumulative"
    assert counts[-1] == h["count"] == 4
    assert h["buckets"][-1]["le"] == "+Inf"
    json.dumps(snap)                           # JSON-ready


def test_registry_prometheus_text():
    m = MetricsRegistry()
    m.inc("reqs_total", tenant="a")
    m.set("pending", 3)
    m.observe("lat_s", 0.02)
    text = m.to_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{tenant="a"} 1' in text
    assert "# TYPE pending gauge" in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_count 1" in text


def test_registry_merged_relabels_sources():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("ticks_total", 5, kind="decode")
    b.inc("ticks_total", 7, kind="decode")
    merged = MetricsRegistry.merged({"engine0": a, "engine1": b})
    assert merged.value("ticks_total", kind="decode", source="engine0") == 5
    assert merged.value("ticks_total", kind="decode", source="engine1") == 7


# ---------------------------------------------------------------------------
# Tracer + chrome export
# ---------------------------------------------------------------------------


def test_tracer_begin_end_and_unclosed_export_error():
    clock = TickClock()
    tr = Tracer(clock)
    sp = tr.begin("work", "wl-1", detail="x")
    clock.advance(3.0)
    with pytest.raises(ValueError, match="unclosed"):
        to_chrome_trace(tr)
    doc = to_chrome_trace(tr, allow_open=True)
    [ev] = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert ev["args"]["unclosed"] is True and ev["dur"] == 0.0
    tr.end(sp)
    assert sp.duration == 3.0 and not tr.open_spans()
    doc = to_chrome_trace(tr)
    [ev] = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert ev["dur"] == pytest.approx(3e6)     # ticks export as seconds


def test_chrome_trace_structure_and_threads():
    tr = Tracer(TickClock())
    tr.span("phase", "wl-1", 1.0, 2.0)
    tr.span("phase", "wl-2", 2.0, 4.0)
    tr.event("why", "wl-1", t=1.5, reason="test")
    doc = to_chrome_trace(tr, meta={"backend": "cpu"})
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"wl-1", "wl-2"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert min(e["ts"] for e in xs) == 0.0     # relative to earliest
    assert doc["otherData"] == {"backend": "cpu"}
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert instants[0]["args"]["reason"] == "test"


def test_provenance_header_keys():
    meta = provenance(extra_field=1)
    for key in ("backend", "jax_version", "git_sha", "timestamp",
                "mesh_shape", "extra_field"):
        assert key in meta
    assert meta["backend"] != ""


def test_spans_from_handle_stub():
    handle = SimpleNamespace(
        job=SimpleNamespace(jobid=42),
        events=lambda: [
            {"t": 0.0, "phase": "PENDING"},
            {"t": 1.0, "phase": "BINDING", "node": 3},
            {"t": 2.0, "phase": "BINDING", "node": 4},    # same-phase
            {"t": 3.0, "phase": "RUNNING"},
        ])
    tr = Tracer()
    spans = spans_from_handle(handle, tr)
    assert [(s.name, s.t_start, s.t_end) for s in spans] == [
        ("pending", 0.0, 1.0), ("binding", 1.0, 3.0),
        ("running", 3.0, 3.0)]
    assert all(s.trace == "wl-42" for s in spans)
    [ev] = tr.events                           # same-phase detail
    assert ev["name"] == "binding" and ev["t"] == 2.0


# ---------------------------------------------------------------------------
# Engine instrumentation: shims, exact TTFT reconstruction
# ---------------------------------------------------------------------------


def test_engine_stats_shim_and_counter_restore(params):
    eng = Engine(TINY, ECFG, params=params)
    r = eng.submit([3, 1, 4, 1], max_new_tokens=3)
    eng.run()
    assert r.finished
    s = eng.stats()
    assert set(s) >= {"n_prefills", "n_prefill_tokens", "n_decode_steps",
                      "n_mixed_steps", "n_generated"}
    # the attributes ARE registry series
    assert s["n_generated"] == eng.metrics.value(
        "serve_generated_tokens_total")
    assert s["n_mixed_steps"] == eng.metrics.value(
        "serve_ticks_total", kind="mixed")
    # park/restore tuple-assignment writes through to the registry
    eng.n_prefills, eng.n_decode_steps, eng.n_generated = (5, 7, 9)
    assert eng.stats()["n_prefills"] == 5
    assert eng.metrics.value("serve_prefills_total") == 5
    assert eng.metrics.value("serve_ticks_total", kind="decode") == 7
    assert eng.metrics.value("serve_generated_tokens_total") == 9


def test_engine_page_occupancy_gauges(params):
    eng = Engine(TINY, ECFG, params=params)
    r = eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    eng.step()
    assert eng.metrics.value("serve_pages_in_use", shard=0) > 0
    eng.run()
    assert r.finished
    # after the last eviction the gauge reads the drained pool
    assert eng.metrics.value("serve_pages_in_use", shard=0) == 0


def test_traced_engine_reconstructs_ttft_exactly_wall(params):
    tracer = Tracer(WallClock())
    eng = Engine(TINY, ECFG, params=params, tracer=tracer)
    reqs = [eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
            for _ in range(3)]
    eng.run()
    for r in reqs:
        spans = tracer.spans_for(f"req-{r.rid}")
        assert {s.name for s in spans} >= set(TTFT_SPANS)
        got = ttft_breakdown(spans)
        assert got["sum_s"] == r.ttft_e2e      # EXACT, not approx
        assert got["start"] == r.t_created and got["end"] == r.t_first
    # ttft histograms observed on finish
    assert eng.metrics.histogram("serve_ttft_s")["count"] == 3


def test_traced_engine_reconstructs_ttft_exactly_tick(params):
    clock = TickClock()
    tracer = Tracer(clock)
    eng = Engine(TINY, ECFG, params=params, clock=clock, tracer=tracer)
    reqs = [eng.submit([3, 1, 4, 1], max_new_tokens=3) for _ in range(2)]
    while eng.step():
        clock.advance(1.0)
    for r in reqs:
        assert r.finished
        got = ttft_breakdown(tracer.spans_for(f"req-{r.rid}"))
        assert got["sum_s"] == r.ttft_e2e
        assert float(got["sum_s"]).is_integer()    # pure tick axis


# ---------------------------------------------------------------------------
# Engine tick spans and the compile counter
# ---------------------------------------------------------------------------


def _serve(params, tracer=None, ecfg=ECFG):
    """Two requests, the second arriving mid-decode: mixed ticks while
    a prompt is chunked in, decode ticks otherwise."""
    eng = Engine(TINY, ecfg, params=params, tracer=tracer)
    a = eng.submit([3, 1, 4, 1, 5], max_new_tokens=5)
    for _ in range(4):
        eng.step()
    b = eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=4)
    eng.run()
    return eng, [a.tokens, b.tokens]


def _ticks(tracer):
    """Each ``tick-<n>`` trace's ``engine.step`` span and its other
    spans, in start order."""
    out = {}
    for sp in sorted(tracer.spans, key=lambda s: s.t_start):
        if sp.trace.startswith("tick-"):
            out.setdefault(sp.trace, []).append(sp)
    return out


def test_phase_is_a_tracer_span_and_takes_late_attributes():
    clock = TickClock()
    tr = Tracer(clock)
    with phase(tr, "engine.step", "tick-0", tick=0) as p:
        clock.advance(2.0)
        p.set_metadata(kind="decode")
    with phase(None, "engine.step", "tick-1", tick=1) as q:
        q.set_metadata(kind="mixed")         # no tracer: annotation only
    [sp] = tr.spans
    assert (sp.name, sp.trace, sp.duration) == ("engine.step", "tick-0", 2.0)
    assert sp.attrs == {"tick": 0, "kind": "decode"}
    assert isinstance(q, jax.profiler.TraceAnnotation)
    assert not tr.open_spans()


def test_engine_tokens_identical_with_and_without_tracer(params):
    _, plain = _serve(params)
    _, traced = _serve(params, Tracer(WallClock()))
    assert plain == traced and all(plain)


@pytest.mark.parametrize("chunk", [4, 0], ids=["chunked", "one_shot"])
def test_engine_step_spans_nest_six_phases_in_order(params, chunk):
    tracer = Tracer(WallClock())
    ecfg = EngineConfig(n_slots=2, page_size=4, max_seq_len=32,
                        max_prompt_len=8, prefill_chunk=chunk)
    eng, _ = _serve(params, tracer, ecfg)
    ticks = _ticks(tracer)
    assert len(ticks) == sum(eng.metrics.value("serve_ticks_total", kind=k)
                             for k in ("prefill", "mixed", "decode"))
    kinds = set()
    for n, (step, *kids) in enumerate(ticks[t] for t in sorted(
            ticks, key=lambda t: int(t.split("-")[1]))):
        assert step.name == "engine.step" and step.trace == f"tick-{n}"
        assert step.attrs["tick"] == n
        kinds.add(step.attrs["kind"])
        # one request a one-shot tick here: six children either way
        assert tuple(k.name for k in kids) == ENGINE_PHASES
        for k in kids:
            assert step.t_start <= k.t_start <= k.t_end <= step.t_end
        for a, b in zip(kids, kids[1:]):
            assert a.t_end <= b.t_start
        assert sum(k.duration for k in kids) <= step.duration
        [launch] = [k for k in kids if k.name == "engine.launch"]
        assert launch.attrs["program"] == step.attrs["kind"]
    assert kinds == ({"mixed", "decode"} if chunk else {"prefill", "decode"})


def test_engine_counts_compiles_on_warm_up_ticks_only(params):
    tracer = Tracer(WallClock())
    eng = Engine(TINY, ECFG, params=params, tracer=tracer)

    def compiles():
        return {p: eng.metrics.value("serve_compiles_total", program=p)
                for p in ("mixed", "decode")}

    eng.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    c0 = compile_count()
    eng.step()                                   # first mixed tick
    assert compiles()["mixed"] >= 1 and compiles()["decode"] == 0
    while eng.n_decode_steps == 0:
        eng.step()
    warm = compiles()
    assert warm["decode"] >= 1
    assert compile_count() - c0 >= sum(warm.values())
    launches = [s for s in tracer.spans if s.name == "engine.launch"]
    assert launches[0].attrs["compiled"] == warm["mixed"]
    # the same shapes again: decode ticks, a new prompt's mixed ticks
    eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=4)
    eng.run()
    assert eng.n_mixed_steps > 2 and eng.n_decode_steps > 2
    assert compiles() == warm
    assert sum("compiled" in s.attrs for s in tracer.spans
               if s.name == "engine.launch") == 2


def test_engine_spans_reach_the_profiler_trace(params, tmp_path):
    from jax.profiler import ProfileData
    eng = Engine(TINY, ECFG, params=params)
    eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    eng.step()                                     # compile outside
    jax.profiler.start_trace(str(tmp_path))
    eng.run()
    jax.profiler.stop_trace()
    [path] = list(tmp_path.rglob("*.xplane.pb"))
    got = [(ev.name, dict(ev.stats))
           for plane in ProfileData.from_file(str(path)).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("engine.")]
    steps = [st for name, st in got if name == "engine.step"]
    assert len(steps) == eng.n_mixed_steps + eng.n_decode_steps - 1
    assert {st["kind"] for st in steps} == {"mixed", "decode"}
    assert {name for name, _ in got} == {"engine.step", *ENGINE_PHASES}
    assert {st["program"] for name, st in got
            if name == "engine.launch"} == {"mixed", "decode"}


# ---------------------------------------------------------------------------
# Clock split fix: deterministic SLO-slack ordering under sim time
# ---------------------------------------------------------------------------


def _slack_run(params):
    clock = TickClock()
    one_slot = EngineConfig(n_slots=1, page_size=4, max_seq_len=16,
                            max_prompt_len=8, prefill_chunk=4)
    eng = Engine(TINY, one_slot, params=params, clock=clock)
    router = Router([eng])
    assert router.clock is clock       # inherited, not raw wall time
    # a arrives first with a loose SLO; b arrives 5 ticks later with a
    # tight one — slack(a) = 100-5 = 95, slack(b) = 2: b must dispatch
    # first even though a is ahead in FIFO order
    a = router.submit([3, 1, 4, 1], max_new_tokens=2, ttft_slo_s=100.0)
    clock.advance(5.0)
    b = router.submit([2, 7, 1, 8], max_new_tokens=2, ttft_slo_s=2.0)
    router.step()
    order = (b.t_submit is not None, a.t_submit is None)
    while router.has_work:
        clock.advance(1.0)
        router.step()
    return order, [(r.t_created, r.t_submit, r.t_admit, r.t_first)
                   for r in (a, b)]


def test_router_slack_ordering_deterministic_under_tick_clock(params):
    order1, stamps1 = _slack_run(params)
    order2, stamps2 = _slack_run(params)
    assert order1 == (True, True), "tight-slack request dispatches first"
    # bit-identical stamps across runs: sim time, not wall time
    assert stamps1 == stamps2


# ---------------------------------------------------------------------------
# Autoscaler decision logging
# ---------------------------------------------------------------------------


class _Script:
    def __init__(self, vals):
        self.vals = list(vals)

    def desired(self, mc):
        return self.vals.pop(0) if len(self.vals) > 1 else self.vals[0]


def _mini_cluster(size, max_size, seed=0):
    clock = SimClock(seed=seed)
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=8, chips_per_host=2)
    mc = FluxMiniCluster(clock, NetModel(), fleet,
                         MiniClusterSpec(name="obs", size=size,
                                         max_size=max_size))
    mc.create()
    mc.wait_ready()
    return clock, mc


def test_autoscaler_decisions_counted_distinctly_and_traced():
    """scale_up / scale_down / deferred land in the registry as three
    distinct series; the deferred target applies at window expiry; the
    tracer carries the why-events at the decision's sim time."""
    clock, mc = _mini_cluster(size=6, max_size=8)
    reg = MetricsRegistry()
    tracer = Tracer(SimTime(clock))
    sc = Autoscaler(clock, mc, _Script([8, 4, 3, 3, 3, 3, 3]),
                    interval=10.0, stabilization=35.0,
                    metrics=reg, tracer=tracer)
    sc.start()
    clock.run(until=clock.now + 75.0)
    sc.stop()

    applied = [d for d in sc.decisions if len(d) == 3]
    deferred = [d for d in sc.decisions if len(d) == 4]
    # the decisions list format is unchanged (pinned elsewhere); here
    # the registry must agree with it, decision kinds counted apart
    assert [(d[1], d[2]) for d in applied] == [(6, 8), (8, 4), (4, 3)]
    assert deferred and all(d[3] == "deferred" for d in deferred)
    assert reg.value("autoscale_decisions_total", decision="scale_up") == 1
    assert reg.value("autoscale_decisions_total", decision="scale_down") == 2
    assert reg.value("autoscale_decisions_total",
                     decision="deferred") == len(deferred)

    events = [e for e in tracer.events if e["trace"] == "autoscaler"]
    names = [e["name"] for e in events]
    assert names.count("autoscale_scale_up") == 1
    assert names.count("autoscale_scale_down") == 2
    assert names.count("autoscale_deferred") == len(deferred)
    # the window-expiry apply is stamped at the decision's sim time and
    # lands AFTER the last deferral
    last_down = [e for e in events if e["name"] == "autoscale_scale_down"][-1]
    assert last_down["t"] == applied[-1][0]
    assert last_down["attrs"]["target"] == 3
    assert last_down["t"] > deferred[-1][0]
