"""What a cell's files describe, the harness builds: the model kind its
reference declares, session traffic, and a fleet of one-chip replicas
behind the Router, on the CPU at test size."""
import hashlib
import json
import sys
import time
import types

import _paths
import _smoke
import jax
import pytest

from bench import check, counts, harness, traffic

SEED = 2**31 + 55
DENSE = check.reference({"reference": "dense_gqa"})
# a reference of another kind, as a later configuration would bring one:
# its own sizes (the experts' group compared key by key) and kind
MOE = types.SimpleNamespace(
    FIELDS=("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
            "vocab_size", "tie_embeddings", "moe"),
    KIND={"family": "moe", "block_pattern": ("attn",),
          "norm_type": "rmsnorm", "mlp_type": "swiglu", "pos_type": "rope"},
    COUNTS="counts")
GRANITE = dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=16, vocab_size=256, tie_embeddings=True,
               moe={"n_experts": 8, "top_k": 4, "d_ff_expert": 64})


def model_of(arch):
    return GRANITE if arch.startswith("granite") else _smoke.SIZES[arch]


@pytest.mark.parametrize("arch, ref, fits", [
    ("yi-6b-smoke", DENSE, True),
    ("chatglm3-6b-smoke", DENSE, True),
    ("granite-moe-1b-a400m-smoke", MOE, True),
    ("granite-moe-1b-a400m-smoke", DENSE, False),
    ("yi-6b-smoke", MOE, False),
])
def test_check_model_holds_the_program_to_its_reference(arch, ref, fits):
    from repro.configs import registry
    cfg = registry.get(arch)
    m = dict(_smoke.SIZES["yi-6b-smoke"], **model_of(arch))
    if ref is MOE:
        m.setdefault("moe", GRANITE["moe"])
    if fits:
        harness.check_model(cfg, m, ref)
    else:
        with pytest.raises(SystemExit, match="family"):
            harness.check_model(cfg, m, ref)


def test_check_model_compares_a_group_key_by_key():
    from repro.configs import registry
    cfg = registry.get("granite-moe-1b-a400m-smoke")
    m = dict(GRANITE, moe=dict(GRANITE["moe"], top_k=2))
    with pytest.raises(SystemExit, match="moe"):
        harness.check_model(cfg, m, MOE)


def test_a_new_model_kind_comes_as_new_files(monkeypatch):
    """A configuration naming a reference module that is not under
    ``bench/`` yet: the harness builds its engine and takes its counts
    from what that module names, with no file of the harness edited."""
    mod = types.ModuleType("bench.references.moe_smoke")
    mod.FIELDS, mod.KIND, mod.COUNTS = MOE.FIELDS, MOE.KIND, "counts_moe"
    monkeypatch.setitem(sys.modules, "bench.references.moe_smoke", mod)
    own_counts = types.ModuleType("bench.counts_moe")
    monkeypatch.setitem(sys.modules, "bench.counts_moe", own_counts)
    config = {"model": GRANITE, "reference": "moe_smoke",
              "spec": {"kind": "serve", "arch": "granite-moe-1b-a400m-smoke",
                       "name": "smoke",
                       "serve": {"n_slots": 2, "page_size": 16,
                                 "max_seq_len": 64, "max_prompt_len": 32,
                                 "prefill_chunk": 16, "max_new": 8,
                                 "n_requests": 1}}}
    eng = harness.build_engine(config, None, jax.devices()[0])
    assert eng.cfg.family == "moe"
    assert check.counts(config) is own_counts
    assert check.counts({"reference": "dense_gqa"}) is counts


# The readers' values on the parent's formulas, read once with the code
# before the counts came from the record: the recorded yi-6b ticks (as
# test_engine_trace reads them) and a synthetic chatglm3-6b record.
FIXTURE = {"step_mfu_pct": 16.80358390377951,
           "paged_decode_roofline": 1.7353282498414828,
           "paged_prefill_roofline": 1.1916762743160279}
SYNTHETIC = {"step_mfu_pct": 2.517552698737056,
             "paged_decode_roofline": 2.330168888888889,
             "paged_prefill_roofline": 5.912688116204609}


def _host(name, start, dur, tick=None):
    e = {"kind": "host", "device": -1, "name": name,
         "start_ns": float(start), "dur_ns": float(dur)}
    if tick is not None:
        e["tick"] = tick
    return e


def _op(name, start, dur):
    return {"kind": "op", "device": 0, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def _config(name):
    return json.loads((_paths.ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_readers_read_as_before_on_a_recorded_window():
    import gzip

    from bench import trace_reduce
    rec = json.loads(gzip.decompress(
        (_paths.ROOT / "bench" / "tests" / "data"
         / "trace_yi6b_engine_ticks.json.gz").read_bytes()))
    cfg = _config("yi-6b")
    rec = {"trace": rec["events"], "ticks": rec["ticks"],
           "model": cfg["model"], "engine": cfg["spec"]["serve"],
           "peaks": _smoke.PEAKS, "counts": check.counts(cfg), "chips": 1,
           "window_s": trace_reduce.window_s(rec["events"])}
    for name, value in FIXTURE.items():
        assert harness.reader(name).read(rec) == pytest.approx(
            value, rel=1e-12), name


def test_readers_read_as_before_on_a_synthetic_record():
    cfg = _config("chatglm3-6b")
    ev = [_host("bench_trace_window", 0, 2e9),
          _host("engine_step", 0, 1e8, 0), _host("engine_step", 1e8, 1e8, 1),
          _op("paged_decode_attention_kernel.1", 1e7, 3e6),
          _op("paged_decode_attention_kernel.2", 1.2e8, 2e6),
          _op("paged_prefill_attention_kernel.3", 2e7, 8e6),
          _op("paged_prefill_attention_kernel.4", 1.3e8, 5e6)]
    ticks = [{"tick": 0, "t0": 0.0, "t1": 0.1, "decode": [100, 517, 2048],
              "chunks": [[0, 512]]},
             {"tick": 1, "t0": 0.1, "t1": 0.2, "decode": [101, 518],
              "chunks": [[512, 300]]},
             {"tick": 2, "t0": 1.9, "t1": 2.1, "decode": [5], "chunks": []}]
    rec = {"trace": ev, "ticks": ticks, "model": cfg["model"],
           "engine": cfg["spec"]["serve"], "peaks": _smoke.PEAKS,
           "counts": check.counts(cfg), "chips": 1, "window_s": 2.0}
    for name, value in SYNTHETIC.items():
        assert harness.reader(name).read(rec) == pytest.approx(
            value, rel=1e-12), name
    # four chips serving the same work: a quarter of the share of peak
    assert harness.reader("step_mfu_pct").read(dict(rec, chips=4)) == \
        pytest.approx(SYNTHETIC["step_mfu_pct"] / 4, rel=1e-12)


# -- session traffic -----------------------------------------------------------

def _digest(arrivals):
    return hashlib.sha256(json.dumps(
        [(a.due, a.prompt, a.max_new) for a in arrivals]).encode()
    ).hexdigest()


# schedules of mixes without sessions, as the generator drew them before
# sessions existed
@pytest.mark.parametrize("mix, seed, seconds, slots, vocab, digest", [
    (_smoke.MIX, 2**31 + 77, 3.0, 4, 256,
     "4ba45cba236241d0a35fc91bf108d7c02f9867e91e2bf4aa1a6c49376a2b9f7e"),
    ("prefill-heavy", 4_200_000_001, 51.0, 12, 64000,
     "ddf8e126c56b0855365a58c20ff33e53a8ad583e6c2280d385ffa4390080d1f6"),
    ("decode-heavy", 4_200_000_002, 51.0, 24, 65024,
     "381312b3bdb3488d51df814918e8cb821c0698b68e5fc5de160989193795994b"),
])
def test_mixes_without_sessions_draw_as_before(mix, seed, seconds, slots,
                                               vocab, digest):
    if isinstance(mix, str):
        mix = traffic.load_mix(mix)
    arrivals = traffic.schedule(mix, seed, seconds, slots, vocab)
    assert _digest(arrivals) == digest
    assert {a.tenant for a in arrivals} == {"default"}


# the sessions of a fleet cell at full size: 16 tenants' 1024-token
# system prompts, turns of 64-256 tokens
SESSIONS = {"arrival": "poisson", "rate_rps": 2.0,
            "sessions": {"tenants": 16, "system_prompt_tokens": 1024,
                         "tenant_zipf": 1.0},
            "prompt_tokens": {"dist": "lognormal", "median": 128,
                              "sigma": 0.5, "min": 64, "max": 256},
            "output_tokens": {"dist": "lognormal", "median": 64,
                              "sigma": 0.6, "min": 16, "max": 256},
            "due_at_start": 0, "drain_s": 30, "check_requests": 8}


def test_session_schedules():
    mix = SESSIONS
    s = mix["sessions"]
    a = traffic.schedule(mix, SEED, 51.0, 12, 64000)
    b = traffic.schedule(mix, SEED, 51.0, 12, 64000)
    c = traffic.schedule(mix, SEED + 1, 51.0, 12, 64000)
    assert [(x.due, x.prompt, x.max_new, x.tenant) for x in a] == \
        [(x.due, x.prompt, x.max_new, x.tenant) for x in b]
    # another seed: the same lengths, gaps and tenants, other token ids
    assert [(x.due, len(x.prompt), x.max_new, x.tenant) for x in a] == \
        [(x.due, len(x.prompt), x.max_new, x.tenant) for x in c]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    n = s["system_prompt_tokens"]
    for arrivals in (a, c):
        system = {}
        for x in arrivals:
            assert system.setdefault(x.tenant, x.prompt[:n]) == x.prompt[:n]
            assert 64 <= len(x.prompt) - n <= 256
        # every tenant its own system prompt
        assert len({tuple(p) for p in system.values()}) == len(system)
    # Zipf over 16 tenants, exponent 1: tenant 0 sends 1/H(16) = 29.6%
    # of the requests, tenant 15 a sixteenth of that
    share = [sum(x.tenant == f"tenant{i}" for x in a) / len(a)
             for i in range(s["tenants"])]
    h = sum(1 / (i + 1) for i in range(16))
    assert share[0] == pytest.approx(1 / h, abs=0.01)
    assert share[15] == pytest.approx(1 / h / 16, abs=0.01)
    assert traffic.zipf_quantiles(3, 0.0, 6).tolist() == [0, 0, 1, 1, 2, 2]


# -- the fleet ---------------------------------------------------------------------

def test_fleet_replicas_each_on_a_chip_of_their_own():
    c = _smoke.cell("yi6b.fleet4.sessions", "yi-6b-smoke")
    server = harness.build_server(c, SEED)
    engines = harness.engines(server)
    assert len(engines) == c.chips == 4
    homes = []
    for e in engines:
        held = {d for tree in (e.params, e.pool)
                for leaf in jax.tree_util.tree_leaves(tree)
                for d in leaf.devices()}
        assert len(held) == 1
        homes.append(held.pop())
    assert len(set(homes)) == 4
    assert server.prefix_cache is not None
    # warm-up compiles every replica's programs and the copy of a
    # system prompt's pages: the window compiles nothing more
    from repro.obs.metrics import compile_count
    harness.warm_up(server, c.mix)
    assert all(e.n_mixed_steps and e.n_decode_steps for e in engines)
    assert server.prefix_cache.hits == len(engines)
    before = compile_count()
    arrivals = traffic.schedule(c.mix, SEED, 2.0, 4, 256)
    rec = harness.serve_window(server, arrivals, 2.0, c.mix["drain_s"])
    assert compile_count() == before
    assert len({server.assignments[f.req.rid] for f in rec["flights"]}) > 1
    # a request that adopted a prefix computed only the rest of its prompt
    adopted = [f for f in rec["flights"] if f.adopted]
    assert adopted
    for f in adopted:
        assert f.adopted % 16 == 0 and f.adopted < len(f.prompt)
    computed = sum(n for t in rec["ticks"] for _, n in t["chunks"])
    assert computed == sum(len(f.prompt) - f.adopted
                           for f in rec["flights"])


@pytest.mark.parametrize("trace", [False, True])
def test_fleet_rehearsal_result_line(trace):
    c = _smoke.cell("yi6b.fleet4.sessions", "yi-6b-smoke")
    r = harness.run_cell(c, SEED, 2.0, trace, time.perf_counter(),
                         _smoke.PEAKS)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    names = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    if trace:
        assert set(r["metrics"]) <= names
        assert r["metrics"]["prefix_hit_pct.fleet"]["value"] > 0
        assert r["metrics"]["window_compiles.fleet"]["value"] == 0
    else:
        assert set(r["metrics"]) == names == {
            "serve_tokens_per_s", "itl_p99_ms", "setup_s"}
