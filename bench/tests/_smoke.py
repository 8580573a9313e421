"""A cell at CPU-test size: the harness's code paths on a tiny model of
each configuration's kind, with the real cells' chips and limits."""
import copy
import json

import _paths

from bench import harness

ROOT = _paths.ROOT
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]

SIZES = {  # the registry's -smoke configurations
    "yi-6b-smoke": dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, vocab_size=256,
                        rope_theta=10000.0, rope_fraction=1.0,
                        qkv_bias=False, norm_eps=1e-5,
                        tie_embeddings=False),
}
SIZES["chatglm3-6b-smoke"] = dict(SIZES["yi-6b-smoke"], rope_fraction=0.5,
                                  qkv_bias=True)

MIX = {"arrival": "poisson", "rate_rps": 6.0,
       "prompt_tokens": {"dist": "lognormal", "median": 100, "sigma": 0.5,
                         "min": 16, "max": 192},
       "output_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.7,
                         "min": 8, "max": 64},
       "due_at_start": "n_slots", "drain_s": 20, "check_requests": 8}

# session traffic at test size: 4 tenants' 4-page system prompts
SESSIONS = {"arrival": "poisson", "rate_rps": 8.0,
            "sessions": {"tenants": 4, "system_prompt_tokens": 64,
                         "tenant_zipf": 1.0},
            "prompt_tokens": {"dist": "lognormal", "median": 32,
                              "sigma": 0.5, "min": 16, "max": 64},
            "output_tokens": {"dist": "lognormal", "median": 32,
                              "sigma": 0.7, "min": 8, "max": 64},
            "due_at_start": 0, "drain_s": 20, "check_requests": 8}


# A fleet cell as a manifest entry would describe it: four one-chip
# replicas behind the Router under session traffic, reporting what such
# a cell would (no fleet cell is in BENCHMARK.json yet)
FLEET = "yi6b.fleet4.sessions"
FLEET_E2E = [dict(m, workloads=[FLEET]) for m in MANIFEST["end_to_end"]
             if m["name"] in ("serve_tokens_per_s", "itl_p99_ms",
                              "setup_s")]
FLEET_PER_LAYER = [
    {"name": f"{n}.fleet", "unit": u, "better": b, "source": src,
     "layer": layer, "moves": "serve_tokens_per_s", "workloads": [FLEET]}
    for n, u, b, src, layer in [
        ("prefix_hit_pct", "%", "higher", "program_counter", "router"),
        ("step_mfu_pct", "%", "higher", "host_clock", "steps"),
        ("device_idle_pct", "%", "lower", "device_trace", "device"),
        ("window_compiles", "compiles", "lower", "program_counter",
         "steps")]]


def cell(name: str, arch: str, mix=None) -> harness.Cell:
    """Cell ``name`` of the manifest (or the fleet cell above), its
    configuration swapped for the -smoke model ``arch`` and its traffic
    for a copy of ``mix`` (by default the test-size mix of the cell's
    kind of traffic)."""
    if name == FLEET:
        real = harness.Cell(name, 4, {}, SESSIONS, {"logit_gap": 0.9},
                            FLEET_E2E, FLEET_PER_LAYER)
    else:
        real = harness.load_cell(name, MANIFEST)
    if mix is None:
        mix = SESSIONS if "sessions" in real.mix else MIX
    config = {
        "model": SIZES[arch], "reference": "dense_gqa",
        "spec": {"kind": "serve", "arch": arch, "name": "smoke",
                 "serve": {"n_slots": 4, "page_size": 16,
                           "max_seq_len": 256, "max_prompt_len": 256,
                           "prefill_chunk": 64, "max_new": 32,
                           "n_requests": 1}}}
    return harness.Cell(name, real.chips, config, copy.deepcopy(mix),
                        real.limits, real.end_to_end, real.per_layer)
