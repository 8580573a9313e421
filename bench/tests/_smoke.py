"""A cell at CPU-test size: the harness's code paths on a tiny model of
each configuration's kind, with the real cells' limits."""
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


def cell(name: str, arch: str, mix=None) -> harness.Cell:
    """Cell ``name`` of the manifest, its configuration swapped for the
    -smoke model ``arch`` and its traffic for ``mix``."""
    real = harness.load_cell(name, MANIFEST)
    config = {
        "model": SIZES[arch], "reference": "dense_gqa",
        "spec": {"kind": "serve", "arch": arch, "name": "smoke",
                 "serve": {"n_slots": 4, "page_size": 16,
                           "max_seq_len": 256, "max_prompt_len": 256,
                           "prefill_chunk": 64, "max_new": 32,
                           "n_requests": 1}}}
    return harness.Cell(name, 1, config, dict(mix or MIX), real.limits,
                        real.end_to_end, real.per_layer)
