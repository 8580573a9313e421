"""The output check fails what it must: the fp8 control, and a run whose
timed path is broken underneath, on the CPU at test size."""
import time

import _smoke
import pytest

from bench import check, harness, traffic

SEED = 4_000_000_123


def run(name, arch):
    return harness.run_cell(_smoke.cell(name, arch), SEED, 2.0, False,
                            time.perf_counter(), _smoke.PEAKS)


@pytest.fixture
def token_altered(monkeypatch):
    """Every sampled token moved to the next id, where it is produced."""
    from repro.serve import engine
    real = engine.sample_tokens

    def altered(logits, temps, key):
        return (real(logits, temps, key) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine, "sample_tokens", altered)


@pytest.fixture
def state_unchanged(monkeypatch):
    """The decode and mixed steps hand back the KV pool they were given."""
    from repro.dist import steps

    def frozen(build):
        def build_frozen(*a, **k):
            fn, *rest = build(*a, **k)

            def step(params, pool, *args):
                *out, _ = fn(params, pool, *args)
                return (*out, pool)
            return (step, *rest)
        return build_frozen
    monkeypatch.setattr(steps, "build_decode_step",
                        frozen(steps.build_decode_step))
    monkeypatch.setattr(steps, "build_mixed_step",
                        frozen(steps.build_mixed_step))


@pytest.fixture
def prefix_not_copied(monkeypatch):
    """An adoption from the fleet's prefix cache skips the cached prompt
    tokens without copying their pages into the slot."""
    from repro.serve.fleet import PrefixCache

    def adopt(self, engine, req):
        k, _ = (0, None) if req.prefill_progress else self.lookup(req.prompt)
        req.prefill_progress = req.prefill_progress or k * self.page_size
        return k * self.page_size
    monkeypatch.setattr(PrefixCache, "adopt", adopt)


CASES = [("yi6b.prefill", "yi-6b-smoke"),
         ("chatglm3.decode", "chatglm3-6b-smoke"),
         ("yi6b.fleet4.sessions", "yi-6b-smoke")]


@pytest.mark.parametrize("name, arch", CASES)
def test_sound_run_is_correct(name, arch):
    r = run(name, arch)
    assert r["correct"] is True
    assert r["check"]["logit_gap"]["value"] <= \
        r["check"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name, arch", CASES)
def test_altered_token_is_not_correct(name, arch, token_altered):
    r = run(name, arch)
    assert r["correct"] is False
    assert r["check"]["logit_gap"]["value"] > \
        r["check"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name, arch", CASES)
def test_unchanged_state_is_not_correct(name, arch, state_unchanged):
    r = run(name, arch)
    assert r["correct"] is False
    assert r["check"]["logit_gap"]["value"] > \
        r["check"]["logit_gap"]["limit"]


def test_prefix_not_copied_is_not_correct(prefix_not_copied):
    r = run("yi6b.fleet4.sessions", "yi-6b-smoke")
    assert r["correct"] is False
    assert r["check"]["logit_gap"]["value"] > \
        r["check"]["logit_gap"]["limit"]


def _medium(monkeypatch, name):
    """Cell ``name`` on a model the CPU can hold that is large enough
    for its rounding to look like the chip's: d_model 256, 4 layers, 8
    heads over 2 KV heads, vocab 4096; slots of 512 tokens."""
    import dataclasses

    from repro.configs import registry
    small = dict(_smoke.SIZES["yi-6b-smoke"], d_model=256, n_layers=4,
                 n_heads=8, n_kv_heads=2, head_dim=32, d_ff=688,
                 vocab_size=4096)
    cfg = dataclasses.replace(registry.get("yi-6b-smoke"),
                              **{k: small[k] for k in (
                                  "d_model", "n_layers", "n_heads",
                                  "n_kv_heads", "d_ff", "vocab_size")})
    real_get = registry.get
    monkeypatch.setattr(registry, "get", lambda i: cfg
                        if i == "yi-6b-smoke" else real_get(i))
    monkeypatch.setitem(_smoke.SIZES, "yi-6b-smoke", small)
    c = _smoke.cell(name, "yi-6b-smoke")
    c.config["spec"]["serve"].update(max_seq_len=512, max_prompt_len=512,
                                     prefill_chunk=128)
    return c, small


def _program_and_control(c, seed, vocab):
    server = harness.build_server(c, seed)
    harness.warm_up(server, c.mix)
    arrivals = traffic.schedule(c.mix, seed, 2.0, 4, vocab)
    rec = harness.serve_window(server, arrivals, 2.0, c.mix["drain_s"])
    picked = check.sample(rec["flights"], seed, 4)
    del server
    weights = check.make_weights(c.config, seed)
    return (picked, check.served_gap(c.config, weights, picked),
            check.control_gaps(c.config, weights, picked))


def test_fleet_control_reads_far_above_the_program(monkeypatch):
    """The fleet's control at the same size: four replicas serving
    sessions whose 256-token system prompts come from the prefix cache;
    not yet measured on the chip."""
    c, small = _medium(monkeypatch, "yi6b.fleet4.sessions")
    c.mix["sessions"].update(system_prompt_tokens=256)
    for seed in (SEED, SEED + 1):
        picked, program, control = _program_and_control(
            c, seed, small["vocab_size"])
        assert any(f.adopted for f in picked)
        assert sum(len(f.tokens) for f in picked) >= 30
        assert control > 3 * max(program, 0.01), (program, control)


def test_control_reads_far_above_the_program(monkeypatch):
    """The control (the reference in fp8) at a size the CPU can hold:
    d_model 256, 4 layers, 8 heads over 2 KV heads, vocab 4096.  On the
    chip, at yi-6b's size, it read 1.23-4.75 against the program's
    0-0.19 (checks/yi6b.prefill.json); here both are smaller, and the
    control still reads over three times what the program does."""
    import dataclasses

    from repro.configs import registry
    small = dict(_smoke.SIZES["yi-6b-smoke"], d_model=256, n_layers=4,
                 n_heads=8, n_kv_heads=2, head_dim=32, d_ff=688,
                 vocab_size=4096)
    cfg = dataclasses.replace(registry.get("yi-6b-smoke"),
                              **{k: small[k] for k in (
                                  "d_model", "n_layers", "n_heads",
                                  "n_kv_heads", "d_ff", "vocab_size")})
    real_get = registry.get
    monkeypatch.setattr(registry, "get", lambda i: cfg
                        if i == "yi-6b-smoke" else real_get(i))
    monkeypatch.setitem(_smoke.SIZES, "yi-6b-smoke", small)
    c, small = _medium(monkeypatch, "yi6b.prefill")
    c.mix["prompt_tokens"].update(median=300, max=400)
    c.mix["output_tokens"].update(median=24, max=64)
    for seed in (SEED, SEED + 1):
        picked, program, control = _program_and_control(
            c, seed, small["vocab_size"])
        assert sum(len(f.tokens) for f in picked) >= 50
        assert control > 3 * max(program, 0.01), (program, control)
