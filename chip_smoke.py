#!/usr/bin/env python3
"""Chip smoke test: the operator's main path at yi-6b's published width
on a TPU, through the entry points a user calls.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four one-chip replicas, one host

One chip runs four phases, in one process:

* device   — platform, device kind and count; anything but a TPU fails
  (there is no CPU fallback);
* kernels  — each main-path Pallas kernel (paged decode, paged prefill,
  flash attention forward and backward, rmsnorm) against its jnp
  reference run in float32 on the same bf16 inputs, at yi-6b shapes;
* serve    — a yi-6b serve ``WorkloadSpec`` applied to a one-chip
  ``FluxMiniCluster`` (32 layers, full width, random bf16 weights from
  ``--seed``): 8 greedy requests with 64-512 token prompts and 32 new
  tokens each, once with chunked prefill (128 rows a tick) and once
  with whole-prompt prefill.  Every request must finish with its token
  count, and the tokens the engine chose for one prompt must be the
  argmax of a plain non-paged float32 forward on the same weights, up
  to ``LOGIT_GAP_BOUND`` (random weights give near-ties);
* train    — 3 ``Trainer`` steps (what ``launch.train`` builds) at
  yi-6b width with the depth cut to one layer; the loss must be finite.

``--chips 4`` runs only the routed fleet: four one-chip yi-6b replicas
behind the ``Router`` through ``apply`` with ``replicas=4``, and one
engine on the same 16 prompts for comparison.

Every phase prints its compile time, its run time and the device's
peak memory.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {...}}``.  Any failure exits non-zero, and
nothing is caught.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

ARCH = "yi-6b"
PAGE = 16
SLOTS, MAX_SEQ, MAX_PROMPT = 8, 1024, 512
PROMPT_LENS = (64, 512)           # inclusive range of prompt lengths
MAX_NEW = 32
CHUNK = 128
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 3
# bf16 keeps 8 significant bits: one rounding of an output is 0.2% of
# its value; the bound leaves room for f32 accumulation order
KERNEL_TOL = 2e-2                 # max |kernel - ref| / max |ref|
# a logit std of ~1.3 over a 64000-token vocabulary puts a random
# token ~5 below the top logit; bf16 serving error is well under 0.5
LOGIT_GAP_BOUND = 0.5

# XLA's compile of each program (tracing and lowering nest, so only the
# backend compile is summed; a persistent-cache hit skips it)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Fail(SystemExit):
    """A failed check: exits non-zero with the reason."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Fail(msg)


class Phase:
    """Times a phase: wall time, JAX compile time inside it, and the
    devices' peak memory at its end."""

    compile_s = 0.0

    @classmethod
    def listen(cls, jax):
        def on_event(event, duration, **_):
            if event == COMPILE_EVENT:
                cls.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name: str, devices):
        self.name, self.devices = name, devices

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        print(f"== {self.name}", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        wall = time.perf_counter() - self.t0
        comp = Phase.compile_s - self.c0
        peaks = [gib(d, "peak_bytes_in_use") for d in self.devices]
        print(f"[{self.name}] compile {comp:.3f} s  run {wall - comp:.3f} s"
              f"  peak_bytes_in_use "
              + " ".join(f"{p:.3f}" for p in peaks) + " GiB", flush=True)
        return False


def gib(device, stat: str) -> float:
    return device.memory_stats()[stat] / 2**30


def make_prompts(n: int, seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, n)
    return [rng.integers(0, vocab, int(L)).tolist() for L in lens]


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def device_check(jax, chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(dev["platform"] == "tpu",
          f"no TPU: JAX platform is {dev['platform']!r}")
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {dev['count']}")
    return dev


def kernel_phase(jax, jnp, cfg, seed: int) -> None:
    from repro.kernels import ops
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(shape):
        return jax.random.normal(next(keys), shape, f32).astype(bf)

    def up(*xs):
        return [x.astype(f32) for x in xs]

    def compare(name, kernel, ref, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(kernel)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(*args))
        outs = out if isinstance(out, tuple) else (out,)
        wants = want if isinstance(want, tuple) else (want,)
        err = max(rel_err(o, w) for o, w in zip(outs, wants))
        print(f"  {name}: max|kernel-ref|/max|ref| = {err:.6f} "
              f"(bound {KERNEL_TOL}; {time.perf_counter() - t0:.3f} s)",
              flush=True)
        check(bool(np.isfinite(err)) and err <= KERNEL_TOL,
              f"{name} disagrees with its reference: {err} > {KERNEL_TOL}")

    maxp = MAX_SEQ // PAGE
    n_pages = SLOTS * maxp + 1
    kp, vp = rnd((n_pages, kv, PAGE, d)), rnd((n_pages, kv, PAGE, d))
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(1 + rng.permutation(n_pages - 1)[:SLOTS * maxp]
                     .reshape(SLOTS, maxp).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, MAX_SEQ + 1, SLOTS), jnp.int32)
    q = rnd((SLOTS, 1, h, d))
    compare("paged decode",
            lambda *a: ops.paged_decode_attention(*a, impl="pallas"),
            lambda q_, k_, v_, b_, l_: ops.paged_decode_attention(
                *up(q_, k_, v_), b_, l_, impl="ref"),
            q, kp, vp, bt, lens)

    qc = rnd((1, CHUNK, h, d))
    start = jnp.asarray([300], jnp.int32)
    n_valid = jnp.asarray([CHUNK], jnp.int32)
    compare("paged prefill",
            lambda *a: ops.paged_prefill_attention(*a, impl="pallas"),
            lambda q_, k_, v_, b_, s_, n_: ops.paged_prefill_attention(
                *up(q_, k_, v_), b_, s_, n_, impl="ref"),
            qc, kp, vp, bt[:1], start, n_valid)

    b, s = TRAIN_BATCH, TRAIN_SEQ
    qf, kf, vf = rnd((b, s, h, d)), rnd((b, s, kv, d)), rnd((b, s, kv, d))
    do = rnd((b, s, h, d))

    def flash(impl):
        return lambda q_, k_, v_: ops.flash_attention(q_, k_, v_,
                                                      causal=True, impl=impl)
    compare("flash attention fwd", flash("pallas"),
            lambda *a: flash("ref")(*up(*a)), qf, kf, vf)

    def grads(impl, cast):
        def fn(q_, k_, v_, do_):
            _, vjp = jax.vjp(flash(impl), *cast(q_, k_, v_))
            return vjp(*cast(do_))
        return fn
    compare("flash attention bwd", grads("pallas", lambda *a: a),
            grads("ref", up), qf, kf, vf, do)

    x, w = rnd((b, s, cfg.d_model)), rnd((cfg.d_model,))
    compare("rmsnorm",
            lambda x_, w_: ops.rmsnorm(x_, w_, eps=cfg.norm_eps,
                                       impl="pallas"),
            lambda x_, w_: ops.rmsnorm(*up(x_, w_), eps=cfg.norm_eps,
                                       impl="ref"),
            x, w)


def serve_spec(name: str, chunk: int, n_requests: int, replicas: int = 1):
    from repro.spec import ResourceSpec, ServeSpec, WorkloadSpec
    return WorkloadSpec(
        kind="serve", arch=ARCH, name=name,
        resources=ResourceSpec(n_nodes=1),
        serve=ServeSpec(n_slots=SLOTS, max_new=MAX_NEW, page_size=PAGE,
                        max_prompt_len=MAX_PROMPT, max_seq_len=MAX_SEQ,
                        n_requests=n_requests, prefill_chunk=chunk,
                        replicas=replicas))


def apply_on_cluster(spec, prompts, hosts: int):
    """Apply ``spec`` to a ``FluxMiniCluster`` of ``hosts`` one-chip
    hosts, serve ``prompts`` and return the handle once it finished."""
    from repro.core import (FluxMiniCluster, MiniClusterSpec, NetModel,
                            ResourceGraph, SimClock)
    clock = SimClock(seed=0)
    graph = ResourceGraph(n_pods=1, hosts_per_pod=hosts, chips_per_host=1)
    mc = FluxMiniCluster(clock, NetModel(), graph,
                         MiniClusterSpec(name="chip-smoke", size=hosts))
    mc.create()
    mc.wait_ready()
    h = mc.apply(spec)
    h.job.spec.args["prompts"] = prompts
    clock.run(until=clock.now + 1e7, stop_when=lambda: h.done)
    check(h.phase == "Completed", f"{spec.name}: workload ended {h.phase}")
    return h


def check_completed(name: str, rec: dict, prompts) -> None:
    toks = rec["tokens"]
    check(len(toks) == len(prompts),
          f"{name}: {len(toks)} of {len(prompts)} requests came back")
    short = [i for i, t in enumerate(toks) if len(t) != MAX_NEW]
    check(not short, f"{name}: requests {short} did not get {MAX_NEW} "
          "tokens")
    print(f"  {name}: {len(toks)} requests x {MAX_NEW} tokens completed",
          flush=True)


def logit_gap(jax, jnp, cfg, params, prompt, tokens) -> float:
    """Largest gap, over the engine's generated tokens, between a plain
    non-paged float32 forward's top logit and its logit for the token
    the engine chose (0 where they agree)."""
    from repro.models.model import Model
    model = Model(cfg)

    @jax.jit
    def forward(params, toks):
        with jax.default_matmul_precision("highest"):
            logits, _, _ = model._trunk(params, toks, mode="prefill",
                                        remat=False,
                                        compute_dtype=jnp.float32)
        return logits[0]

    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    logits = np.asarray(forward(params, seq))[len(prompt) - 1:]
    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    gaps = logits.max(axis=1) - chosen
    agree = int((logits.argmax(axis=1) == np.asarray(tokens)).sum())
    print(f"  reference forward: argmax agrees on {agree}/{len(tokens)} "
          f"tokens, max logit gap {gaps.max():.6f} (bound "
          f"{LOGIT_GAP_BOUND}), logit std {logits.std():.4f}", flush=True)
    return float(gaps.max())


def serve_once(jax, jnp, cfg, chunk: int, prompts):
    """One applied serve workload; returns plain token lists so every
    device buffer it made is free once it returns."""
    name = f"serve prefill_chunk={chunk}"
    h = apply_on_cluster(serve_spec(f"yi6b-c{chunk}", chunk, len(prompts)),
                         prompts, hosts=1)
    rec = h.executor.ran[h.job.jobid]
    check_completed(name, rec, prompts)
    print(f"  {name}: engine run {rec['measured_s']:.3f} s, "
          f"{rec['n_tokens']} tokens, mean TTFT {rec['ttft_mean_s']:.3f} s",
          flush=True)
    (eng,) = h.executor._engines.values()
    gap = logit_gap(jax, jnp, cfg, eng.params, prompts[0], rec["tokens"][0])
    check(gap <= LOGIT_GAP_BOUND,
          f"{name}: engine token logit gap {gap} > {LOGIT_GAP_BOUND}")
    return rec["tokens"]


def agreement(a, b) -> str:
    same = sum(x == y for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    total = sum(len(r) for r in a)
    return f"{same}/{total} tokens identical"


def serve_phase(jax, jnp, cfg, seed: int, devices) -> None:
    prompts = make_prompts(SLOTS, seed, cfg.vocab_size)
    print(f"  prompts: {len(prompts)} of lengths "
          f"{[len(p) for p in prompts]}", flush=True)
    out = {}
    for chunk in (CHUNK, 0):
        out[chunk] = serve_once(jax, jnp, cfg, chunk, prompts)
        gc.collect()
        print(f"  bytes_in_use after release "
              f"{gib(devices[0], 'bytes_in_use'):.3f} GiB", flush=True)
    print(f"  chunked vs whole-prompt prefill: "
          f"{agreement(out[CHUNK], out[0])}", flush=True)


def train_phase(jax, cfg, seed: int) -> None:
    from repro.configs import TrainConfig
    from repro.configs.base import WorkloadShape
    from repro.launch.mesh import make_local_mesh
    from repro.models.model import Model
    from repro.train import Trainer
    cut = dataclasses.replace(cfg, n_layers=1)
    print(f"  cut: depth {cfg.n_layers} -> {cut.n_layers} layer at full "
          f"width ({Model(cut).n_params() / 1e9:.3f} B params, float32 "
          f"params + AdamW state); batch {TRAIN_BATCH} x seq {TRAIN_SEQ}",
          flush=True)
    tr = Trainer(cut, TrainConfig(total_steps=TRAIN_STEPS, warmup_steps=1),
                 WorkloadShape("chip-smoke", "train", TRAIN_SEQ, TRAIN_BATCH),
                 make_local_mesh(), seed=seed)
    hist = tr.run(TRAIN_STEPS, log_every=1)
    losses = [r["loss"] for r in hist]
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          f"train losses not finite: {losses}")


def fleet_phase(jax, jnp, cfg, seed: int, devices) -> None:
    from repro.dist.sharding import make_mesh
    from repro.serve import Engine
    n = len(devices)
    prompts = make_prompts(16, seed, cfg.vocab_size)
    spec = serve_spec("yi6b-fleet", CHUNK, len(prompts), replicas=n)
    with Phase("one engine (reference)", devices[:1]):
        eng = Engine(cfg, spec.engine_config(), seed=0,
                     mesh=make_mesh((1, 1), ("data", "model"),
                                    devices=devices[:1]))
        reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        eng.run()
        single = [list(r.tokens) for r in reqs]
        check_completed("one engine", {"tokens": single}, prompts)
        del eng, reqs
        gc.collect()
    with Phase(f"fleet: {n} replicas behind the Router", devices):
        h = apply_on_cluster(spec, prompts, hosts=n)
        rec = h.executor.ran[h.job.jobid]
        check_completed("fleet", rec, prompts)
        check(rec["replicas"] == n, f"fleet ran {rec['replicas']} replicas")
        (router,) = h.executor._fleets.values()
        homes = []
        for i, e in enumerate(router.engines):
            held = {dev for tree in (e.params, e.pool)
                    for leaf in jax.tree_util.tree_leaves(tree)
                    for dev in leaf.devices()}
            print(f"  replica {i}: weights + KV pool on "
                  f"{sorted(str(d) for d in held)}", flush=True)
            check(len(held) == 1, f"replica {i} spans devices {held}")
            homes.append(held.pop())
        check(len(set(homes)) == n,
              f"replicas share devices: {[str(d) for d in homes]}")
        print(f"  fleet run {rec['measured_s']:.3f} s, prefix cache "
              f"{rec['prefix_cache']}; fleet vs one engine: "
              f"{agreement(rec['tokens'], single)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.launch.cache import enable_compile_cache

    Phase.listen(jax)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    dev = device_check(jax, args.chips)
    devices = jax.devices()[:args.chips]
    cfg = registry.get(ARCH)
    if args.chips == 4:
        fleet_phase(jax, jnp, cfg, args.seed, devices)
    else:
        with Phase("kernels", devices):
            kernel_phase(jax, jnp, cfg, args.seed)
        with Phase("serve", devices):
            serve_phase(jax, jnp, cfg, args.seed, devices)
        with Phase("train", devices):
            train_phase(jax, cfg, args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
