"""Serving launcher: a thin client of the continuous-batching engine.

All decode mechanics (paged KV cache, slot scheduling, temperature
sampling) live in ``repro.serve.Engine``, which runs on the shared
sharded-step API (``dist/steps.py``) — the same builders the dry-run
lowers on the production mesh drive this local mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b-smoke \
      --prompt-len 32 --gen 16 --batch 4 [--dp 1 --tp 1] \
      [--temperature 0.8]

``--arch`` is a registry id: ``yi-6b`` serves the published config,
``yi-6b-smoke`` its reduced CPU-test config.

or declaratively, from the same WorkloadSpec the operator applies:

  PYTHONPATH=src python -m repro.launch.serve \
      --spec examples/specs/serve_batch.json
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def _apply_tuned_flags(arch: str, dp: int, tp: int, path: str) -> str:
    """Load the swept winner for this (arch, mesh) cell and export it
    via XLA_FLAGS *before* the jax backend initializes (compiler flags
    are process-wide; this is the cross-process application path — the
    in-process path is ``compiler_options`` inside the tune sweep).

    Returns the applied flag-set key, or "" when nothing was tuned.
    """
    from repro.tune.autotune import load_tuned, tune_key
    key = tune_key(arch, (dp, tp))
    flags = load_tuned(key, path)
    if not flags:
        return ""
    frag = " ".join(f"--{k}={v}" for k, v in flags.items())
    prev = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = f"{prev} {frag}".strip()
    return key


def main():
    from repro.configs import STRATEGIES
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="config registry id (e.g. yi-6b, yi-6b-smoke)")
    ap.add_argument("--spec", default=None,
                    help="declarative WorkloadSpec JSON (kind: serve); "
                         "engine shapes + request knobs come from the "
                         "spec")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel mesh axis size")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis size")
    ap.add_argument("--strategy", default="baseline",
                    choices=list(STRATEGIES))
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: chunked prefill inside the decode tick")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1: a Router over N engine replicas (the "
                         "fleet tier; shared prefix cache when the "
                         "engines support it)")
    ap.add_argument("--tuned-flags", default=None, metavar="JSON",
                    help="TUNED_FLAGS.json from repro.tune.autotune; the "
                         "(arch, mesh) cell's winning XLA flags are "
                         "applied before the backend starts")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the serving "
                         "section (post-build) into DIR (opt-in; view "
                         "in Perfetto / TensorBoard)")
    args = ap.parse_args()

    tuned = ""
    if args.tuned_flags and args.arch:
        tuned = _apply_tuned_flags(args.arch, args.dp, args.tp,
                                   args.tuned_flags)

    from repro.configs import registry
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from repro.serve import Engine, EngineConfig
    from repro.serve.paging import round_up

    if args.spec:
        from repro.spec import load_spec
        wspec = load_spec(args.spec)
        assert wspec.kind == "serve", \
            f"launch.serve needs a serve spec, got kind={wspec.kind!r}"
        args.arch = wspec.arch
        strategy = wspec.resolved_strategy
        s = wspec.serve
        args.batch = s.n_slots
        args.gen = s.max_new
        args.temperature = s.temperature
        args.prompt_len = min(args.prompt_len, s.max_prompt_len)
        args.replicas = max(args.replicas, s.replicas)
        ecfg = wspec.engine_config()
    else:
        assert args.arch, "--arch or --spec is required"
        strategy = STRATEGIES[args.strategy]
        ecfg = EngineConfig(
            n_slots=args.batch, page_size=args.page_size,
            max_prompt_len=round_up(args.prompt_len, args.page_size),
            max_seq_len=round_up(args.prompt_len + args.gen,
                                 args.page_size),
            prefill_chunk=args.prefill_chunk)
    cfg, mesh = registry.get(args.arch), make_local_mesh(args.dp, args.tp)
    enable_compile_cache()
    t_build = time.perf_counter()
    if args.replicas > 1:
        from repro.serve import Router
        eng = Router([Engine(cfg, ecfg, strategy=strategy, mesh=mesh)
                      for _ in range(args.replicas)])
    else:
        eng = Engine(cfg, ecfg, strategy=strategy, mesh=mesh)
    if args.profile_dir:
        import jax
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.perf_counter()                    # serving clock: post-build
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    args.prompt_len).tolist(),
                       max_new_tokens=args.gen,
                       temperature=args.temperature)
            for _ in range(args.batch)]
    eng.run()
    elapsed = time.perf_counter() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"[profile] jax.profiler trace in {args.profile_dir}")

    n_tok = sum(len(r.tokens) for r in reqs)
    ttft = [r.ttft for r in reqs]
    per_tok = (elapsed - max(ttft)) / max(args.gen - 1, 1)
    print(f"mesh {dict(mesh.shape)} strategy {strategy.name} "
          f"temperature {args.temperature} "
          + (f"replicas {args.replicas} " if args.replicas > 1 else "")
          + f"(engine build {(t0 - t_build)*1e3:.0f} ms)"
          + (f" tuned_flags {tuned}" if tuned else ""))
    print(f"prefill {args.prompt_len} toks x{args.batch}: "
          f"ttft {min(ttft)*1e3:.1f}-{max(ttft)*1e3:.1f} ms (incl. compile)")
    print(f"decode {args.gen} toks x{args.batch}: {n_tok} tokens in "
          f"{elapsed*1e3:.1f} ms ({per_tok*1e3:.1f} ms/step incl. compile)")
    print(f"engine stats: {eng.stats()}")
    print("generated ids (request 0):", reqs[0].tokens[:16])


if __name__ == "__main__":
    main()
