"""Training launcher.

``--arch`` is a registry id: ``yi-6b`` trains the published config,
``yi-6b-smoke`` its reduced CPU-test config:

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b-smoke \
      --steps 20 --batch 8 --seq 64

--production runs on the 16x16 production mesh instead of a local one
(requires 256 devices — on real hardware the jax distributed runtime
provides them; here the dry-run path in launch/dryrun.py is the
no-hardware proof).

Elastic: --elastic splits the run into grow/shrink phases across this
host's devices — the trainer checkpoints, reshards and resumes at each
transition (the same remesh path the operator's ElasticTrainExecutor
drives from MiniCluster patch_size events):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.train --arch yi-6b-smoke \
      --elastic --steps 12 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import SHAPES, STRATEGIES, TrainConfig, registry
from repro.configs.base import WorkloadShape
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.train import Trainer


def phase_steps(total: int, n_phases: int):
    """Split ``total`` steps over the elastic phases, front-loaded so
    the sum is EXACTLY ``total`` and trailing phases may get 0 (those
    are skipped — never a negative run, never an overrun)."""
    base, rem = divmod(total, n_phases)
    return [base + (1 if i < rem else 0) for i in range(n_phases)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=registry.CONFIG_IDS)
    ap.add_argument("--spec", default=None,
                    help="declarative WorkloadSpec JSON (kind: train); "
                         "arch/steps/batch/seq/strategy/ckpt-dir come "
                         "from the spec, CLI flags override nothing")
    ap.add_argument("--production", action="store_true",
                    help="run on the 16x16 production mesh")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--strategy", default="baseline",
                    choices=list(STRATEGIES))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--elastic", action="store_true",
                    help="smoke-only: run grow/shrink mesh phases with "
                         "checkpoint-resharded transitions in between")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the train "
                         "loop into DIR (opt-in; view in Perfetto / "
                         "TensorBoard)")
    args = ap.parse_args()

    if args.spec:
        from repro.spec import load_spec
        wspec = load_spec(args.spec)
        assert wspec.kind == "train", \
            f"launch.train needs a train spec, got kind={wspec.kind!r}"
        args.arch = wspec.arch
        args.steps = wspec.train.total_steps
        args.batch = wspec.train.global_batch
        args.seq = wspec.train.seq_len
        args.ckpt_dir = wspec.train.ckpt_dir or args.ckpt_dir
        args.elastic = args.elastic or wspec.resources.elastic
        strategy = wspec.resolved_strategy
    else:
        assert args.arch, "--arch or --spec is required"
        strategy = STRATEGIES[args.strategy]
    cfg = registry.get(args.arch)
    mesh = make_production_mesh() if args.production else make_local_mesh()
    enable_compile_cache()
    if args.production:
        shape = SHAPES["train_4k"]
    else:
        shape = WorkloadShape("smoke", "train", args.seq, args.batch)

    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1))
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    if args.elastic:
        assert not args.production, "--elastic is a smoke-mode proof"
        nd = len(jax.devices())
        grown = (min(2, nd), nd // min(2, nd))
        phases = [(1, 1), grown, (1, 1)] if nd > 1 else [(1, 1)]
        tr = Trainer(cfg, tcfg, shape, make_local_mesh(*phases[0]),
                     strategy=strategy, ckpt_dir=args.ckpt_dir)
        hist, started = [], False
        for (d, m), n in zip(phases, phase_steps(args.steps, len(phases))):
            if n == 0:
                continue
            if started:
                dt = tr.remesh(make_local_mesh(d, m))
                print(f"[elastic] remesh -> mesh (data={d}, model={m}) "
                      f"resumed at step {tr.start_step} in {dt:.2f}s",
                      flush=True)
            started = True
            hist = tr.run(n, ckpt_every=args.ckpt_every, log_every=5)
    else:
        tr = Trainer(cfg, tcfg, shape, mesh, strategy=strategy,
                     ckpt_dir=args.ckpt_dir)
        hist = tr.run(args.steps, ckpt_every=args.ckpt_every, log_every=5)
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"[profile] jax.profiler trace in {args.profile_dir}")
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(first {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
