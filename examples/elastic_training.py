"""End-to-end driver: train a (reduced) model a few hundred steps UNDER
the operator, with a checkpoint/restart mid-run and an elastic resize —
the full fault-tolerance story in one script.

    PYTHONPATH=src python examples/elastic_training.py [--steps 200]
"""
import argparse
import tempfile

import jax

from repro.configs import TrainConfig, registry
from repro.configs.base import WorkloadShape
from repro.core import (FluxMiniCluster, MiniClusterSpec, NetModel,
                        ResourceGraph, SimClock)
from repro.launch.mesh import make_local_mesh
from repro.spec import ResourceSpec, TrainSpec, WorkloadSpec
from repro.train import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="granite-moe-1b-a400m-smoke")
    args = ap.parse_args()

    # --- control plane: the operator schedules the training job ---
    clock = SimClock(seed=0)
    net = NetModel()
    fleet = ResourceGraph(n_pods=1, hosts_per_pod=16)
    mc = FluxMiniCluster(clock, net, fleet,
                         MiniClusterSpec(name="train", size=4, max_size=8))
    mc.create()
    print(f"cluster ready in {mc.wait_ready():.1f}s")
    h = mc.apply(WorkloadSpec(
        kind="train", arch=args.arch, name="elastic-demo",
        resources=ResourceSpec(n_nodes=4),
        train=TrainSpec(total_steps=1, seq_len=16)))
    job = h.job
    clock.run(until=clock.now + 5)
    assert job.allocation is not None, "job must hold an allocation"
    print(f"workload {job.jobid} ({h.phase}) allocated hosts "
          f"{list(job.allocation.hosts)}")

    # --- data plane: the allocated job runs the Trainer ---
    cfg = registry.get(args.arch)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=args.steps,
                       warmup_steps=10)
    shape = WorkloadShape("t", "train", 64, 8)
    mesh = make_local_mesh(1, 1)
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_ckpt_")

    half = args.steps // 2
    tr = Trainer(cfg, tcfg, shape, mesh, ckpt_dir=ckpt_dir)
    hist = tr.run(half, ckpt_every=25, log_every=25)
    print(f"[phase 1] loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")

    # --- simulate a node failure: elastic resize + restart from ckpt ---
    print("simulating failure + elastic resize 4 -> 8 ...")
    mc.patch_size(8)
    clock.run(until=clock.now + 120)
    print(f"cluster now {mc.pool.n_up()} nodes")

    tr2 = Trainer(cfg, tcfg, shape, mesh, ckpt_dir=ckpt_dir)
    how = tr2.init_or_resume()
    print(f"trainer {how} at step {tr2.start_step} (resharded restore)")
    hist2 = tr2.run(args.steps - tr2.start_step, ckpt_every=50,
                    log_every=25)
    print(f"[phase 2] loss {hist2[0]['loss']:.3f} -> "
          f"{hist2[-1]['loss']:.3f}")
    assert hist2[-1]["loss"] < hist[0]["loss"], "training must progress"
    print("OK: loss decreased across restart + resize")


if __name__ == "__main__":
    main()
