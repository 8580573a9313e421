"""Quickstart: bring up a Flux MiniCluster on a simulated fleet, apply
declarative WorkloadSpecs for three different architectures, and watch
each workload's lifecycle through its handle.

This is the operator pattern end to end: a spec describes WHAT should
run (kind, arch, resources, strategy); ``mc.apply`` validates it at
submit time, reconciles resources (pod-local packing), binds the right
executor, and hands back a WorkloadHandle whose ``status()``/
``events()`` expose the Pending -> Bound -> Running -> Completed
lifecycle.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import (FluxMiniCluster, MiniClusterSpec, NetModel,
                        ResourceGraph, SimClock)
from repro.spec import ResourceSpec, TrainSpec, WorkloadSpec


def main():
    clock = SimClock(seed=0)
    net = NetModel()
    # a 2-pod fleet, 16 hosts per pod, 4 chips per host
    fleet = ResourceGraph(n_pods=2, hosts_per_pod=16)

    # declarative MiniCluster: 8 nodes now, head-room to 16
    spec = MiniClusterSpec(name="quickstart", size=8, max_size=16)
    mc = FluxMiniCluster(clock, net, fleet, spec)
    mc.create()
    t_ready = mc.wait_ready()
    print(f"MiniCluster ready in {t_ready:.1f}s "
          f"({mc.pool.n_up()} brokers up)")

    # apply real JAX training workloads (the reduced "-smoke" configs
    # run on this host, on the sub-mesh each job's allocation describes)
    handles = []
    for arch, nodes in [("yi-6b-smoke", 4),
                        ("granite-moe-1b-a400m-smoke", 2),
                        ("lammps-proxy-smoke", 2)]:
        h = mc.apply(WorkloadSpec(
            kind="train", arch=arch, name=f"qs-{arch}", user="quickstart",
            resources=ResourceSpec(n_nodes=nodes),
            train=TrainSpec(total_steps=1, seq_len=16)))
        handles.append(h)
        print(f"applied workload {h.job.jobid}: {arch} on {nodes} nodes "
              f"-> {h.phase}")

    clock.run(until=clock.now + 600)
    for h in handles:
        st = h.status()
        phases = [e["phase"] for e in h.events()]
        print(f"job {st['jobid']} [{h.spec.arch:22s}] -> {st['phase']} "
              f"(result {st['result']}, lifecycle {' -> '.join(phases)})")
        assert st["phase"] == "Completed", st
    print("queue stats:", mc.instance.queue.stats())
    print("metrics:", mc.instance.metrics())


if __name__ == "__main__":
    main()
