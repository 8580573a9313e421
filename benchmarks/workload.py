"""Paper Figure 3 + 5: the same application under Flux Operator vs MPI
Operator — total wall time (Fig 3) and launcher latency (Fig 5).

The application is REAL JAX compute (a reduced train step of the
lammps-proxy config, executed and timed on this host); orchestration
costs are structural: TBON parallel bootstrap + flux-pmix wireup for
Flux vs serial per-worker ssh + mpirun wireup for the MPI Operator.
Strong scaling: ranks halve per node count step, like the paper's
64/32/16/8-node LAMMPS runs.
"""
from __future__ import annotations

import statistics

from repro.core import (FluxMiniCluster, JaxWorkloadExecutor, JobSpec,
                        MiniClusterSpec, MPIJob, NetModel, ResourceGraph,
                        SimClock)

SIZES = (8, 16, 32, 64)
RUNS = 5   # real JAX compute per run; 5 is enough for the mean on CPU


def bench(seed: int = 0):
    rows = []
    # measure the app kernel ONCE (identical binary + problem under
    # both operators, as in the paper)
    _m = SimClock(seed=seed)
    probe = JaxWorkloadExecutor(_m, NetModel(), steps=2)
    base = probe._step_fn("lammps-proxy")()
    for size in SIZES:
        # ---- Flux Operator path ----
        clock = SimClock(seed=seed + size)
        net = NetModel()
        fleet = ResourceGraph(n_pods=1, hosts_per_pod=65)
        ex = JaxWorkloadExecutor(clock, net, steps=2, time_scale=4e5,
                                 fixed_measure=base)
        spec = MiniClusterSpec(name=f"flux-{size}", size=size,
                               max_size=size)
        mc = FluxMiniCluster(clock, net, fleet, spec, executor=ex)
        mc.create()
        mc.wait_ready()
        flux_wall, flux_launch = [], []
        for r in range(RUNS):
            # strong scaling: fixed problem, so per-node work ~ 1/size
            job = mc.instance.submit(
                JobSpec(n_nodes=size, walltime=0,
                        command="lammps-proxy-smoke"))
            t_submit = clock.now
            clock.run(stop_when=lambda: job.result is not None)
            # paper Fig 5: "time for the launcher to submit and
            # complete a job" — submission -> completion
            flux_launch.append(job.t_done - t_submit)
            flux_wall.append(job.t_done - job.t_run)

        # ---- MPI Operator path (needs size+1 hosts: launcher) ----
        clock2 = SimClock(seed=seed + size)
        net2 = NetModel()
        fleet2 = ResourceGraph(n_pods=1, hosts_per_pod=65)
        ex2 = JaxWorkloadExecutor(clock2, net2, steps=2, time_scale=4e5,
                                  fixed_measure=base)
        mj = MPIJob(clock2, net2, fleet2, n_workers=size,
                    executor=ex2.mpi_executor())
        mj.create()
        clock2.run(stop_when=lambda: mj.status.phase == "Running")
        mpi_wall, mpi_launch = [], []
        for r in range(RUNS):
            res = {}
            t0 = clock2.now
            mj.mpirun(JobSpec(n_nodes=size, walltime=0,
                              command="lammps-proxy-smoke"),
                      lambda wall: res.setdefault("wall", wall))
            clock2.run(stop_when=lambda: "wall" in res)
            mpi_launch.append(net2.ssh_handshake * size + res["wall"])
            mpi_wall.append(res["wall"])

        rows.append({
            "size": size,
            "flux_wall": statistics.mean(flux_wall),
            "mpi_wall": statistics.mean(mpi_wall),
            "flux_launch": statistics.mean(flux_launch),
            "mpi_launch": statistics.mean(mpi_launch),
            "nodes_billed_flux": size,
            "nodes_billed_mpi": size + 1,
        })
    return rows


def validate(rows):
    flux_faster = all(r["flux_wall"] < r["mpi_wall"] for r in rows)
    launch_faster = all(r["flux_launch"] < r["mpi_launch"] for r in rows)
    gaps = [1 - r["flux_wall"] / r["mpi_wall"] for r in rows]
    return {"flux_wall_faster": flux_faster,
            "flux_launch_faster": launch_faster,
            "wall_gap_pct": [round(g * 100, 1) for g in gaps]}


def main(emit):
    rows = bench()
    for r in rows:
        emit(f"fig3_wall_flux_size{r['size']}", r["flux_wall"] * 1e6,
             f"mpi={r['mpi_wall']:.3f}s flux={r['flux_wall']:.3f}s")
        emit(f"fig5_launch_flux_size{r['size']}", r["flux_launch"] * 1e6,
             f"mpirun={r['mpi_launch']:.3f}s "
             f"flux_submit={r['flux_launch']:.3f}s")
    v = validate(rows)
    emit("fig3_fig5_claims", 0,
         f"flux_wall_faster={v['flux_wall_faster']} "
         f"flux_launch_faster={v['flux_launch_faster']} "
         f"gap_pct={v['wall_gap_pct']}")
    return rows
