#!/usr/bin/env python3
"""Readings that a cell's output-check limit is set from.

    python3 bench/calibrate.py --workload yi6b.prefill \\
        --seeds 1,2,3 --control-seeds 1,2 --seconds 20

One process: for each seed the cell's server is built with that seed's
weights, warmed up, and offered the cell's traffic for ``--seconds``
(then drained), exactly as a run does.  The finished requests sampled
as a run samples them are compared with the plain reference: the
program's reading is the widest gap of a served token below the
reference's best logit.  For each control seed the same positions are
also read with the token an fp8 computation of the reference puts
first (the control, which has to fail the limit).  One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    from bench import check, harness, traffic
    from bench.run import enable_cache
    import jax
    enable_cache(jax)
    if harness.device_info(jax)["platform"] != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    cfg = cell.config
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        server = harness.build_server(cell, seed)
        harness.warm_up(server, cell.mix)
        arrivals = traffic.schedule(cell.mix, seed, args.seconds,
                                    cfg["spec"]["serve"]["n_slots"],
                                    cfg["model"]["vocab_size"])
        rec = harness.serve_window(server, arrivals, args.seconds,
                                   cell.mix["drain_s"])
        picked = check.sample(rec["flights"], seed,
                              cell.mix["check_requests"])
        del server, rec
        gc.collect()
        weights = check.make_weights(cfg, seed)
        row = {"seed": seed, "checked": len(picked),
               "served_tokens": sum(len(f.tokens) for f in picked),
               "adopted_tokens": sum(f.adopted for f in picked),
               "program_gap": check.served_gap(cfg, weights, picked)}
        if seed in controls:
            row["control_gap"] = check.control_gaps(cfg, weights, picked)
        row["wall_s"] = time.perf_counter() - t
        del weights
        gc.collect()
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
