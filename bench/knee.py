#!/usr/bin/env python3
"""Sweep of offered rates for one cell, to find its knee.

    python3 bench/knee.py --workload yi6b.prefill --seed 7 \\
        --rates 2,2.5,3,3.5 --seconds 30

One process, one server (one engine, or a cell's replicas behind the
Router): for each rate the cell's traffic mix is
offered open loop at that rate for ``--seconds``, then drained.  Each
rate prints one JSON line: requests due and finished, the backlog (due
but not yet admitted) sampled once a second, tokens per second, and the
time-to-first-token and inter-token percentiles.  The knee is the
highest rate whose backlog does not grow through the window; the cell's
traffic file then fixes its rate as a share of it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()

    from bench import harness, traffic
    from bench.run import enable_cache
    import jax
    enable_cache(jax)
    if harness.device_info(jax)["platform"] != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    cfg = cell.config
    server = harness.build_server(cell, args.seed)
    harness.warm_up(server, cell.mix)
    metric = {n: harness.reader(n) for n in
              ("ttft_p90_ms", "itl_p99_ms", "serve_tokens_per_s",
               "queue_wait_p50_ms", "prefix_hit_pct")}
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate)
        arrivals = traffic.schedule(mix, args.seed + i, args.seconds,
                                    cfg["spec"]["serve"]["n_slots"],
                                    cfg["model"]["vocab_size"])
        t = time.perf_counter()
        rec = harness.serve_window(server, arrivals, args.seconds, 600.0)
        fl = rec["flights"]
        backlog = [sum(1 for f in fl if f.due <= s and
                       (f.t_admit is None or f.t_admit > s))
                   for s in range(int(args.seconds) + 1)]
        row = {"rate_rps": rate, "due": len(fl),
               "finished_in_window": sum(
                   1 for f in fl if f.t_tokens
                   and f.t_tokens[-1] <= args.seconds and f.finished),
               "backlog_each_s": backlog,
               "wall_s": time.perf_counter() - t}
        row.update({n: m.read(rec) for n, m in metric.items()})
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
