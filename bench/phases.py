#!/usr/bin/env python3
"""Where an ``Engine.step`` tick's time goes, from one traced window.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--fixture <path.json.gz>]

Run from the checkout's root on a machine with the cell's chips.  It
sets the cell up as ``bench/run.py`` does (weights from ``--seed``, the
engine, warm-up, the traffic), offers the traffic for ``--seconds`` with
the last stretch under ``jax.profiler``, and keeps the engine's own
spans and the device's programs from that trace
(``bench/engine_trace.py``).  It prints one JSON object:

* ``phases_ms``: per tick kind, the mean ``engine.step`` time and each
  phase's (``other`` is the step's time outside its phases);
* ``step_host_ms``: the host's own work per tick (a step less its
  ``engine.sync``), beside ``host_gap_ms`` and ``device_idle_pct`` as
  the benchmark reads them from the same trace;
* ``host_lead_ns``: an ``engine.launch`` to the device's start of its
  step program, and ``split_lead_ns`` to its first program, the key
  split; ``idle_by_host`` (the benchmark's breakdown), and the device's
  idle time by innermost engine phase, as recorded (``idle_by_phase``)
  and with the device's events shifted by the larger of the two leads'
  ``shift_ns`` (``idle_by_phase_shifted``); ``idle_split_shifted``
  splits each shifted gap over every phase it overlaps;
* ``slowest_ticks``: the three ticks with the most host time, by phase;
* ``window_compiles`` and ``span_cost_us``: the engine's spans timed
  with no profile being taken, per tick, on this host.

``--fixture`` also writes two ticks of the trace (a decode tick and the
mixed tick after it, where there is one), 1 ms either side, times
rebased to the first kept instant, with the harness's records of those
ticks: the recorded trace the reduction's tests run on.  No output
check runs: ``bench/run.py`` decides ``correct``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import (check, engine_trace, harness, trace_reduce,  # noqa: E402
                   traffic)

PAD_NS = 1e6                  # a fixture's margin either side of its ticks


class KeepingTracer(harness.Tracer):
    """The harness's traced stretch, loaded with the engine's spans and
    the device's programs."""

    def stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.events = engine_trace.load(
            trace_reduce.find_xplane(self.dir))


def slowest(events: list, k: int = 3) -> list:
    """The ``k`` complete ticks with the most host time (step less
    sync), each phase's time in ms."""
    rows = []
    for s in engine_trace.steps(events):
        parts = engine_trace.phases_of(events, s)
        sync = sum(p["dur_ns"] for p in parts
                   if p["name"] == engine_trace.SYNC)
        row = {"tick": s["attrs"].get("tick"), "kind": s["attrs"].get("kind"),
               "step_ms": s["dur_ns"] / 1e6,
               "host_ms": (s["dur_ns"] - sync) / 1e6}
        for p in parts:
            name = p["name"][len(engine_trace.ENGINE):]
            row[name] = row.get(name, 0.0) + p["dur_ns"] / 1e6
        rows.append(row)
    return sorted(rows, key=lambda r: -r["host_ms"])[:k]


def span_cost_us(n: int = 20000):
    """Host time of one tick's spans (``engine.step``, its six phases and
    two late attributes) with no tracer and no profile, per tick."""
    try:
        from repro.obs import phase
    except ImportError:
        return None

    def tick():
        with phase(None, "engine.step", "tick-0", tick=0) as st:
            st.set_metadata(kind="decode")
            for name in ("engine.admit", "engine.plan"):
                with phase(None, name, "tick-0"):
                    pass
            with phase(None, "engine.launch", "tick-0",
                       program="decode") as la:
                la.set_metadata(compiled=0)
            for name in ("engine.sync", "engine.emit", "engine.metrics"):
                with phase(None, name, "tick-0"):
                    pass
    tick()
    t0 = time.perf_counter()
    for _ in range(n):
        tick()
    return (time.perf_counter() - t0) / n * 1e6


def fixture(events: list, rec: dict) -> dict:
    """Two ticks of the traced stretch and the harness's records of
    them, times rebased (see the module's docstring)."""
    steps = engine_trace.steps(events)
    pairs = list(zip(steps, steps[1:]))
    a, b = next(((x, y) for x, y in pairs
                 if (x["attrs"].get("kind"), y["attrs"].get("kind"))
                 == ("decode", "mixed")), pairs[0])
    harness_ticks = [e for e in trace_reduce.steps(events)
                     if e["start_ns"] <= b["start_ns"]
                     and engine_trace.end(e) >= a["start_ns"]]
    lo = min([a["start_ns"]] + [e["start_ns"] for e in harness_ticks]) \
        - PAD_NS
    hi = max([engine_trace.end(b)]
             + [engine_trace.end(e) for e in harness_ticks]) + PAD_NS
    kept = [dict(e, start_ns=e["start_ns"] - lo) for e in events
            if lo <= e["start_ns"] < hi and e["name"] != trace_reduce.WINDOW]
    kept.append({"kind": "host", "device": -1, "name": trace_reduce.WINDOW,
                 "start_ns": 0.0, "dur_ns": hi - lo})
    by_tick = {t["tick"]: t for t in rec["ticks"]}
    ticks = [{"tick": e["tick"], "decode": by_tick[e["tick"]]["decode"],
              "chunks": by_tick[e["tick"]]["chunks"],
              "t0": (e["start_ns"] - lo) / 1e9,
              "t1": (engine_trace.end(e) - lo) / 1e9}
             for e in harness_ticks]
    return {"events": kept, "ticks": ticks}


def measure(cell, seed: int, seconds: float, t_start: float,
            peak_table: dict, fixture_path=None) -> dict:
    cfg = cell.config
    m, n_slots = cfg["model"], cfg["spec"]["serve"]["n_slots"]
    server = harness.build_server(cell, seed)
    harness.warm_up(server, cell.mix)
    arrivals = traffic.schedule(cell.mix, seed, seconds, n_slots,
                                m["vocab_size"])
    setup_s = time.perf_counter() - t_start
    tracer = KeepingTracer(min(harness.TRACE_SECONDS, seconds / 2))
    try:
        rec = harness.serve_window(server, arrivals, seconds,
                                   cell.mix["drain_s"], tracer)
    finally:
        tracer.close()
    rec.update(setup_s=setup_s, model=m, engine=cfg["spec"]["serve"],
               chips=len(harness.engines(server)), counts=check.counts(cfg),
               peaks=peak_table)
    ev = rec["trace"]
    base = dict(rec, trace=engine_trace.strip(ev))
    lead = engine_trace.host_lead_ns(ev)
    split = engine_trace.host_lead_ns(ev, program=engine_trace.SPLIT)
    shift = max([0.0] + [x["shift_ns"] for x in (lead, split) if x])
    out = {
        "setup_s": setup_s,
        "step_host_ms": engine_trace.step_host_ms(ev),
        "host_gap_ms": harness.reader("host_gap_ms").read(base),
        "device_idle_pct": harness.reader("device_idle_pct").read(base),
        "window_compiles": harness.reader("window_compiles").read(rec),
        "phases_ms": engine_trace.phase_split(ev),
        "host_lead_ns": lead,
        "split_lead_ns": split,
        "idle_by_host": trace_reduce.idle_by_host(base["trace"]),
        "idle_by_phase": engine_trace.idle_by_phase(ev),
        "idle_by_phase_shifted": engine_trace.idle_by_phase(
            ev, shift_ns=shift),
        "idle_split_shifted": engine_trace.idle_split(ev, shift_ns=shift),
        "slowest_ticks": slowest(ev),
        "span_cost_us": span_cost_us(),
    }
    if fixture_path:
        Path(fixture_path).parent.mkdir(parents=True, exist_ok=True)
        Path(fixture_path).write_bytes(gzip.compress(json.dumps(
            fixture(ev, rec), separators=(",", ":")).encode()))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args()

    from bench import peaks
    from bench.run import enable_cache
    cell = harness.load_cell(args.workload)
    import jax
    enable_cache(jax)
    dev = harness.device_info(jax)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU chips, JAX sees "
              f"{dev['count']} {dev['platform']!r}", file=sys.stderr)
        return 2
    out = measure(cell, args.seed, args.seconds, T_START,
                  peaks.for_kind(dev["kind"]), args.fixture)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": dev, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
