"""One run of one cell: set-up, the measured window, metrics, the check.

A cell's server is what its files describe.  Every engine holds the
whole model on one chip, as its configuration file's ``spec`` builds
it; a cell on one chip serves from one ``Engine``, a cell on N chips
from N such replicas, each on its own chip with its own copy of the
seed's weights, behind one ``repro.serve.Router`` (as ``launch.serve``
builds a fleet).  The configuration's reference module
(``references/<reference>.py``) says which model kind it computes
(``FIELDS``, ``KIND``) and names the operation counts of that kind
(``COUNTS``, else ``counts.py``); the harness holds the program's
configuration to it and hands the counts to the metric readers.

The window drives the server through its public API only: ``submit``
for each request when it falls due, and ``step`` while any submitted
request is unfinished.  After each step the harness polls every
request in flight and stamps the host time at which each new token
became visible; time to first token counts from the request's due
time, so a late generator or a stalled loop shows in it.  Every step,
submit and wait for an arrival is wrapped in a
``jax.profiler.TraceAnnotation`` so that a traced run can attribute the
device's idle gaps to what the host was doing.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

from bench import check, traffic, trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
TRACE_SECONDS = 6.0           # traced stretch at the end of the window
WARM_PROMPT = 16              # warm-up request: one chunk, two tokens


# -- the cell, from BENCHMARK.json and its files ----------------------------

@dataclass
class Cell:
    name: str
    chips: int                # one engine replica on each
    config: dict              # configs/<config>.json
    mix: dict                 # traffic/<traffic>.json
    limits: dict              # checks/<cell>.json
    end_to_end: List[dict]    # the manifest's metrics this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with ``workloads`` is reported in those cells; an
    end-to-end one without it in every cell, a per-layer one without it
    in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, manifest: Optional[dict] = None) -> Cell:
    manifest = manifest or json.loads(MANIFEST.read_text())
    (w,) = [w for w in manifest["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise SystemExit(f"no workload {name!r} in {MANIFEST.name}")
    (c,) = [c for c in manifest["configs"] if c["name"] == w["config"]]
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    per = [m for m in manifest["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=w["chips"],
                config=json.loads((ROOT / c["file"]).read_text()),
                mix=traffic.load_mix(w["traffic"]),
                limits=json.loads((BENCH / "checks" / f"{name}.json")
                                  .read_text()),
                end_to_end=e2e, per_layer=per)


# -- the server -------------------------------------------------------------

def _same(program, bench) -> bool:
    """A group of the configuration file (a dict) matches the program's
    attribute of that name key by key; anything else compares equal."""
    if isinstance(bench, dict):
        return program is not None and all(
            _same(getattr(program, k), v) for k, v in bench.items())
    return program == bench


def check_model(cfg, m: dict, ref) -> None:
    """The program's configuration must be the model the reference
    ``ref`` computes: the sizes it reads from the configuration file's
    ``model`` group (``ref.FIELDS``) and the kind of model it is
    (``ref.KIND``, program attributes and their values)."""
    bad = [f"{k}: program {getattr(cfg, k)!r}, bench {m[k]!r}"
           for k in ref.FIELDS if not _same(getattr(cfg, k), m[k])]
    bad += [f"{k}: program {getattr(cfg, k)!r}, reference wants {v!r}"
            for k, v in ref.KIND.items() if not _same(getattr(cfg, k), v)]
    if bad:
        raise SystemExit("program config differs from the benchmark's:\n  "
                         + "\n  ".join(bad))


def build_engine(config: dict, weights, device):
    """The engine the way ``launch.serve`` builds it from a serve spec,
    holding the benchmark's weights, on ``device`` alone."""
    from repro.configs import registry
    from repro.dist.sharding import make_mesh
    from repro.serve import Engine
    from repro.spec import WorkloadSpec
    spec = WorkloadSpec.from_dict(config["spec"]).validate()
    cfg = registry.get(spec.arch)
    check_model(cfg, config["model"], check.reference(config))
    return Engine(cfg, spec.engine_config(), strategy=spec.resolved_strategy,
                  mesh=make_mesh((1, 1), ("data", "model"), devices=[device]),
                  params=weights)


def build_server(cell: Cell, seed: int):
    """The cell's server with the seed's weights: one engine on each of
    the cell's chips, behind a Router where there are several."""
    import jax
    cfg = cell.config
    reps = [build_engine(cfg, check.make_weights(cfg, seed, d), d)
            for d in jax.devices()[:cell.chips]]
    if len(reps) == 1:
        return reps[0]
    from repro.serve import Router
    return Router(reps)


def engines(server) -> list:
    return list(getattr(server, "engines", [server]))


def warm_up(server, mix: Optional[dict] = None) -> None:
    """Compile (or load from the cache) the programs the window runs, on
    every replica: a mixed tick, then a decode tick.  Behind a prefix
    cache, session traffic also copies a cached system prompt's pages
    into a slot: a second wave of requests adopts what the first one's
    prompts registered, at the pages a system prompt fills."""
    n = len(engines(server))
    prompts = [[1] * WARM_PROMPT]
    sessions = (mix or {}).get("sessions")
    if sessions and getattr(server, "prefix_cache", None) is not None:
        system = [3] * sessions["system_prompt_tokens"]
        prompts = [system + [1] * WARM_PROMPT, system + [2] * WARM_PROMPT]
    for prompt in prompts:
        reqs = [server.submit(prompt, max_new_tokens=2) for _ in range(n)]
        while not all(r.finished for r in reqs):
            server.step()


# -- the window ----------------------------------------------------------------

@dataclass
class Flight:
    """One scheduled request as the harness sees it (times in seconds
    after the window opened)."""
    due: float
    prompt: List[int]
    max_new: int
    tenant: str = "default"
    req: object = None
    t_submit: Optional[float] = None
    failed: bool = False
    t_tokens: List[float] = field(default_factory=list)
    progress: int = 0                  # prompt tokens seen in the pages
    adopted: int = 0                   # of them copied from a prefix cache
    t_admit: Optional[float] = None

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.finished

    @property
    def tokens(self) -> List[int]:
        return list(self.req.tokens) if self.req is not None else []


class Tracer:
    """A ``jax.profiler`` trace of the last stretch of the window, in a
    temporary directory that ``close`` removes."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.events: Optional[list] = None
        self._ann = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._ann.__enter__()

    def stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.events = trace_reduce.load(trace_reduce.find_xplane(self.dir))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _tick_counts(server) -> dict:
    """The registry's tokens-per-tick counts, summed over the replicas
    (a fleet's registry labels each engine's series ``source``)."""
    if hasattr(server, "metrics_view"):
        reg = server.metrics_view()
        labels = [{"source": f"engine{i}"}
                  for i in range(len(server.engines))]
    else:
        reg, labels = server.metrics, [{}]
    out = {"count": 0.0, "sum": 0.0}
    for kind in ("mixed", "decode"):
        for lab in labels:
            h = reg.histogram("serve_tokens_per_tick", kind=kind,
                              **lab) or {}
            out["count"] += h.get("count", 0)
            out["sum"] += h.get("sum", 0.0)
    return out


def _recording_adoptions(cache, adopted: dict):
    """Has the fleet's prefix cache note, by request id, how many prompt
    tokens each adoption copied from it (the count ``adopt`` returns);
    undone by deleting the instance attribute."""
    real = cache.adopt

    def adopt(engine, req):
        n = real(engine, req)
        adopted[req.rid] = n
        return n
    cache.adopt = adopt


def serve_window(server, arrivals, seconds: float, drain_s: float,
                 tracer: Optional[Tracer] = None) -> dict:
    """Offer ``arrivals`` open loop for ``seconds``, then keep stepping
    up to ``drain_s`` more for the window's requests to finish.

    Returns the run's record: the flights due in the window (their
    submit, admission and token times in seconds after the window
    opened, and the prompt tokens a prefix cache supplied), every tick
    (its host interval, the key count of each slot it decoded and the
    prompt chunks it computed), the registry's tokens-per-tick counts
    over the window, the generator's lateness, whether a prefix cache
    served, and the trace's events when ``tracer`` is given.
    """
    from jax.profiler import TraceAnnotation

    from repro.serve.scheduler import SubmitError
    flights = [Flight(a.due, a.prompt, a.max_new, a.tenant)
               for a in arrivals]
    live: List[Flight] = []
    ticks: List[dict] = []
    adopted: dict = {}
    cache = getattr(server, "prefix_cache", None)
    nxt = 0
    clock = time.perf_counter          # the engine's own stamps' clock
    t0 = clock()
    c0 = _tick_counts(server)

    def step() -> None:
        nonlocal live
        k = len(ticks)
        ts = clock() - t0
        with TraceAnnotation("engine_step", tick=k):
            server.step()
        te = clock() - t0
        decode, chunks = [], []
        for f in live:
            p = f.req.prefill_progress
            if p > f.progress:
                skipped = adopted.pop(f.req.rid, 0)
                f.adopted += skipped
                start = f.progress + skipped
                if p > start:
                    chunks.append((start, p - start))
                f.progress = p
            n = len(f.req.tokens)
            if n > len(f.t_tokens):
                if f.t_tokens:
                    decode.append(len(f.prompt) + len(f.t_tokens))
                f.t_tokens.extend([te] * (n - len(f.t_tokens)))
        ticks.append({"tick": k, "t0": ts, "t1": te, "decode": decode,
                      "chunks": chunks})
        live = [f for f in live if not f.req.finished]

    if cache is not None:
        _recording_adoptions(cache, adopted)
    tracing = False
    try:
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            if tracer is not None and not tracing \
                    and now >= seconds - tracer.seconds:
                tracer.start()
                tracing = True
            while nxt < len(flights) and flights[nxt].due <= now:
                f = flights[nxt]
                nxt += 1
                try:
                    with TraceAnnotation("submit"):
                        f.req = server.submit(f.prompt,
                                              max_new_tokens=f.max_new,
                                              tenant=f.tenant)
                except SubmitError:
                    f.failed = True
                    continue
                f.t_submit = clock() - t0
                live.append(f)
            if live:
                step()
            else:
                until = min(flights[nxt].due if nxt < len(flights)
                            else seconds, seconds)
                with TraceAnnotation("wait_arrival"):
                    time.sleep(max(0.0, until - (clock() - t0)))
        c1 = _tick_counts(server)
        if tracing:
            tracer.stop()
        while live and clock() - t0 < seconds + drain_s:
            step()
    finally:
        if cache is not None:
            del cache.adopt
    due = flights[:nxt]
    for f in due:
        if f.req is not None and f.req.t_admit is not None:
            f.t_admit = f.req.t_admit - t0
    late = [f.t_submit - f.due for f in due if f.t_submit is not None]
    return {
        "window_s": seconds,
        "flights": due,
        "ticks": ticks,
        "tokens_per_tick": {k: c1[k] - c0[k] for k in c1},
        "lateness_s": {"mean": sum(late) / len(late) if late else 0.0,
                       "max": max(late) if late else 0.0},
        "prefix_cache": cache is not None,
        "trace": tracer.events if tracer is not None else None,
    }


# -- metrics -------------------------------------------------------------------

def reader(name: str):
    """The module that reads metric ``name``: ``metrics/<name>.py``,
    else ``metrics/<name up to its first dot>.py``."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r} in metrics/")


def read_metrics(specs: List[dict], rec: dict) -> dict:
    out = {}
    for m in specs:
        v = reader(m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# -- one run -------------------------------------------------------------------

def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> Optional[int]:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    return max(peak) if all(p is not None for p in peak) else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peak_table: dict) -> dict:
    """Set-up, window, metrics and check of one run; returns the result
    object (the last line the benchmark prints) with the check's
    numbers under ``check``."""
    import jax
    cfg = cell.config
    m, n_slots = cfg["model"], cfg["spec"]["serve"]["n_slots"]
    server = build_server(cell, seed)
    warm_up(server, cell.mix)
    arrivals = traffic.schedule(cell.mix, seed, seconds, n_slots,
                                m["vocab_size"])
    setup_s = time.perf_counter() - t_start

    tracer = Tracer(min(TRACE_SECONDS, seconds / 2)) if trace else None
    try:
        rec = serve_window(server, arrivals, seconds, cell.mix["drain_s"],
                           tracer)
    finally:
        if tracer is not None:
            tracer.close()
    rec.update(setup_s=setup_s, model=m, engine=cfg["spec"]["serve"],
               chips=len(engines(server)), counts=check.counts(cfg),
               peaks=peak_table)
    print(f"generator lateness: mean {rec['lateness_s']['mean'] * 1e3:.3f} "
          f"ms, max {rec['lateness_s']['max'] * 1e3:.3f} ms over "
          f"{len(rec['flights'])} requests", file=sys.stderr, flush=True)
    dev = device_info(jax)
    dev["memory_peak_bytes"] = memory_peak(jax)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(specs, rec)
    result = {"correct": None,
              "attempted": len(rec["flights"]),
              "failed": sum(f.failed for f in rec["flights"]),
              "metrics": metrics, "device": dev}
    if trace:
        ev = rec["trace"]
        dev["busy_s"] = trace_reduce.busy_s(ev)
        dev["window_s"] = trace_reduce.window_s(ev)
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(ev),
                               "idle_gaps": trace_reduce.idle_by_host(ev)}

    picked = check.sample(rec["flights"], seed, cell.mix["check_requests"])
    del server, rec
    gc.collect()
    weights = check.make_weights(cfg, seed)
    gap = check.served_gap(cfg, weights, picked)
    del weights
    short = sum(len(f.tokens) != f.max_new for f in picked)
    numbers = {
        "logit_gap": {"value": gap, "limit": cell.limits["logit_gap"]},
        "short_requests": {"value": short, "limit": 0},
        "failed_requests": {"value": result["failed"], "limit": 0},
    }
    print(f"output check: {len(picked)} finished requests, "
          f"{sum(len(f.tokens) for f in picked)} served tokens",
          file=sys.stderr, flush=True)
    result["correct"] = bool(
        not math.isnan(gap) and gap <= cell.limits["logit_gap"]
        and short == 0 and len(picked) >= 1 and result["failed"] == 0)
    result["check"] = numbers
    return result
