"""The engine's spans in a trace against numbers worked out by hand: the
innermost annotation, the host's own time per tick, the host's lead over
the device, and the compile count of the window.  Runs on plain event
dicts, never on a chip, and once through the phase tool at CPU-test
size."""
import gzip
import json
import sys
import time
import types
from types import SimpleNamespace

import _paths
import _smoke
import pytest

from bench import engine_trace as et
from bench import check, harness, phases, trace_reduce
from repro.obs import ENGINE_PHASES

DATA = _paths.ROOT / "bench" / "tests" / "data"

SEED = 2**31 + 91


def host(name, start, dur, **attrs):
    e = {"kind": "host", "device": -1, "name": name,
         "start_ns": float(start), "dur_ns": float(dur)}
    if name.startswith("engine."):
        e["attrs"] = attrs
    elif attrs:
        e.update(attrs)
    return e


def dev(kind, name, start, dur):
    return {"kind": kind, "device": 0, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


# window 0-1000 ns: a decode tick, a wait for an arrival, a submit and a
# mixed tick, each tick the harness's engine_step around the engine's
# engine.step and its six phases; each step program is one operation.
HAND = [
    host("bench_trace_window", 0, 1000),
    host("engine_step", 0, 400, tick=7),
    host("engine.step", 10, 380, tick=0, kind="decode"),
    host("engine.admit", 10, 10),
    host("engine.plan", 20, 20),
    host("engine.launch", 40, 60, program="decode"),
    host("engine.sync", 100, 200),
    host("engine.emit", 300, 70),
    host("engine.metrics", 370, 15),
    dev("program", "jit_decode_fn", 70, 250),              # 70-320
    dev("op", "fusion.1", 70, 250),
    host("wait_arrival", 400, 300),
    host("submit", 700, 20),
    host("engine_step", 720, 280, tick=8),
    host("engine.step", 730, 260, tick=1, kind="mixed"),
    host("engine.admit", 730, 5),
    host("engine.plan", 735, 25),
    host("engine.launch", 760, 40, program="mixed"),
    host("engine.sync", 800, 150),
    host("engine.emit", 950, 30),
    host("engine.metrics", 980, 8),
    dev("program", "jit_mixed_fn", 790, 150),              # 790-940
    dev("op", "paged_prefill_attention_kernel.2", 790, 150),
]


def test_flatten_names_each_stretch_for_the_innermost_span():
    spans = [(0, 100, "a"), (10, 50, "b"), (20, 30, "c"), (60, 70, "d")]
    assert et.flatten(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 50, "b"),
        (50, 60, "a"), (60, 70, "d"), (70, 100, "a")]
    at = et.host_activity(HAND)
    assert at(5) == "engine_step"             # before engine.step opens
    assert at(30) == "engine.plan"
    assert at(387) == "engine.step"           # after its last phase
    assert at(395) == "engine_step"
    assert at(500) == "wait_arrival"
    assert at(710) == "submit"
    assert at(1005) == "harness"


def test_idle_by_phase():
    # device idle 0-70, 320-790, 940-1000; midpoints 35 (engine.plan),
    # 555 (wait_arrival), 970 (engine.emit)
    assert dict(map(tuple, et.idle_by_phase(HAND))) == {
        "engine.plan": pytest.approx(70e-9),
        "wait_arrival": pytest.approx(470e-9),
        "engine.emit": pytest.approx(60e-9)}


def test_idle_split_over_every_phase_a_gap_overlaps():
    # gap 0-70: engine_step 0-10, admit 10-20, plan 20-40, launch 40-70;
    # gap 320-790: emit 320-370, metrics 370-385, engine.step 385-390,
    # engine_step 390-400, wait_arrival 400-700, submit 700-720,
    # engine_step 720-730, admit 730-735, plan 735-760, launch 760-790;
    # gap 940-1000: sync 940-950, emit 950-980, metrics 980-988,
    # engine.step 988-990, engine_step 990-1000
    got = dict(map(tuple, et.idle_split(HAND)))
    want = {"engine_step": 10 + 10 + 10 + 10, "engine.admit": 10 + 5,
            "engine.plan": 20 + 25, "engine.launch": 30 + 30,
            "engine.sync": 0 + 10, "engine.emit": 50 + 30,
            "engine.metrics": 15 + 8, "engine.step": 5 + 2,
            "wait_arrival": 300, "submit": 20}
    assert got == {n: pytest.approx(v * 1e-9) for n, v in want.items()}
    assert sum(got.values()) == pytest.approx(600e-9)


def test_step_host_time_and_phase_split():
    # tick 0: 380 - 200 of sync; tick 1: 260 - 150 -> mean 145 ns
    assert et.step_host_ms(HAND) == pytest.approx(145e-6)
    split = et.phase_split(HAND)
    assert split["decode"]["ticks"] == 1 and split["mixed"]["ticks"] == 1
    assert split["decode"]["step"] == pytest.approx(380e-6)
    assert split["decode"]["sync"] == pytest.approx(200e-6)
    assert split["decode"]["other"] == pytest.approx(5e-6)
    assert split["mixed"]["launch"] == pytest.approx(40e-6)
    assert split["mixed"]["other"] == pytest.approx(2e-6)
    # a step that ends past the window does not count
    late = HAND + [host("engine.step", 995, 20, tick=2, kind="decode")]
    assert et.step_host_ms(late) == pytest.approx(145e-6)


def test_host_lead_and_a_device_plane_that_reads_early():
    assert et.leads_ns(HAND) == [pytest.approx(30.0), pytest.approx(30.0)]
    assert et.host_lead_ns(HAND)["shift_ns"] == 0.0
    # the device's events read 50 ns early: each program now starts 20 ns
    # before its launch, which no device can do
    early = [dict(e, start_ns=e["start_ns"] - 50)
             if e["kind"] in ("op", "program") else e for e in HAND]
    lead = et.host_lead_ns(early)
    assert (lead["n"], lead["min"], lead["median"], lead["max"]) == \
        (2, -20.0, -20.0, -20.0)
    assert lead["shift_ns"] == 20.0
    # shifted back by the 50 ns, the attribution is the recorded one
    assert et.idle_by_phase(early, shift_ns=50.0) == et.idle_by_phase(HAND)


def test_lead_to_another_program_of_each_launch():
    # a key split runs first in each launch: 5 ns and 2 ns after it starts
    ev = HAND + [dev("program", "jit__threefry_split", 45, 3),
                 dev("program", "jit__threefry_split", 762, 3)]
    assert et.leads_ns(ev, program=et.SPLIT) == [pytest.approx(5.0),
                                                  pytest.approx(2.0)]
    assert et.host_lead_ns(ev, program=et.SPLIT)["median"] == 3.5
    assert et.leads_ns(ev) == et.leads_ns(HAND)


def test_strip_leaves_what_the_benchmark_loads():
    kept = et.strip(HAND)
    assert {e["kind"] for e in kept} == {"host", "op"}
    assert not [e for e in kept if e["name"].startswith("engine.")]
    assert len(kept) == len(HAND) - 16


def _rec(window_s=2.0):
    flight = SimpleNamespace(t_admit=0.5, req=SimpleNamespace(t_admit=100.5))
    return {"flights": [SimpleNamespace(t_admit=None), flight],
            "window_s": window_s,
            "ticks": [{"t0": 1.9, "t1": 2.3}, {"t0": 2.3, "t1": 2.6}]}


def test_window_compiles_counts_from_the_open_to_the_last_tick(monkeypatch):
    from repro.obs import metrics
    # the window opened at 100.0 on the program's clock; its last tick
    # ended at 102.3
    monkeypatch.setattr(metrics, "_compile_times",
                        [99.0, 100.2, 102.25, 102.4])
    assert harness.reader("window_compiles").read(_rec()) == 2


def test_window_compiles_is_silent_without_the_counter(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.metrics",
                        types.ModuleType("repro.obs.metrics"))
    assert harness.reader("window_compiles").read(_rec()) is None
    monkeypatch.undo()
    assert harness.reader("window_compiles").read(
        dict(_rec(), flights=[])) is None


def test_phase_tool_at_cpu_test_size():
    c = _smoke.cell("yi6b.prefill", "yi-6b-smoke")
    out = phases.measure(c, SEED, 2.0, time.perf_counter(), _smoke.PEAKS)
    assert out["window_compiles"] == 0
    assert out["step_host_ms"] > 0
    assert set(out["phases_ms"]) <= {"mixed", "decode"} and out["phases_ms"]
    for row in out["phases_ms"].values():
        assert row["ticks"] >= 1
        parts = sum(row[p[len("engine."):]] for p in
                    ("engine.admit", "engine.plan", "engine.launch",
                     "engine.sync", "engine.emit", "engine.metrics"))
        assert parts + row["other"] == pytest.approx(row["step"])
    assert out["slowest_ticks"][0]["host_ms"] >= \
        out["slowest_ticks"][-1]["host_ms"]
    assert out["span_cost_us"] > 0
    # no device plane on the CPU: nothing for the device's metrics
    assert out["host_gap_ms"] is None and out["host_lead_ns"] is None
    assert out["split_lead_ns"] is None


# Two ticks of yi6b.prefill traced on a TPU v5e with the engine's spans
# (bench/phases.py --fixture): the engine's decode tick 459, then mixed
# tick 460 (the harness's 457 and 458), 1 ms either side, times rebased.
# Worked out once with a sweep independent of the code under test:
# window 184,368,201 ns, busy 173,475,176; step less sync 2,715,821 and
# 3,847,250 ns; launch to step program 505,159 and 1,452,526 ns; launch
# to key split -1,086,528, -1,136,162 and -1,085,405 ns (the third
# launch's step program runs past the end of the trace).
@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress(
        (DATA / "trace_yi6b_engine_ticks.json.gz").read_bytes()))


def test_recorded_engine_ticks(recorded):
    ev = recorded["events"]
    steps = et.steps(ev)
    assert [(s["attrs"]["tick"], s["attrs"]["kind"]) for s in steps] == \
        [(459, "decode"), (460, "mixed")]
    for s in steps:
        assert tuple(p["name"] for p in et.phases_of(ev, s)) == \
            ENGINE_PHASES
    assert et.step_host_ms(ev) == pytest.approx(
        (2_715_821 + 3_847_250) / 2 / 1e6)
    assert et.leads_ns(ev) == [505_159.0, 1_452_526.0]
    # every key split starts on the device's plane before its launch
    # starts on the host's: that plane reads at least 1.14 ms early
    split = et.host_lead_ns(ev, program=et.SPLIT)
    assert (split["n"], split["min"], split["shift_ns"]) == \
        (3, -1_136_162.0, 1_136_162.0)


def test_recorded_idle_by_engine_phase(recorded):
    ev = recorded["events"]
    idle = (184_368_201 - 173_475_176) / 1e9
    assert trace_reduce.window_s(ev) - trace_reduce.busy_s(ev) == \
        pytest.approx(idle)
    for shift in (0.0, 1_136_162.0):
        by_mid = dict(map(tuple, et.idle_by_phase(ev, shift_ns=shift)))
        split = dict(map(tuple, et.idle_split(ev, shift_ns=shift)))
        assert sum(by_mid.values()) == pytest.approx(idle, rel=1e-3)
        assert sum(split.values()) == pytest.approx(idle, rel=1e-3)
        # engine phases, not the harness's one engine_step, take it
        assert max(by_mid, key=by_mid.get).startswith("engine.")
        assert split.get("engine_step", 0.0) < 0.1 * idle
    # the benchmark's own breakdown names the harness's annotation
    base = dict(map(tuple, trace_reduce.idle_by_host(et.strip(ev))))
    assert set(base) <= {"engine_step", "submit", "harness"}


def test_existing_readers_read_the_same_beside_engine_events(recorded):
    ev = recorded["events"]
    cfg = json.loads((_paths.ROOT / "bench" / "configs" / "yi-6b.json")
                     .read_text())
    rec = {"trace": ev, "ticks": recorded["ticks"], "model": cfg["model"],
           "engine": cfg["spec"]["serve"], "peaks": _smoke.PEAKS,
           "counts": check.counts(cfg), "chips": 1, "prefix_cache": False,
           "flights": [], "window_s": trace_reduce.window_s(ev),
           "tokens_per_tick": {"count": 0.0, "sum": 0.0}, "setup_s": 0.0}
    bare = dict(rec, trace=et.strip(ev))
    assert len(bare["trace"]) < len(ev)
    read = set()
    for m in _smoke.MANIFEST["per_layer"]:
        r = harness.reader(m["name"])
        assert r.read(rec) == r.read(bare), m["name"]
        if r.read(rec) is not None:
            read.add(m["name"].split(".")[0])
    assert read >= {"host_gap_ms", "device_idle_pct", "step_mfu_pct",
                    "paged_decode_roofline", "paged_prefill_roofline"}
