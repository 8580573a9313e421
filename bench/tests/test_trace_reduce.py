"""The trace reduction against numbers worked out by hand: busy and idle
union, kernel time and the attribution of idle gaps to host activity.
Runs on plain event dicts, never on a chip."""
import gzip
import json

import _paths
import pytest

from bench import trace_reduce as tr

DATA = _paths.ROOT / "bench" / "tests" / "data"


def op(name, start, dur, device=0):
    return {"kind": "op", "device": device, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def host(name, start, dur, tick=None):
    e = {"kind": "host", "device": -1, "name": name,
         "start_ns": float(start), "dur_ns": float(dur)}
    if tick is not None:
        e["tick"] = tick
    return e


# window 0-1000 ns; two engine steps with a wait for an arrival between.
# Operations nest as on the device's XLA Ops line: a while loop holds
# the ops of its body.
HAND = [
    host("bench_trace_window", 0, 1000),
    host("engine_step", 0, 400, tick=7),
    op("while.1", 40, 220),                                # 40-260
    op("fusion.1", 50, 100),                               # inside it
    op("paged_decode_attention_kernel.3", 150, 100),       # inside it
    op("paged_decode_attention_kernel.5", 300, 50),        # 300-350
    host("wait_arrival", 400, 300),
    host("submit", 700, 20),
    host("engine_step", 720, 280, tick=8),
    op("paged_prefill_attention_kernel.2", 800, 150),      # 800-950
    op("fusion.1.remat", 980, 40),                         # past the end
]


def test_union_and_busy():
    # overlapping and touching intervals merge
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    # busy: 40-260 (220) + 300-350 (50) + 800-950 (150) + 980-1000 (20)
    assert tr.busy_s(HAND) == pytest.approx(440e-9)
    assert tr.window_s(HAND) == pytest.approx(1000e-9)


def test_step_idle():
    # tick 7 (0-400): busy 220 + 50 -> idle 130; tick 8 (720-1000):
    # busy 150 + 20 -> idle 110
    assert tr.step_idle_ns(HAND) == {7: pytest.approx(130.0),
                                     8: pytest.approx(110.0)}


def test_kernel_time_and_self_time():
    assert tr.kernel_s(HAND, "paged_decode_attention_kernel") == \
        pytest.approx(150e-9)
    assert tr.kernel_s(HAND, "paged_prefill_attention_kernel") == \
        pytest.approx(150e-9)
    # self time: the loop keeps 220 - 100 - 100; an operation counts in
    # full toward the window it starts in
    assert dict(map(tuple, tr.top_ops(HAND))) == {
        "paged_decode_attention_kernel": pytest.approx(150e-9),
        "paged_prefill_attention_kernel": pytest.approx(150e-9),
        "fusion": pytest.approx(140e-9),
        "while": pytest.approx(20e-9)}
    assert tr.op_name("%fusion.281 = bf16[512]{0} fusion(x)") == "fusion.281"


def test_idle_gaps_by_host_activity():
    # gaps 0-40, 260-300, 350-800, 950-980
    assert tr.idle_gaps(HAND) == [[0, 40], [260, 300], [350, 800],
                                  [950, 980]]
    # midpoints 20 and 280 in step 7, 575 in wait_arrival, 965 in step 8
    got = dict(map(tuple, tr.idle_by_host(HAND)))
    assert got == {"engine_step": pytest.approx(110e-9),
                   "wait_arrival": pytest.approx(450e-9)}


def test_only_steps_inside_the_window_count():
    ev = HAND + [host("engine_step", 1100, 50, tick=9)]
    assert sorted(tr.step_idle_ns(ev)) == [7, 8]


# Two ticks of yi6b.prefill traced on a TPU v5e (a decode tick, tick 145,
# then a mixed tick, 146), trimmed to 1 ms either side, times rebased
# to the window's start.  The numbers below were worked out once with a
# sweep over the operations' start and end points, independently of
# union(): busy 211,386,783 ns of 222,991,575; the two steps' idle
# 4,197,595 and 5,316,383 ns; 32 paged decode kernel launches a tick
# (one a layer) and 32 paged prefill launches in the mixed tick.
@pytest.fixture(scope="module")
def recorded():
    rows = json.loads(gzip.decompress(
        (DATA / "trace_yi6b_two_ticks.json.gz").read_bytes()))
    return [dict(zip(("kind", "device", "name", "start_ns", "dur_ns",
                      "tick"), r)) for r in rows]


def test_recorded_busy_and_idle(recorded):
    assert tr.window_s(recorded) == pytest.approx(0.222991575)
    assert tr.busy_s(recorded) == pytest.approx(0.211386783)
    assert tr.step_idle_ns(recorded) == {
        145: pytest.approx(4_197_595.0), 146: pytest.approx(5_316_383.0)}
    idle = dict(map(tuple, tr.idle_by_host(recorded)))
    assert set(idle) == {"engine_step", "harness"}
    assert sum(idle.values()) == pytest.approx(0.222991575 - 0.211386783)


def test_recorded_kernels(recorded):
    names = [e["name"] for e in recorded if e["kind"] == "op"]
    assert sum(n.startswith("paged_decode_attention_kernel")
               for n in names) == 64
    assert sum(n.startswith("paged_prefill_attention_kernel")
               for n in names) == 32
    assert tr.kernel_s(recorded, "paged_decode_attention_kernel") == \
        pytest.approx(0.070802848)
    assert tr.kernel_s(recorded, "paged_prefill_attention_kernel") == \
        pytest.approx(0.0309416)
    top = tr.top_ops(recorded)
    assert top[0][0] == "paged_decode_attention_kernel"
    # self times never exceed busy time
    assert sum(t for _, t in top) <= tr.busy_s(recorded) + 1e-9
