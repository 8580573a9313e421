"""The harness's window loop on the CPU at -smoke size: due-time TTFT,
inter-token gaps, and the result line's keys."""
import time

import _smoke
import pytest

from bench import harness, traffic

SEED = 2**31 + 77          # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def engine():
    c = _smoke.cell("yi6b.prefill", "yi-6b-smoke")
    eng = harness.build_server(c, SEED)
    harness.warm_up(eng, c.mix)
    return c, eng


def test_same_seed_same_arrivals_other_seed_same_work():
    a = traffic.schedule(_smoke.MIX, SEED, 3.0, 4, 256)
    b = traffic.schedule(_smoke.MIX, SEED, 3.0, 4, 256)
    c = traffic.schedule(_smoke.MIX, SEED + 1, 3.0, 4, 256)
    assert [(x.due, x.prompt, x.max_new) for x in a] == \
        [(x.due, x.prompt, x.max_new) for x in b]
    # another seed: the same sizes at the same times, other token ids
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in c]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    assert sum(x.due == 0 for x in a) == 4          # due_at_start: n_slots
    lens = sorted(len(x.prompt) for x in a)
    assert lens[0] >= 16 and lens[-1] <= 192


def test_ttft_counts_from_due_time_and_a_stall_shows(engine):
    c, eng = engine
    arrivals = traffic.schedule(c.mix, SEED, 2.0, 4, 256)
    real_step, stalled = eng.step, []

    def step():                  # the first step stalls the loop 0.5 s
        if not stalled:
            stalled.append(True)
            time.sleep(0.5)
        return real_step()
    eng.step = step
    try:
        rec = harness.serve_window(eng, arrivals, 2.0, c.mix["drain_s"])
    finally:
        del eng.step
    flights = rec["flights"]
    assert flights and all(f.finished for f in flights)
    for f in flights:
        assert f.t_submit >= f.due
        assert len(f.t_tokens) == f.max_new == len(f.tokens)
        assert f.t_tokens == sorted(f.t_tokens)
    # the requests due at 0 waited out the stall: their first token is
    # at least 0.5 s after their due time
    assert all(f.t_tokens[0] - f.due >= 0.5 for f in flights if f.due == 0)
    ttft = harness.reader("ttft_p90_ms").read(rec)
    late = sorted(f.t_tokens[0] - f.due for f in flights)
    assert ttft == pytest.approx(late[-(-9 * len(late) // 10) - 1] * 1e3)


def test_inter_token_gaps_are_tick_to_tick(engine):
    c, eng = engine
    arrivals = traffic.schedule(c.mix, SEED + 3, 2.0, 4, 256)
    rec = harness.serve_window(eng, arrivals, 2.0, c.mix["drain_s"])
    ends = [t["t1"] for t in rec["ticks"]]
    gaps = []
    for f in rec["flights"]:
        for a, b in zip(f.t_tokens, f.t_tokens[1:]):
            assert a in ends and b in ends and b > a
            if b <= rec["window_s"]:
                gaps.append(b - a)
    assert harness.reader("itl_p99_ms").read(rec) == pytest.approx(
        sorted(gaps)[-(-99 * len(gaps) // 100) - 1] * 1e3)
    n = sum(t <= 2.0 for f in rec["flights"] for t in f.t_tokens)
    assert harness.reader("serve_tokens_per_s").read(rec) == n / 2.0


@pytest.mark.parametrize("name, arch, trace", [
    ("yi6b.prefill", "yi-6b-smoke", False),
    ("chatglm3.decode", "chatglm3-6b-smoke", True),
])
def test_result_line(name, arch, trace):
    c = _smoke.cell(name, arch)
    r = harness.run_cell(c, SEED, 2.0, trace, time.perf_counter(),
                         _smoke.PEAKS)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r) == keys + (["breakdown"] if trace else []) + ["check"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    want = c.per_layer if trace else c.end_to_end
    got = set(r["metrics"])
    assert got <= {m["name"] for m in want}
    if not trace:
        assert got == {m["name"] for m in want}
    assert set(r["check"]) == {"logit_gap", "short_requests",
                               "failed_requests"}
    for n in r["check"].values():
        assert set(n) == {"value", "limit"}
