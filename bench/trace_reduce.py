"""From a profiler trace to busy time, idle gaps and kernel time.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps two kinds of event as plain dicts: the device's operations (the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the harness's
own host annotations (``bench_trace_window``, ``engine_step``,
``submit``, ``wait_arrival``).  Everything else here is arithmetic on
those dicts, so it is tested on a small recorded trace without a chip.

An event is ``{"kind": "op" | "host", "device": int, "name": str,
"start_ns": float, "dur_ns": float}``; an ``engine_step`` host event
also carries ``"tick"``, the harness's tick number.  An operation's
name is its HLO instruction name (``paged_decode_attention_kernel.9``):
the trace gives the whole instruction text, ``load`` keeps the name.
Operations nest on the ``XLA Ops`` line (a ``while`` loop holds the ops
of its body), so time per operation is self time, its children's
removed; busy time is the union of all of them.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

HOST_NAMES = ("bench_trace_window", "engine_step", "submit", "wait_arrival")
WINDOW = "bench_trace_window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    events.append({"kind": "op", "device": dev,
                                   "name": op_name(ev.name),
                                   "start_ns": float(ev.start_ns),
                                   "dur_ns": float(ev.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name not in HOST_NAMES:
                        continue
                    e = {"kind": "host", "device": -1, "name": ev.name,
                         "start_ns": float(ev.start_ns),
                         "dur_ns": float(ev.duration_ns)}
                    if ev.name == "engine_step":
                        e["tick"] = int(dict(ev.stats)["tick"])
                    events.append(e)
    return events


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge overlapping [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged: List[List[float]], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` covered by the merged intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def window(events: List[dict]) -> Tuple[float, float]:
    w = [e for e in events if e["kind"] == "host" and e["name"] == WINDOW]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(w)}")
    return w[0]["start_ns"], w[0]["start_ns"] + w[0]["dur_ns"]


def ops(events: List[dict]) -> List[dict]:
    return [e for e in events if e["kind"] == "op"]


def devices(events: List[dict]) -> List[int]:
    return sorted({e["device"] for e in ops(events)})


def busy(events: List[dict], device: int) -> List[List[float]]:
    return union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in ops(events) if e["device"] == device)


def busy_s(events: List[dict]) -> float:
    """Seconds in the window with an operation running, averaged over
    the devices that ran any."""
    lo, hi = window(events)
    devs = devices(events)
    if not devs:
        return 0.0
    return sum(covered(busy(events, d), lo, hi) for d in devs) \
        / len(devs) / 1e9


def window_s(events: List[dict]) -> float:
    lo, hi = window(events)
    return (hi - lo) / 1e9


def steps(events: List[dict]) -> List[dict]:
    """``engine_step`` annotations inside the window, in time order."""
    lo, hi = window(events)
    return sorted((e for e in events if e["name"] == "engine_step"
                   and lo <= e["start_ns"] and
                   e["start_ns"] + e["dur_ns"] <= hi),
                  key=lambda e: e["start_ns"])


def step_idle_ns(events: List[dict], device: int = 0) -> Dict[int, float]:
    """Per tick: time inside its ``engine_step`` annotation with no
    operation running on ``device``."""
    merged = busy(events, device)
    return {e["tick"]: e["dur_ns"] - covered(merged, e["start_ns"],
                                             e["start_ns"] + e["dur_ns"])
            for e in steps(events)}


def kernel_s(events: List[dict], prefix: str) -> float:
    """Summed device time of the operations whose name starts with
    ``prefix``, inside the window."""
    lo, hi = window(events)
    return sum(covered([[e["start_ns"], e["start_ns"] + e["dur_ns"]]],
                       lo, hi)
               for e in ops(events) if e["name"].startswith(prefix)) / 1e9


def op_name(text: str) -> str:
    """``%fusion.281 = bf16[...] fusion(...)`` -> ``fusion.281``."""
    return text[1:].split(" ", 1)[0] if text.startswith("%") else text


def op_family(name: str) -> str:
    """An instruction name without its instance and remat suffixes:
    ``copy.136.remat`` -> ``copy``."""
    parts = name.split(".")
    while len(parts) > 1 and (parts[-1].isdigit() or parts[-1] == "remat"):
        parts.pop()
    return ".".join(parts)


def self_ns(events: List[dict], device: int) -> List[Tuple[dict, float]]:
    """Each operation of ``device`` with its self time: its duration
    less the time of the operations nested directly inside it."""
    evs = sorted((e for e in ops(events) if e["device"] == device),
                 key=lambda e: (e["start_ns"], -e["dur_ns"]))
    own = {id(e): e["dur_ns"] for e in evs}
    stack: List[dict] = []
    for e in evs:
        while stack and stack[-1]["start_ns"] + stack[-1]["dur_ns"] \
                <= e["start_ns"]:
            stack.pop()
        if stack:
            p = stack[-1]
            own[id(p)] -= min(e["dur_ns"], p["start_ns"] + p["dur_ns"]
                              - e["start_ns"])
        stack.append(e)
    return [(e, own[id(e)]) for e in evs]


def top_ops(events: List[dict], k: int = 10) -> List[list]:
    """Self seconds per operation family, averaged over the devices,
    most first.  An operation counts in full toward the window it
    starts in."""
    lo, hi = window(events)
    devs = devices(events)
    total: Dict[str, float] = defaultdict(float)
    for d in devs:
        for e, own in self_ns(events, d):
            if lo <= e["start_ns"] < hi:
                total[op_family(e["name"])] += own / len(devs)
    return [[n, t / 1e9] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events: List[dict], device: int = 0) -> List[List[float]]:
    """The window's stretches with no operation on ``device``."""
    lo, hi = window(events)
    gaps, t = [], lo
    for s, e in busy(events, device):
        if s > t:
            gaps.append([t, min(s, hi)])
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append([t, hi])
    return [g for g in gaps if g[1] > g[0]]


def host_activity(events: List[dict]):
    """A lookup ``t -> name`` of the harness annotation the host was
    inside at ``t`` ("harness" between them).  The annotations other
    than the window follow one another without nesting."""
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events
                   if e["kind"] == "host" and e["name"] != WINDOW)
    starts = [s for s, _, _ in spans]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else "harness"
    return at


def idle_by_host(events: List[dict], device: int = 0,
                 k: int = 10) -> List[list]:
    """Idle seconds of ``device`` summed by what the host was doing
    (the annotation around each gap's midpoint), most first."""
    total: Dict[str, float] = defaultdict(float)
    at = host_activity(events)
    for s, e in idle_gaps(events, device):
        total[at((s + e) / 2)] += (e - s) / 1e9
    return [[n, t] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
