"""The engine's own spans in a profiler trace, beside the device's programs.

``load`` reads what ``trace_reduce.load`` reads and two kinds of event
more, as the same plain dicts:

* ``"host"`` events named ``engine.*``: the program's ``Engine.step``
  span and its phases (``repro.obs.ENGINE_PHASES``), each with its
  annotation's attributes under ``"attrs"`` (``engine.step``: ``tick``,
  ``kind``; ``engine.launch``: ``program``, and ``compiled`` when the
  launch compiled);
* ``"program"`` events: the ``XLA Modules`` line of each device plane,
  one event per run of a jitted program, named without its fingerprint
  (``jit_mixed_fn(2092913466585917779)`` -> ``jit_mixed_fn``).

The rest is arithmetic on those dicts, tested on a recorded trace:
the innermost host annotation at a time, the host's own time per tick
(``step_host_ms``), each phase's share of a tick, and the lead from an
``engine.launch`` to the device's start of the program it launched
(``host_lead_ns``), which bounds how far the device's plane reads early
against the host's.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

ENGINE = "engine."
STEP = "engine.step"
SYNC = "engine.sync"
LAUNCH = "engine.launch"
MODULES_LINE = "XLA Modules"
PROGRAMS = {"mixed": "jit_mixed_fn", "decode": "jit_decode_fn",
            "prefill": "jit_prefill_fn"}
SPLIT = "jit__threefry_split"  # the launch's first program: a fresh key
# a launch's programs start within this of it on the device's plane (the
# planes disagree by about a millisecond; ticks are 50 ms and more apart)
MATCH_NS = 5e6


def module_name(text: str) -> str:
    """``jit_decode_fn(6982929892340205292)`` -> ``jit_decode_fn``."""
    return text.split("(", 1)[0]


def load(path: str) -> List[dict]:
    from jax.profiler import ProfileData
    events = trace_reduce.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            dev = int(plane.name[len(trace_reduce.DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    events.append({"kind": "program", "device": dev,
                                   "name": module_name(ev.name),
                                   "start_ns": float(ev.start_ns),
                                   "dur_ns": float(ev.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ENGINE):
                        events.append({"kind": "host", "device": -1,
                                       "name": ev.name,
                                       "start_ns": float(ev.start_ns),
                                       "dur_ns": float(ev.duration_ns),
                                       "attrs": dict(ev.stats)})
    return events


def strip(events: List[dict]) -> List[dict]:
    """The events ``trace_reduce.load`` alone keeps."""
    return [e for e in events if e["kind"] != "program"
            and not e["name"].startswith(ENGINE)]


def end(e: dict) -> float:
    return e["start_ns"] + e["dur_ns"]


# -- the innermost host annotation ------------------------------------------

def flatten(spans: List[Tuple[float, float, str]]
            ) -> List[Tuple[float, float, str]]:
    """Nested ``(start, end, name)`` intervals as disjoint segments, each
    named for the innermost interval over it.  An interval that starts
    inside another ends inside it (annotations nest on one thread)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    t = None

    def emit(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][2]))
        t = upto

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        if t is not None:
            emit(s)
        t = s
        stack.append((s, min(e, stack[-1][1]) if stack else e, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def host_activity(events: List[dict]):
    """A lookup ``t -> name`` of the innermost host annotation open at
    ``t`` ("harness" outside all of them; the window annotation does not
    count)."""
    segs = flatten([(e["start_ns"], end(e), e["name"]) for e in events
                    if e["kind"] == "host"
                    and e["name"] != trace_reduce.WINDOW])
    starts = [s for s, _, _ in segs]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return segs[i][2] if i >= 0 and t < segs[i][1] else "harness"
    return at


def _shifted_gaps(events: List[dict], device: int, shift_ns: float):
    moved = [dict(e, start_ns=e["start_ns"] + shift_ns)
             if e["kind"] == "op" else e for e in events]
    return trace_reduce.idle_gaps(moved, device)


def _ranked(total: Dict[str, float], k: int) -> List[list]:
    return [[n, t] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_phase(events: List[dict], device: int = 0, shift_ns: float = 0.0,
                  k: int = 12) -> List[list]:
    """Idle seconds of ``device`` summed by the innermost host annotation
    around each gap's midpoint, most first.  ``shift_ns`` moves the
    device's operations later before the lookup, for a device plane that
    reads early (see :func:`host_lead_ns`)."""
    total: Dict[str, float] = defaultdict(float)
    at = host_activity(events)
    for s, e in _shifted_gaps(events, device, shift_ns):
        total[at((s + e) / 2)] += (e - s) / 1e9
    return _ranked(total, k)


def idle_split(events: List[dict], device: int = 0, shift_ns: float = 0.0,
               k: int = 12) -> List[list]:
    """As :func:`idle_by_phase`, but each gap's seconds are split over
    every innermost annotation the gap overlaps ("harness" where none
    is open), not given whole to the one at its midpoint."""
    segs = flatten([(e["start_ns"], end(e), e["name"]) for e in events
                    if e["kind"] == "host"
                    and e["name"] != trace_reduce.WINDOW])
    ends = [e for _, e, _ in segs]
    total: Dict[str, float] = defaultdict(float)
    for s, e in _shifted_gaps(events, device, shift_ns):
        inside = 0.0
        for lo, hi, name in segs[bisect.bisect_right(ends, s):]:
            if lo >= e:
                break
            part = min(hi, e) - max(lo, s)
            total[name] += part / 1e9
            inside += part
        if e - s > inside:
            total["harness"] += (e - s - inside) / 1e9
    return _ranked(total, k)


# -- ticks -------------------------------------------------------------------

def steps(events: List[dict]) -> List[dict]:
    """``engine.step`` spans inside the window, in time order."""
    lo, hi = trace_reduce.window(events)
    return sorted((e for e in events if e["kind"] == "host"
                   and e["name"] == STEP
                   and lo <= e["start_ns"] and end(e) <= hi),
                  key=lambda e: e["start_ns"])


def phases_of(events: List[dict], step: dict) -> List[dict]:
    """The ``engine.*`` spans inside ``step``, in time order."""
    lo, hi = step["start_ns"], end(step)
    return sorted((e for e in events if e["kind"] == "host"
                   and e["name"].startswith(ENGINE) and e is not step
                   and lo <= e["start_ns"] and end(e) <= hi),
                  key=lambda e: e["start_ns"])


def step_host_ms(events: List[dict]) -> Optional[float]:
    """Mean over the window's complete ``engine.step`` spans of the span
    less its ``engine.sync`` time: the host's own work per tick, in ms."""
    own = [s["dur_ns"] - sum(p["dur_ns"] for p in phases_of(events, s)
                             if p["name"] == SYNC)
           for s in steps(events)]
    return sum(own) / len(own) / 1e6 if own else None


def phase_split(events: List[dict]) -> Dict[str, dict]:
    """Per tick kind: the number of complete ticks, their mean
    ``engine.step`` time and each phase's mean time (ms); ``other`` is
    the step's time outside its phases."""
    out: Dict[str, dict] = {}
    for s in steps(events):
        row = out.setdefault(s["attrs"].get("kind", "?"),
                             defaultdict(float, ticks=0))
        row["ticks"] += 1
        row["step"] += s["dur_ns"]
        inner = 0.0
        for p in phases_of(events, s):
            row[p["name"][len(ENGINE):]] += p["dur_ns"]
            inner += p["dur_ns"]
        row["other"] += s["dur_ns"] - inner
    return {kind: {k: (v if k == "ticks" else v / row["ticks"] / 1e6)
                   for k, v in row.items()}
            for kind, row in out.items()}


# -- the host's lead over the device ----------------------------------------

def leads_ns(events: List[dict], device: int = 0,
             program: Optional[str] = None) -> List[float]:
    """Per ``engine.launch`` inside the window: the device's start of the
    step program it launched (or of ``program``, another program every
    launch runs; the run whose start lies nearest, within ``MATCH_NS``
    of the launch) minus the launch's start."""
    lo, hi = trace_reduce.window(events)
    runs: Dict[str, List[float]] = defaultdict(list)
    for e in events:
        if e["kind"] == "program" and e["device"] == device:
            runs[e["name"]].append(e["start_ns"])
    for v in runs.values():
        v.sort()
    out = []
    for e in events:
        if e["kind"] != "host" or e["name"] != LAUNCH \
                or not lo <= e["start_ns"] <= hi:
            continue
        name = program or PROGRAMS.get(e["attrs"].get("program"))
        starts = runs.get(name, [])
        i = bisect.bisect_left(starts, e["start_ns"])
        near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)
                and e["start_ns"] - MATCH_NS <= starts[j]
                <= end(e) + MATCH_NS]
        if near:
            d = min(near, key=lambda s: abs(s - e["start_ns"]))
            out.append(d - e["start_ns"])
    return out


def host_lead_ns(events: List[dict], device: int = 0,
                 program: Optional[str] = None) -> Optional[dict]:
    """:func:`leads_ns` summarised over the window: ``n``, ``min``,
    ``median``, ``max``.  A device cannot start a program before the
    host launches it, so a negative ``min`` says the device's plane
    reads at least that much early; ``shift_ns`` is the least shift of
    the device's events that puts every program after its launch."""
    lead = leads_ns(events, device, program)
    if not lead:
        return None
    return {"n": len(lead), "min": min(lead),
            "median": statistics.median(lead), "max": max(lead),
            "shift_ns": max(0.0, -min(lead))}
