"""host_gap_ms: mean time per engine step, in the traced stretch, during
which the device ran no operation although the host was inside
``Engine.step`` (profiler trace): the host's own share of a tick."""
from bench import trace_reduce


def read(rec):
    ev = rec["trace"]
    if not ev or not trace_reduce.devices(ev):
        return None
    idle = trace_reduce.step_idle_ns(ev, trace_reduce.devices(ev)[0])
    if not idle:
        return None
    return sum(idle.values()) / len(idle) / 1e6
