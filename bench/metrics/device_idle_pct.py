"""device_idle_pct: share of the traced stretch in which no operation
ran on the device (1 - union of operation intervals / window)."""
from bench import trace_reduce


def read(rec):
    ev = rec["trace"]
    if not ev or not trace_reduce.devices(ev):
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ev)
                    / trace_reduce.window_s(ev))
