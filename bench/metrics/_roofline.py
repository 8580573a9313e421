"""Shared arithmetic of the kernels' roofline shares: the least time
the chip could take for the traced ticks' work of one kernel (the
larger of its FLOPs over peak and its bytes over bandwidth, from the
benchmark's own counts of the cell's model kind) over that kernel's
device time in the trace."""
from bench import counts, trace_reduce


def share(rec, kernel: str, work) -> float:
    """``work(counts, model, page, tick) -> (flops, bytes)`` of one
    tick, ``counts`` the record's counts module."""
    ev = rec["trace"]
    if not ev:
        return None
    traced = {e["tick"] for e in trace_reduce.steps(ev)}
    m, page = rec["model"], rec["engine"]["page_size"]
    best = 0.0
    for t in rec["ticks"]:
        if t["tick"] in traced:
            flops, nbytes = work(rec["counts"], m, page, t)
            best += counts.roofline_s(flops, nbytes, rec["peaks"])
    spent = trace_reduce.kernel_s(ev, kernel)
    if not best or not spent:
        return None
    return 100.0 * best / spent
