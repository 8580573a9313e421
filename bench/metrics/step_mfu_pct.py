"""step_mfu_pct: model FLOPs of the work the window's ticks did - every
prompt token consumed and every token decoded, attention over its
actual context - over the window's length times the chip's bf16 peak.
The FLOPs are the benchmark's own counts (``counts.py``)."""
from bench import counts


def read(rec):
    m, end = rec["model"], rec["window_s"]
    flops = 0
    for t in rec["ticks"]:
        if t["t1"] > end:
            break
        flops += sum(counts.token_flops(m, c) for c in t["decode"])
        flops += sum(counts.chunk_flops(m, s, n) for s, n in t["chunks"])
    if not flops:
        return None
    return 100.0 * flops / (end * rec["peaks"]["bf16_flops_per_s"])
