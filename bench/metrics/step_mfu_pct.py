"""step_mfu_pct: model FLOPs of the work the window's ticks did - every
prompt token computed and every token decoded, attention over its
actual context - over the window's length times the bf16 peak of the
chips that served it.  The FLOPs are the benchmark's own counts of the
cell's model kind (``rec["counts"]``, as its reference names them)."""


def read(rec):
    counts, m, end = rec["counts"], rec["model"], rec["window_s"]
    flops = 0
    for t in rec["ticks"]:
        if t["t1"] > end:
            break
        flops += sum(counts.token_flops(m, c) for c in t["decode"])
        flops += sum(counts.chunk_flops(m, s, n) for s, n in t["chunks"])
    if not flops:
        return None
    return 100.0 * flops / (end * rec["chips"]
                            * rec["peaks"]["bf16_flops_per_s"])
