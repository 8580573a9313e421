"""paged_prefill_roofline: the paged prefill kernel's share of its
roofline in the traced ticks.  The work is each computed chunk's causal
attention over its slot's filled pages: FLOPs of its real rows, bytes
of those pages plus the chunk's queries and outputs, by the counts of
the cell's model kind (``rec["counts"]``)."""
from bench.metrics import _roofline

KERNEL = "paged_prefill_attention_kernel"


def work(counts, m, page, tick):
    flops = sum(counts.paged_prefill_flops(m, s, n)
                for s, n in tick["chunks"])
    nbytes = sum(counts.paged_prefill_bytes(m, s, n, page)
                 for s, n in tick["chunks"])
    return flops, nbytes


def read(rec):
    return _roofline.share(rec, KERNEL, work)
