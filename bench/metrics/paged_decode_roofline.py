"""paged_decode_roofline: the paged decode kernel's share of its
roofline in the traced ticks.  The work is what the decoding slots
need: the filled pages of their K and V (bytes) and their score and
value products (FLOPs), from each slot's key count at that tick, by
the counts of the cell's model kind (``rec["counts"]``)."""
from bench.metrics import _roofline

KERNEL = "paged_decode_attention_kernel"


def work(counts, m, page, tick):
    return (counts.paged_decode_flops(m, tick["decode"]),
            counts.paged_decode_bytes(m, tick["decode"], page))


def read(rec):
    return _roofline.share(rec, KERNEL, work)
