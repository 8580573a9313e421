"""Operations and bytes a dense GQA transformer needs, from its sizes.

These are the benchmark's own counts: they depend on the configuration
and on the work the traffic asked for, never on how the program under
test compiles it, so a program change cannot move them.  ``m`` is the
``model`` group of a configuration file (``configs/<name>.json``).
"""
from __future__ import annotations

BF16 = 2                       # bytes per weight and per cached value


def layer_params(m: dict) -> int:
    d, h, kv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if m["qkv_bias"]:
        attn += h * hd + 2 * kv * hd
    mlp = 3 * d * ff                                  # gate, up, down
    norms = 2 * d
    return attn + mlp + norms


def n_params(m: dict) -> int:
    """Every weight, embedding and output head included."""
    embed = m["vocab_size"] * m["d_model"]
    head = 0 if m["tie_embeddings"] else embed
    return embed + head + m["n_layers"] * layer_params(m) + m["d_model"]


def matmul_params(m: dict) -> int:
    """Weights that take part in a matrix product for each token: every
    layer's projections and the output head (the embedding is a lookup,
    norms and biases are elementwise)."""
    d, h, kv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return m["n_layers"] * per_layer + m["d_model"] * m["vocab_size"]


def kv_bytes_per_token(m: dict) -> int:
    """Keys and values of one position in every layer, bf16."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def attn_flops(m: dict, keys: int, rows: int = 1) -> int:
    """Score and value products of ``rows`` queries against ``keys``
    keys each, all layers (2 FLOPs a multiply-add, two products)."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys * rows


def token_flops(m: dict, keys: int) -> int:
    """Model FLOPs of one token that attends to ``keys`` positions
    (itself included)."""
    return 2 * matmul_params(m) + attn_flops(m, keys)


def chunk_flops(m: dict, start: int, n: int) -> int:
    """Model FLOPs of ``n`` prompt tokens at positions start..start+n-1,
    each attending causally to every position up to its own."""
    keys = n * start + n * (n + 1) // 2                # sum of (start+j+1)
    return 2 * matmul_params(m) * n + attn_flops(m, keys)


def pages(keys: int, page: int) -> int:
    return -(-keys // page)


def paged_decode_bytes(m: dict, contexts, page: int) -> int:
    """HBM bytes the paged decode kernel must read for one tick, all
    layers: the filled pages of K and V of every decoding slot, where
    ``contexts`` lists each slot's key count (its new token included)."""
    per_page = m["n_layers"] * 2 * m["n_kv_heads"] * page * m["head_dim"] \
        * BF16
    return per_page * sum(pages(c, page) for c in contexts)


def paged_decode_flops(m: dict, contexts) -> int:
    return sum(attn_flops(m, c) for c in contexts)


def paged_prefill_bytes(m: dict, start: int, n: int, page: int) -> int:
    """One chunk's attention, all layers: the slot's filled pages of K
    and V read once, plus the chunk's queries read and outputs written."""
    per_page = m["n_layers"] * 2 * m["n_kv_heads"] * page * m["head_dim"] \
        * BF16
    qo = m["n_layers"] * 2 * n * m["n_heads"] * m["head_dim"] * BF16
    return per_page * pages(start + n, page) + qo


def paged_prefill_flops(m: dict, start: int, n: int) -> int:
    return attn_flops(m, n * start + n * (n + 1) // 2)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
