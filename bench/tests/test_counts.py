"""The benchmark's own operation and byte counts, by hand arithmetic."""
import json

import _paths  # noqa: F401
import pytest

from bench import counts

CONFIGS = _paths.ROOT / "bench" / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name, total", [
    # yi: 2 x 64000 x 4096 embed+head; 32 x (q 4096^2 + k,v 2 x 4096 x 512
    # + o 4096^2 + 3 x 4096 x 11008 + 2 norms); final norm
    ("yi-6b", 524_288_000 + 32 * 173_023_232 + 4096),
    # chatglm3: 2 x 65024 x 4096; 28 x (q + k,v 2 x 4096 x 256 + o
    # + 3 x 4096 x 13696 + qkv bias 4096 + 512 + 2 norms); final norm
    ("chatglm3-6b", 532_676_608 + 28 * 203_960_832 + 4096),
])
def test_param_counts(name, total):
    assert counts.n_params(model(name)) == total
    assert round(total / 1e9, 3) == {"yi-6b": 6.061,
                                     "chatglm3-6b": 6.244}[name]


@pytest.mark.parametrize("name, kib", [("yi-6b", 64), ("chatglm3-6b", 28)])
def test_kv_bytes_per_token(name, kib):
    # layers x (K, V) x kv heads x 128 x 2 bytes
    assert counts.kv_bytes_per_token(model(name)) == kib * 1024


def test_paged_decode_bytes_count_filled_pages_only():
    m = model("yi-6b")
    # keys 1, 16, 17, 100 at page 16 fill 1 + 1 + 2 + 7 = 11 pages; a page
    # is 32 layers x (K, V) x 4 kv heads x 16 x 128 x 2 bytes = 1 MiB
    assert counts.paged_decode_bytes(m, [1, 16, 17, 100], 16) \
        == 11 * 1_048_576
    assert counts.paged_decode_bytes(m, [], 16) == 0
    # 2048 keys: the whole slot, 128 pages
    assert counts.paged_decode_bytes(m, [2048], 16) == 128 * 1_048_576


def test_token_flops():
    m = model("yi-6b")
    # 32 x (q, k, v, o, gate, up, down) + the output head
    matmul = 32 * 173_015_040 + 4096 * 64000
    assert counts.matmul_params(m) == matmul
    # 2 FLOPs a weight, plus 4 x layers x heads x 128 x keys of attention
    assert counts.token_flops(m, 1000) \
        == 2 * matmul + 4 * 32 * 32 * 128 * 1000


def test_chunk_flops_are_the_tokens_of_the_chunk():
    m = model("chatglm3-6b")
    start, n = 300, 256
    per_token = sum(counts.token_flops(m, start + j + 1) for j in range(n))
    assert counts.chunk_flops(m, start, n) == per_token
    assert counts.paged_prefill_flops(m, start, n) == sum(
        counts.attn_flops(m, start + j + 1) for j in range(n))


def test_prefill_bytes_and_roofline():
    m = model("yi-6b")
    # chunk of 100 rows at 300: pages of 400 keys (25), queries and outputs
    page_bytes = 32 * 2 * 4 * 16 * 128 * 2
    qo = 32 * 2 * 100 * 32 * 128 * 2
    assert counts.paged_prefill_bytes(m, 300, 100, 16) \
        == 25 * page_bytes + qo
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(300.0, 20.0, peaks) == 3.0    # compute bound
    assert counts.roofline_s(100.0, 50.0, peaks) == 5.0    # memory bound
