"""Per-kernel validation: Pallas (interpret mode) and the streaming jnp
ref, both against the naive oracle — shape/dtype sweeps + gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_attention import kernel as dec_kernel
from repro.kernels.decode_attention import ref as dec_ref
from repro.kernels.flash_attention import ref as fa_ref

SHAPES = [
    # b, sq, skv, h, hkv, d, causal
    (2, 128, 128, 4, 2, 64, True),
    (1, 100, 100, 4, 4, 32, True),      # ragged (padding paths)
    (2, 64, 192, 6, 2, 32, False),      # cross-attention shape
    (1, 48, 48, 8, 1, 16, True),        # MQA
    (1, 33, 65, 2, 2, 128, True),       # odd sizes, offset
]


def _mk(b, sq, skv, h, hkv, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, sq, h, d), dtype),
            jax.random.normal(ks[1], (b, skv, hkv, d), dtype),
            jax.random.normal(ks[2], (b, skv, hkv, d), dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_fwd_matches_oracle(shape, dtype):
    b, sq, skv, h, hkv, d, causal = shape
    q, k, v = _mk(b, sq, skv, h, hkv, d, dtype)
    qo = skv - sq
    ref = fa_ref.naive(q, k, v, causal=causal, q_offset=qo)
    out = ops.flash_attention(q, k, v, causal=causal, q_offset=qo,
                              impl="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_flash_pallas_grads_match_oracle(shape):
    b, sq, skv, h, hkv, d, causal = shape
    q, k, v = _mk(b, sq, skv, h, hkv, d, jnp.float32)
    qo = skv - sq

    def f_ref(q, k, v):
        return (fa_ref.naive(q, k, v, causal=causal, q_offset=qo) ** 2).sum()

    def f_pal(q, k, v):
        return (ops.flash_attention(q, k, v, causal=causal, q_offset=qo,
                                    impl="interpret") ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(f_pal, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", SHAPES)
def test_flash_ref_chunked_matches_oracle(shape):
    b, sq, skv, h, hkv, d, causal = shape
    q, k, v = _mk(b, sq, skv, h, hkv, d, jnp.float32)
    qo = skv - sq
    ref = fa_ref.naive(q, k, v, causal=causal, q_offset=qo)
    out = fa_ref.chunked(q, k, v, causal=causal, q_offset=qo, block_kv=37)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("smax,fill", [(96, 96), (96, 40), (64, 1)])
@pytest.mark.parametrize("h,hkv", [(4, 2), (8, 8), (8, 1)])
def test_decode_pallas_matches_oracle(smax, fill, h, hkv):
    b, d = 2, 32
    q, k, v = _mk(b, 1, smax, h, hkv, d, jnp.float32)
    ref = dec_ref.decode_ref(q, k, v, fill)
    out = ops.decode_attention(q, k, v, fill, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _contiguous(pool, bt):
    """Each block-table row's pages, token-major: (B, maxp * page, hkv,
    d)."""
    rows = []
    for r in range(bt.shape[0]):
        pages = [np.asarray(pool[int(p)]).transpose(1, 0, 2)
                 for p in np.asarray(bt[r])]
        rows.append(np.concatenate(pages, axis=0))
    return jnp.asarray(np.stack(rows))


def _mk_paged(b, n_pages, page, maxp, hkv, h, d, fills, seed=0):
    """Random (n_pages, hkv, page, d) pool + a block table whose rows
    own disjoint pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(ks[0], (n_pages, hkv, page, d), jnp.float32)
    vp = jax.random.normal(ks[1], (n_pages, hkv, page, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, 1, h, d), jnp.float32)
    bt = np.zeros((b, maxp), np.int32)
    nxt = 1                                 # page 0 is the null page
    for r, fill in enumerate(fills):
        for j in range(-(-fill // page)):
            bt[r, j] = nxt
            nxt += 1
    assert nxt <= n_pages
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(fills, jnp.int32)


@pytest.mark.parametrize("h,hkv", [(4, 2), (8, 8), (8, 1)])
def test_paged_decode_ref_matches_gathered_oracle(h, hkv):
    """The paged ref == contiguous oracle over the gathered pages."""
    b, page, maxp, d = 3, 16, 4, 32
    q, kp, vp, bt, fills = _mk_paged(b, 16, page, maxp, hkv, h, d,
                                     fills=[64, 33, 1])
    out = dec_ref.paged_decode_ref(q, kp, vp, bt, fills)
    k = _contiguous(kp, bt)
    v = _contiguous(vp, bt)
    for r in range(b):
        ref = dec_ref.decode_ref(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                 int(fills[r]))
        np.testing.assert_array_equal(np.asarray(out[r:r + 1]),
                                      np.asarray(ref))


def _edge_fills(page, hkv, d, maxp):
    """Fills at the paged decode kernel's edges: one key, one page less
    one, exactly one block, one block plus one, a full slot."""
    bk = page * dec_kernel.pages_per_block(page, hkv, d, 4, maxp)
    return [1, page - 1, bk, bk + 1, maxp * page]


@pytest.mark.parametrize("h,hkv,d", [
    pytest.param(4, 2, 32, id="4-2"),       # d off the 128 lanes: by page
    pytest.param(8, 8, 32, id="8-8"),
    *[pytest.param(g * hkv, hkv, 128, id=f"g{g}-hkv{hkv}")
      for g in (1, 8, 16) for hkv in (1, 2, 4)],
])
def test_paged_decode_pallas_matches_ref(h, hkv, d):
    """Every edge fill in one batch, slots more than a block long, and a
    last slot of length 1 on the null page (a slot mid-prefill in the
    mixed step)."""
    page = 16
    maxp = 2 * dec_kernel.pages_per_block(page, hkv, d, 4, 1 << 20) + 1
    fills = _edge_fills(page, hkv, d, maxp) + [1]
    n_pages = sum(-(-f // page) for f in fills) + 1
    q, kp, vp, bt, fills = _mk_paged(len(fills), n_pages, page, maxp, hkv,
                                     h, d, fills=fills)
    bt = bt.at[-1].set(0)
    ref = dec_ref.paged_decode_ref(q, kp, vp, bt, fills)
    out = ops.paged_decode_attention(q, kp, vp, bt, fills,
                                     impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_block_walk_under_tpu_interpreter():
    """The TPU interpreter fills scratch with NaN and lands each DMA only
    at its wait: the block walk reads no row it did not copy and no
    buffer before its copy has landed."""
    from jax.experimental.pallas import tpu as pltpu
    page, hkv, d, g = 16, 2, 128, 8
    maxp = 2 * dec_kernel.pages_per_block(page, hkv, d, 4, 1 << 20) + 1
    fills = _edge_fills(page, hkv, d, maxp)
    n_pages = sum(-(-f // page) for f in fills) + 1
    q, kp, vp, bt, fills = _mk_paged(len(fills), n_pages, page, maxp, hkv,
                                     g * hkv, d, fills=fills)
    ref = dec_ref.paged_decode_ref(q, kp, vp, bt, fills)
    out = dec_kernel.paged_decode_attention_kernel(
        q, kp, vp, bt, fills, interpret=pltpu.InterpretParams())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,hkv,start,valid",
                         [(4, 2, 0, 8), (4, 2, 8, 8), (8, 8, 5, 3),
                          (8, 1, 13, 8)])
def test_paged_prefill_ref_matches_whole_prompt_oracle(h, hkv, start,
                                                       valid):
    """A chunk written at positions start..start+valid attends exactly
    like the same rows of a whole-(prefix+chunk) flash pass over the
    gathered pages."""
    from repro.kernels.flash_attention import ref as fl_ref
    b, page, maxp, d, chunk = 2, 8, 4, 32, 8
    total = start + chunk
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    kp = jax.random.normal(ks[0], (16, hkv, page, d), jnp.float32)
    vp = jax.random.normal(ks[1], (16, hkv, page, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, chunk, h, d), jnp.float32)
    bt = np.zeros((b, maxp), np.int32)
    nxt = 1
    for r in range(b):
        for j in range(-(-total // page)):
            bt[r, j] = nxt
            nxt += 1
    bt = jnp.asarray(bt)
    starts = jnp.full((b,), start, jnp.int32)
    n_valid = jnp.full((b,), valid, jnp.int32)
    out = dec_ref.paged_prefill_ref(q, kp, vp, bt, starts, n_valid)
    kg = _contiguous(kp, bt)[:, :total]
    vg = _contiguous(vp, bt)[:, :total]
    # oracle: full causal flash over [0, total) with the chunk's q rows
    qf = jnp.zeros((b, total, h, d), jnp.float32)
    qf = qf.at[:, start:].set(q)
    oracle = fl_ref.chunked(qf, kg, vg)[:, start:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_pallas_matches_ref():
    b, page, maxp, d, h, hkv, chunk = 2, 8, 4, 32, 4, 2, 8
    start, valid = 5, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    kp = jax.random.normal(ks[0], (16, hkv, page, d), jnp.float32)
    vp = jax.random.normal(ks[1], (16, hkv, page, d), jnp.float32)
    q = jax.random.normal(ks[2], (b, chunk, h, d), jnp.float32)
    bt = np.zeros((b, maxp), np.int32)
    nxt = 1
    for r in range(b):
        for j in range(-(-(start + chunk) // page)):
            bt[r, j] = nxt
            nxt += 1
    bt = jnp.asarray(bt)
    starts = jnp.full((b,), start, jnp.int32)
    n_valid = jnp.full((b,), valid, jnp.int32)
    ref = dec_ref.paged_prefill_ref(q, kp, vp, bt, starts, n_valid)
    out = ops.paged_prefill_attention(q, kp, vp, bt, starts, n_valid,
                                      impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows,d", [(8, 64), (100, 128), (256, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_matches_oracle(rows, d, dtype):
    from repro.kernels.rmsnorm import ref as rn_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (2, rows, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,), dtype)
    ref = rn_ref.rmsnorm_ref(x, w)
    out = ops.rmsnorm(x, w, impl="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_pallas_gradients_match_ref():
    """The kernel trains: its custom VJP gives the ref's gradients."""
    from repro.kernels.rmsnorm import ref as rn_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)

    def loss(fn):
        return lambda x_, w_: (fn(x_, w_) ** 2).sum()
    want = jax.grad(loss(rn_ref.rmsnorm_ref), argnums=(0, 1))(x, w)
    got = jax.grad(loss(lambda a, b: ops.rmsnorm(a, b, impl="interpret")),
                   argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_decode_kernel_consistent_with_flash_prefill():
    """Last-token flash output == decode kernel on the same cache."""
    b, s, h, hkv, d = 1, 64, 4, 2, 32
    q, k, v = _mk(b, s, s, h, hkv, d, jnp.float32)
    full = fa_ref.naive(q, k, v, causal=True)
    dec = ops.decode_attention(q[:, -1:], k, v, s, impl="interpret")
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("e,t,d,f", [(4, 8, 16, 32), (2, 100, 64, 48),
                                     (8, 16, 130, 70)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_pallas_matches_oracle(e, t, d, f, dtype):
    from repro.kernels.moe_gemm import ref as mg_ref
    from repro.kernels.moe_gemm.ops import moe_gemm
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (e, t, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    ref = mg_ref.moe_gemm_ref(x, w)
    out = moe_gemm(x, w, interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * d)
