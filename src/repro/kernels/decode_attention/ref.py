"""Pure-jnp oracle for single-token decode attention over a KV cache.

q: (B, 1, H, D); k, v: (B, S_max, Hkv, D); cache_len: scalar int —
positions >= cache_len are masked out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_ref(q, k, v, cache_len, *, scale=None):
    b, sq, h, d = q.shape
    _, smax, hkv, _ = k.shape
    g = h // hkv
    scale = scale or d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg,
                        k.astype(jnp.float32)) * scale
    pos = jnp.arange(smax)
    valid = (pos[None] < jnp.reshape(cache_len, (-1,))[:, None])
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def gather_pages(pages, block_table):
    """(P, Hkv, page, D) pool + (B, maxp) block table -> the slots'
    contiguous (B, maxp * page, Hkv, D) caches."""
    b, maxp = block_table.shape
    _, hkv, page, d = pages.shape
    return pages[block_table].transpose(0, 1, 3, 2, 4).reshape(
        b, maxp * page, hkv, d)


def paged_decode_ref(q, k_pages, v_pages, block_table, lengths, *,
                     scale=None):
    """Gather-then-attend oracle for the paged layout.

    q: (B, 1, H, D); k_pages, v_pages: (P, Hkv, page_size, D);
    block_table: (B, pages_per_slot) int32; lengths: (B,) valid tokens.
    The gather materializes each slot's pages as a contiguous
    (B, pages_per_slot * page_size) cache, so this is bit-identical to
    ``decode_ref`` over the equivalent contiguous layout.
    """
    k = gather_pages(k_pages, block_table)
    v = gather_pages(v_pages, block_table)
    return decode_ref(q, k, v, lengths, scale=scale)


def paged_prefill_ref(q, k_pages, v_pages, block_table, start, n_valid, *,
                      scale=None, block_kv=1024):
    """Gather-then-attend oracle for a chunk of prompt positions.

    q: (B, C, H, D) — the chunk's query rows at absolute positions
    ``start[b] + j``; the chunk's own KV must already be written into
    the pages.  Row ``j >= n_valid[b]`` is padding: its output is
    garbage (it attends whatever the causal window holds) and callers
    discard it.

    The math replays ``flash_attention.ref._fwd`` exactly — same GQA
    head repeat, same block scan, running max/normalizer, same
    ``p @ v`` accumulation dtype — with a per-row causal limit of
    ``start + j``, so a chunked prefill is bit-identical to the legacy
    whole-prompt flash prefill over the same positions (masked-out
    positions contribute exact zeros).
    """
    from repro import flags
    b, sq, h, d = q.shape
    _, hkv, page, _ = k_pages.shape
    maxp = block_table.shape[1]
    g = h // hkv
    scale = scale or d ** -0.5
    k = gather_pages(k_pages, block_table)
    v = gather_pages(v_pages, block_table)
    if g > 1:                      # mirror flash ref's GQA repeat
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    skv = maxp * page
    bs = min(flags.inner_blocks(skv, block_kv), skv)
    pad = (-skv) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (skv + pad) // bs
    kb = k.reshape(b, nb, bs, h, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, bs, h, d).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(b, sq, h, 1, d)
    qpos = jnp.reshape(start, (-1, 1)) + jnp.arange(sq)[None, :]  # (B, C)
    F32 = jnp.float32
    m0 = jnp.full((b, sq, h, 1), NEG_INF, F32)
    l0 = jnp.zeros((b, sq, h, 1), F32)
    a0 = jnp.zeros((b, sq, h, 1, d), F32)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, i = inp
        logits = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kblk,
                            preferred_element_type=F32) * scale
        kpos = i * bs + jnp.arange(bs)
        valid = ((kpos[None, None, :] < skv)
                 & (kpos[None, None, :] <= qpos[:, :, None]))
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        mb = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - mb[..., None])
        alpha = jnp.exp(m - mb)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=F32)
        return (mb, l, acc), None

    (_, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))
    l = jnp.maximum(l, 1e-30)
    del n_valid                    # padding rows are the caller's problem
    return (acc / l[..., None]).reshape(b, sq, h, d).astype(q.dtype)
