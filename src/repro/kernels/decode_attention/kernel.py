"""Pallas TPU flash-decode: one query token vs a long KV cache.

Grid (batch, kv_head, kv_blocks): the g query heads sharing a kv head
are processed together as a (g, d) tile (they read the same KV block —
one HBM stream serves g heads, the decode-bandwidth optimization that
matters at 32k-512k contexts).  Running max/normalizer live in VMEM
scratch across the kv sweep; positions >= cache_len are masked, and
whole blocks past cache_len are skipped (@pl.when) so decode cost
scales with the FILLED cache, not the allocated buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BKV = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, bkv, g):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    cache_len = len_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * bkv < cache_len)          # skip blocks past the fill
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # g x d
        k = k_ref[0, 0].astype(jnp.float32)            # bkv x d
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bkv + jax.lax.broadcasted_iota(
            jnp.int32, (g, bkv), 1)
        s = jnp.where(kpos < cache_len, s, NEG_INF)    # g x bkv
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bkv", "interpret"))
def decode_attention_kernel(q, k, v, cache_len, *, scale=None,
                            bkv=DEFAULT_BKV, interpret=False):
    """q: (B, 1, H, D); k, v: (B, S, Hkv, D); cache_len: scalar int."""
    b, one, h, d = q.shape
    _, smax, hkv, _ = k.shape
    g = h // hkv
    scale = scale or d ** -0.5
    bkv = min(bkv, smax)
    pk = (-smax) % bkv
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    kp = kp.transpose(0, 2, 1, 3)                       # B Hkv S D
    vp = vp.transpose(0, 2, 1, 3)
    qg = q[:, 0].reshape(b, hkv, g, d)                  # B Hkv g D
    nk = kp.shape[2] // bkv
    lens = jnp.full((1,), cache_len, jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bkv=bkv, g=g),
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b_, hk, ki: (b_, hk, 0, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, ki:
                         (b_, hk, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d), lambda b_, hk, ki:
                         (b_, hk, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, hk, ki:
                               (b_, hk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
        interpret=interpret,
    )(lens, qg, kp, vp)
    return out.reshape(b, 1, h, d)


# --------------------------------------------------------------------------
# Paged variant: the KV sweep walks the slot's block table instead of a
# contiguous cache.  Scalar-prefetched block tables let the BlockSpec
# index maps DMA exactly the pages the slot owns — decode reads scale
# with the FILLED pages, and no gather materializes the cache in HBM.
# --------------------------------------------------------------------------


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, page, g):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    np_ = pl.num_programs(2)
    length = len_ref[bi]

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(pi * page < length)            # skip unfilled pages
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # g x d
        k = k_ref[0, 0].astype(jnp.float32)            # page x d
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = pi * page + jax.lax.broadcasted_iota(
            jnp.int32, (g, page), 1)
        s = jnp.where(kpos < length, s, NEG_INF)       # g x page
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_kernel(q, k_pages, v_pages, block_table,
                                  lengths, *, scale=None, interpret=False):
    """q: (B, 1, H, D); k_pages, v_pages: (P, Hkv, page, D) — one
    page of one kv head is a contiguous (page, D) tile, the kernel's
    block; block_table: (B, pages_per_slot) int32; lengths: (B,) int32."""
    b, one, h, d = q.shape
    _, hkv, page, _ = k_pages.shape
    maxp = block_table.shape[1]
    g = h // hkv
    scale = scale or d ** -0.5
    qg = q[:, 0].reshape(b, hkv, g, d)                  # B Hkv g D

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                          # block_table, lengths
        grid=(b, hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b_, hk, pi, bt, ln: (b_, hk, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, hk, pi, bt, ln: (bt[b_, pi], hk, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, hk, pi, bt, ln: (bt[b_, pi], hk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, hk, pi, bt, ln: (b_, hk, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page=page, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(b, 1, h, d)


# --------------------------------------------------------------------------
# Chunked-prefill variant: C query rows of ONE admitting slot attend its
# pages mid-prefill.  Same block-table walk as the decode kernel, but the
# query tile carries all (C, g) rows at once and the causal limit is
# per-row (position start + j), so partially-filled final pages are
# honored: page pi is processed iff pi * page < start + n_valid, and
# inside it keys past each row's own position are masked.
# --------------------------------------------------------------------------


def _paged_prefill_kernel(bt_ref, start_ref, nv_ref, q_ref, k_ref, v_ref,
                          o_ref, m_scr, l_scr, acc_scr, *, scale, page, g,
                          chunk):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    np_ = pl.num_programs(2)
    start = start_ref[bi]
    filled = start + nv_ref[bi]
    rows = chunk * g

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(pi * page < filled)            # skip pages past the fill
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # rows x d
        k = k_ref[0, 0].astype(jnp.float32)            # page x d
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = pi * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 0) // g           # row j*g+h_ -> pos j
        s = jnp.where(kpos <= qpos, s, NEG_INF)        # rows x page
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == np_ - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention_kernel(q, k_pages, v_pages, block_table,
                                   start, n_valid, *, scale=None,
                                   interpret=False):
    """q: (B, C, H, D) chunk queries at positions start..start+C-1;
    k_pages, v_pages: (P, Hkv, page, D) with the chunk's own KV already
    written; block_table: (B, pages_per_slot) int32; start, n_valid:
    (B,) int32.  Rows past ``n_valid`` produce garbage (discarded)."""
    b, chunk, h, d = q.shape
    _, hkv, page, _ = k_pages.shape
    maxp = block_table.shape[1]
    g = h // hkv
    scale = scale or d ** -0.5
    # (B, Hkv, C*g, D): position-major rows so row // g is the position
    qg = q.reshape(b, chunk, hkv, g, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, hkv, chunk * g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # block_table, start, n_valid
        grid=(b, hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, chunk * g, d),
                         lambda b_, hk, pi, bt, st, nv: (b_, hk, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, hk, pi, bt, st, nv:
                         (bt[b_, pi], hk, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, hk, pi, bt, st, nv:
                         (bt[b_, pi], hk, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk * g, d),
                               lambda b_, hk, pi, bt, st, nv:
                               (b_, hk, 0, 0)),
        scratch_shapes=[pltpu.VMEM((chunk * g, 1), jnp.float32),
                        pltpu.VMEM((chunk * g, 1), jnp.float32),
                        pltpu.VMEM((chunk * g, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=scale, page=page,
                          g=g, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, chunk * g, d), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), start.astype(jnp.int32),
      n_valid.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(b, hkv, chunk, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(b, chunk, h, d)
