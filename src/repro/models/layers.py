"""Core transformer layers: norms, RoPE, GQA attention, MLPs, embeddings.

Each layer exposes ``*_defs(cfg)`` (a PDef tree — the schema) and
``*_apply(cfg, params, ...)`` (the math).  Logical axis names used here
are resolved to mesh axes by ``dist/sharding.py``:

  embed     — model dim of weights        (fsdp: -> data)
  heads     — q-head dim of weights       (tp:   -> model)
  kv_heads  — kv-head dim of weights      (tp:   -> model if divisible)
  ff        — mlp inner dim               (tp:   -> model)
  vocab     — embedding/logit vocab dim   (tp:   -> model)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.actsharding import constrain
from repro.kernels import ops
from repro.models.params import PDef


class PagedView(NamedTuple):
    """Block-table view over a paged KV pool (built by repro/serve).

    With a ``PagedView``, decode attention reads per-request pages out
    of a shared ``(n_pages, kv, page_size, hd)`` pool instead of one
    contiguous ``(batch, seq)`` cache; ``lengths`` doubles as the
    per-slot write position for the incoming token(s).

    Chunked prefill (s > 1) additionally sets ``n_valid`` — how many of
    the s incoming rows are real prompt tokens — and ``null_page``, the
    page id that absorbs the padding rows' KV writes (pad positions may
    fall past the slot's reserved pages, so their destination must be
    forced to the null page rather than left to index clamping, which
    would corrupt the slot's last real page).
    """

    block_table: jax.Array      # (n_slots, pages_per_slot) int32 page ids
    lengths: jax.Array          # (n_slots,) int32 filled tokens per slot
    n_valid: Optional[jax.Array] = None    # (B,) real rows per chunk
    null_page: Optional[jax.Array] = None  # scalar int32 pad sink page

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def norm_defs(cfg: ModelConfig):
    d = {"scale": PDef((cfg.d_model,), (None,), init="ones")}
    if cfg.norm_type == "layernorm":
        d["bias"] = PDef((cfg.d_model,), (None,), init="zeros")
    return d


def norm_apply(cfg: ModelConfig, p, x):
    if cfg.norm_type == "layernorm":
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) / jnp.sqrt(var + cfg.norm_eps)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)
    return ops.rmsnorm(x, p["scale"], eps=cfg.norm_eps)


# --------------------------------------------------------------------------
# Rotary embeddings (full / partial "2d" fraction)
# --------------------------------------------------------------------------


def apply_rope(x, positions, theta: float, fraction: float = 1.0):
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        ang = ang[None, :, None, :]                       # 1 S 1 half
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs
        ang = ang[:, :, None, :]                          # B S 1 half
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


def sinusoidal_positions(seq_len: int, d_model: int, offset=0):
    pos = jnp.arange(seq_len) + offset
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half) * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# Attention (GQA, optional cross-attention / cache)
# --------------------------------------------------------------------------


def attention_defs(cfg: ModelConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    defs = {
        "wq": PDef((d, h * hd), ("embed", "heads")),
        "wk": PDef((d, kv * hd), ("embed", "kv_heads")),
        "wv": PDef((d, kv * hd), ("embed", "kv_heads")),
        "wo": PDef((h * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = PDef((h * hd,), ("heads",), init="zeros")
        defs["bk"] = PDef((kv * hd,), ("kv_heads",), init="zeros")
        defs["bv"] = PDef((kv * hd,), ("kv_heads",), init="zeros")
    return defs


def _project_qkv(cfg, p, x, kv_input=None):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_in = x if kv_input is None else kv_input
    skv = kv_in.shape[1]
    q = x @ p["wq"].astype(x.dtype)
    k = kv_in @ p["wk"].astype(x.dtype)
    v = kv_in @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (q.reshape(b, s, h, hd), k.reshape(b, skv, kv, hd),
            v.reshape(b, skv, kv, hd))


def attention_apply(cfg: ModelConfig, p, x, *, positions=None, causal=True,
                    cache=None, cache_index=None, cross_kv=None,
                    paging: Optional[PagedView] = None):
    """Self- or cross-attention.

    cache: dict(k=(B,Smax,KV,hd), v=...) for decode; ``cache_index`` is the
    scalar write position.  With ``paging`` the cache leaves are instead
    page pools ``(n_pages, KV, page_size, hd)`` shared by all slots, and
    the write position is per-row (``paging.lengths``).  cross_kv:
    precomputed (k, v) from the encoder.  Returns (out, new_cache_kv | None).
    """
    b, s, _ = x.shape
    if cross_kv is not None:
        from repro.dist.actsharding import model_axis_divides
        k_full, v_full = cross_kv
        q = (x @ p["wq"].astype(x.dtype)).reshape(
            b, s, cfg.n_heads, cfg.head_dim)
        if model_axis_divides(cfg.n_heads) or s == 1:
            q = constrain(q, "act_batch", None, "act_heads", None)
        else:
            q = constrain(q, "act_batch", "act_seq_force", None, None)
        if positions is not None and cfg.pos_type == "rope":
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        out = ops.flash_attention(q, k_full, v_full, causal=False)
        out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
        return out, None

    q, k, v = _project_qkv(cfg, p, x)
    from repro.dist.actsharding import model_axis_divides
    if model_axis_divides(cfg.n_heads) or s == 1:
        q = constrain(q, "act_batch", None, "act_heads", None)
    else:
        # heads unshardable on this mesh: shard attention over q-sequence
        q = constrain(q, "act_batch", "act_seq_force", None, None)
    k = constrain(k, "act_batch", None, "act_kv", None)
    v = constrain(v, "act_batch", None, "act_kv", None)
    if cfg.pos_type == "rope":
        assert positions is not None
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    if cache is None:                                   # train / prefill
        out = ops.flash_attention(q, k, v, causal=causal)
        if model_axis_divides(cfg.n_heads) or s == 1:
            out = constrain(out, "act_batch", None, "act_heads", None)
        else:
            out = constrain(out, "act_batch", "act_seq_force", None, None)
        new_kv = (k, v)
    elif paging is not None and s == 1:                 # paged decode
        page_size = cache["k"].shape[2]
        pos = paging.lengths                                       # (B,)
        page = paging.block_table[jnp.arange(b), pos // page_size]
        off = pos % page_size
        ck = cache["k"].at[page, :, off].set(
            k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[page, :, off].set(
            v[:, 0].astype(cache["v"].dtype))
        ck = constrain(ck, None, "act_kv", None, None)
        cv = constrain(cv, None, "act_kv", None, None)
        out = ops.paged_decode_attention(q, ck, cv, paging.block_table,
                                         pos + 1)
        new_kv = (ck, cv)
    elif paging is not None:                            # paged chunk prefill
        page_size = cache["k"].shape[2]
        maxp = paging.block_table.shape[1]
        pos = paging.lengths                                       # (B,)
        offs = pos[:, None] + jnp.arange(s)[None, :]               # (B, s)
        valid = jnp.arange(s)[None, :] < paging.n_valid[:, None]
        page = paging.block_table[jnp.arange(b)[:, None],
                                  jnp.minimum(offs // page_size, maxp - 1)]
        # pad rows sink into the null page (their offs may point past
        # the slot's reserved pages — never let them clamp onto a real
        # page and corrupt prompt KV)
        page = jnp.where(valid, page, paging.null_page)
        # (B, s) page and offset indices around the kv slice: the
        # indexed view is (B, s, KV, hd), the layout of k and v
        ck = cache["k"].at[page, :, offs % page_size].set(
            k.astype(cache["k"].dtype))
        cv = cache["v"].at[page, :, offs % page_size].set(
            v.astype(cache["v"].dtype))
        ck = constrain(ck, None, "act_kv", None, None)
        cv = constrain(cv, None, "act_kv", None, None)
        out = ops.paged_prefill_attention(q, ck, cv, paging.block_table,
                                          pos, paging.n_valid)
        new_kv = (ck, cv)
    else:                                               # decode: s == 1
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(
            cache["k"].dtype), cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(
            cache["v"].dtype), cache_index, axis=1)
        ck = constrain(ck, "act_batch", "act_kv_seq", None, None)
        cv = constrain(cv, "act_batch", "act_kv_seq", None, None)
        out = ops.decode_attention(q, ck, cv, cache_index + 1)
        new_kv = (ck, cv)
    out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
    return out, new_kv


# --------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# --------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    defs = {"w_in": PDef((d, f), ("embed", "ff")),
            "w_out": PDef((f, d), ("ff", "embed"))}
    if cfg.mlp_type == "swiglu":
        defs["w_gate"] = PDef((d, f), ("embed", "ff"))
    return defs


def mlp_apply(cfg: ModelConfig, p, x):
    h = x @ p["w_in"].astype(x.dtype)
    h = constrain(h, "act_batch", None, "act_ff")
    if cfg.mlp_type == "swiglu":
        g = x @ p["w_gate"].astype(x.dtype)
        g = constrain(g, "act_batch", None, "act_ff")
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return h @ p["w_out"].astype(x.dtype)


# --------------------------------------------------------------------------
# Embeddings / logits
# --------------------------------------------------------------------------


def embed_defs(cfg: ModelConfig):
    defs = {"tok": PDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                        init="normal", scale=0.02)}
    if not cfg.tie_embeddings:
        defs["head"] = PDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    defs["final_norm"] = norm_defs(cfg)
    return defs


def embed_apply(cfg: ModelConfig, p, tokens, dtype, offset=0):
    x = jnp.take(p["tok"], tokens, axis=0).astype(dtype)
    if cfg.pos_type == "sinusoidal":
        s = tokens.shape[1]
        x = x + sinusoidal_positions(s, cfg.d_model, offset).astype(dtype)[None]
    return x


def logits_apply(cfg: ModelConfig, p, x):
    x = norm_apply(cfg, p["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ p["tok"].T.astype(x.dtype)
    else:
        logits = x @ p["head"].astype(x.dtype)
    return constrain(logits, "act_batch", None, "act_vocab")
