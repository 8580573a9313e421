"""Block-stack assembly: heterogeneous super-blocks under lax.scan.

The layer stack cycles ``cfg.block_pattern`` (the "super-block");
parameters for each pattern position are stacked over
``cfg.n_repeats`` and the stack runs under one ``jax.lax.scan`` so the
lowered HLO is O(pattern) — not O(n_layers) — which is what makes a
95-layer dry-run compile quickly.  Training wraps the body in
``jax.checkpoint`` (full remat: only super-block inputs are saved).

Caches (decode/prefill) are trees with a leading ``reps`` dim threaded
through the scan as xs/ys.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import flags

from repro.configs.base import ModelConfig
from repro.dist.actsharding import constrain
from repro.models import layers, mamba, moe, xlstm
from repro.models.params import PDef, stack

F32_STATES = ("ssm", "C", "n", "m", "c", "h")   # cache leaves kept fp32


def _pos_has_ffn(cfg: ModelConfig, i: int) -> bool:
    # xLSTM cells are complete blocks; attn/mamba positions carry an FFN.
    return cfg.block_pattern[i] in ("attn", "mamba") and (
        cfg.d_ff > 0 or cfg.moe is not None)


def _pos_is_moe(cfg: ModelConfig, i: int) -> bool:
    return (cfg.moe is not None and _pos_has_ffn(cfg, i)
            and (i % cfg.moe.every) == (cfg.moe.every - 1))


def position_defs(cfg: ModelConfig, i: int, cross: bool = False):
    kind = cfg.block_pattern[i]
    d = {"norm1": layers.norm_defs(cfg)}
    if kind == "attn":
        d["attn"] = layers.attention_defs(cfg)
    elif kind == "mamba":
        d["mamba"] = mamba.mamba_defs(cfg)
    elif kind == "mlstm":
        d["mlstm"] = xlstm.mlstm_defs(cfg)
    elif kind == "slstm":
        d["slstm"] = xlstm.slstm_defs(cfg)
    else:
        raise ValueError(kind)
    if cross:
        d["norm_x"] = layers.norm_defs(cfg)
        d["xattn"] = layers.attention_defs(cfg, cross=True)
    if _pos_has_ffn(cfg, i):
        d["norm2"] = layers.norm_defs(cfg)
        if _pos_is_moe(cfg, i):
            d["moe"] = moe.moe_defs(cfg)
        else:
            d["mlp"] = layers.mlp_defs(cfg)
    return d


def stack_defs(cfg: ModelConfig, cross: bool = False):
    return {f"p{i}": stack(position_defs(cfg, i, cross), cfg.n_repeats)
            for i in range(cfg.pattern_len)}


def encoder_defs(cfg: ModelConfig):
    """Non-causal attention + MLP encoder stack (whisper)."""
    d = {"norm1": layers.norm_defs(cfg),
         "attn": layers.attention_defs(cfg),
         "norm2": layers.norm_defs(cfg),
         "mlp": layers.mlp_defs(cfg)}
    return {"enc": stack(d, cfg.encoder_layers),
            "enc_norm": layers.norm_defs(cfg)}


# --------------------------------------------------------------------------
# Cache schemas (PDef trees; materialized as zeros or ShapeDtypeStruct)
# --------------------------------------------------------------------------


def cache_defs(cfg: ModelConfig, batch: int, seq_len: int,
               enc_len: int = 0, stacked: bool = True):
    """Decode-state schema per pattern position, stacked over reps."""
    r = cfg.n_repeats
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            c = {"k": PDef((batch, seq_len, kv, hd),
                           ("batch", "kv_seq", "kv_heads", None),
                           init="zeros", dtype="bfloat16"),
                 "v": PDef((batch, seq_len, kv, hd),
                           ("batch", "kv_seq", "kv_heads", None),
                           init="zeros", dtype="bfloat16")}
            if cfg.encoder_layers:
                c["xk"] = PDef((batch, enc_len, kv, hd),
                               ("batch", "kv_seq", "kv_heads", None),
                               init="zeros", dtype="bfloat16")
                c["xv"] = PDef((batch, enc_len, kv, hd),
                               ("batch", "kv_seq", "kv_heads", None),
                               init="zeros", dtype="bfloat16")
        elif kind == "mamba":
            sh = mamba.mamba_cache_shape(cfg, batch)
            c = {"conv": PDef(sh["conv"], ("batch", None, "mamba_in"),
                              init="zeros", dtype="bfloat16"),
                 "ssm": PDef(sh["ssm"], ("batch", "mamba_in", None),
                             init="zeros", dtype="float32")}
        elif kind == "mlstm":
            sh = xlstm.mlstm_cache_shape(cfg, batch)
            c = {"conv": PDef(sh["conv"], ("batch", None, "xl_in"),
                              init="zeros", dtype="bfloat16"),
                 "C": PDef(sh["C"], ("batch", "xl_heads", None, None),
                           init="zeros", dtype="float32"),
                 "n": PDef(sh["n"], ("batch", "xl_heads", None),
                           init="zeros", dtype="float32"),
                 "m": PDef(sh["m"], ("batch", "xl_heads"),
                           init="zeros", dtype="float32")}
        elif kind == "slstm":
            sh = xlstm.slstm_cache_shape(cfg, batch)
            c = {k: PDef(v, ("batch", None), init="zeros", dtype="float32")
                 for k, v in sh.items()}
        out[f"p{i}"] = stack(c, r) if stacked else c
    return out


def paged_cache_defs(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, n_shards: int = 1):
    """Paged decode-state schema: attention KV lives in one shared page
    pool per position (``(n_pages, kv, page_size, hd)``, indexed by the
    engine's block table; page 0 is the never-allocated null page), while
    seq-mixer states stay slot-major.  Sharding resolves through the same
    ``cache_rules`` axis names as the contiguous cache.  One page of one
    kv head is a contiguous ``(page_size, hd)`` tile — the block the
    paged Pallas kernels read — and the page axis stays leading, so
    ``pool[:, pages]`` slices whole pages.

    ``n_shards > 1`` marks the page dim with the logical ``pages`` axis
    so the pool shards over the data tier (slot-sharded page shards with
    per-shard free lists and null pages — see ``serve/paging``) instead
    of replicating; the allocator must have been built with the same
    shard count so every slot's pages stay within its own shard.
    """
    assert not cfg.encoder_layers, \
        "paged serving supports decoder-only architectures"
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    pages_ax = "pages" if n_shards > 1 else None
    base = cache_defs(cfg, n_slots, 1, 0, stacked=False)
    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        c = base[f"p{i}"]
        if kind == "attn":
            c = {n: PDef((n_pages, kv, page_size, hd),
                         (pages_ax, "kv_heads", None, None),
                         init="zeros", dtype="bfloat16")
                 for n in ("k", "v")}
        out[f"p{i}"] = stack(c, cfg.n_repeats)
    return out


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _apply_position(cfg, i, p, x, *, positions, cache=None, cache_index=None,
                    enc_out=None, mode="train", paging=None):
    """One pattern position. Returns (x, new_cache, aux)."""
    kind = cfg.block_pattern[i]
    aux = None
    new_cache = {}
    h = layers.norm_apply(cfg, p["norm1"], x)
    if kind == "attn":
        sub = None
        if cache is not None and mode == "decode":
            sub = {"k": cache["k"], "v": cache["v"]}
        out, kvs = layers.attention_apply(
            cfg, p["attn"], h, positions=positions, causal=cfg.causal,
            cache=sub, cache_index=cache_index, paging=paging)
        if kvs is not None and cache is not None:
            new_cache["k"], new_cache["v"] = kvs
        x = x + out
        if cfg.encoder_layers:                     # cross attention
            hx = layers.norm_apply(cfg, p["norm_x"], x)
            if mode == "decode":
                xk, xv = cache["xk"], cache["xv"]
                # cross-KV is static during decode; thread it through the
                # scan so the cache tree structure is preserved
                new_cache["xk"], new_cache["xv"] = xk, xv
            else:                                  # prefill: project enc_out
                _, xk, xv = layers._project_qkv(
                    cfg, p["xattn"], hx, kv_input=enc_out.astype(hx.dtype))
                if cache is not None:
                    new_cache["xk"] = xk.astype(jnp.bfloat16)
                    new_cache["xv"] = xv.astype(jnp.bfloat16)
            out, _ = layers.attention_apply(
                cfg, p["xattn"], hx, positions=None, causal=False,
                cross_kv=(xk.astype(hx.dtype), xv.astype(hx.dtype)))
            x = x + out
    elif kind == "mamba":
        out, nc = mamba.mamba_apply(cfg, p["mamba"], h, cache=cache)
        if nc is not None:
            new_cache = nc
        x = x + out
    elif kind == "mlstm":
        out, nc = xlstm.mlstm_apply(cfg, p["mlstm"], h, cache=cache)
        if nc is not None:
            new_cache = nc
        x = x + out
    elif kind == "slstm":
        out, nc = xlstm.slstm_apply(cfg, p["slstm"], h, cache=cache)
        if nc is not None:
            new_cache = nc
        x = x + out

    if _pos_has_ffn(cfg, i):
        h = layers.norm_apply(cfg, p["norm2"], x)
        if _pos_is_moe(cfg, i):
            out, aux = moe.moe_apply(cfg, p["moe"], h)
        else:
            out = layers.mlp_apply(cfg, p["mlp"], h)
        x = x + out
    return x, new_cache, aux


def superblock_apply(cfg: ModelConfig, pslice, x, *, positions, cslice=None,
                     cache_index=None, enc_out=None, mode="train",
                     paging=None):
    """One super-block (all pattern positions once).

    pslice/cslice: per-layer (unstacked) params/caches keyed "p{i}".
    Returns (x, new_caches, aux_scalar).  Shared by the scanned stack
    and the dry-run's per-layer cost probe.
    """
    x = constrain(x, "act_batch", "act_seq", None)
    aux_acc = jnp.zeros((), jnp.float32)
    new_cs = {}
    for i in range(cfg.pattern_len):
        key = f"p{i}"
        cache_i = None if cslice is None else cslice.get(key)
        # (a nested per-position remat was tried for jamba's 8-position
        # super-block and REFUTED: peak memory is set by the fused-SSM
        # backward transients, not the union of position working sets —
        # see EXPERIMENTS.md §Perf)
        x, nc, aux = _apply_position(
            cfg, i, pslice[key], x, positions=positions,
            cache=cache_i, cache_index=cache_index, enc_out=enc_out,
            mode=mode, paging=paging)
        new_cs[key] = nc
        if aux is not None:
            aux_acc = aux_acc + aux["moe_aux_loss"]
    x = constrain(x, "act_batch", "act_seq", None)
    return x, new_cs, aux_acc


def stack_apply(cfg: ModelConfig, blocks, x, *, positions, caches=None,
                cache_index=None, enc_out=None, mode="train", remat=True,
                paging=None):
    """Run the full layer stack.

    blocks: {"p{i}": stacked params}; caches: same keying or None.
    Returns (x, new_caches | None, aux_sum).
    """
    def body(carry, xs):
        xc, aux_acc = carry
        pslice, cslice = xs
        xc, new_cs, aux = superblock_apply(
            cfg, pslice, xc, positions=positions, cslice=cslice,
            cache_index=cache_index, enc_out=enc_out, mode=mode,
            paging=paging)
        return (xc, aux_acc + aux), (new_cs if cslice is not None else None)

    if mode == "train" and remat:
        body = jax.checkpoint(body, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    (x, aux), new_caches = jax.lax.scan(body, (x, aux0), (blocks, caches))
    return x, new_caches, aux


def encoder_apply(cfg: ModelConfig, enc_params, frames, *, remat=True,
                  mode="train"):
    """Whisper-style encoder over precomputed frame embeddings."""
    x = frames + layers.sinusoidal_positions(
        frames.shape[1], cfg.d_model).astype(frames.dtype)[None]

    def body(xc, pslice):
        h = layers.norm_apply(cfg, pslice["norm1"], xc)
        out, _ = layers.attention_apply(
            cfg, pslice["attn"], h, positions=None, causal=False)
        xc = xc + out
        h = layers.norm_apply(cfg, pslice["norm2"], xc)
        xc = xc + layers.mlp_apply(cfg, pslice["mlp"], h)
        return xc, None

    if mode == "train" and remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, enc_params["enc"])
    return layers.norm_apply(cfg, enc_params["enc_norm"], x)
