"""Plain reference of a dense decoder with grouped-query attention.

Covers Llama-style models (Yi) and ChatGLM2/3: RMSNorm before each
block, rotary embeddings on the leading ``rope_fraction`` of each head
(rotate-half pairing), optional bias on the query, key and value
projections, SwiGLU feed-forward, an untied output head.  Everything is
float32 at ``highest`` matmul precision, one layer at a time under a
scan, over one whole sequence with a causal mask: no cache, no paging,
no kernels, no batching.  It imports nothing from the program.

``init_weights`` makes the weights from a key, in bf16 (the type they
are served in).  Their tree follows the program's parameter layout
(``embed``/``blocks.p0`` with the layer index leading), so the harness
can hand the same tree to the engine; the reference reads it by name.

Convention noted against the published ChatGLM code: it rotates
interleaved pairs ``(2i, 2i+1)`` of the rotary dims, this reference and
the program rotate ``(i, i + rot/2)``.  The two agree up to a fixed
permutation of the query and key projection columns, so on weights drawn
from a seed they are the same model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

# what this reference computes (``bench/check.py``'s ``reference``): the
# sizes it reads, and the program attributes of the model kind it is
FIELDS = ("d_model", "n_layers", "n_heads", "n_kv_heads", "head_dim",
          "d_ff", "vocab_size", "rope_theta", "rope_fraction", "qkv_bias",
          "norm_eps", "tie_embeddings")
KIND = {"family": "dense", "block_pattern": ("attn",), "moe": None,
        "norm_type": "rmsnorm", "mlp_type": "swiglu", "pos_type": "rope"}


def shapes(m: dict) -> dict:
    d, h, kv, hd, ff, v, n = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab_size"],
                              m["n_layers"])
    attn = {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
            "wv": (n, d, kv * hd), "wo": (n, h * hd, d)}
    if m["qkv_bias"]:
        attn.update(bq=(n, h * hd), bk=(n, kv * hd), bv=(n, kv * hd))
    return {
        "embed": {"tok": (v, d), "head": (d, v),
                  "final_norm": {"scale": (d,)}},
        "blocks": {"p0": {
            "norm1": {"scale": (n, d)},
            "attn": attn,
            "norm2": {"scale": (n, d)},
            "mlp": {"w_in": (n, d, ff), "w_gate": (n, d, ff),
                    "w_out": (n, ff, d)},
        }},
    }


def _leaf(name: str, key, shape):
    """Matrices scaled by 1/sqrt(fan-in), so activations and logits keep
    unit size at any width; norm scales near 1; biases small but not
    zero, so the bias path is exercised."""
    z = jax.random.normal(key, shape, jnp.bfloat16)
    if name == "scale":
        return (1.0 + 0.1 * z).astype(jnp.bfloat16)
    if name in ("bq", "bk", "bv"):
        return (0.1 * z).astype(jnp.bfloat16)
    if name == "tok":
        return z
    return (z * (shape[-2] ** -0.5)).astype(jnp.bfloat16)


def init_weights(m: dict, key):
    """All weights from one key, in bf16 (call under ``jax.jit``): one
    key per leaf, in the leaves' flattened (sorted-name) order."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        _leaf(path[-1].key, k, shape)
        for (path, shape), k in zip(leaves, keys)])


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def _mm(x, w, fp8: bool):
    """x @ w in float32; under ``fp8`` both operands are first rounded
    to float8 (activations per row, weights per output column)."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta, fraction):
    """x: (S, H, D); rotate-half on the first ``fraction`` of D."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            rest], -1)


def logits(m: dict, w, tokens, fp8: bool = False):
    """Logits (S, V) of one sequence ``tokens`` (S,), position i seeing
    positions 0..i.  ``fp8`` rounds every projection's operands to
    float8 (the control of the output check)."""
    h, kv, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], \
        m["norm_eps"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(F32), lw)
        a = lw["attn"]
        y = _rmsnorm(x, lw["norm1"]["scale"], eps)
        q, k, v = (_mm(y, a[n], fp8) for n in ("wq", "wk", "wv"))
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(s, h, hd), pos, m["rope_theta"],
                  m["rope_fraction"])
        k = _rope(k.reshape(s, kv, hd), pos, m["rope_theta"],
                  m["rope_fraction"])
        v = v.reshape(s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)          # head i reads kv i//g
        v = jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, h * hd)
        x = x + _mm(o, a["wo"], fp8)
        y = _rmsnorm(x, lw["norm2"]["scale"], eps)
        f = lw["mlp"]
        g = jax.nn.silu(_mm(y, f["w_gate"], fp8)) * _mm(y, f["w_in"], fp8)
        return x + _mm(g, f["w_out"], fp8), None

    with jax.default_matmul_precision("highest"):
        x = w["embed"]["tok"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, w["blocks"]["p0"])
        e = w["embed"]
        x = _rmsnorm(x, e["final_norm"]["scale"].astype(F32), eps)
        return _mm(x, e["head"].astype(F32), fp8)
