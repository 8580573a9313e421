"""Compile rehearsals: the main-path Pallas kernels at yi-6b's published
widths, compiled for a described (not attached) TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses — block
shapes off the (8, 128) tiling, VMEM overruns — so each test lowers the
real kernel for a v5e device and compiles it (about two seconds each).
Nothing runs: these say nothing about results or times.

The topology is described inside a module fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.  Keep these tests in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import ops

CFG = registry.get("yi-6b")
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
SLOTS, SEQ = 8, 1024            # serving: 8 slots x 1024 tokens
CHUNK = 128                     # chunked-prefill rows per tick
TRAIN_B, TRAIN_S = 4, 512       # training batch x sequence


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2 host, with the persistent
    compile cache off (a compile for a described chip is written to it
    but can never be read back here)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is in it
    return compiled


@pytest.mark.parametrize("page", [8, 16])
def test_paged_decode_compiles_for_v5e(chip, page):
    maxp = SEQ // page
    n_pages = SLOTS * maxp + 1
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(chip, functools.partial(ops.paged_decode_attention,
                                     impl="pallas"),
             ((SLOTS, 1, H, D), bf), ((n_pages, KV, page, D), bf),
             ((n_pages, KV, page, D), bf), ((SLOTS, maxp), i32),
             ((SLOTS,), i32))


@pytest.mark.parametrize("page", [8, 16])
def test_paged_prefill_compiles_for_v5e(chip, page):
    maxp = SEQ // page
    n_pages = SLOTS * maxp + 1
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(chip, functools.partial(ops.paged_prefill_attention,
                                     impl="pallas"),
             ((1, CHUNK, H, D), bf), ((n_pages, KV, page, D), bf),
             ((n_pages, KV, page, D), bf), ((1, maxp), i32), ((1,), i32),
             ((1,), i32))


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, impl="pallas")


def test_flash_attention_forward_compiles_for_v5e(chip):
    bf = jnp.bfloat16
    _compile(chip, _flash, ((TRAIN_B, TRAIN_S, H, D), bf),
             ((TRAIN_B, TRAIN_S, KV, D), bf),
             ((TRAIN_B, TRAIN_S, KV, D), bf))


def test_flash_attention_backward_compiles_for_v5e(chip):
    bf = jnp.bfloat16

    def fwd_bwd(q, k, v, do):
        _, vjp = jax.vjp(_flash, q, k, v)
        return vjp(do)
    _compile(chip, fwd_bwd, ((TRAIN_B, TRAIN_S, H, D), bf),
             ((TRAIN_B, TRAIN_S, KV, D), bf),
             ((TRAIN_B, TRAIN_S, KV, D), bf),
             ((TRAIN_B, TRAIN_S, H, D), bf))


def test_rmsnorm_forward_and_backward_compile_for_v5e(chip):
    bf = jnp.bfloat16

    def fwd_bwd(x, w):
        y, vjp = jax.vjp(functools.partial(ops.rmsnorm, eps=CFG.norm_eps,
                                           impl="pallas"), x, w)
        return y, vjp(y)
    _compile(chip, fwd_bwd, ((TRAIN_B, TRAIN_S, CFG.d_model), bf),
             ((CFG.d_model,), bf))
