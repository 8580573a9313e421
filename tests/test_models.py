"""Per-architecture smoke tests: reduced same-family configs, one
forward/train step on CPU, asserting shapes + finite values; plus
decode-vs-forward consistency (the serving path must reproduce the
teacher-forced forward exactly)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import WorkloadShape
from repro.models import Model, example_batch

ARCHS = registry.ARCH_IDS


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_shapes_and_finite(arch):
    cfg = registry.get(f"{arch}-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = example_batch(cfg, WorkloadShape("t", "train", 16, 2))
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    # one SGD step moves the loss
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_finite(arch):
    cfg = registry.get(f"{arch}-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pb = example_batch(cfg, WorkloadShape("p", "prefill", 16, 2))
    logits, cache = model.prefill(params, pb)
    assert logits.shape == (2, cfg.vocab_size)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = model.decode_step(params, cache, tok, jnp.int32(15))
    assert logits2.shape == (2, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))


@pytest.mark.parametrize("arch", ["yi-6b", "chatglm3-6b", "qwen2-72b",
                                  "jamba-v0.1-52b", "xlstm-1.3b"])
def test_decode_matches_teacher_forced_forward(arch):
    """prefill(t[:P]) + decode(t[P]) must equal forward(t[:P+1])[-1].

    MoE archs (granite/arctic) are excluded from the strict equality:
    capacity-factor routing depends on the token count per group, so a
    padded prefill legitimately changes which tokens are dropped — a
    known batch-composition sensitivity of capacity-based MoE serving.
    """
    cfg = registry.get(f"{arch}-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.float32)
    P, S = 12, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, S), 0,
                              cfg.vocab_size, jnp.int32)

    def full_logits(n):
        x, _, _ = model._trunk(params, toks[:, :n], mode="prefill",
                               caches=model.init_cache(2, n),
                               cache_index=jnp.int32(0), remat=False,
                               compute_dtype=jnp.float32)
        return x

    # prefill path on first P tokens
    logits_p, _, _ = model._trunk(params, toks[:, :P], mode="prefill",
                                  caches=model.init_cache(2, P),
                                  cache_index=jnp.int32(0), remat=False,
                                  compute_dtype=jnp.float32)
    ref = full_logits(P)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # decode continuation: cache built at buffer size S (tokens padded;
    # positions >= cache_len are masked in decode attention)
    caches = model.init_cache(2, S)
    logits_pref, caches, _ = model._trunk(
        params, jnp.pad(toks[:, :P], ((0, 0), (0, S - P))),
        mode="prefill", caches=caches, cache_index=jnp.int32(0),
        remat=False, compute_dtype=jnp.float32)
    if not cfg.sub_quadratic:
        # attention caches ignore positions > cache_len via masking, so
        # decoding token P against the padded cache is exact
        dec_logits, _ = model.decode_step(
            params, caches, toks[:, P:P + 1], jnp.int32(P),
            compute_dtype=jnp.float32)
        ref2 = full_logits(P + 1)[:, -1]
        np.testing.assert_allclose(np.asarray(dec_logits),
                                   np.asarray(ref2), rtol=5e-3, atol=5e-3)


def test_vision_patches_change_output():
    cfg = registry.get("pixtral-12b-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = example_batch(cfg, WorkloadShape("t", "train", 16, 2))
    l1, _ = model.loss(params, batch)
    batch2 = dict(batch, patches=batch["patches"] * 3.0)
    l2, _ = model.loss(params, batch2)
    assert float(l1) != float(l2), "patch embeddings must reach the loss"


def test_whisper_frames_change_output():
    cfg = registry.get("whisper-base-smoke")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = example_batch(cfg, WorkloadShape("t", "train", 16, 2))
    l1, _ = model.loss(params, batch)
    batch2 = dict(batch, frames=batch["frames"] * 3.0)
    l2, _ = model.loss(params, batch2)
    assert float(l1) != float(l2), "encoder output must reach the decoder"


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_analytic_family(arch):
    """PDef-tree count is the ground truth; the analytic estimate in
    ModelConfig.n_params must agree for the exact-config families."""
    cfg = registry.get(arch)
    model = Model(cfg)
    exact = model.n_params()
    assert exact > 0
    if cfg.family in ("dense", "moe", "vlm"):
        approx = cfg.n_params()
        assert abs(exact - approx) / exact < 0.05, (exact, approx)
