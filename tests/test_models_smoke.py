"""Per-architecture smoke tests (reduced same-family configs, CPU):
one forward/train step + one decode step; output shapes + no NaNs.
Also decode-vs-forward consistency for each layer-kind family.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_smoke_config
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

KEY = jax.random.PRNGKey(0)
B, S = 2, 24


def _batch(cfg):
    b = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab),
    }
    if cfg.frontend != "none":
        b["frontend"] = jax.random.normal(
            KEY, (B, cfg.n_frontend_tokens, cfg.d_model)).astype(jnp.dtype(cfg.dtype))
    return b


@pytest.mark.parametrize("arch", ARCHITECTURES)
def test_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 5 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, aux = jax.jit(lambda p, b: T.forward(p, b["tokens"], cfg, frontend=b.get("frontend"), kv_block=16))(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # one real optimizer step reduces nothing but must stay finite
    opt = get_optimizer("adamw", 1e-3)
    state = opt.init(params)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: T.loss_fn(p, batch, cfg, kv_block=16), has_aux=True))(params)
    assert bool(jnp.isfinite(loss))
    new_params, _ = opt.update(grads, state, params, jnp.int32(0))
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHITECTURES)
def test_decode_step_shapes(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, KEY)
    cache = T.init_cache(cfg, B, S)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = jax.jit(
        lambda p, t, c: T.decode_step(p, t, c, jnp.int32(0), cfg))(params, tok, cache)
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-2.7b", "recurrentgemma-2b",
                                  "h2o-danube-1.8b", "whisper-small", "granite-moe-1b-a400m"])
def test_prefill_decode_consistency(arch):
    """Greedy next-token from prefill+decode must match teacher-forced
    forward logits (exactness of the cache path per family)."""
    cfg = get_smoke_config(arch)
    # f32 for a tight comparison
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    batch = _batch(cfg)
    toks = batch["tokens"]
    fe = batch.get("frontend")
    logits_all, _ = T.forward(params, toks, cfg, frontend=fe, kv_block=0, remat=False)

    s_pre = S - 1
    logits_pre, cache = T.prefill(params, toks[:, :s_pre], cfg, frontend=fe,
                                  kv_block=0, cache_len=S)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0]), np.asarray(logits_all[:, s_pre - 1]),
        rtol=2e-3, atol=2e-3)
    # one decode step with the true next token
    logits_dec, _ = T.decode_step(params, toks[:, s_pre:s_pre + 1], cache,
                                  jnp.int32(s_pre), cfg)
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0]), np.asarray(logits_all[:, s_pre]),
        rtol=2e-3, atol=2e-3)


def test_swa_ring_buffer_decode():
    """Sliding-window decode past the window edge stays consistent with the
    windowed teacher-forced forward."""
    cfg = get_smoke_config("h2o-danube-1.8b")
    cfg = dataclasses.replace(cfg, dtype="float32", sliding_window=8)
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 20), 0, cfg.vocab)
    logits_all, _ = T.forward(params, toks, cfg, kv_block=0, remat=False)
    # decode sequentially from scratch with a ring cache of size 8
    cache = T.init_cache(cfg, 1, 20)
    assert cache["blocks"]["p0_attn"]["k"].shape[2] == 8  # ring = window
    outs = []
    for t in range(20):
        lg, cache = T.decode_step(params, toks[:, t:t + 1], cache, jnp.int32(t), cfg)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits_all),
                               rtol=3e-3, atol=3e-3)


def test_vlm_prefix_stripping():
    cfg = get_smoke_config("internvl2-1b")
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg)
    logits, _ = T.forward(params, batch["tokens"], cfg, frontend=batch["frontend"], kv_block=0)
    assert logits.shape == (B, S, cfg.vocab)  # patch positions stripped


def test_long_context_variant_cache_is_windowed():
    from repro.configs import INPUT_SHAPES
    from repro.launch.steps import long_context_cfg

    cfg = get_smoke_config("llama3.2-3b")
    cfg = dataclasses.replace(cfg, long_context_window=8)
    cfg = long_context_cfg(cfg, INPUT_SHAPES["long_500k"])
    assert cfg.name.endswith("+swa")
    cache = T.init_cache(cfg, 1, 1024)
    assert cache["blocks"]["p0_attn"]["k"].shape[2] == 8
