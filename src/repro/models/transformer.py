"""Unified model stack for all assigned architectures.

One decoder skeleton serves every family:
  embed/frontend → lax.scan over homogeneous layer groups → norm → lm head

Layer kinds (``ModelConfig.layer_kind``):
  ``attn`` — GQA + RoPE (+ optional qk-norm / sliding window) + FFN
             (SwiGLU dense or top-k MoE);
  ``ssm``  — Mamba-2 SSD mixer (no FFN);
  ``rec``  — RecurrentGemma recurrent block (conv1d + RG-LRU, GeGLU FFN).

Hybrid patterns are scanned over *super-blocks* (one pattern repetition),
with any remainder layers unrolled. Whisper adds an encoder stack and
cross-attention; VLM/audio frontends are stubs that consume precomputed
patch/frame embeddings (see DESIGN.md — the one allowed stub).

Granite's residual and logit path (``ModelConfig.embedding_multiplier``,
``attention_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``tie_embeddings``) applies on every path; at their defaults the model is
as before.  A tied model keeps one (D, V) matrix, the ``lm_head`` leaf,
and embeds a token as its column.

Entry points:
  init_params(cfg, key)                  -> params pytree
  loss_fn(params, batch, cfg, ...)       -> (scalar loss, stats) (train)
  prefill(params, batch, cfg, ...)       -> (logits_last, cache)
  decode_step(params, token, cache, pos, cfg, ...) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.launch import spans
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.sharding import NULL_CTX, ShardCtx

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _times(x: jax.Array, multiplier: float) -> jax.Array:
    """``x`` times a configuration's multiplier (no op at 1)."""
    return x if multiplier == 1.0 else x * multiplier


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Token embeddings times ``embedding_multiplier``: rows of ``embed``,
    or for a tied model columns of ``lm_head``."""
    table = params["lm_head"].T if cfg.tie_embeddings else params["embed"]
    return _times(table.astype(_dt(cfg))[tokens], cfg.embedding_multiplier)


def _logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    logits = x @ params["lm_head"]
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def _no_aux() -> Dict[str, jax.Array]:
    """A layer without experts: a load-balance term of 0."""
    return {"balance": jnp.float32(0.0)}


def _merge_aux(total: Dict[str, jax.Array], a: Dict[str, jax.Array]):
    """Layer statistics over the layers: the load-balance terms add up,
    the expert load is the largest layer's."""
    out = dict(total, balance=total["balance"] + a["balance"])
    if "expert_load" in a:
        out["expert_load"] = jnp.maximum(total["expert_load"], a["expert_load"])
    return out


def _init_attn_layer(key, cfg: ModelConfig, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 10)
    std = 0.02
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
        "wq": (std * jax.random.normal(ks[0], (d, h * hd))).astype(dtype),
        "wk": (std * jax.random.normal(ks[1], (d, kv * hd))).astype(dtype),
        "wv": (std * jax.random.normal(ks[2], (d, kv * hd))).astype(dtype),
        "wo": (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[3], (h * hd, d))).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    if cfg.moe is not None:
        e, fe = cfg.moe.num_experts, cfg.moe.d_expert
        p["router"] = (std * jax.random.normal(ks[4], (d, e))).astype(dtype)
        p["we_g"] = (std * jax.random.normal(ks[5], (e, d, fe))).astype(dtype)
        p["we_u"] = (std * jax.random.normal(ks[6], (e, d, fe))).astype(dtype)
        p["we_d"] = (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[7], (e, fe, d))).astype(dtype)
    elif cfg.d_ff:
        p["wg"] = (std * jax.random.normal(ks[4], (d, cfg.d_ff))).astype(dtype)
        p["wu"] = (std * jax.random.normal(ks[5], (d, cfg.d_ff))).astype(dtype)
        p["wd"] = (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[6], (cfg.d_ff, d))).astype(dtype)
    return p


def _init_ssm_layer(key, cfg: ModelConfig, dtype) -> Params:
    s = cfg.ssm or SSMConfig()
    d = cfg.d_model
    di = s.expand * d
    nheads = di // s.head_dim
    conv_dim = di + 2 * s.d_state
    ks = jax.random.split(key, 5)
    std = 0.02
    return {
        "ln1": jnp.zeros((d,), dtype),
        "w_in": (std * jax.random.normal(ks[0], (d, 2 * di + 2 * s.d_state + nheads))).astype(dtype),
        "conv_w": (std * jax.random.normal(ks[1], (s.conv_width, conv_dim))).astype(dtype),
        "A_log": jnp.zeros((nheads,), jnp.float32),  # A = -exp(A_log) = -1
        "dt_bias": jnp.full((nheads,), -2.0, jnp.float32),  # softplus^-1-ish small dt
        "D_skip": jnp.ones((nheads,), jnp.float32),
        "out_norm": jnp.zeros((di,), dtype),
        "w_out": (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[2], (di, d))).astype(dtype),
    }


def _init_rec_layer(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    c = d  # lru width = d_model
    ks = jax.random.split(key, 10)
    std = 0.02
    return {
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
        "w_bx": (std * jax.random.normal(ks[0], (d, c))).astype(dtype),
        "w_bg": (std * jax.random.normal(ks[1], (d, c))).astype(dtype),
        "conv_w": (std * jax.random.normal(ks[2], (4, c))).astype(dtype),
        "w_a": (std * jax.random.normal(ks[3], (c, c))).astype(dtype),
        "b_a": jnp.zeros((c,), jnp.float32),
        "w_xg": (std * jax.random.normal(ks[4], (c, c))).astype(dtype),
        "b_x": jnp.zeros((c,), jnp.float32),
        "lam": jnp.full((c,), 0.5, jnp.float32),
        "w_ro": (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[5], (c, d))).astype(dtype),
        "wg": (std * jax.random.normal(ks[6], (d, cfg.d_ff))).astype(dtype),
        "wu": (std * jax.random.normal(ks[7], (d, cfg.d_ff))).astype(dtype),
        "wd": (std / jnp.sqrt(2.0 * cfg.n_layers) * jax.random.normal(ks[8], (cfg.d_ff, d))).astype(dtype),
    }


def _init_layer(kind: str, key, cfg: ModelConfig, dtype) -> Params:
    if kind == "attn":
        return _init_attn_layer(key, cfg, dtype)
    if kind == "ssm":
        return _init_ssm_layer(key, cfg, dtype)
    if kind == "rec":
        return _init_rec_layer(key, cfg, dtype)
    raise ValueError(kind)


def layer_groups(cfg: ModelConfig):
    """Split layers into (scan groups, tail): each group is a maximal run of
    repeated patterns. Returns list of (kinds_tuple, count) + tail kinds."""
    kinds = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    if not cfg.hybrid_pattern:
        return [((kinds[0],), cfg.n_layers)], []
    plen = len(cfg.hybrid_pattern)
    n_super = cfg.n_layers // plen
    tail = kinds[n_super * plen :]
    return [(tuple(cfg.hybrid_pattern), n_super)], tail


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = _dt(cfg)
    std = 0.02
    k_embed, k_head, k_layers, k_enc, k_cross, k_tail, k_fe = jax.random.split(key, 7)
    params: Params = {
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
        "lm_head": (std * jax.random.normal(k_head, (cfg.d_model, cfg.vocab))).astype(dtype),
    }
    if not cfg.tie_embeddings:
        params["embed"] = (std * jax.random.normal(k_embed, (cfg.vocab, cfg.d_model))).astype(dtype)
    groups, tail = layer_groups(cfg)
    (pattern, n_super) = groups[0]

    def init_block(key):
        ks = jax.random.split(key, len(pattern))
        return {f"p{i}_{kind}": _init_layer(kind, ks[i], cfg, dtype) for i, kind in enumerate(pattern)}

    block_keys = jax.random.split(k_layers, n_super)
    params["blocks"] = jax.vmap(init_block)(block_keys)  # leaves stacked (n_super, ...)
    if tail:
        tkeys = jax.random.split(k_tail, len(tail))
        params["tail"] = [
            _init_layer(kind, tkeys[i], cfg, dtype) for i, kind in enumerate(tail)
        ]
    if cfg.n_enc_layers:
        ekeys = jax.random.split(k_enc, cfg.n_enc_layers)
        enc_cfg = dataclasses.replace(cfg, moe=None, qk_norm=False)
        params["enc_blocks"] = jax.vmap(lambda k: _init_attn_layer(k, enc_cfg, dtype))(ekeys)
        params["enc_norm"] = jnp.zeros((cfg.d_model,), dtype)
        if cfg.cross_attention:
            ckeys = jax.random.split(k_cross, n_super)
            params["cross_blocks"] = jax.vmap(lambda k: _init_attn_layer(k, enc_cfg, dtype))(ckeys)
    return params


def param_shapes(cfg: ModelConfig):
    """Parameter structure without materialising anything (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))


def count_params(cfg: ModelConfig) -> int:
    import math

    return sum(math.prod(l.shape) if l.shape else 1
               for l in jax.tree.leaves(param_shapes(cfg)))


def count_active_params(cfg: ModelConfig) -> int:
    """Params touched per token: MoE counts top_k of num_experts experts."""
    total = count_params(cfg)
    if cfg.moe is None:
        return total
    e, k, fe, d = cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_expert, cfg.d_model
    expert_params = cfg.n_layers * e * 3 * d * fe
    active_expert = cfg.n_layers * k * 3 * d * fe
    return total - expert_params + active_expert


# ---------------------------------------------------------------------------
# layer forward (full-sequence: train / prefill)
# ---------------------------------------------------------------------------


def _attn_layer_fwd(
    p: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    ctx: ShardCtx,
    *,
    causal: bool = True,
    window: int = 0,
    positions: Optional[jax.Array] = None,
    kv_block: int = 1024,
    enc_out: Optional[jax.Array] = None,  # cross-attention memory
    cross_p: Optional[Params] = None,
    return_kv: bool = False,
):
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // kv
    if positions is None:
        positions = jnp.arange(s)[None, :]
    with jax.named_scope(spans.ATTN):
        y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q = (y @ p["wq"]).reshape(b, s, kv, g, hd)
        k = (y @ p["wk"]).reshape(b, s, kv, hd)
        v = (y @ p["wv"]).reshape(b, s, kv, hd)
        if cfg.qk_norm:
            q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
        q = L.rope(q.reshape(b, s, kv * g, hd), positions, cfg.rope_theta).reshape(b, s, kv, g, hd)
        k = L.rope(k, positions, cfg.rope_theta)
        q = ctx.constrain(q, ("b", None, "m", None, None))
        k = ctx.constrain(k, ("b", None, "m", None))
        v = ctx.constrain(v, ("b", None, "m", None))
        o = attn_lib.attention(q, k, v, causal=causal, window=window, kv_block=kv_block,
                               shards=ctx.auto_shards(), scale=cfg.attention_multiplier or None)
        o = o.reshape(b, s, h * hd) @ p["wo"]
        x = x + _times(o, cfg.residual_multiplier)
        if enc_out is not None and cross_p is not None:
            yc = L.rms_norm(x, cross_p["ln1"], cfg.norm_eps)
            qc = (yc @ cross_p["wq"]).reshape(b, s, kv, g, hd)
            kc = (enc_out @ cross_p["wk"]).reshape(b, enc_out.shape[1], kv, hd)
            vc = (enc_out @ cross_p["wv"]).reshape(b, enc_out.shape[1], kv, hd)
            oc = attn_lib.attention(qc, kc, vc, causal=False, kv_block=kv_block)
            x = x + oc.reshape(b, s, h * hd) @ cross_p["wo"]
    with jax.named_scope(spans.MLP):
        y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        aux = _no_aux()
        if cfg.moe is not None:
            f, balance, load = moe_lib.moe_ffn(y, p["router"], p["we_g"], p["we_u"], p["we_d"],
                                               cfg.moe.top_k, shards=ctx.auto_shards())
            aux = {"balance": balance, "expert_load": load}
            f = ctx.constrain(f, ("b", None, None))
            x = x + _times(f, cfg.residual_multiplier)
        elif cfg.d_ff:
            hdn = jax.nn.silu(y @ p["wg"]) * (y @ p["wu"])
            hdn = ctx.constrain(hdn, ("b", None, "m"))
            x = x + _times(hdn @ p["wd"], cfg.residual_multiplier)
    if return_kv:
        return x, aux, (k, v)
    return x, aux


def _ssm_layer_fwd(p: Params, x: jax.Array, cfg: ModelConfig, ctx: ShardCtx):
    s_cfg = cfg.ssm or SSMConfig()
    b, s, d = x.shape
    di = s_cfg.expand * d
    n = s_cfg.d_state
    nheads = di // s_cfg.head_dim
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    proj = y @ p["w_in"]  # (B,S, 2di+2n+nh)
    z, xs, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out, _ = ssm_lib.causal_conv1d(conv_in, p["conv_w"])
    xs, Bm, Cm = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    loga = -jnp.exp(p["A_log"]) * dt  # (B,S,H)
    xh = xs.reshape(b, s, nheads, s_cfg.head_dim)
    xh = ctx.constrain(xh, ("b", None, "m", None))
    y_ssd, _ = ssm_lib.ssd_chunked(xh * dt[..., None].astype(xh.dtype), loga, Bm, Cm, chunk=s_cfg.chunk)
    y_ssd = y_ssd + p["D_skip"][None, None, :, None].astype(y_ssd.dtype) * xh
    y_out = y_ssd.reshape(b, s, di) * jax.nn.silu(z)
    y_out = L.rms_norm(y_out, p["out_norm"], cfg.norm_eps)
    return x + y_out @ p["w_out"], _no_aux()


def _rec_layer_fwd(p: Params, x: jax.Array, cfg: ModelConfig, ctx: ShardCtx):
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    bx = y @ p["w_bx"]
    bg = jax.nn.gelu(y @ p["w_bg"])
    conv_out, _ = ssm_lib.causal_conv1d(bx, p["conv_w"])
    r, _ = rglru_lib.rglru_scan(conv_out, p["w_a"], p["b_a"], p["w_xg"], p["b_x"], p["lam"])
    r = ctx.constrain(r, ("b", None, "m"))
    x = x + (r * bg) @ p["w_ro"]
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    hdn = jax.nn.gelu(y @ p["wg"]) * (y @ p["wu"])
    hdn = ctx.constrain(hdn, ("b", None, "m"))
    return x + hdn @ p["wd"], _no_aux()


def _attn_window(cfg: ModelConfig) -> int:
    """Training/prefill attention window: native SWA, or the hybrid
    pattern's local-attention window (0 = full attention)."""
    if cfg.sliding_window:
        return cfg.sliding_window
    return cfg.local_window if cfg.hybrid_pattern else 0


def _layer_fwd(kind: str, p, x, cfg, ctx, **kw):
    if kind == "attn":
        return _attn_layer_fwd(p, x, cfg, ctx, window=_attn_window(cfg), **kw)
    if kind == "ssm":
        return _ssm_layer_fwd(p, x, cfg, ctx)
    if kind == "rec":
        return _rec_layer_fwd(p, x, cfg, ctx)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# full forward
# ---------------------------------------------------------------------------


def _encoder_fwd(params: Params, frontend: jax.Array, cfg: ModelConfig, ctx: ShardCtx,
                 remat: bool, kv_block: int):
    """Whisper-style encoder over stub frame embeddings (B, T, D)."""
    x = frontend + L.sinusoidal_positions(frontend.shape[1], cfg.d_model, frontend.dtype)[None]

    def body(x, p):
        out, _ = _attn_layer_fwd(p, x, cfg, ctx, causal=False, kv_block=kv_block)
        return out, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(
    params: Params,
    tokens: jax.Array,  # (B, S)
    cfg: ModelConfig,
    ctx: ShardCtx = NULL_CTX,
    frontend: Optional[jax.Array] = None,  # (B, T, D) audio frames / vision patches
    remat: bool = True,
    kv_block: int = 1024,
    block_provider=None,  # FSDP: per-block weight gather (see launch/steps.py)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence forward -> (logits (B, S_text, V), aux): ``aux`` holds
    the load-balance term summed over layers (``balance``) and, for a
    model with experts, the largest layer's expert load
    (``expert_load``: its largest expert's routed rows over the mean)."""
    b, s = tokens.shape
    with jax.named_scope(spans.EMBED):
        x = _embed(params, tokens, cfg)
    enc_out = None
    n_prefix = 0
    if cfg.frontend == "audio" and cfg.n_enc_layers:
        assert frontend is not None, "audio model needs frontend frame embeddings"
        enc_out = _encoder_fwd(params, frontend, cfg, ctx, remat, kv_block)
    elif cfg.frontend == "vision":
        assert frontend is not None, "vlm needs frontend patch embeddings"
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        n_prefix = frontend.shape[1]
    x = ctx.constrain(x, ("b", None, None))
    aux_total = _no_aux()
    if cfg.moe is not None:
        aux_total["expert_load"] = jnp.float32(0.0)
    groups, tail = layer_groups(cfg)
    (pattern, n_super) = groups[0]
    positions = jnp.arange(x.shape[1])[None, :]

    has_cross = cfg.cross_attention and enc_out is not None

    def block_body(carry, bp):
        x, aux = carry
        block_p, cross_p = bp
        if block_provider is not None:
            # FSDP: all-gather this block's weight shards (backward pass =
            # robust reduce-scatter of the per-worker gradients)
            block_p = block_provider(block_p)
        for i, kind in enumerate(pattern):
            kw = {}
            if kind == "attn":
                kw = dict(positions=positions, kv_block=kv_block)
                if has_cross:
                    kw.update(enc_out=enc_out, cross_p=cross_p)
            x, a = _layer_fwd(kind, block_p[f"p{i}_{kind}"], x, cfg, ctx, **kw)
            if ctx.seq_parallel:
                x = ctx.constrain(x, ("b", "m", None))  # residual S-sharded
            aux = _merge_aux(aux, a)
        return (x, aux), None

    if remat:
        # the remat'd block keeps the expert layer's routing for its backward
        # pass; a dense block names none of it, so saves nothing
        policy = jax.checkpoint_policies.save_only_these_names(*moe_lib.ROUTING)
        block_body = jax.checkpoint(block_body, prevent_cse=False, policy=policy)
    xs = (params["blocks"], params["cross_blocks"] if has_cross else None)
    (x, aux_total), _ = jax.lax.scan(block_body, (x, aux_total), xs)
    for i, kind in enumerate(tail):
        kw = dict(positions=positions, kv_block=kv_block) if kind == "attn" else {}
        x, a = _layer_fwd(kind, params["tail"][i], x, cfg, ctx, **kw)
        aux_total = _merge_aux(aux_total, a)
    with jax.named_scope(spans.HEAD_LOSS):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if n_prefix:
            x = x[:, n_prefix:]
        logits = _logits(params, x, cfg)
        logits = ctx.constrain(logits, ("b", None, "m"))
    return logits, aux_total


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    ctx: ShardCtx = NULL_CTX,
    remat: bool = True,
    kv_block: int = 1024,
    aux_weight: float = 0.01,
    block_provider=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """-> (cross entropy + ``aux_weight`` x the load-balance term, stats):
    ``stats`` holds ``forward``'s statistics other than that term (the
    expert load of a model with experts; none otherwise)."""
    logits, aux = forward(
        params, batch["tokens"], cfg, ctx, frontend=batch.get("frontend"),
        remat=remat, kv_block=kv_block, block_provider=block_provider,
    )
    stats = {k: v for k, v in aux.items() if k != "balance"}
    with jax.named_scope(spans.HEAD_LOSS):
        loss = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
        return loss + aux_weight * aux["balance"], stats


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def _empty_layer_cache(kind: str, cfg: ModelConfig, b: int, cache_len: int, dtype):
    if kind == "attn":
        kv, hd = cfg.n_kv_heads, cfg.hd
        eff = cache_len
        if cfg.hybrid_pattern:
            eff = min(cache_len, cfg.local_window)
        elif cfg.sliding_window:
            eff = min(cache_len, cfg.sliding_window)
        elif cfg.long_context_window:
            eff = min(cache_len, cfg.long_context_window)
        return {
            "k": jnp.zeros((b, eff, kv, hd), dtype),
            "v": jnp.zeros((b, eff, kv, hd), dtype),
            "kpos": jnp.full((eff,), -1, jnp.int32),  # absolute position per slot
        }
    if kind == "ssm":
        s = cfg.ssm or SSMConfig()
        di = s.expand * cfg.d_model
        nheads = di // s.head_dim
        conv_dim = di + 2 * s.d_state
        return {
            "conv": jnp.zeros((b, s.conv_width - 1, conv_dim), dtype),
            "ssd": jnp.zeros((b, nheads, s.head_dim, s.d_state), jnp.float32),
        }
    if kind == "rec":
        c = cfg.d_model
        return {
            "conv": jnp.zeros((b, 3, c), dtype),
            "h": jnp.zeros((b, c), jnp.float32),
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, b: int, cache_len: int) -> Params:
    dtype = _dt(cfg)
    groups, tail = layer_groups(cfg)
    (pattern, n_super) = groups[0]

    def one_block(_):
        return {f"p{i}_{kind}": _empty_layer_cache(kind, cfg, b, cache_len, dtype)
                for i, kind in enumerate(pattern)}

    blocks = jax.vmap(one_block)(jnp.arange(n_super))
    cache: Params = {"blocks": blocks}
    if tail:
        cache["tail"] = [
            _empty_layer_cache(kind, cfg, b, cache_len, dtype) for kind in tail
        ]
    if cfg.cross_attention and cfg.n_enc_layers:
        kv, hd = cfg.n_kv_heads, cfg.hd
        t = cfg.n_frontend_tokens
        cache["cross"] = {
            "k": jnp.zeros((n_super, b, t, kv, hd), dtype),
            "v": jnp.zeros((n_super, b, t, kv, hd), dtype),
        }
    return cache


def _attn_decode(p, x, lc, cfg: ModelConfig, ctx: ShardCtx, pos, window: int,
                 cross_kv=None, cross_p=None):
    """One-token attention layer step against the cache. pos: scalar int."""
    b = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    g = h // kv
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = (y @ p["wq"]).reshape(b, 1, kv, g, hd)
    k = (y @ p["wk"]).reshape(b, 1, kv, hd)
    v = (y @ p["wv"]).reshape(b, 1, kv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    posv = jnp.full((1, 1), pos)
    q = L.rope(q.reshape(b, 1, h, hd), posv, cfg.rope_theta).reshape(b, 1, kv, g, hd)
    k = L.rope(k, posv, cfg.rope_theta)
    eff = lc["k"].shape[1]
    slot = pos % eff  # ring buffer (== pos when cache is full-length)
    k_cache = jax.lax.dynamic_update_slice_in_dim(lc["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(lc["v"], v, slot, axis=1)
    kpos = jax.lax.dynamic_update_slice_in_dim(lc["kpos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    k_cache = ctx.constrain(k_cache, ("b", None, "m", None))
    v_cache = ctx.constrain(v_cache, ("b", None, "m", None))
    o = _cache_attention(q, k_cache, v_cache, kpos, pos, window, cfg.attention_multiplier or None)
    x = x + _times(o.reshape(b, 1, h * hd) @ p["wo"], cfg.residual_multiplier)
    if cross_kv is not None and cross_p is not None:
        yc = L.rms_norm(x, cross_p["ln1"], cfg.norm_eps)
        qc = (yc @ cross_p["wq"]).reshape(b, 1, kv, g, hd)
        t = cross_kv["k"].shape[1]
        oc = _cache_attention(qc, cross_kv["k"], cross_kv["v"],
                              jnp.arange(t, dtype=jnp.int32), jnp.int32(2**30), 0)
        x = x + oc.reshape(b, 1, h * hd) @ cross_p["wo"]
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        f = moe_lib.moe_ffn(y, p["router"], p["we_g"], p["we_u"], p["we_d"], cfg.moe.top_k,
                            shards=ctx.auto_shards())[0]
        x = x + _times(f, cfg.residual_multiplier)
    elif cfg.d_ff:
        x = x + _times((jax.nn.silu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"],
                       cfg.residual_multiplier)
    return x, {"k": k_cache, "v": v_cache, "kpos": kpos}


def _cache_attention(q, k_cache, v_cache, kpos, pos, window: int, scale=None):
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32), k_cache.astype(jnp.float32)) * scale
    ok = (kpos >= 0) & (kpos <= pos)
    if window:
        ok &= kpos > pos - window
    logits = jnp.where(ok[None, None, None, None, :], logits, attn_lib.NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)


def _ssm_decode(p, x, lc, cfg: ModelConfig, ctx: ShardCtx):
    s_cfg = cfg.ssm or SSMConfig()
    b = x.shape[0]
    d = cfg.d_model
    di = s_cfg.expand * d
    n = s_cfg.d_state
    nheads = di // s_cfg.head_dim
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    proj = y @ p["w_in"]
    z, xs, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)  # (B,1,conv_dim)
    conv_out, new_conv = ssm_lib.causal_conv1d(conv_in, p["conv_w"], prev=lc["conv"])
    xs, Bm, Cm = jnp.split(conv_out[:, 0], [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    loga = -jnp.exp(p["A_log"]) * dt
    xh = xs.reshape(b, nheads, s_cfg.head_dim)
    yh, new_state = ssm_lib.ssd_decode_step(lc["ssd"], xh * dt[..., None].astype(xh.dtype), loga, Bm, Cm)
    yh = yh + p["D_skip"][None, :, None].astype(yh.dtype) * xh
    y_out = yh.reshape(b, 1, di) * jax.nn.silu(z)
    y_out = L.rms_norm(y_out, p["out_norm"], cfg.norm_eps)
    return x + y_out @ p["w_out"], {"conv": new_conv, "ssd": new_state}


def _rec_decode(p, x, lc, cfg: ModelConfig, ctx: ShardCtx):
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    bx = y @ p["w_bx"]
    bg = jax.nn.gelu(y @ p["w_bg"])
    conv_out, new_conv = ssm_lib.causal_conv1d(bx, p["conv_w"], prev=lc["conv"])
    r, new_h = rglru_lib.rglru_decode_step(lc["h"], conv_out, p["w_a"], p["b_a"], p["w_xg"], p["b_x"], p["lam"])
    x = x + (r * bg) @ p["w_ro"]
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + (jax.nn.gelu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"]
    return x, {"conv": new_conv, "h": new_h}


def decode_step(
    params: Params,
    token: jax.Array,  # (B, 1) int32
    cache: Params,
    pos: jax.Array,  # scalar int32: absolute position being generated
    cfg: ModelConfig,
    ctx: ShardCtx = NULL_CTX,
) -> Tuple[jax.Array, Params]:
    """One decode step: returns (logits (B, 1, V), updated cache)."""
    x = _embed(params, token, cfg)
    groups, tail = layer_groups(cfg)
    (pattern, n_super) = groups[0]
    window_attn = cfg.local_window if cfg.hybrid_pattern else (
        cfg.sliding_window or cfg.long_context_window or 0
    )
    has_cross = cfg.cross_attention and "cross" in cache

    def block_body(x, xs):
        block_p, block_c, cross_kv, cross_p = xs
        new_c = {}
        for i, kind in enumerate(pattern):
            key = f"p{i}_{kind}"
            if kind == "attn":
                x, nc = _attn_decode(block_p[key], x, block_c[key], cfg, ctx, pos,
                                     window_attn, cross_kv=cross_kv, cross_p=cross_p)
            elif kind == "ssm":
                x, nc = _ssm_decode(block_p[key], x, block_c[key], cfg, ctx)
            else:
                x, nc = _rec_decode(block_p[key], x, block_c[key], cfg, ctx)
            new_c[key] = nc
        return x, new_c

    xs = (
        params["blocks"],
        cache["blocks"],
        cache.get("cross") if has_cross else None,
        params.get("cross_blocks") if has_cross else None,
    )
    x, new_blocks = jax.lax.scan(block_body, x, xs)
    new_cache = dict(cache)
    new_cache["blocks"] = new_blocks
    if tail:
        new_tail = []
        for i, kind in enumerate(tail):
            if kind == "attn":
                x, nc = _attn_decode(params["tail"][i], x, cache["tail"][i], cfg, ctx, pos, window_attn)
            elif kind == "ssm":
                x, nc = _ssm_decode(params["tail"][i], x, cache["tail"][i], cfg, ctx)
            else:
                x, nc = _rec_decode(params["tail"][i], x, cache["tail"][i], cfg, ctx)
            new_tail.append(nc)
        new_cache["tail"] = new_tail
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)
    logits = ctx.constrain(logits, ("b", None, "m"))
    return logits, new_cache


def prefill(
    params: Params,
    tokens: jax.Array,  # (B, S)
    cfg: ModelConfig,
    ctx: ShardCtx = NULL_CTX,
    frontend: Optional[jax.Array] = None,
    kv_block: int = 1024,
    cache_len: Optional[int] = None,  # total cache capacity (>= S); default S
) -> Tuple[jax.Array, Params]:
    """Full forward that also builds the serving cache.

    ``cache_len`` sizes the KV cache (prompt + generation budget); the
    logits for the *last* token are returned (what a serving system
    samples from).
    """
    b, s = tokens.shape
    cache_len = cache_len or s
    assert cache_len >= s, (cache_len, s)
    cache = init_cache(cfg, b, cache_len)
    x = _embed(params, tokens, cfg)
    enc_out = None
    if cfg.frontend == "audio" and cfg.n_enc_layers:
        enc_out = _encoder_fwd(params, frontend, cfg, ctx, remat=False, kv_block=kv_block)
    elif cfg.frontend == "vision":
        # prefill keeps the visual prefix in the cache; only the final-token
        # logits are consumed, so no prefix-stripping here (contrast fwd)
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    x = ctx.constrain(x, ("b", None, None))
    groups, tail = layer_groups(cfg)
    (pattern, n_super) = groups[0]
    positions = jnp.arange(x.shape[1])[None, :]
    has_cross = cfg.cross_attention and enc_out is not None

    def block_body(x, xs):
        block_p, cross_p = xs
        caches = {}
        for i, kind in enumerate(pattern):
            key = f"p{i}_{kind}"
            if kind == "attn":
                kw = dict(positions=positions, kv_block=kv_block, return_kv=True,
                          window=_attn_window(cfg))
                if has_cross:
                    kw.update(enc_out=enc_out, cross_p=cross_p)
                x, _, (k, v) = _attn_layer_fwd(block_p[key], x, cfg, ctx, **kw)
                eff = _empty_layer_cache(kind, cfg, b, cache_len, k.dtype)["k"].shape[1]
                caches[key] = _fill_attn_cache(k, v, eff, s)
            elif kind == "ssm":
                x, _, st = _ssm_prefill(block_p[key], x, cfg, ctx)
                caches[key] = st
            else:
                x, _, st = _rec_prefill(block_p[key], x, cfg, ctx)
                caches[key] = st
        out = (x, caches)
        if has_cross:
            kc = (enc_out @ cross_p["wk"]).reshape(b, enc_out.shape[1], cfg.n_kv_heads, cfg.hd)
            vc = (enc_out @ cross_p["wv"]).reshape(b, enc_out.shape[1], cfg.n_kv_heads, cfg.hd)
            out = (x, (caches, {"k": kc, "v": vc}))
        return out[0], out[1]

    xs = (params["blocks"], params.get("cross_blocks") if has_cross else None)
    x, ys = jax.lax.scan(block_body, x, xs)
    if has_cross:
        blocks_cache, cross_cache = ys
        cache["blocks"] = blocks_cache
        cache["cross"] = cross_cache
    else:
        cache["blocks"] = ys
    if tail:
        new_tail = []
        for i, kind in enumerate(tail):
            if kind == "attn":
                x, _, (k, v) = _attn_layer_fwd(params["tail"][i], x, cfg, ctx,
                                               positions=positions, kv_block=kv_block,
                                               return_kv=True, window=_attn_window(cfg))
                eff = _empty_layer_cache(kind, cfg, b, cache_len, k.dtype)["k"].shape[1]
                new_tail.append(_fill_attn_cache(k, v, eff, s))
            elif kind == "ssm":
                x, _, st = _ssm_prefill(params["tail"][i], x, cfg, ctx)
                new_tail.append(st)
            else:
                x, _, st = _rec_prefill(params["tail"][i], x, cfg, ctx)
                new_tail.append(st)
        cache["tail"] = new_tail
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x[:, -1:], cfg)
    return logits, cache


def _fill_attn_cache(k, v, eff: int, s: int):
    """Place the last ``eff`` keys/values in ring order (slot = pos % eff)."""
    if eff >= s:
        kpos = jnp.arange(eff, dtype=jnp.int32)
        kpos = jnp.where(kpos < s, kpos, -1)
        pad = eff - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return {"k": kc, "v": vc, "kpos": kpos}
    # ring: keep positions s-eff .. s-1, slot = pos % eff
    last_k = k[:, s - eff :]
    last_v = v[:, s - eff :]
    pos = jnp.arange(s - eff, s, dtype=jnp.int32)
    slots = pos % eff
    order = jnp.argsort(slots)
    return {
        "k": last_k[:, order],
        "v": last_v[:, order],
        "kpos": pos[order],
    }


def _ssm_prefill(p, x, cfg: ModelConfig, ctx: ShardCtx):
    """SSM layer forward that also returns the final recurrent state."""
    s_cfg = cfg.ssm or SSMConfig()
    b, s, d = x.shape
    di = s_cfg.expand * d
    n = s_cfg.d_state
    nheads = di // s_cfg.head_dim
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    proj = y @ p["w_in"]
    z, xs_, Bm, Cm, dt = jnp.split(proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs_, Bm, Cm], axis=-1)
    conv_out, conv_state = ssm_lib.causal_conv1d(conv_in, p["conv_w"])
    xs_, Bm, Cm = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    loga = -jnp.exp(p["A_log"]) * dt
    xh = xs_.reshape(b, s, nheads, s_cfg.head_dim)
    y_ssd, final_state = ssm_lib.ssd_chunked(xh * dt[..., None].astype(xh.dtype), loga, Bm, Cm, chunk=s_cfg.chunk)
    y_ssd = y_ssd + p["D_skip"][None, None, :, None].astype(y_ssd.dtype) * xh
    y_out = y_ssd.reshape(b, s, di) * jax.nn.silu(z)
    y_out = L.rms_norm(y_out, p["out_norm"], cfg.norm_eps)
    return x + y_out @ p["w_out"], jnp.float32(0.0), {"conv": conv_state, "ssd": final_state}


def _rec_prefill(p, x, cfg: ModelConfig, ctx: ShardCtx):
    y = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    bx = y @ p["w_bx"]
    bg = jax.nn.gelu(y @ p["w_bg"])
    conv_out, conv_state = ssm_lib.causal_conv1d(bx, p["conv_w"])
    r, h_final = rglru_lib.rglru_scan(conv_out, p["w_a"], p["b_a"], p["w_xg"], p["b_x"], p["lam"])
    x = x + (r * bg) @ p["w_ro"]
    y = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + (jax.nn.gelu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"]
    return x, jnp.float32(0.0), {"conv": conv_state, "h": h_final}
