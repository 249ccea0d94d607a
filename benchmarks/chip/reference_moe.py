"""Plain float32 reference of a robust data-parallel AdamW step on a
sparse-expert decoder LM (granite-3.0-1b-a400m's block).

Independent of ``src/repro``: it reads the configuration file's sizes,
makes its own weights and tokens from the seed, and computes in float32
under ``Precision.HIGHEST``.  What it follows:

- the embedding: a token's column of the tied (D, V) head matrix, times
  ``embedding_multiplier``;
- per layer: RMSNorm (scale ``1 + w``) -> GQA causal attention with
  rotate-half RoPE, scores times ``attention_multiplier`` -> residual
  plus ``residual_multiplier`` times the branch -> RMSNorm -> the expert
  layer -> residual plus ``residual_multiplier`` times it; final RMSNorm,
  the tied head, logits divided by ``logits_scaling``, mean token cross
  entropy;
- the expert layer, computed densely: router logits ``y Wr``, the gates
  the softmax over the ``num_experts_per_tok`` largest logits and zero
  elsewhere, every expert ``(silu(y Wg_e) * (y Wu_e)) Wd_e`` on every
  token, and their gate-weighted sum.  No sort, no capacity, no kernel;
- the load-balance term the loss adds, 0.01 times, per layer ``E / k``
  times the sum over experts of (routed rows' share of the worker's
  tokens) times (the expert's mean router probability over them), the
  Switch form;
- the data, the weights' draw, the attack, the median and AdamW of
  ``reference_decoder`` (imported from it).

Departures from the published model: the norm is written as scale
``1 + w`` with ``w`` initialised at 0 (an equivalent reparametrisation);
the load-balance weight is the program's 0.01; the weights are the
program's draw (N(0, 0.02), output projections and each expert's down
projection scaled by ``1/sqrt(2 L)``), not trained ones.

Rows of a worker are processed one at a time under ``jax.checkpoint``;
the load-balance term needs the worker's tokens together, so each row
adds its routed-row counts and router probabilities to the scan's carry
and the term is formed after the last row.

``precision="fp8"`` rounds every matmul operand and its gradient to
float8 e4m3 (the router's among them); ``fault`` plants ``half_batch``
or ``no_exchange`` as in ``reference_decoder``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip.reference_decoder import (  # noqa: F401  (leaf_names: the interface)
    NEG_INF, STD, _adamw, _attack, _mm, _rms, _rope, alie, leaf_names, lm_tokens, median)

AUX_WEIGHT = 0.01


def sizes(c: dict) -> dict:
    """The reference's view of a configuration file (HF key names)."""
    if not c.get("num_local_experts") or not c.get("tie_word_embeddings"):
        raise ValueError("the reference follows tied sparse-expert decoders only")
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"], "D": d, "H": h,
        "KV": c["num_key_value_heads"], "hd": c.get("head_dim") or d // h,
        "V": c["vocab_size"], "F": c["intermediate_size"],
        "E": c["num_local_experts"], "K": c["num_experts_per_tok"],
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "emb": float(c["embedding_multiplier"]), "att": float(c["attention_multiplier"]),
        "res": float(c["residual_multiplier"]), "logits": float(c["logits_scaling"]),
        "dtype": jnp.dtype(c["torch_dtype"]),
    }


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _layer_init(key, z: dict) -> dict:
    d, f, e, hd, dt = z["D"], z["F"], z["E"], z["hd"], z["dtype"]
    ks = jax.random.split(key, 10)
    out_std = STD / jnp.sqrt(2.0 * z["L"])
    return {
        "ln1": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "wq": (STD * jax.random.normal(ks[0], (d, z["H"] * hd))).astype(dt),
        "wk": (STD * jax.random.normal(ks[1], (d, z["KV"] * hd))).astype(dt),
        "wv": (STD * jax.random.normal(ks[2], (d, z["KV"] * hd))).astype(dt),
        "wo": (out_std * jax.random.normal(ks[3], (z["H"] * hd, d))).astype(dt),
        "router": (STD * jax.random.normal(ks[4], (d, e))).astype(dt),
        "we_g": (STD * jax.random.normal(ks[5], (e, d, f))).astype(dt),
        "we_u": (STD * jax.random.normal(ks[6], (e, d, f))).astype(dt),
        "we_d": (out_std * jax.random.normal(ks[7], (e, f, d))).astype(dt),
    }


def init_params(c: dict, key) -> dict:
    """Weights in the configuration's dtype: ``{lm_head (D, V), final_norm,
    layers: {name: (L, ...)}}``, from ``key = PRNGKey(seed)``."""
    z = sizes(c)
    _, k_head, k_layers = jax.random.split(key, 7)[:3]
    layer_keys = jax.random.split(k_layers, z["L"])
    return {
        "final_norm": jnp.zeros((z["D"],), z["dtype"]),
        "lm_head": (STD * jax.random.normal(k_head, (z["D"], z["V"]))).astype(z["dtype"]),
        "layers": jax.vmap(lambda k: _layer_init(jax.random.split(k, 1)[0], z))(layer_keys),
    }


# ---------------------------------------------------------------------------
# forward of one sequence
# ---------------------------------------------------------------------------


def _attention(p, y, z, precision):
    s = y.shape[0]
    h, kv, hd = z["H"], z["KV"], z["hd"]
    q = _rope(_mm("sd,de->se", y, p["wq"], precision).reshape(s, h, hd), z["theta"])
    k = _rope(_mm("sd,de->se", y, p["wk"], precision).reshape(s, kv, hd), z["theta"])
    v = _mm("sd,de->se", y, p["wv"], precision).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    logits = _mm("qhd,khd->hqk", q, k, precision) * z["att"]
    pos = jnp.arange(s)
    probs = jax.nn.softmax(jnp.where((pos[None, :] <= pos[:, None])[None], logits, NEG_INF),
                           axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, precision).reshape(s, h * hd)
    return _mm("se,ed->sd", o, p["wo"], precision)


def _experts(p, y, z, precision):
    """(output (S, D), routed rows per expert (E,), router probabilities
    summed over the tokens (E,))."""
    logits = _mm("sd,de->se", y, p["router"], precision)
    top, idx = jax.lax.top_k(logits, z["K"])
    routed = jax.nn.one_hot(idx, z["E"], dtype=jnp.float32)  # (S, K, E)
    gates = jnp.einsum("sk,ske->se", jax.nn.softmax(top, axis=-1), routed)
    h = jax.nn.silu(_mm("sd,edf->esf", y, p["we_g"], precision)) * _mm(
        "sd,edf->esf", y, p["we_u"], precision)
    out = _mm("esf,efd->esd", h, p["we_d"], precision)
    y = jnp.einsum("se,esd->sd", gates, out)
    return y, jnp.sum(routed, axis=(0, 1)), jnp.sum(jax.nn.softmax(logits, axis=-1), axis=0)


def _seq_forward(params, tokens, z, precision):
    """Final hidden states (S, D), and per layer the routed rows (L, E)
    and summed router probabilities (L, E)."""
    x = params["lm_head"].T[tokens] * z["emb"]

    @jax.checkpoint
    def layer(x, p):
        x = x + z["res"] * _attention(p, _rms(x, p["ln1"], z["eps"]), z, precision)
        y, rows, probs = _experts(p, _rms(x, p["ln2"], z["eps"]), z, precision)
        return x + z["res"] * y, (rows, probs)

    x, (rows, probs) = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"], z["eps"]), rows, probs


def _seq_ce(params, x, labels, z, precision):
    logits = _mm("sd,dv->sv", x, params["lm_head"], precision) / z["logits"]
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def worker_loss(params, tokens, labels, z, precision="f32"):
    """Mean cross entropy over the rows plus ``AUX_WEIGHT`` times the
    load-balance term over the worker's tokens, summed over layers."""
    rows_n, seq = tokens.shape

    @jax.checkpoint
    def row(carry, tl):
        total, routed, probs = carry
        t, l = tl
        x, r, p = _seq_forward(params, t, z, precision)
        return (total + _seq_ce(params, x, l, z, precision) / rows_n,
                routed + r, probs + p), None

    zeros = jnp.zeros((z["L"], z["E"]), jnp.float32)
    (total, routed, probs), _ = jax.lax.scan(
        row, (jnp.float32(0.0), zeros, zeros), (tokens, labels))
    n = rows_n * seq
    balance = z["E"] * jnp.sum((routed / n) * (probs / n)) / z["K"]
    return total + AUX_WEIGHT * balance


# ---------------------------------------------------------------------------
# the robust step (as reference_decoder's)
# ---------------------------------------------------------------------------


def make_grads(c: dict, traffic: dict, mesh, precision: str = "f32",
               fault: Optional[str] = None):
    """jit'd ``(params, tokens, labels) -> (losses (m,), grads (m, ...))``
    over the workers laid out on ``mesh``'s ``w`` axis."""
    z = sizes(c)
    rows = traffic["batch_per_worker"]
    if fault == "half_batch":
        rows //= 2

    def local(params, tok, lab):
        def one(tl):
            return jax.value_and_grad(worker_loss)(
                params, tl[0][:rows], tl[1][:rows], z, precision)
        return jax.lax.map(one, (tok, lab))

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("w"), P("w")),
        out_specs=(P("w"), P("w")), check_vma=False))


def run(c: dict, traffic: dict, seed: int, *, steps: int = 2,
        precision: str = "f32", fault: Optional[str] = None,
        devices=None, against: Optional[Dict] = None,
        keep_moments: bool = False) -> Dict:
    """The first ``steps`` (1 or 2) steps of a cell: what
    ``reference_decoder.run`` returns, for this model."""
    if steps not in (1, 2):
        raise ValueError("the reference follows one or two steps")
    z = sizes(c)
    m, rows, seq, lr = (traffic[k] for k in ("workers", "batch_per_worker", "seq_len", "lr"))
    q, strength = _attack(traffic)
    devices = list(devices or jax.devices())[:m]
    if m % len(devices):
        raise ValueError(f"{m} workers do not split over {len(devices)} devices")
    mesh = Mesh(np.array(devices), ("w",))
    rep = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.float32), init_params(c, k)), out_shardings=rep)(key)
        grads_fn = make_grads(c, traffic, mesh, precision, fault)
        tokens_fn = jax.jit(
            lambda k, t: jax.vmap(lambda w: lm_tokens(k, t, w, rows, seq, z["V"]))(
                jnp.arange(m)), out_shardings=NamedSharding(mesh, P("w")))

        def update_leaf(p, stacked, g_first, t):
            g = stacked[0] if fault == "no_exchange" else median(alie(stacked, q, strength))
            new_p, mom = _adamw(p, g, g_first, t, lr)
            return new_p, g, mom, jnp.sum(g * g)

        update = jax.jit(update_leaf, static_argnums=3, donate_argnums=(0,),
                         out_shardings=rep)
        losses, gnorms = [], []
        first_g = moments = None
        for t in range(steps):
            tok, lab = tokens_fn(key, t)
            loss, grads = grads_fn(params, tok, lab)
            losses.append(float(jnp.mean(loss)))
            flat_p, tdef = jax.tree.flatten(params)
            flat_g = jax.tree.leaves(grads)
            flat_first = jax.tree.leaves(first_g) if t else [None] * len(flat_p)
            del grads, params, first_g
            new_p, new_g, new_m, sq = [], [], [], []
            for i in range(len(flat_p)):
                p_i, g_i, mom_i, sq_i = update(flat_p[i], flat_g[i], flat_first[i], t)
                flat_p[i] = flat_g[i] = flat_first[i] = None
                new_p.append(p_i)
                new_g.append(g_i if t == 0 else None)
                new_m.append(mom_i if t == steps - 1 else None)
                sq.append(sq_i)
            gnorms.append(math.sqrt(sum(map(float, sq))))
            params = jax.tree.unflatten(tdef, new_p)
            first_g = jax.device_get(jax.tree.unflatten(tdef, new_g)) if t == 0 else None
            moments = new_m
            del new_p, new_g, new_m
        del first_g
        names = leaf_names(jax.tree.unflatten(tdef, moments))
        m_norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(x * x)) for x in xs])(moments)
        out = {}
        if against is not None:
            dist = jax.jit(lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)))
            out["moment_error"] = {n: float(dist(x, against[n])) for n, x in zip(names, moments)}
        if keep_moments:
            out["moments"] = dict(zip(names, jax.device_get(moments)))
        del moments
        p0 = jax.jit(lambda k: init_params(c, k), out_shardings=rep)(key)
        d_norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum((x - y.astype(jnp.float32)) ** 2))
                                        for x, y in zip(a, b)])(
            jax.tree.leaves(params), jax.tree.leaves(p0))
        del params, p0
    return {
        "loss": float(np.mean(losses)),
        "grad_norm": float(np.mean(gnorms)),
        "moment_norms": dict(zip(names, map(float, m_norms))),
        "change_norms": dict(zip(names, map(float, d_norms))),
        **out,
    }
