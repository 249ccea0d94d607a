"""Plain float32 reference of a robust data-parallel AdamW step on a decoder LM.

Independent of ``src/repro``: it reads the configuration file's sizes,
makes its own weights and tokens from the seed, and computes in float32
under ``Precision.HIGHEST``.  What it follows:

- the decoder: token embedding, per layer RMSNorm (scale ``1 + w``) ->
  GQA causal attention with rotate-half RoPE (head ``h`` reads KV head
  ``h // (heads / kv_heads)``, optional sliding window) -> residual ->
  RMSNorm -> FFN -> residual, final RMSNorm, untied LM head, mean token
  cross entropy;
- FFN ``swiglu``: ``(silu(y Wg) * (y Wu)) Wd``; a configuration with
  sparse experts is refused;
- the data: the synthetic LM stream (next token ``(5 t + 7) mod V`` with
  probability 0.9, else uniform), keyed by (seed, step, worker);
- the weights: N(0, 0.02) matrices, output projections scaled by
  ``1/sqrt(2 L)``, norm scales 0, rounded to the configuration's dtype
  (the weights *are* those rounded values; everything after is float32);
- per step: each worker's gradient of its own rows' loss, the attack on
  the Byzantine rows (``alie``: honest mean minus ``strength`` honest
  standard deviations, workers ``0 .. ceil(alpha m) - 1``), the
  coordinate-wise median over the m rows, then AdamW (b1 0.9, b2 0.999,
  eps 1e-8, no weight decay, bias-corrected).

Rows of a worker are processed one at a time under ``jax.checkpoint`` so
the reference fits beside nothing else on one chip.

``precision="fp8"`` is the control: every matmul operand, and the
gradient that flows back into it, is rounded to float8 e4m3 with a
per-tensor scale, the step below the bfloat16 the configurations state.  ``fault`` plants one of the
faults the comparison has to catch: ``half_batch`` (each worker's loss is
the mean over its first half of rows) and ``no_exchange`` (the step takes
worker 0's own gradient, with no aggregation over workers).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
STD = 0.02
B1, B2, EPS = 0.9, 0.999, 1e-8
VAR_EPS = 1e-12
E4M3_MAX = 448.0


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(c: dict) -> dict:
    """The reference's view of a configuration file (HF key names)."""
    if c.get("num_local_experts", 0):
        raise ValueError("the reference follows dense decoders only")
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"], "D": d, "H": h,
        "KV": c["num_key_value_heads"], "hd": c.get("head_dim") or d // h,
        "V": c["vocab_size"], "F": c["intermediate_size"],
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "window": c.get("sliding_window") or 0, "dtype": jnp.dtype(c["torch_dtype"]),
    }


# ---------------------------------------------------------------------------
# weights and tokens from the seed
# ---------------------------------------------------------------------------


def _layer_init(key, z: dict) -> dict:
    d, f, hd, dt = z["D"], z["F"], z["hd"], z["dtype"]
    ks = jax.random.split(key, 10)
    out_std = STD / jnp.sqrt(2.0 * z["L"])
    return {
        "ln1": jnp.zeros((d,), dt),
        "ln2": jnp.zeros((d,), dt),
        "wq": (STD * jax.random.normal(ks[0], (d, z["H"] * hd))).astype(dt),
        "wk": (STD * jax.random.normal(ks[1], (d, z["KV"] * hd))).astype(dt),
        "wv": (STD * jax.random.normal(ks[2], (d, z["KV"] * hd))).astype(dt),
        "wo": (out_std * jax.random.normal(ks[3], (z["H"] * hd, d))).astype(dt),
        "wg": (STD * jax.random.normal(ks[4], (d, f))).astype(dt),
        "wu": (STD * jax.random.normal(ks[5], (d, f))).astype(dt),
        "wd": (out_std * jax.random.normal(ks[6], (f, d))).astype(dt),
    }


def init_params(c: dict, key) -> dict:
    """Weights in the configuration's dtype: ``{embed, lm_head, final_norm,
    layers: {name: (L, ...)}}``, from ``key = PRNGKey(seed)``."""
    z = sizes(c)
    k_embed, k_head, k_layers = jax.random.split(key, 7)[:3]
    layer_keys = jax.random.split(k_layers, z["L"])
    return {
        "embed": (STD * jax.random.normal(k_embed, (z["V"], z["D"]))).astype(z["dtype"]),
        "final_norm": jnp.zeros((z["D"],), z["dtype"]),
        "lm_head": (STD * jax.random.normal(k_head, (z["D"], z["V"]))).astype(z["dtype"]),
        "layers": jax.vmap(lambda k: _layer_init(jax.random.split(k, 1)[0], z))(layer_keys),
    }


def lm_tokens(key, step, worker, rows: int, seq: int, vocab: int):
    """(tokens, labels), each (rows, seq) int32, of one worker at one step;
    ``key = PRNGKey(seed)``."""
    key = jax.random.fold_in(jax.random.fold_in(key, step), worker)
    k1, k2, _ = jax.random.split(key, 3)
    first = jax.random.randint(k1, (rows, 1), 0, vocab)

    def nxt(tok, ks):
        noise = jax.random.randint(ks[0], tok.shape, 0, vocab)
        keep = jax.random.bernoulli(ks[1], 0.9, tok.shape)
        tok = jnp.where(keep, (5 * tok + 7) % vocab, noise)
        return tok, tok

    keys = jax.random.split(k2, 2 * seq).reshape(seq, 2, -1)
    _, toks = jax.lax.scan(nxt, first[:, 0], keys)
    stream = jnp.concatenate([first, toks.T], axis=1)
    return stream[:, :-1], stream[:, 1:]


# ---------------------------------------------------------------------------
# forward of one sequence
# ---------------------------------------------------------------------------


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """A matmul operand in float8: rounded on the way in, and its gradient
    rounded on the way back, as a float8 training step keeps both."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (_round_fp8(g),))


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):  # x (S, heads, hd)
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, y, z, precision):
    s = y.shape[0]
    h, kv, hd = z["H"], z["KV"], z["hd"]
    q = _rope(_mm("sd,de->se", y, p["wq"], precision).reshape(s, h, hd), z["theta"])
    k = _rope(_mm("sd,de->se", y, p["wk"], precision).reshape(s, kv, hd), z["theta"])
    v = _mm("sd,de->se", y, p["wv"], precision).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    logits = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if z["window"]:
        ok &= pos[None, :] > pos[:, None] - z["window"]
    probs = jax.nn.softmax(jnp.where(ok[None], logits, NEG_INF), axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, precision).reshape(s, h * hd)
    return _mm("se,ed->sd", o, p["wo"], precision)


def _seq_forward(params, tokens, z, precision):
    """Final hidden states (S, D)."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(p, _rms(x, p["ln1"], z["eps"]), z, precision)
        y = _rms(x, p["ln2"], z["eps"])
        h = jax.nn.silu(_mm("sd,df->sf", y, p["wg"], precision)) * _mm(
            "sd,df->sf", y, p["wu"], precision)
        return x + _mm("sf,fd->sd", h, p["wd"], precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"], z["eps"])


def _seq_ce(params, x, labels, precision):
    logits = _mm("sd,dv->sv", x, params["lm_head"], precision)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def worker_loss(params, tokens, labels, z, precision="f32"):
    """Mean cross entropy over the rows."""
    rows = tokens.shape[0]

    @jax.checkpoint
    def row(total, tl):
        t, l = tl
        ce = _seq_ce(params, _seq_forward(params, t, z, precision), l, precision)
        return total + ce / rows, None

    total, _ = jax.lax.scan(row, jnp.float32(0.0), (tokens, labels))
    return total


# ---------------------------------------------------------------------------
# the robust step
# ---------------------------------------------------------------------------


def alie(rows, q: int, strength: float):
    """Replace rows 0..q-1 by the honest mean minus ``strength`` std."""
    if q == 0:
        return rows
    honest = rows[q:]
    mean = jnp.mean(honest, axis=0)
    std = jnp.sqrt(jnp.mean((honest - mean) ** 2, axis=0) + VAR_EPS)
    bad = mean - strength * std
    return rows.at[:q].set(jnp.broadcast_to(bad, rows[:q].shape))


def median(rows):
    s = jnp.sort(rows, axis=0)
    m = rows.shape[0]
    if m % 2:
        return s[m // 2]
    return 0.5 * (s[m // 2 - 1] + s[m // 2])


def num_byzantine(alpha: float, m: int) -> int:
    return min(m - 1, math.ceil(alpha * m)) if alpha > 0 else 0


def _adamw(p, g, g_first, t: int, lr: float):
    """AdamW at step t (0-based) with the moments written out from the
    gradients seen so far: t = 0 starts from zero moments, t = 1 from the
    moments step 0 left, which are functions of its gradient alone.
    Returns (new params, first moment)."""
    if t == 0:
        m = (1 - B1) * g
        v = (1 - B2) * g * g
    else:
        m = B1 * ((1 - B1) * g_first) + (1 - B1) * g
        v = B2 * ((1 - B2) * g_first * g_first) + (1 - B2) * g * g
    c1 = 1.0 - B1 ** (t + 1)
    c2 = 1.0 - B2 ** (t + 1)
    return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS)), m


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(k, simple=True, separator=".")
            for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _attack(traffic: dict):
    attack = traffic.get("attack") or {}
    name = attack.get("name", "none")
    if name not in ("none", "alie"):
        raise ValueError(f"the reference knows no attack {name!r}")
    q = num_byzantine(attack.get("alpha", 0.0), traffic["workers"]) if name == "alie" else 0
    return q, attack.get("strength", 1.0)


def make_grads(c: dict, traffic: dict, mesh, precision: str = "f32",
               fault: Optional[str] = None):
    """jit'd ``(params, tokens, labels) -> (losses (m,), grads (m, ...))``
    over the workers laid out on ``mesh``'s ``w`` axis."""
    z = sizes(c)
    rows = traffic["batch_per_worker"] // 2 if fault == "half_batch" else traffic["batch_per_worker"]

    def local(params, tok, lab):  # this device's workers
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
    """The first ``steps`` (1 or 2) steps of a cell.  Returns the mean loss
    and mean aggregated-gradient norm over them, and per leaf the norm of
    the first moment and of the change of the parameters after them.
    ``against`` (per leaf, first moments from elsewhere) adds per leaf the
    norm of their difference from these (``moment_error``);
    ``keep_moments`` returns these first moments, copied to the host."""
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
    key = jax.random.PRNGKey(seed)  # an argument, so no program depends on the seed

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.float32), init_params(c, k)),
            out_shardings=rep)(key)
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
            # the first step's gradient waits on the host, so that the
            # second step's backward pass has the chip to itself
            first_g = jax.device_get(jax.tree.unflatten(tdef, new_g)) if t == 0 else None
            moments = new_m
            del new_p, new_g, new_m
        del first_g
        names = leaf_names(jax.tree.unflatten(tdef, moments))
        norm = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(x * x)) for x in xs])
        m_norms = norm(moments)
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
