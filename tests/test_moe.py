"""The dropless expert layer (``models/moe.py``) and its grouped products
(``kernels/grouped_matmul.py``) on the CPU, and Granite's residual and
logit path in ``models/transformer.py``.

The layer must equal a dense formula, every expert on every token times
a gate that is zero off the top-k, forward and backward, also when every
token goes to one expert (a capacity would drop rows there).  Its token
permutation (one sort, routing saved for the remat'd backward, dispatch
and combine with backward passes that only gather) must give the forward
of the same gathers under plain autodiff bit for bit, and their
gradients to rounding; the lowered step must hold one sort of the T·k
(token, slot) keys and no (T, k, D) broadcast or (T·k, D) scatter.  The
megablox kernel, run interpreted, must equal ``jax.lax.ragged_dot``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels import grouped_matmul as G
from repro.launch.hlo_analysis import permutation_census
from repro.models import moe
from repro.models import transformer as T
from repro.models.sharding import NULL_CTX

T_, D, F, E, K = 48, 32, 16, 8, 3


def _weights(seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(ks[0], (2, T_ // 2, D)).astype(dtype),
        router=(0.3 * jax.random.normal(ks[1], (D, E))).astype(dtype),
        wg=(0.2 * jax.random.normal(ks[2], (E, D, F))).astype(dtype),
        wu=(0.2 * jax.random.normal(ks[3], (E, D, F))).astype(dtype),
        wd=(0.2 * jax.random.normal(ks[4], (E, F, D))).astype(dtype))


def _dense(x, router, wg, wu, wd, k):
    """Every expert on every token, times a gate that is zero off the
    top-k: the softmax over the k largest router logits."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    logits = jnp.dot(xf, router, precision="highest")
    top, idx = jax.lax.top_k(logits, k)
    gate = jnp.zeros_like(logits).at[jnp.arange(b * s)[:, None], idx].set(
        jax.nn.softmax(top, axis=-1))
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xf, wg, precision="highest")) * jnp.einsum(
        "td,edf->etf", xf, wu, precision="highest")
    y = jnp.einsum("etf,efd->etd", h, wd, precision="highest")
    return jnp.einsum("te,etd->td", gate, y, precision="highest").reshape(b, s, d)


def _layer(w, k=K):
    return moe.moe_ffn(w["x"], w["router"], w["wg"], w["wu"], w["wd"], k)


@pytest.mark.parametrize("one_expert", [False, True], ids=["spread", "all_to_one"])
def test_layer_equals_the_dense_formula(one_expert):
    w = _weights(1)
    if one_expert:
        # every token's largest logit is expert 3's
        w["x"] = jnp.abs(w["x"])
        w["router"] = w["router"].at[:, 3].set(5.0)
    y, _, load = _layer(w)
    np.testing.assert_allclose(y, _dense(w["x"], w["router"], w["wg"], w["wu"], w["wd"], K),
                               rtol=1e-5, atol=1e-5)
    if one_expert:
        # expert 3 takes all T of the T·K rows: E / K times the mean
        assert float(load) == pytest.approx(E / K)

    def loss(f, w):
        return jnp.sum(f(w["x"], w["router"], w["wg"], w["wu"], w["wd"], K) ** 2)

    got = jax.grad(loss, argnums=1)(lambda *a: moe.moe_ffn(*a)[0], w)
    want = jax.grad(loss, argnums=1)(_dense, w)
    for name in w:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-4, atol=2e-5,
                                   err_msg=name)


def _plain_gathers(x, router, wg, wu, wd, k):
    """The layer's math with its permutation as plain gathers under jax's
    autodiff (whose backward passes scatter): the tokens broadcast to
    (T, k, D) and gathered by expert, the inverse permutation a second
    argsort.  The forward pass of ``moe_ffn`` must equal it bit for bit."""
    b, s, d = x.shape
    e = router.shape[1]
    t = b * s
    xf = x.reshape(t, d)
    logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_logits, top_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    experts = top_idx.reshape(t * k)
    order = jnp.argsort(experts, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.int32), axis=0)
    xs = jnp.broadcast_to(xf[:, None], (t, k, d)).reshape(t * k, d)[order]
    h = jax.nn.silu(G.ragged(xs, wg, sizes)) * G.ragged(xs, wu, sizes)
    ys = G.ragged(h.astype(x.dtype), wd, sizes)
    slots = ys[inverse].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", slots.astype(jnp.float32), gates).astype(x.dtype)
    frac = sizes.astype(jnp.float32) / t
    mean_p = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = e * jnp.sum(frac * mean_p) / k
    load = jnp.max(sizes).astype(jnp.float32) * e / (t * k)
    return y.reshape(b, s, d), aux, load


# (top-k, experts, batch, sequence, dtype, routing): "starve" keeps expert
# 5 off every token's top-k, "favour" puts expert 3 first on every token;
# 37 tokens x top-3 = 111 rows fit no tile; decode is T = B
PERMUTATION_CASES = {
    "k1": (1, 8, 2, 24, jnp.float32, None),
    "k2": (2, 8, 2, 24, jnp.float32, None),
    "k8": (8, 16, 2, 24, jnp.float32, None),
    "expert_with_zero_rows": (2, 8, 2, 24, jnp.float32, "starve"),
    "all_rows_on_one_expert": (1, 8, 2, 24, jnp.float32, "favour"),
    "rows_fit_no_tile": (3, 8, 1, 37, jnp.float32, None),
    "bf16": (2, 8, 2, 24, jnp.bfloat16, None),
    "decode": (2, 8, 4, 1, jnp.float32, None),
}


def _case(name):
    k, e, b, s, dtype, routing = PERMUTATION_CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (b, s, D))
    router = 0.3 * jax.random.normal(ks[1], (D, e))
    if routing == "starve":
        x, router = jnp.abs(x), router.at[:, 5].set(-5.0)
    elif routing == "favour":
        x, router = jnp.abs(x), router.at[:, 3].set(5.0)
    w = dict(x=x, router=router,
             wg=0.2 * jax.random.normal(ks[2], (e, D, F)),
             wu=0.2 * jax.random.normal(ks[3], (e, D, F)),
             wd=0.2 * jax.random.normal(ks[4], (e, F, D)))
    w = {n: v.astype(dtype) for n, v in w.items()}
    ct = jax.random.normal(ks[5], (b, s, D))
    return w, k, ct


def _grads(layer, w, k, ct, balance):
    def loss(w):
        y, aux, _ = layer(w["x"], w["router"], w["wg"], w["wu"], w["wd"], k)
        return jnp.sum(y.astype(jnp.float32) * ct) + balance * aux
    return jax.grad(loss)(w)


@pytest.mark.parametrize("name", list(PERMUTATION_CASES))
def test_permutation_matches_plain_gathers_and_the_dense_formula(name):
    w, k, ct = _case(name)
    e = w["router"].shape[1]
    args = (w["x"], w["router"], w["wg"], w["wu"], w["wd"], k)
    got, want = moe.moe_ffn(*args), _plain_gathers(*args)
    for g, w_, what in zip(got, want, ("output", "balance term", "load")):
        np.testing.assert_array_equal(g, w_, err_msg=what)
    t = w["x"].shape[0] * w["x"].shape[1]
    chosen = np.asarray(jax.lax.top_k(jnp.dot(
        w["x"].reshape(t, D).astype(jnp.float32), w["router"].astype(jnp.float32),
        precision="highest"), k)[1])
    if name == "expert_with_zero_rows":
        assert 5 not in chosen
    if name == "all_rows_on_one_expert":
        assert (chosen == 3).all() and float(got[2]) == e

    # gradients, the balance term's included, against the plain gathers:
    # they differ only in the order of f32 sums (and, in bf16, where the
    # plain backward adds a token's k slots in bf16 and moe_ffn in f32)
    bf16 = w["x"].dtype == jnp.bfloat16
    got_g = _grads(moe.moe_ffn, w, k, ct, 0.1)
    want_g = _grads(_plain_gathers, w, k, ct, 0.1)
    for n in w:
        a, b_ = np.asarray(got_g[n], np.float32), np.asarray(want_g[n], np.float32)
        scale = float(np.abs(b_).max())
        tol = (2.0 ** -6 if bf16 else 1e-5)
        np.testing.assert_allclose(a, b_, rtol=tol, atol=tol * scale, err_msg=n)

    # and the dense formula, every expert on every token (in f32 from the
    # same weights: bf16 rounds the products' inputs and outputs)
    up = {n: v.astype(jnp.float32) for n, v in w.items()}
    dense = _dense(up["x"], up["router"], up["wg"], up["wu"], up["wd"], k)
    rel = 2e-2 if bf16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[0], np.float32), dense, rtol=rel,
                               atol=rel * float(np.abs(dense).max()))
    want_d = _grads(lambda *a: (_dense(*a), 0.0, 0.0), up, k, ct, 0.0)
    got_d = _grads(moe.moe_ffn, w, k, ct, 0.0)
    for n in w:
        a, b_ = np.asarray(got_d[n], np.float32), np.asarray(want_d[n])
        if bf16:
            assert np.linalg.norm(a - b_) <= 2e-2 * np.linalg.norm(b_), n
        else:
            np.testing.assert_allclose(a, b_, rtol=2e-4, atol=2e-5, err_msg=n)


def _granite_block():
    cfg = dataclasses.replace(get_smoke_config("granite-moe-1b-a400m"), n_layers=1)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab)
    return cfg, params, {"tokens": toks, "labels": toks}


def test_remat_keeps_the_routing_and_sorts_once():
    """The gradient of a remat'd granite block (one MoE layer, 48 tokens,
    top-2 of 4 experts, width 128), lowered on the CPU: one sort of the 96
    (token, slot) keys, no (T, k, D) broadcast, no scatter of 96 rows; the
    remat'd gradient equals the one with nothing recomputed."""
    cfg, params, batch = _granite_block()
    t, k, d = 48, cfg.moe.top_k, cfg.d_model

    def grad(remat):
        return jax.jit(jax.grad(lambda p: T.loss_fn(p, batch, cfg, remat=remat,
                                                    kv_block=0)[0]))

    text = grad(True).lower(params).as_text()
    assert permutation_census(text, t, k, d) == {"sorts": 1, "broadcasts": 0, "scatters": 0}
    for a, b_ in zip(jax.tree.leaves(grad(True)(params)), jax.tree.leaves(grad(False)(params))):
        np.testing.assert_array_equal(a, b_)

    # the census sees what the plain gathers do, remat'd with no policy:
    # the forward's two sorts again in the recompute, the broadcast in
    # both, and the scatters that are the two gathers' transposes
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, d), jnp.bfloat16)
    p = params["blocks"]["p0_attn"]
    weights = [p[n][0] for n in ("router", "we_g", "we_u", "we_d")]

    def scanned(x, *w):
        body = jax.checkpoint(lambda h, _: (h + _plain_gathers(h, *w, k)[0], None),
                              prevent_cse=False)
        return jnp.sum(jax.lax.scan(body, x, None, length=1)[0].astype(jnp.float32))

    seen = permutation_census(jax.jit(jax.grad(scanned, argnums=(0, 1, 2, 3, 4))).lower(
        x, *weights).as_text(), t, k, d)
    assert seen == {"sorts": 4, "broadcasts": 2, "scatters": 2}


def test_remat_policy_leaves_a_dense_block_as_it_was():
    """The block's save-only-the-routing policy names nothing a dense block
    computes, so a dense block's gradient lowers to the same text with it
    as without; a MoE block's does not."""
    policy = jax.checkpoint_policies.save_only_these_names(*moe.ROUTING)

    def lowered(cfg, params, policy):
        block_p = jax.tree.map(lambda a: a[0], params["blocks"]["p0_attn"])
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.d_model), jnp.bfloat16)
        positions = jnp.arange(x.shape[1])[None, :]

        def block(x, p):
            return T._layer_fwd("attn", p, x, cfg, NULL_CTX, positions=positions,
                                kv_block=0)[0]

        body = jax.checkpoint(block, prevent_cse=False, policy=policy)
        loss = lambda x, p: jnp.sum(body(x, p).astype(jnp.float32))  # noqa: E731
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, block_p).as_text()

    dense = dataclasses.replace(get_smoke_config("h2o-danube-1.8b"), n_layers=1)
    dense_params = T.init_params(dense, jax.random.PRNGKey(0))
    assert lowered(dense, dense_params, None) == lowered(dense, dense_params, policy)
    cfg, params, _ = _granite_block()
    assert lowered(cfg, params, None) != lowered(cfg, params, policy)


def test_load_balance_term_and_load():
    w = _weights(2)
    _, aux, load = _layer(w)
    xf = w["x"].reshape(T_, D)
    logits = jnp.dot(xf, w["router"], precision="highest")
    idx = jax.lax.top_k(logits, K)[1]
    rows = np.bincount(np.asarray(idx).ravel(), minlength=E)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1)).mean(axis=0)
    assert float(aux) == pytest.approx(E * np.sum(rows / T_ * probs) / K, rel=1e-5)
    assert float(load) == pytest.approx(rows.max() / rows.mean(), rel=1e-6)


@pytest.mark.parametrize("sizes", [[40, 0, 100, 5, 0, 83, 12, 16], [256, 0, 0, 0, 0, 0, 0, 0]],
                         ids=["uneven", "one_group"])
def test_megablox_interpreted_equals_ragged_dot(sizes):
    m = sum(sizes)
    assert G.kernel_applies(m)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(ks[0], (m, D))
    rhs = jax.random.normal(ks[1], (len(sizes), D, F))
    cot = jax.random.normal(ks[2], (m, F))
    gs = jnp.asarray(sizes, jnp.int32)

    def run(f):
        out, vjp = jax.vjp(lambda a, b: f(a, b, gs), lhs, rhs)
        return (out,) + vjp(cot)

    got = run(lambda a, b, g: G.gmm(a, b, g, interpret=True))
    want = run(G.ragged)
    for g, w_, what in zip(got, want, ("out", "d_lhs", "d_rhs")):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-4, err_msg=what)


def test_tiling_divides_the_rows():
    tm, tk, tn = G.TILING
    assert G.tiling(65536, 1024, 512) == (tm, min(tk, 1024), min(tn, 512))
    assert G.tiling(384, 64, 32) == (128, 64, 32)
    assert not G.kernel_applies(200)
    assert moe.kernel_applies(65536, 1, "tpu")
    assert not moe.kernel_applies(65536, 2, "tpu") and not moe.kernel_applies(65536, 1, "cpu")


def test_granite_takes_its_published_path():
    full, smoke = get_config("granite-moe-1b-a400m"), get_smoke_config("granite-moe-1b-a400m")
    for cfg in (full, smoke):
        assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
                cfg.logits_scaling, cfg.tie_embeddings) == (12.0, 0.015625, 0.22, 6.0, True)
    params = T.init_params(smoke, jax.random.PRNGKey(0))
    assert "embed" not in params and params["lm_head"].shape == (smoke.d_model, smoke.vocab)
    # the tied lookup reads the head's columns, times the multiplier
    tok = jnp.array([[3, 17]])
    np.testing.assert_array_equal(T._embed(params, tok, smoke),
                                  params["lm_head"].T[tok] * 12.0)
    # each of the four multipliers moves the logits
    plain = dataclasses.replace(smoke, embedding_multiplier=1.0, attention_multiplier=0.0,
                                residual_multiplier=1.0, logits_scaling=1.0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, smoke.vocab)
    base = T.forward(params, toks, plain, kv_block=0)[0]
    for field, value in (("embedding_multiplier", 12.0), ("attention_multiplier", 0.015625),
                         ("residual_multiplier", 0.22), ("logits_scaling", 6.0)):
        moved = T.forward(params, toks, dataclasses.replace(plain, **{field: value}),
                          kv_block=0)[0]
        assert not np.allclose(moved, base), field


def test_forward_reports_the_expert_load():
    cfg = get_smoke_config("granite-moe-1b-a400m")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss, stats = T.loss_fn(params, batch, cfg, kv_block=0)
    assert set(stats) == {"expert_load"}
    assert 1.0 <= float(stats["expert_load"]) <= cfg.moe.num_experts / cfg.moe.top_k
    dense = get_smoke_config("h2o-danube-1.8b")
    assert T.loss_fn(T.init_params(dense, jax.random.PRNGKey(0)), batch, dense,
                     kv_block=0)[1] == {}


def test_train_cli_prints_the_expert_load(capsys):
    from repro.launch import train

    assert train.main(["--config", "granite-moe-1b-a400m", "--smoke", "--steps", "2",
                       "--device-steps", "2", "--workers", "1", "--seq-len", "32",
                       "--global-batch", "2", "--strategy", "bucketed"]) == 0
    lines = [line for line in capsys.readouterr().out.splitlines() if line.startswith("step")]
    assert len(lines) == 1 and "expert load" in lines[0]
    load = float(lines[0].split("expert load")[1])
    assert 1.0 <= load <= 2.0  # the smoke config's 4 experts, top-2: at most 4 / 2
