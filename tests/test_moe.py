"""The dropless expert layer (``models/moe.py``) and its grouped products
(``kernels/grouped_matmul.py``) on the CPU, and Granite's residual and
logit path in ``models/transformer.py``.

The layer must equal a dense formula, every expert on every token times
a gate that is zero off the top-k, forward and backward, also when every
token goes to one expert (a capacity would drop rows there).  The
megablox kernel, run interpreted, must equal ``jax.lax.ragged_dot``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels import grouped_matmul as G
from repro.models import moe
from repro.models import transformer as T

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
