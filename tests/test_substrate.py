"""Substrate tests: data pipeline, optimizers, checkpointing, configs,
attacks, sharding rules, HLO analyzer."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_config, get_smoke_config
from repro.core.attacks import AttackConfig, label_flip
from repro.data.pipeline import DataConfig, make_classification_shards, make_lm_batch
from repro.data.synthetic import lm_batch, mnist_analog
from repro.models import transformer as T
from repro.models.sharding import param_partition_spec
from repro.optim.optimizers import get_optimizer

KEY = jax.random.PRNGKey(0)


class TestData:
    def test_lm_batch_learnable_structure(self):
        b = lm_batch(KEY, 4, 64, vocab=97)
        toks, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
        assert toks.shape == (4, 64) and labels.shape == (4, 64)
        # ~90% of labels follow the deterministic next-token rule
        frac = ((5 * toks + 7) % 97 == labels).mean()
        assert 0.8 < frac <= 1.0

    def test_lm_batch_matches_eager_per_token_loop(self):
        """The compiled scan draws exactly the stream the per-token eager
        loop it replaced drew: same keys, same ops, bit for bit."""
        batch, seq, vocab = 3, 40, 97
        k1, k2, _ = jax.random.split(KEY, 3)
        toks = [jax.random.randint(k1, (batch, 1), 0, vocab)[:, 0]]
        keys = jax.random.split(k2, 2 * seq).reshape(seq, 2, -1)
        for i in range(seq):
            nxt = (5 * toks[-1] + 7) % vocab
            noise = jax.random.randint(keys[i, 0], (batch,), 0, vocab)
            pick = jax.random.bernoulli(keys[i, 1], 0.9, (batch,))
            toks.append(jnp.where(pick, nxt, noise))
        stream = np.asarray(jnp.stack(toks, axis=1))
        b = lm_batch(KEY, batch, seq, vocab)
        np.testing.assert_array_equal(np.asarray(b["tokens"]), stream[:, :-1])
        np.testing.assert_array_equal(np.asarray(b["labels"]), stream[:, 1:])

    def test_mnist_analog_separable(self):
        d = mnist_analog(KEY, 2000)
        assert d["x"].shape == (2000, 784)
        assert set(np.unique(np.asarray(d["y"]))) <= set(range(10))

    def test_label_flip(self):
        y = jnp.array([0, 1, 9])
        np.testing.assert_array_equal(np.asarray(label_flip(y)), [9, 8, 0])

    def test_byzantine_shards_corrupted(self):
        cfg = DataConfig(kind="mnist", global_batch=400, num_workers=4, seed=1)
        atk = AttackConfig("label_flip", alpha=0.25)
        clean = make_classification_shards(cfg, None)
        bad = make_classification_shards(cfg, atk)
        # worker 0 corrupted, others identical
        assert not np.array_equal(np.asarray(clean["y"][0]), np.asarray(bad["y"][0]))
        np.testing.assert_array_equal(np.asarray(clean["y"][1:]), np.asarray(bad["y"][1:]))
        np.testing.assert_array_equal(
            np.asarray(bad["y"][0]), 9 - np.asarray(clean["y"][0]))

    def test_lm_batch_deterministic(self):
        cfg = DataConfig(kind="lm", vocab=50, seq_len=16, global_batch=8, num_workers=4)
        a = make_lm_batch(cfg, 3)
        b = make_lm_batch(cfg, 3)
        np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))


class TestOptim:
    @pytest.mark.parametrize("name", ["sgd", "momentum", "adamw"])
    def test_quadratic_convergence(self, name):
        opt = get_optimizer(name, 0.1)
        params = {"w": jnp.ones((5,)) * 3.0}
        state = opt.init(params)
        for i in range(200):
            grads = {"w": params["w"]}  # grad of ||w||^2/2
            params, state = opt.update(grads, state, params, jnp.int32(i))
        assert float(jnp.linalg.norm(params["w"])) < 1e-2

    def test_adamw_weight_decay(self):
        opt = get_optimizer("adamw", 0.1, weight_decay=0.1)
        params = {"w": jnp.ones((3,))}
        state = opt.init(params)
        grads = {"w": jnp.zeros((3,))}
        p2, _ = opt.update(grads, state, params, jnp.int32(0))
        assert float(p2["w"][0]) < 1.0

    def test_bf16_params_fp32_state(self):
        opt = get_optimizer("adamw", 1e-2)
        params = {"w": jnp.ones((4,), jnp.bfloat16)}
        state = opt.init(params)
        assert state["m"]["w"].dtype == jnp.float32
        p2, _ = opt.update({"w": jnp.ones((4,), jnp.bfloat16)}, state, params, jnp.int32(0))
        assert p2["w"].dtype == jnp.bfloat16

    def test_schedules(self):
        from repro.optim.schedules import cosine, inverse_sqrt

        s = cosine(1.0, warmup=10, total=100)
        assert float(s(0)) == 0.0
        assert abs(float(s(10)) - 1.0) < 1e-6
        assert float(s(100)) < 0.2
        r = inverse_sqrt(1.0, warmup=4)
        assert float(r(1)) == 0.25


class TestCheckpoint:
    def test_roundtrip(self):
        from repro.checkpoint import restore, save

        cfg = get_smoke_config("llama3.2-3b")
        params = T.init_params(cfg, KEY)
        with tempfile.TemporaryDirectory() as d:
            save(d, {"params": params}, step=7, extra={"arch": cfg.name})
            restored, step = restore(d, {"params": params})
            assert step == 7
            a = jax.tree.leaves(params)
            b = jax.tree.leaves(restored["params"])
            assert len(a) == len(b)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(
                    np.asarray(x, np.float32), np.asarray(y, np.float32))

    def test_shape_mismatch_raises(self):
        from repro.checkpoint import restore, save

        with tempfile.TemporaryDirectory() as d:
            save(d, {"w": jnp.ones((3,))})
            with pytest.raises(ValueError):
                restore(d, {"w": jnp.ones((4,))})


class TestConfigs:
    def test_all_archs_have_full_and_smoke(self):
        for arch in ARCHITECTURES:
            full = get_config(arch)
            smoke = get_smoke_config(arch)
            assert full.family == smoke.family
            assert smoke.n_layers <= 5 and smoke.d_model <= 512
            if smoke.moe:
                assert smoke.moe.num_experts <= 4
            assert full.source

    def test_exact_assigned_dims(self):
        specs = {
            "granite-moe-1b-a400m": (24, 1024, 16, 8, 49155),
            "llama3-405b": (126, 16384, 128, 8, 128256),
            "mamba2-2.7b": (64, 2560, None, None, 50280),
            "whisper-small": (12, 768, 12, 12, 51865),
            "recurrentgemma-2b": (26, 2560, 10, 1, 256000),
            "llama3.2-3b": (28, 3072, 24, 8, 128256),
            "internvl2-1b": (24, 896, 14, 2, 151655),
            "qwen3-14b": (40, 5120, 40, 8, 151936),
            "grok-1-314b": (64, 6144, 48, 8, 131072),
            "h2o-danube-1.8b": (24, 2560, 32, 8, 32000),
        }
        for arch, (L, d, h, kv, v) in specs.items():
            cfg = get_config(arch)
            assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab == v, arch
            if h is not None:
                assert cfg.n_heads == h and cfg.n_kv_heads == kv, arch

    def test_param_counts_match_model_names(self):
        # within 35% of the size in the model's name
        expect = {"llama3-405b": 405e9, "grok-1-314b": 314e9, "qwen3-14b": 14e9,
                  "mamba2-2.7b": 2.7e9, "llama3.2-3b": 3.0e9, "h2o-danube-1.8b": 1.8e9}
        for arch, n in expect.items():
            got = T.count_params(get_config(arch))
            assert 0.65 * n < got < 1.35 * n, (arch, got)
        # granite active ~400M of 1B+
        g = get_config("granite-moe-1b-a400m")
        assert 0.3e9 < T.count_active_params(g) < 0.65e9
        assert 1.0e9 < T.count_params(g) < 1.7e9

    def test_input_shapes_table(self):
        assert INPUT_SHAPES["train_4k"].seq_len == 4096
        assert INPUT_SHAPES["train_4k"].global_batch == 256
        assert INPUT_SHAPES["prefill_32k"].global_batch == 32
        assert INPUT_SHAPES["decode_32k"].global_batch == 128
        assert INPUT_SHAPES["long_500k"].seq_len == 524288


class TestShardingRules:
    def test_divisible_rules(self):
        assert param_partition_spec("blocks/p0_attn/wq", (24, 3072, 3072))[2] == "model"
        assert param_partition_spec("embed", (128256, 4096))[0] == "model"
        # vocab not divisible -> falls back to d_model
        s = param_partition_spec("embed", (49155, 1024))
        assert s[0] is None and s[1] == "model"
        # grok experts=8 over 16 -> falls back to F
        s = param_partition_spec("blocks/p0_attn/we_g", (64, 8, 6144, 32768))
        assert s[1] is None and s[3] == "model"
        # norms replicated
        assert all(x is None for x in param_partition_spec("ln1", (1024,)))


class TestAttacks:
    def test_mask_count(self):
        atk = AttackConfig("sign_flip", alpha=0.3)
        assert int(atk.byzantine_mask(10).sum()) == 3
        assert int(AttackConfig("none", 0.0).byzantine_mask(10).sum()) == 0
        # never all workers
        assert int(AttackConfig("sign_flip", alpha=1.0).byzantine_mask(4).sum()) == 3

    def test_gradient_attacks_replace_rows(self):
        from repro.core.attacks import apply_gradient_attack

        rng = np.random.default_rng(0)
        x = jnp.asarray(1.0 + rng.standard_normal((8, 4)), jnp.float32)
        for name in ("sign_flip", "large_value", "mean_shift", "inner_product"):
            atk = AttackConfig(name, alpha=0.25, scale=7.0, shift=5.0)
            out = apply_gradient_attack(atk, x, atk.byzantine_mask(8))
            np.testing.assert_array_equal(np.asarray(out[2:]), np.asarray(x[2:]))
            assert not np.allclose(np.asarray(out[:2]), np.asarray(x[:2])), name
