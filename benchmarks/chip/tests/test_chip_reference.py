"""The plain float32 reference against the program, at a tiny size on the CPU.

The reference makes its own weights and tokens from the seed; they must
be the program's bit for bit.  Driven through the benchmark's own run
(``run.run_cell``: ``train_loop``, its first window, the state it leaves)
the program must agree with the reference within the tiny cell's limits,
and the float8 control, put in the program's place, must not.
"""
import jax
import jax.numpy as jnp
import pytest

from chip_tiny import DENSE, LIMITS, SEED, reference, run, run_tiny, traffic

from benchmarks.chip import compare
from benchmarks.chip import reference_decoder as R


@pytest.mark.parametrize("config", [DENSE], ids=["dense"])
def test_weights_are_the_programs(config):
    from repro.models import transformer as T

    mine = R.init_params(config, jax.random.PRNGKey(SEED))
    theirs = T.init_params(run.program_config(config), jax.random.PRNGKey(SEED))
    theirs = {jax.tree_util.keystr(k, simple=True, separator=".").replace(
        run.PROGRAM_LAYERS, run.REFERENCE_LAYERS): v
        for k, v in jax.tree_util.tree_flatten_with_path(theirs)[0]}
    mine = dict(zip(R.leaf_names(mine), jax.tree.leaves(mine)))
    assert set(mine) == set(theirs)
    for k in mine:
        assert mine[k].dtype == theirs[k].dtype, k
        assert bool(jnp.array_equal(mine[k], theirs[k])), k


def test_tokens_are_the_programs():
    from repro.data.pipeline import DataConfig, make_lm_batch

    dcfg = DataConfig(kind="lm", vocab=256, seq_len=32, global_batch=8, num_workers=2,
                      seed=SEED)
    batch = make_lm_batch(dcfg, 3, None)
    parts = [R.lm_tokens(jax.random.PRNGKey(SEED), 3, w, 4, 32, 256) for w in range(2)]
    assert bool(jnp.array_equal(batch["tokens"], jnp.concatenate([p[0] for p in parts])))
    assert bool(jnp.array_equal(batch["labels"], jnp.concatenate([p[1] for p in parts])))


def test_reference_refuses_sparse_experts():
    with pytest.raises(ValueError):
        R.sizes(dict(DENSE, num_local_experts=8, num_experts_per_tok=2))


@pytest.mark.parametrize("config", [DENSE], ids=["dense"])
def test_program_agrees_with_reference(config):
    result = run_tiny(config)
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


def test_float8_control_is_not_correct():
    ref = reference(DENSE, keep_moments=True)
    control = R.run(DENSE, traffic(1), SEED, precision="fp8", against=ref["moments"])
    numbers = compare.gaps(control, ref, control["moment_error"])
    assert not compare.verdict(numbers, LIMITS), numbers
    # it fails where the limits were set to catch it
    assert numbers["grad_error"] > LIMITS["grad_error"]
