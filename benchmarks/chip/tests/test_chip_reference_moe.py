"""The sparse-expert reference (``reference_moe.py``) against the program,
at a tiny size on the CPU.

A tiny tied MoE decoder with Granite's multipliers (2 layers, width 64,
8 experts top-2, vocabulary 256, seq 32): the reference's weights must
be the program's bit for bit; driven through the benchmark's own run
(``run.run_cell``) the program must agree with the reference within the
tiny limits below, and the float8 control and half the batch, put in the
program's place, must not.  The benchmark's configuration file must
carry the program's multipliers and tie, since ``run.program_config``
takes them from the program's ``CONFIG``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_tiny import ROOT, SEED, run, traffic

from benchmarks.chip import compare
from benchmarks.chip import reference_moe as R

TINY = {"name": "tiny-moe", "arch": "granite-moe-1b-a400m", "reference": "reference_moe",
        "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "num_local_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 256, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6, "embedding_multiplier": 12.0,
        "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
        "logits_scaling": 6.0, "tie_word_embeddings": True, "torch_dtype": "bfloat16"}
# Set from CPU readings of this tiny cell (seeds 1-3 and SEED): the program
# reads loss 3.7e-6..6.8e-6, |g| 1.9e-5..8.5e-5, grad 1.5e-3..0.019,
# update 8.1e-3..8.9e-3 and grad error 3.9e-3..4.7e-3; the float8 control
# reads |g| 4.2e-4..1.4e-3 and grad error 0.028..0.033 (its loss, grad and
# update gaps lie among the program's); half the batch reads loss
# 6.8e-5..7.5e-4, |g| 0.28..0.46, grad 0.47..1.9, update 0.070..0.12 and
# grad error 0.70..0.89.
LIMITS = {"loss_gap": 3e-5, "gnorm_gap": 2e-4, "grad_gap": 0.1, "update_gap": 0.04,
          "grad_error": 0.012}
CONFIG_FILE = ROOT / "benchmarks" / "chip" / "configs" / "granite-moe-1b-a400m-l8.json"

_RUNS: dict = {}
_REFERENCE_RUN = R.run


def reference(seed: int = SEED, **kw) -> dict:
    """The reference's first window of the tiny cell, once per process."""
    key = (seed, tuple(sorted(kw.items())))
    if key not in _RUNS:
        _RUNS[key] = _REFERENCE_RUN(TINY, traffic(1), seed, devices=jax.devices()[:1], **kw)
    return _RUNS[key]


def run_tiny(seed: int = SEED) -> dict:
    """One run of the tiny cell through ``run.run_cell``, the reference's
    window taken from :func:`reference`."""
    def cached(c, tr, s, against=None, **kw):
        ref = reference(s, keep_moments=True)
        return dict(ref, moment_error={
            n: float(np.linalg.norm(np.asarray(m, np.float64) - np.asarray(against[n], np.float64)))
            for n, m in ref["moments"].items()})

    cell = run.Cell(name=TINY["name"], chips=1, config=TINY, traffic=traffic(1),
                    limits=dict(LIMITS), end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                                                     {"name": "setup_s", "unit": "s"}],
                    per_layer=[])
    R.run = cached
    try:
        return run.run_cell(cell, seed, 0.2, False, jax.devices()[:1])
    finally:
        R.run = _REFERENCE_RUN


def test_weights_are_the_programs():
    from repro.models import transformer as T

    mine = R.init_params(TINY, jax.random.PRNGKey(SEED))
    theirs = T.init_params(run.program_config(TINY), jax.random.PRNGKey(SEED))
    theirs = {jax.tree_util.keystr(k, simple=True, separator=".").replace(
        run.PROGRAM_LAYERS, run.REFERENCE_LAYERS): v
        for k, v in jax.tree_util.tree_flatten_with_path(theirs)[0]}
    mine = dict(zip(R.leaf_names(mine), jax.tree.leaves(mine)))
    assert set(mine) == set(theirs)
    assert "embed" not in mine and {"layers.router", "layers.we_g", "layers.we_d"} <= set(mine)
    for k in mine:
        assert mine[k].dtype == theirs[k].dtype, k
        assert bool(jnp.array_equal(mine[k], theirs[k])), k


def test_program_agrees_with_reference():
    result = run_tiny()
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("variant", ["fp8", "half_batch"])
def test_control_and_fault_are_not_correct(variant):
    ref = reference(keep_moments=True)
    kw = {"precision": "fp8"} if variant == "fp8" else {"fault": variant}
    got = R.run(TINY, traffic(1), SEED, against=ref["moments"], **kw)
    numbers = compare.gaps(got, ref, got["moment_error"])
    assert not compare.verdict(numbers, LIMITS), numbers
    assert numbers["grad_error"] > LIMITS["grad_error"]


def test_reference_refuses_an_untied_or_dense_model():
    with pytest.raises(ValueError):
        R.sizes(dict(TINY, tie_word_embeddings=False))
    with pytest.raises(ValueError):
        R.sizes(dict(TINY, num_local_experts=0))


def test_config_file_carries_the_programs_multipliers_and_tie():
    c = json.loads(CONFIG_FILE.read_text())
    cfg = run.program_config(c)
    assert (c["embedding_multiplier"], c["attention_multiplier"], c["residual_multiplier"],
            c["logits_scaling"], c["tie_word_embeddings"]) == (
        cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
        cfg.logits_scaling, cfg.tie_embeddings)
    assert cfg.norm_eps == c["rms_norm_eps"]
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.d_expert) == (
        c["num_local_experts"], c["num_experts_per_tok"], c["intermediate_size"])
