"""A tiny cell for the benchmark's CPU tests: the cells' code paths at a
size a test run can hold (2 layers, width 64, seq 32)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import reference_decoder, run  # noqa: E402

_REFERENCE_RUN = reference_decoder.run

DENSE = {"name": "tiny-dense", "arch": "h2o-danube-1.8b", "reference": "reference_decoder",
         "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "sliding_window": 16,
         "torch_dtype": "bfloat16"}
# Set from CPU readings of these tiny cells (seeds 1-3): the program reads
# loss 6e-5, |g| 1.2e-4, grad 3.9e-4, update 1.1e-2 and grad error 5e-3;
# the float8 control reads grad error 0.047..0.050; half the batch reads
# loss 5e-4..2e-3, |g| 0.30..0.46, grad 0.37..0.48, update 0.24..0.30 and
# grad error 0.76..0.91.
LIMITS = {"loss_gap": 3e-4, "gnorm_gap": 4e-4, "grad_gap": 2e-3, "update_gap": 0.05,
          "grad_error": 0.015}
SEED = 2**31 + 12345  # above 32 signed bits, as the benchmark's seeds are


def traffic(workers: int) -> dict:
    attack = {"name": "alie", "alpha": 0.25, "strength": 1.0} if workers > 1 else {"name": "none"}
    return {"workers": workers, "mesh": [workers, 1], "strategy": "bucketed", "agg": "median",
            "attack": attack, "seq_len": 32, "batch_per_worker": 4, "optimizer": "adamw",
            "lr": 1e-3, "remat": True, "attn_chunk": 0, "device_steps": 2}


def cell(config: dict, workers: int = 1) -> "run.Cell":
    return run.Cell(name=config["name"], chips=workers, config=config,
                    traffic=traffic(workers), limits=dict(LIMITS),
                    end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                                {"name": "setup_s", "unit": "s"}],
                    per_layer=[])


_REFERENCE_RUNS: dict = {}


def reference(config: dict, workers: int = 1, seed: int = SEED, **kw) -> dict:
    """The reference's first window, computed once per process: the tests
    drive several runs (sound and with faults planted in the program) of
    the same tiny cell."""
    import jax

    key = (config["name"], workers, seed, tuple(sorted(kw.items())))
    if key not in _REFERENCE_RUNS:
        _REFERENCE_RUNS[key] = _REFERENCE_RUN(
            config, traffic(workers), seed, devices=jax.devices()[:workers], **kw)
    return _REFERENCE_RUNS[key]


def run_tiny(config: dict, workers: int = 1, seed: int = SEED) -> dict:
    """One run of the tiny cell through ``run.run_cell``."""
    import jax
    import numpy as np

    def cached(c, tr, s, against=None, **kw):
        ref = reference(c, tr["workers"], s, keep_moments=True)
        return dict(ref, moment_error={
            n: float(np.linalg.norm(np.asarray(m, np.float64) - np.asarray(against[n], np.float64)))
            for n, m in ref["moments"].items()})

    reference_decoder.run = cached
    try:
        return run.run_cell(cell(config, workers), seed, 0.2, False, jax.devices()[:workers])
    finally:
        reference_decoder.run = _REFERENCE_RUN
