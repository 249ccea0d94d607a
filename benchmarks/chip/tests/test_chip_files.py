"""The benchmark's files: each cell resolves by name, each configuration's
model FLOPs follow from the program's parameter shapes, the peaks table
refuses an unknown chip, and the command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chip_tiny import ROOT, run

from benchmarks.chip import compare, flops, peaks

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
# sparse experts at a tiny size, for the FLOP count's expert branch
TINY_MOE = {"arch": "granite-moe-1b-a400m", "hidden_size": 64, "intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
            "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16"}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = run.load_cell(name)
    assert cell.chips in (1, 4)
    assert set(cell.limits) == set(compare.NUMBERS)
    assert cell.traffic["mesh"][0] * cell.traffic["mesh"][1] == cell.chips
    assert {m["name"] for m in cell.end_to_end} >= {"tokens_per_s", "setup_s"}
    for m in cell.per_layer:
        assert callable(run._reader(m["name"]))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_file_is_what_runs(name):
    entry = CONFIGS[name]
    c = json.loads((ROOT / entry["file"]).read_text())
    assert c["name"] == name and c["source"] == entry["source"]
    assert sorted(c["reduced"]) == sorted(entry["reduced"])
    cfg = run.program_config(c)
    assert cfg.n_layers == c["num_hidden_layers"] and cfg.vocab == c["vocab_size"]


@pytest.mark.parametrize("name", sorted(CONFIGS) + ["tiny-moe"])
def test_model_flops_follow_the_parameter_shapes(name):
    import jax

    from repro.configs.base import ParallelConfig
    from repro.launch import trainer
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import get_optimizer

    c = TINY_MOE if name == "tiny-moe" else json.loads((ROOT / CONFIGS[name]["file"]).read_text())
    cfg = run.program_config(c)
    state = trainer.abstract_state(cfg, make_mesh((1, 1), ("data", "model")),
                                   get_optimizer("adamw", 1e-3), ParallelConfig())
    active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state["params"])[0]:
        key = path[-1].key
        if key in ("embed", "final_norm", "ln1", "ln2"):
            continue  # a lookup and norm scales: no matmul
        share = cfg.moe.top_k / cfg.moe.num_experts if key.startswith("we_") else 1.0
        active += leaf.size * share
    assert flops.matmul_params_per_token(c) == active
    attn = flops.model_flops_per_token(c, 2048) - 6 * active
    keys = flops.mean_keys_per_query(2048, c.get("sliding_window") or 0)
    assert keys == 2049 / 2  # a 4096 window (or none) covers all of 2048
    assert attn == 12 * cfg.n_layers * cfg.n_heads * cfg.hd * keys


def test_mean_keys_with_a_window():
    assert flops.mean_keys_per_query(6, 2) == (1 + 2 * 5) / 6


def test_peaks_know_v5e_and_refuse_unknown_chips():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_memory_peak_counts_the_reserved_region():
    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"peak_bytes_in_use": 5, "peak_bytes_reserved": 3}), Chip(None),
             Chip({"peak_bytes_in_use": 7})]
    assert run._memory_peak(chips) == 8


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run(ROOT)
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout == ""


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    out = _run(tmp_path)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
