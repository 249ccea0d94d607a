"""Dry-run-lite: the full lower+compile path on a small (8-device) mesh in
subprocesses — the same code path the 512-device production dry-run uses,
kept fast enough for CI. One representative arch per family."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMS = [
    ("llama3.2-3b", "dense"),
    ("granite-moe-1b-a400m", "moe"),
    ("mamba2-2.7b", "ssm"),
    ("recurrentgemma-2b", "hybrid"),
    ("whisper-small", "audio"),
    ("internvl2-1b", "vlm"),
]


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.parametrize("arch,fam", FAMS)
def test_train_and_decode_lower_compile(arch, fam):
    run_sub(f"""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, ParallelConfig
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("{arch}")
mesh = make_debug_mesh(4, 2)
pcfg = ParallelConfig(agg_method="median", agg_strategy="gather", remat=True, attn_chunk=16)
opt = get_optimizer("adamw", 1e-3)
shape_t = ShapeConfig("t", 64, 8, "train")
shape_d = ShapeConfig("d", 64, 8, "decode")
with jax.set_mesh(mesh):
    params = steps.abstract_params(cfg, mesh)
    opt_state = steps.abstract_opt_state(opt, cfg, mesh)
    # train
    ins = steps.input_specs(cfg, shape_t, mesh)
    fn = steps.make_train_step(cfg, pcfg, mesh, opt)
    c = fn.lower(params, opt_state, ins, jnp.int32(0)).compile()
    assert c.cost_analysis() is not None
    # decode
    ins = steps.input_specs(cfg, shape_d, mesh)
    fn = steps.make_decode_step(cfg, mesh)
    c = fn.lower(params, ins["token"], ins["cache"], ins["pos"]).compile()
print("OK {arch}")
""")


def test_multi_pod_mesh_lowering():
    """pod axis shards: 2x2x2 debug multi-pod mesh, robust agg across
    ('pod','data') jointly."""
    run_sub("""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, ParallelConfig
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("qwen3-14b")
mesh = make_debug_mesh(data=2, model=2, pod=2)
for strategy in ("gather", "bucketed", "hierarchical"):
    pcfg = ParallelConfig(agg_method="median", agg_strategy=strategy, remat=False, attn_chunk=0)
    opt = get_optimizer("sgd", 1e-3)
    with jax.set_mesh(mesh):
        params = steps.abstract_params(cfg, mesh)
        opt_state = steps.abstract_opt_state(opt, cfg, mesh)
        ins = steps.input_specs(cfg, ShapeConfig("t", 32, 8, "train"), mesh)
        fn = steps.make_train_step(cfg, pcfg, mesh, opt)
        c = fn.lower(params, opt_state, ins, jnp.int32(0)).compile()
        txt = c.as_text()
        assert any(op in txt for op in ("all-gather", "all-to-all")), strategy
print("OK")
""")


def test_fsdp_dims_avoid_model_tp_dim():
    """fsdp must not steal the tensor-parallel dim (the grok bug —
    EXPERIMENTS.md §Perf iteration 2)."""
    run_sub("""
import jax
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(4, 2)
for arch in ("grok-1-314b", "llama3-405b", "qwen3-14b"):
    cfg = get_config(arch)
    shard, dims = steps.fsdp_param_shardings(cfg, mesh)
    flat_sh = jax.tree_util.tree_flatten_with_path(
        shard, is_leaf=lambda x: hasattr(x, "spec"))[0]
    flat_d = jax.tree.leaves(dims)
    n_2d = 0
    for (path, s), d in zip(flat_sh, flat_d):
        entries = tuple(s.spec)
        if d >= 0:
            assert entries[d] in ("data", ("data",)), (path, entries, d)
            # model axis must survive on big matmul weights
            if "model" in entries:
                n_2d += 1
    assert n_2d > 0, arch  # 2D-sharded leaves exist
print("OK")
""")


def test_seq_parallel_lowering():
    run_sub("""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, ParallelConfig
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.optim.optimizers import get_optimizer

cfg = get_smoke_config("llama3.2-3b")
mesh = make_debug_mesh(4, 2)
pcfg = ParallelConfig(agg_method="median", seq_parallel=True, remat=True, attn_chunk=16)
opt = get_optimizer("adamw", 1e-3)
with jax.set_mesh(mesh):
    params = steps.abstract_params(cfg, mesh)
    opt_state = steps.abstract_opt_state(opt, cfg, mesh)
    ins = steps.input_specs(cfg, ShapeConfig("t", 64, 8, "train"), mesh)
    fn = steps.make_train_step(cfg, pcfg, mesh, opt)
    fn.lower(params, opt_state, ins, jnp.int32(0)).compile()
print("OK")
""")


def test_long_context_decode_lowering():
    """long_500k-style decode for an SSM (native) and dense+swa variant."""
    run_sub("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig, INPUT_SHAPES
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(2, 2)
shape = ShapeConfig("long", 8192, 1, "decode")  # scaled-down long-context
with jax.set_mesh(mesh):
    for arch in ("mamba2-2.7b", "llama3.2-3b"):
        cfg = get_smoke_config(arch)
        if arch == "llama3.2-3b":
            cfg = dataclasses.replace(cfg, long_context_window=64)
            cfg = steps.long_context_cfg(cfg, dataclasses.replace(shape, name="long_500k"))
            assert cfg.name.endswith("+swa")
        params = steps.abstract_params(cfg, mesh)
        ins = steps.input_specs(cfg, shape, mesh)
        if arch == "llama3.2-3b":
            # window-sized ring cache, not 8192
            assert ins["cache"]["blocks"]["p0_attn"]["k"].shape[2] == 64
        fn = steps.make_decode_step(cfg, mesh)
        fn.lower(params, ins["token"], ins["cache"], ins["pos"]).compile()
print("OK")
""")
