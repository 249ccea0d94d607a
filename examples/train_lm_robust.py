"""End-to-end driver: train a ~100M-param LM for a few hundred steps under
Byzantine attack with median aggregation, on a simulated 8-device mesh
(4 workers × 2-way model parallel).

This is the "real system" example: the production train_step
(shard_map + robust collective aggregation), the sharded data pipeline
with per-worker Byzantine corruption, AdamW, checkpointing.

Run:  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/train_lm_robust.py [--steps 300]
(simulates the 8 devices on the CPU; ~100M params, CPU-friendly settings)
"""
from repro.launch.runtime import simulate_cpu_devices

simulate_cpu_devices(8)

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save as save_ckpt
from repro.configs import ParallelConfig
from repro.configs.base import ModelConfig
from repro.core.attacks import AttackConfig
from repro.data.pipeline import DataConfig, host_to_mesh, make_lm_batch
from repro.launch import steps
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as T
from repro.optim.optimizers import get_optimizer

# ~100M params: 8L, d=768, llama-style
CFG = ModelConfig(
    name="demo-100m", family="dense", n_layers=8, d_model=768, n_heads=12,
    n_kv_heads=4, d_ff=2048, vocab=32000, rope_theta=10000.0,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--agg", default="median")
    ap.add_argument("--attack", default="label_flip")
    ap.add_argument("--attack-alpha", type=float, default=0.25)
    ap.add_argument("--ckpt", default="/tmp/repro_demo_ckpt")
    args = ap.parse_args()

    mesh = make_debug_mesh(4, 2)
    m = 4
    print(f"model: {T.count_params(CFG)/1e6:.1f}M params; mesh 4 workers x 2 TP; "
          f"attack={args.attack} alpha={args.attack_alpha} agg={args.agg}")

    attack = AttackConfig(args.attack, args.attack_alpha)
    pcfg = ParallelConfig(agg_method=args.agg, agg_strategy="bucketed",
                          remat=False, attn_chunk=0)
    opt = get_optimizer("adamw", 3e-4)
    dcfg = DataConfig(kind="lm", vocab=CFG.vocab, seq_len=args.seq_len,
                      global_batch=args.global_batch, num_workers=m)

    with jax.set_mesh(mesh):
        params = T.init_params(CFG, jax.random.PRNGKey(0))
        pshard = steps.param_shardings(CFG, mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, pshard)
        opt_state = opt.init(params)
        train_step = steps.make_train_step(CFG, pcfg, mesh, opt, attack)

        t0 = time.time()
        for step in range(args.steps):
            batch = host_to_mesh(make_lm_batch(dcfg, step, attack), mesh, ("data",))
            params, opt_state, metrics = train_step(params, opt_state, batch,
                                                    jnp.int32(step))
            if step % 20 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                      f"|g| {float(metrics['grad_norm']):.3f}  "
                      f"({(time.time()-t0)/(step+1):.2f}s/step)")
        save_ckpt(args.ckpt, {"params": params}, step=args.steps,
                  extra={"arch": CFG.name, "agg": args.agg})
        print(f"done; checkpoint at {args.ckpt}")


if __name__ == "__main__":
    main()
