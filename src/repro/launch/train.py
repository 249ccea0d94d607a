"""Training CLI: Byzantine-robust distributed LM training at speed.

The CLI front-end of ``launch.trainer``: a donated device-steps window
harness (zero host syncs inside a window) over ``steps.make_step_body``
— robust aggregation fused into the sharded train step, engine attacks
applied in-step with per-micro-step key folding.

Runs on the devices JAX finds and prints which.  The debug mesh needs
``--workers`` x ``--model-par`` of them: one TPU chip runs
``--workers 1``; on the CPU, simulate more with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``.
On a TPU pod the same CLI runs with ``--mesh single|multi``
production meshes.  The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` (default ``.jax_cache/`` in the checkout).

Example (8 simulated CPU devices, 8 data-parallel workers, two Byzantine
workers running ALIE, bucketed median aggregation, 16-step windows):

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --config llama3.2-3b --smoke \\
      --steps 64 --device-steps 16 --workers 8 \\
      --strategy bucketed --agg median --attack alie --attack-alpha 0.25

Compressed transmitted gradients (rounds.compression): add e.g.
``--compression int8`` — the codec runs per worker BEFORE the collective
and before any attack, so Byzantine payloads replace decoded wire
values; ``--compression topk`` threads per-worker error-feedback
residuals through the window state (device-steps trainer only).
"""
from __future__ import annotations

import argparse

from repro.checkpoint import save as save_ckpt
from repro.configs import ParallelConfig, TrainConfig, get_config, get_smoke_config
from repro.core.attacks import AttackConfig
from repro.data.pipeline import DataConfig
from repro.launch import runtime, trainer
from repro.launch.mesh import make_debug_mesh, make_production_mesh, num_workers
from repro.rounds import compression


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.train",
        description="Robust distributed training via the device-steps "
                    "window harness (launch.trainer)")
    ap.add_argument("--config", "--arch", dest="config", required=True,
                    help="architecture name from repro.configs")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=16,
                    help="total optimizer steps (multiple of --device-steps)")
    ap.add_argument("--device-steps", type=int, default=1,
                    help="micro-steps scanned on-device per host round-trip")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--mesh", default="debug", choices=["debug", "single", "multi"])
    ap.add_argument("--workers", type=int, default=4, help="debug mesh data axis")
    ap.add_argument("--model-par", type=int, default=1, help="debug mesh model axis")
    ap.add_argument("--strategy", default="gather",
                    choices=["gather", "bucketed", "hierarchical", "chunked", "psum"])
    ap.add_argument("--agg", default="median",
                    choices=["mean", "median", "trimmed_mean",
                             "approx_median", "approx_trimmed_mean"])
    ap.add_argument("--beta", type=float, default=0.25)
    ap.add_argument("--compression", default="none",
                    choices=list(compression.registered_compressions()),
                    help="codec on each worker's transmitted gradient "
                         "(rounds.compression) — runs before the "
                         "collective and before any attack; topk carries "
                         "error-feedback state in the training window")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--attack-alpha", type=float, default=0.0)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-chunk", type=int, default=0, help="0 = plain attention")
    ap.add_argument("--log-every", type=int, default=1, help="in windows")
    ap.add_argument("--ckpt", default=None,
                    help="save a final params checkpoint here on exit")
    # deterministic mid-run checkpoint/resume (rounds.engine snapshots)
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="snapshot the full window state every "
                         "--ckpt-every windows")
    ap.add_argument("--ckpt-every", type=int, default=1, metavar="N",
                    help="snapshot period in windows (with --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest snapshot in --ckpt-dir "
                         "(bit-for-bit; a fresh directory starts from "
                         "scratch)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    runtime.enable_compile_cache()
    cfg = get_smoke_config(args.config) if args.smoke else get_config(args.config)
    if args.mesh == "debug":
        mesh = make_debug_mesh(args.workers, args.model_par)
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    m = num_workers(mesh)
    dev = mesh.devices.flat[0]
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} workers={m} "
          f"device_steps={args.device_steps} device={dev.platform}:"
          f"{dev.device_kind}")

    attack = AttackConfig(args.attack, args.attack_alpha)
    if args.strategy == "psum" and args.agg != "mean":
        # psum is the plain-DP baseline; it can only average
        print(f"note: --strategy psum forces --agg mean (was {args.agg})")
        args.agg = "mean"
    pcfg = ParallelConfig(agg_method=args.agg, agg_beta=args.beta,
                          agg_strategy=args.strategy, remat=True,
                          attn_chunk=args.attn_chunk,
                          compression=args.compression)
    tcfg = TrainConfig(optimizer=args.optimizer, lr=args.lr, steps=args.steps,
                       seed=args.seed, attack=args.attack,
                       attack_alpha=args.attack_alpha,
                       device_steps=args.device_steps)
    dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.global_batch, num_workers=m,
                      seed=args.seed)

    def on_window(w, met):
        print(f"step {met['step']:5d}  loss {met['loss']:.4f}  "
              f"|g| {met['grad_norm']:.3f}")

    result = trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, attack=attack,
                                log_every=args.log_every, on_window=on_window,
                                ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
                                ckpt_dir=args.ckpt_dir,
                                resume=bool(args.resume))
    print(f"done: {result.steps} steps in windows of {result.device_steps}  "
          f"compile {result.compile_s:.2f}s  "
          f"steady {result.steps_per_s:.2f} steps/s  "
          f"{result.tokens_per_s:.0f} tokens/s")
    if args.ckpt:
        save_ckpt(args.ckpt, {"params": result.state["params"]}, step=result.steps,
                  extra={"arch": cfg.name, "agg": args.agg,
                         "strategy": args.strategy})
        print(f"saved checkpoint to {args.ckpt}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
