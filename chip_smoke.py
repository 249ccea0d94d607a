#!/usr/bin/env python3
"""Chip smoke: drive the robust trainer and its aggregation kernels on a TPU.

    python chip_smoke.py                # one chip: trainer + kernels
    python chip_smoke.py --chips 4      # four chips: robust data-parallel phase only

Everything runs in this one process, which owns the chip.  Data and
weights come from ``--seed``; nothing is read from outside the checkout.

One chip:

1. device check: exits non-zero, printing no result, unless JAX's first
   device is a TPU;
2. train: ``launch.trainer.train_loop`` (what ``python -m
   repro.launch.train`` calls) on h2o-danube-1.8b at its published widths
   with only ``n_layers`` cut, seq 2048, global batch 4, bucketed
   median, AdamW, 4 steps in windows of 2 on a 1x1 mesh, attention
   through the fused kernel (``kernels/attention.py``).  Loss and |g|
   must be finite and the second window must compile nothing.  With one
   worker the median is that worker's gradient, so a psum/mean run from
   the same seed must reach the same parameters;
3. sparse experts: ``train_loop`` on granite-3.0-1b-a400m at its
   published widths and settings (dropless top-8 of 32 experts, tied
   head, the four multipliers) with ``n_layers`` cut to 2, seq 4096,
   global batch 2, bucketed median, 4 steps in windows of 2: finite loss,
   |g| and expert load, no compilation after the first window; the
   token permutation's census of that window step as lowered for the
   chip (``hlo_analysis.permutation_census``): one sort of the T·k
   (token, slot) keys per MoE layer body, no (T, k, D) broadcast and no
   scatter of T·k rows; then the grouped-matmul kernel against
   ``jax.lax.ragged_dot`` at the train cell's shapes, forward and
   backward;
4. kernels: ``kernels.ops.robust_aggregate(backend="pallas")`` (median,
   trimmed mean) against ``kernels/ref.py``, and one ``repro.fed.run``
   round aggregate (approx_median, default sketch settings) on the
   Pallas backend against the XLA one.  Every kernel, the train
   phases' attention and grouped products among them, must have been
   traced for Mosaic and never for interpret mode.

``--chips 4``: a 4x1 mesh (m = 4 workers) at the one-chip phase's
per-device batch: bucketed median under ALIE, the psum/mean baseline,
and one step of bucketed against gather (the same exact estimator).

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
check exits 1 without it.  The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` (default ``.jax_cache/`` in the checkout).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b"
MOE_ARCH, MOE_LAYERS, MOE_SEQ, MOE_BATCH = "granite-moe-1b-a400m", 2, 4096, 2
PUBLISHED_LAYERS = 24
# The window step at 8 layers needs 7.20 GB of arguments (bf16 params +
# f32 AdamW moments, donated) and 8.44 GB of temporaries on one v5e chip
# (memory_analysis of the compile for a described chip); 9 layers do not
# leave headroom in its 16 GiB.
LAYERS = 8
SEQ = 2048
BATCH_PER_DEVICE = 4
STEPS, DEVICE_STEPS = 4, 2
LR = 1e-3
# bucketed vs gather holds m·|g| per device in the gather program, so
# that comparison runs on a shallower cut
COMPARE_LAYERS = 2
# kernel check: m = 16 bf16 rows, each one danube MLP matrix wide
KERNEL_ROWS, KERNEL_WIDTH = 16, 2560 * 6912
# one fed round at the CLI's default sketch settings (nbins 256, block 512)
FED_ARGV = ["--method", "approx_median", "--cohort", "1024", "--chunk", "256",
            "--dim", "65536", "--rounds", "1"]


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def run_train(counter, *, layers, strategy, agg, workers, steps, device_steps,
              attack=None, seed=0, arch=ARCH, seq=SEQ, batch=BATCH_PER_DEVICE):
    """One ``train_loop`` run; returns (result, compiles in each window)."""
    import jax

    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.core.attacks import AttackConfig
    from repro.data.pipeline import DataConfig
    from repro.launch import trainer
    from repro.launch.mesh import make_debug_mesh

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    mesh = make_debug_mesh(workers, 1)
    # the train CLI's defaults: remat on, no XLA attention chunking
    pcfg = ParallelConfig(agg_method=agg, agg_strategy=strategy, remat=True,
                          attn_chunk=0)
    tcfg = TrainConfig(optimizer="adamw", lr=LR, steps=steps, seed=seed,
                       device_steps=device_steps)
    dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=seq,
                      global_batch=batch * workers,
                      num_workers=workers, seed=seed)
    attack = attack or AttackConfig("none", 0.0)
    per_window = []
    start = [counter.n]

    def on_window(w, met):
        per_window.append(counter.n - start[0])
        start[0] = counter.n
        load = f"  expert load {met['expert_load']:.4f}" if "expert_load" in met else ""
        print(f"    window {w}: step {met['step']}  loss {met['loss']:.6f}  "
              f"|g| {met['grad_norm']:.6f}{load}", flush=True)

    print(f"  {arch}: {strategy}/{agg} on {workers} worker(s), attack "
          f"{attack.name}:{attack.alpha}, {layers}/{PUBLISHED_LAYERS} layers, "
          f"seq {seq}, global batch {dcfg.global_batch}, {steps} steps in "
          f"windows of {device_steps}", flush=True)
    result = trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg,
                                attack=attack, on_window=on_window)
    jax.block_until_ready(result.state["params"])
    print(f"    first window (compile + run) {result.compile_s:.3f} s; "
          f"steady windows {result.window_times_s} s -> "
          f"{result.steps_per_s:.4f} steps/s, "
          f"{result.tokens_per_s:.1f} tokens/s", flush=True)
    return result, per_window


def check_train(result, per_window, steps, device_steps):
    import math

    check(len(result.history) == steps // device_steps,
          f"{len(result.history)} windows logged")
    for h in result.history:
        check(all(math.isfinite(v) for v in h.values()),
              f"finite loss {h['loss']:.6f} and |g| {h['grad_norm']:.6f} "
              f"(and expert load, if any) at step {h['step']}")
    check(all(n == 0 for n in per_window[1:]),
          f"no compilation after the first window (compiles per window "
          f"{per_window})")


def host_params(result):
    """Pull the final parameters to the host and free the device state."""
    import jax

    params = jax.device_get(result.state["params"])
    result.state.clear()
    return params


def params_agree(a, b, steps: int, what: str) -> None:
    """Two runs whose aggregated gradients can differ only by floating-
    point rounding (XLA may fuse the two programs' backward passes
    differently) must end at the same parameters up to:

    - per coordinate, 2·lr per step plus one bf16 ulp of the stored
      parameter: each run's bias-corrected AdamW step moves a coordinate
      by at most lr early in training, so even a rounding-flipped step
      sign costs at most 2·lr;
    - over the whole vector, a relative L2 difference of 1e-2: a wrong
      aggregate changes every coordinate's step and lands near 0.1-1.
    """
    import jax
    import numpy as np

    bound = 2 * LR * steps
    sq_d = sq_a = 0.0
    worst = 0.0
    n = n_eq = 0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        d = np.abs(x - y)
        lim = bound + 2.0 ** -7 * np.maximum(np.abs(x), np.abs(y))
        worst = max(worst, float(np.max(d - lim)))
        sq_d += float(np.sum(d.astype(np.float64) ** 2))
        sq_a += float(np.sum(x.astype(np.float64) ** 2))
        n += x.size
        n_eq += int(np.sum(x == y))
    rel = (sq_d / sq_a) ** 0.5
    print(f"    {what}: {n_eq}/{n} parameters bit-identical, relative L2 "
          f"difference {rel:.3e}, worst excess over the per-coordinate "
          f"bound {worst:.3e}", flush=True)
    check(worst <= 0.0, f"{what}: every coordinate within 2*lr*steps + 1 ulp")
    check(rel <= 1e-2, f"{what}: relative L2 difference <= 1e-2")


def phase_train_one_chip(counter, seed):
    print(f"== train: {ARCH} at published widths, n_layers {LAYERS} of "
          f"{PUBLISHED_LAYERS}, seq {SEQ}, 1x1 mesh", flush=True)
    robust, win = run_train(counter, layers=LAYERS, strategy="bucketed",
                            agg="median", workers=1, steps=STEPS,
                            device_steps=DEVICE_STEPS, seed=seed)
    check_train(robust, win, STEPS, DEVICE_STEPS)
    robust_params = host_params(robust)
    base, win = run_train(counter, layers=LAYERS, strategy="psum", agg="mean",
                          workers=1, steps=STEPS, device_steps=DEVICE_STEPS,
                          seed=seed)
    check_train(base, win, STEPS, DEVICE_STEPS)
    params_agree(robust_params, host_params(base), STEPS,
                 "m=1 bucketed/median vs psum/mean")


def phase_moe(counter, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import grouped_matmul as G

    print(f"== sparse experts: {MOE_ARCH} at published widths, n_layers "
          f"{MOE_LAYERS} of {PUBLISHED_LAYERS}, seq {MOE_SEQ}, 1x1 mesh", flush=True)
    result, win = run_train(counter, layers=MOE_LAYERS, strategy="bucketed",
                            agg="median", workers=1, steps=STEPS,
                            device_steps=DEVICE_STEPS, seed=seed, arch=MOE_ARCH,
                            seq=MOE_SEQ, batch=MOE_BATCH)
    check_train(result, win, STEPS, DEVICE_STEPS)
    result.state.clear()
    census = moe_census()
    print(f"    permutation census of the lowered window step: {census}", flush=True)
    check(census == {"sorts": 1, "broadcasts": 0, "scatters": 0},
          "one sort of the (token, slot) keys, no (T, k, D) broadcast, no scatter of "
          "T·k rows")

    # the train cell's grouped product: 8192 tokens x 8 slots over 32
    # experts of 1024 x 512, rows routed by a random top-8
    t, k, d, f, e = MOE_SEQ * MOE_BATCH, 8, 1024, 512, 32
    print(f"== grouped matmul: ({t * k}, {d}) x ({e}, {d}, {f}) bf16, kernel "
          f"vs ragged_dot, forward and backward", flush=True)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    idx = jax.lax.top_k(jax.random.normal(ks[0], (t, e)), k)[1].reshape(-1)
    sizes = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.int32), axis=0)
    lhs = jax.random.normal(ks[1], (t * k, d), jnp.bfloat16)
    rhs = (0.02 * jax.random.normal(ks[2], (e, d, f))).astype(jnp.bfloat16)
    cot = jax.random.normal(ks[3], (t * k, f), jnp.bfloat16)

    def vjp(product, lhs, rhs, sizes, cot):
        out, back = jax.vjp(lambda a, b: product(a, b, sizes), lhs, rhs)
        return (out,) + back(cot)

    args = (lhs, rhs, sizes, cot)
    got = jax.jit(functools.partial(vjp, G.gmm))(*args)
    want = jax.jit(functools.partial(vjp, G.ragged))(*args)
    for g, w, what in zip(got, want, ("output", "rows' gradient", "matrices' gradient")):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        rel = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        print(f"    {what}: relative L2 difference {rel:.3e}", flush=True)
        # both accumulate bf16 products in f32 and round the result to
        # bf16 once: they differ by the summation order, far under 1e-2
        check(rel <= 1e-2, f"grouped matmul {what} agrees with ragged_dot")


def moe_census():
    """``hlo_analysis.permutation_census`` of the sparse-experts phase's
    window step (remat on, as ``run_train`` builds it), lowered for this
    chip, at T = seq x batch tokens."""
    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.core.attacks import AttackConfig
    from repro.launch import trainer
    from repro.launch.hlo_analysis import permutation_census
    from repro.launch.mesh import make_debug_mesh
    from repro.optim.optimizers import get_optimizer

    cfg = dataclasses.replace(get_config(MOE_ARCH), n_layers=MOE_LAYERS)
    mesh = make_debug_mesh(1, 1)
    pcfg = ParallelConfig(agg_method="median", agg_strategy="bucketed", remat=True,
                          attn_chunk=0)
    opt = get_optimizer("adamw", LR)
    window = trainer.make_window_step(cfg, pcfg, mesh, opt, AttackConfig("none", 0.0),
                                      device_steps=DEVICE_STEPS)
    shape = ShapeConfig("smoke", MOE_SEQ, MOE_BATCH, "train")
    text = window.lower(trainer.abstract_state(cfg, mesh, opt, pcfg),
                        trainer.abstract_window_batches(cfg, shape, mesh, DEVICE_STEPS)).as_text()
    return permutation_census(text, MOE_SEQ * MOE_BATCH, cfg.moe.top_k, cfg.d_model)


def check_compiled_kernels(names) -> None:
    from repro.kernels import mode

    interpreted = sorted(k for (k, interp), n in mode.TRACES.items()
                         if interp and n)
    check(not interpreted, f"no kernel ran in interpret mode ({interpreted})")
    for name in names:
        check(mode.TRACES[(name, False)] > 0, f"{name} lowered through Mosaic")


def phase_kernels(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fed import run as fed_run
    from repro.fed.rounds import aggregate_cohort
    from repro.kernels import ops, ref

    print(f"== kernels: robust_aggregate(backend='pallas') on "
          f"({KERNEL_ROWS}, {KERNEL_WIDTH}) bf16", flush=True)
    x = jax.random.normal(jax.random.PRNGKey(seed), (KERNEL_ROWS, KERNEL_WIDTH),
                          jnp.float32).astype(jnp.bfloat16)
    for method, reference in (
            ("median", ref.median_ref),
            ("trimmed_mean", functools.partial(ref.trimmed_mean_ref, beta=0.1))):
        fn = jax.jit(functools.partial(ops.robust_aggregate, method=method,
                                       beta=0.1, backend="pallas"))
        compiled = fn.lower(x).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{method}: the compiled program holds the Mosaic kernel")
        got = jax.block_until_ready(compiled(x))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(x))
        dt = time.perf_counter() - t0
        want = np.asarray(jax.jit(reference)(x), np.float32)
        got = np.asarray(got, np.float32)
        err = np.abs(got - want)
        print(f"    {method}: {dt * 1e3:.3f} ms per call, max |err| "
              f"{float(err.max()):.3e}", flush=True)
        if method == "median":
            # both take the same two middle order statistics and average
            # them in f32: bit-exact
            check(bool(np.array_equal(got, want)), "median equals the sort oracle")
        else:
            # the kernel sums the 14 kept rows in another order than the
            # oracle's sort-then-mean: one bf16 ulp of the result plus the
            # f32 reassociation error of a 14-term sum of N(0,1) draws
            tol = 2.0 ** -7 * np.abs(want) + 1e-5
            check(bool(np.all(err <= tol)),
                  "trimmed mean within 1 bf16 ulp + 1e-5 of the sort oracle")

    argv = FED_ARGV + ["--seed", str(seed)]
    print(f"== fed round aggregate: python -m repro.fed.run {' '.join(argv)} "
          f"--backend pallas|xla", flush=True)
    outs = {}
    for backend in ("pallas", "xla"):
        args = fed_run.build_parser().parse_args(argv + ["--backend", backend])
        pop, rcfg, _ = fed_run.setup(args)
        w0 = jnp.zeros((pop.cfg.dim,))
        # round 0's cohort, as run_rounds samples it
        ids = pop.sample_cohort(
            jax.random.fold_in(jax.random.PRNGKey(rcfg.seed), 0),
            rcfg.cohort_size)
        g = jax.block_until_ready(aggregate_cohort(pop, w0, ids, rcfg))
        t0 = time.perf_counter()
        g = jax.block_until_ready(aggregate_cohort(pop, w0, ids, rcfg))
        print(f"    {backend}: {time.perf_counter() - t0:.3f} s per round "
              f"aggregate (nbins {rcfg.nbins}, chunk {rcfg.chunk_clients})",
              flush=True)
        outs[backend] = np.asarray(g)
    rows = np.concatenate([
        np.asarray(pop.client_grads(w0, ids[s:s + rcfg.chunk_clients]))
        for s in range(0, rcfg.cohort_size, rcfg.chunk_clients)])
    width = (rows.max(axis=0) - rows.min(axis=0)) / rcfg.nbins
    diff = np.abs(outs["pallas"] - outs["xla"])
    print(f"    max |pallas - xla| {float(diff.max()):.3e}; bin width "
          f"{float(width.min()):.3e}..{float(width.max()):.3e}", flush=True)
    check(bool(np.all(np.isfinite(outs["pallas"]))), "finite fed aggregate")
    # the two backends bin identically up to f32 rounding at a bin edge;
    # the 1e-5 relative slack absorbs the rounding of the bin centres
    check(bool(np.all(diff <= width * (1 + 1e-5))),
          "pallas and xla sketch medians agree within one bin width")
    check_compiled_kernels(["splash_attention", "grouped_matmul", "median_pallas",
                            "trimmed_mean_pallas", "minmax_pallas",
                            "histogram_pallas"])


def phase_four_chips(counter, seed):
    from repro.core.attacks import AttackConfig

    print(f"== train on four chips: {ARCH}, n_layers {LAYERS} of "
          f"{PUBLISHED_LAYERS}, 4x1 mesh, per-device batch "
          f"{BATCH_PER_DEVICE}", flush=True)
    for strategy, agg, attack in (
            ("bucketed", "median", AttackConfig("alie", 0.25)),
            ("psum", "mean", None)):
        result, win = run_train(counter, layers=LAYERS, strategy=strategy,
                                agg=agg, workers=4, steps=STEPS,
                                device_steps=DEVICE_STEPS, attack=attack,
                                seed=seed)
        check_train(result, win, STEPS, DEVICE_STEPS)
        result.state.clear()
    print(f"== one step, bucketed vs gather, n_layers {COMPARE_LAYERS} "
          f"(gather holds m*|g| per device)", flush=True)
    params = {}
    for strategy in ("bucketed", "gather"):
        result, win = run_train(counter, layers=COMPARE_LAYERS,
                                strategy=strategy, agg="median", workers=4,
                                steps=1, device_steps=1, seed=seed)
        check_train(result, win, 1, 1)
        params[strategy] = host_params(result)
    params_agree(params["bucketed"], params["gather"], 1,
                 "m=4 bucketed vs gather")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip robust training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.launch import runtime, spans
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    cache = runtime.enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1

    counter = spans.CompileCounter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_four_chips(counter, args.seed)
        else:
            phase_train_one_chip(counter, args.seed)
            phase_moe(counter, args.seed)
            phase_kernels(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
