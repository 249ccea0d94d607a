"""Training-throughput grid: tokens/sec and step time for the device-steps
trainer across {plain data-parallel, gather, bucketed, chunked} ×
{clean, alie, sign_flip}.

Measures the REAL training loop (``launch.trainer.train_loop`` — donated
window state, ``device_steps`` inner scan, robust aggregation fused into
the sharded step) on a simulated multi-worker CPU mesh, at two shapes:

- ``tiny-transformer`` — a 1-layer transformer small enough that every
  strategy (including the nbins-heavy chunked histogram sketch) finishes
  in CI time; the full strategy × attack grid runs here;
- ``llama3.2-bench`` — the reduced-shape llama3.2 variant
  (``configs.llama3_2_3b.bench_config``), the "largest config that
  fits" the benchmark host, where model compute dominates and the <10%
  robust-aggregation overhead gate is measured.  The chunked sketch is
  compute-bound on a CPU host at this size (nbins·|g| histogram work per
  step) and is skipped with an explicit record — it targets huge worker
  counts on real accelerators, not single-host simulation.

Two check families (``violations`` / ``failed_gates`` in the payload,
comm/async-suite style):

- **structure** (always, deterministic): HLO-asserted from the compiled
  window — the lowering has exactly one robust reduction per inner
  micro-step (collective op counts are identical for device_steps 1 and
  4 because the scan body is traced once; bucketed shows exactly one
  all-to-all), compiled collective bytes scale ×device_steps (the
  trip-count-aware ``launch.hlo_analysis``), and there is NO host
  transfer (infeed/outfeed) inside the scan window.  Roofline-bound
  tokens/sec (``launch.roofline``) is recorded alongside for context.
- **overhead gate** (full runs only — wall-clock timing would flake at
  smoke sizes where aggregation is not amortized): at the largest
  benchmarked config, the best robust strategy of {bucketed, chunked}
  must add < ``GATE_MAX_OVERHEAD`` step-time overhead vs the plain
  data-parallel psum baseline, clean cells.  Step time is the MIN over
  steady (post-compile) windows — on a shared host, scheduler
  interference only ever adds time, so the minimum is the noise-robust
  estimator (the mean is recorded as ``step_time_mean_ms`` for the
  trend).  CI re-checks the same gate deterministically against the
  committed BENCH_train.json via ``benchmarks.run --gate-train``.

CLI::

    PYTHONPATH=src python -m benchmarks.train_throughput --json BENCH_train.json
    PYTHONPATH=src python -m benchmarks.train_throughput --smoke  # CI sizes

exits non-zero iff any structural check or (full mode) the overhead gate
fails.  Import of this module is side-effect-free (run.py reads the gate
helper); jax and the XLA device-count flag are touched only by main().
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional, Tuple

GATE_MAX_OVERHEAD = 0.10  # the ISSUE's <10% step-time overhead bar
GATE_STRATEGIES = ("bucketed", "chunked")  # robust candidates for the gate
BASELINE = "psum"  # plain data-parallel mean
DS_REF = 1  # reference window size for the ×device_steps HLO scaling check


@dataclasses.dataclass(frozen=True)
class TrainBenchConfig:
    workers: int = 4
    steps: int = 16
    device_steps: int = 4
    # 512 keeps the big config in the compute-dominated regime the
    # overhead gate is about: on the 1-core CPU bench host the simulated
    # devices SERIALIZE, so per-device aggregation compute is charged
    # x workers while a real pod runs it in parallel — model compute
    # must dominate by enough margin to measure the same ratio a real
    # accelerator would see (real LM training is far more compute-heavy
    # per parameter than any reduced shape).
    seq_len: int = 512
    tiny_seq_len: int = 64
    global_batch: int = 4
    alpha: float = 0.25  # Byzantine fraction for the attacked cells
    attacks: Tuple[str, ...] = ("none", "alie", "sign_flip")
    tiny_strategies: Tuple[str, ...] = ("psum", "gather", "bucketed", "chunked")
    big_strategies: Tuple[str, ...] = ("psum", "gather", "bucketed")
    include_big: bool = True
    optimizer: str = "adamw"
    lr: float = 1e-3


SMOKE = TrainBenchConfig(
    steps=4, device_steps=2, attacks=("none", "alie"),
    tiny_strategies=("psum", "gather", "bucketed", "chunked"),
    include_big=False)


def _tiny_config():
    """The small transformer that fits CI: 1 layer, llama-family shape."""
    from repro.configs import llama3_2_3b

    return dataclasses.replace(
        llama3_2_3b.smoke_config(), name="tiny-transformer",
        n_layers=1, d_model=128, n_heads=4, n_kv_heads=2, d_ff=344, vocab=256)


def _bench_configs(cfg: TrainBenchConfig):
    """[(model_cfg, seq_len, strategies)] — tiny first, largest last."""
    from repro.configs import llama3_2_3b

    out = [(_tiny_config(), cfg.tiny_seq_len, cfg.tiny_strategies)]
    if cfg.include_big:
        out.append((llama3_2_3b.bench_config(), cfg.seq_len,
                    cfg.big_strategies))
    return out


def _coll_op_counts(text: str):
    """Collective op counts from lowered StableHLO / HLO text."""
    import re

    ops = ("all_gather", "all_to_all", "all_reduce", "reduce_scatter",
           "collective_permute")
    counts = {}
    for op in ops:
        pat = op.replace("_", "[_-]")
        counts[op] = len(re.findall(rf"\b{pat}\b(?![_-]done)", text))
    return counts


def _structure_checks(model_cfg, seq_len: int, strategy: str, mesh,
                      cfg: TrainBenchConfig, verbose: bool):
    """Compile the window at device_steps ∈ {1, ds} on abstract inputs and
    assert the lowering contract (see module docstring)."""
    import jax  # noqa: F401  (lazy: keep module import side-effect-free)
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.launch import hlo_analysis, roofline, trainer
    from repro.optim.optimizers import get_optimizer

    ds = cfg.device_steps
    method = "mean" if strategy == BASELINE else "median"
    pcfg = ParallelConfig(agg_method=method, agg_strategy=strategy, remat=False)
    opt = get_optimizer(cfg.optimizer, cfg.lr)
    shape = ShapeConfig("bench", seq_len, cfg.global_batch, "train")
    checks = []

    lowered, compiled, hlo = {}, {}, {}
    for d in (DS_REF, ds):
        w = trainer.make_window_step(model_cfg, pcfg, mesh, opt,
                                     device_steps=d)
        st = trainer.abstract_state(model_cfg, mesh, opt, pcfg=pcfg)
        bt = trainer.abstract_window_batches(model_cfg, shape, mesh, d)
        low = w.lower(st, bt)
        lowered[d] = low.as_text()
        comp = low.compile()
        compiled[d] = comp.as_text()
        hlo[d] = hlo_analysis.analyze(compiled[d])

    def add(name, ok, detail):
        checks.append({"kind": "structure", "config": model_cfg.name,
                       "strategy": strategy, "check": name, "ok": bool(ok),
                       "detail": detail})
        if verbose and not ok:
            print(f"STRUCTURE FAIL {model_cfg.name}/{strategy} {name}: "
                  f"{detail}", file=sys.stderr)

    # one robust reduction per inner micro-step: the scan body is traced
    # once, so the lowered collective op counts must be IDENTICAL for
    # window sizes 1 and ds ...
    c1, cd = _coll_op_counts(lowered[DS_REF]), _coll_op_counts(lowered[ds])
    add("collective_count_ds_invariant", c1 == cd, {"ds1": c1, f"ds{ds}": cd})
    # ... and the bucketed robust reduction fires exactly once per
    # coalesced super-bucket group (one all_to_all each, never ×ds)
    if strategy == "bucketed":
        from repro.core import distributed
        from repro.models import transformer as T

        expected = len(distributed._coalesce_groups(
            jax.tree.leaves(T.param_shapes(model_cfg))))
        add("one_all_to_all_per_super_bucket_per_micro_step",
            cd["all_to_all"] == expected,
            {**cd, "expected_groups": expected})
    if strategy == BASELINE:
        add("psum_is_all_reduce_only",
            cd["all_to_all"] == 0 and cd["all_gather"] == 0
            and cd["all_reduce"] >= 1, cd)
    # the window really is a rolled loop on device
    add("scan_lowers_to_while", "while" in compiled[ds], {"ds": ds})
    # compiled collective bytes scale ×device_steps (trip-count-aware)
    ref = hlo[DS_REF]["collective_bytes"]
    got = hlo[ds]["collective_bytes"]
    scale_ok = ref > 0 and abs(got / ref - ds) <= 0.01 * ds
    add("collective_bytes_scale_x_device_steps", scale_ok,
        {"ds1_bytes": ref, f"ds{ds}_bytes": got, "expected_ratio": ds})
    # zero host syncs inside the window: no host transfer ops compiled in
    host_ops = [op for op in ("infeed", "outfeed")
                if op in compiled[ds].lower()]
    add("no_host_transfer_in_window", not host_ops, {"found": host_ops})

    tokens = cfg.global_batch * seq_len * ds
    bound = roofline.roofline_tokens_per_s(
        hlo[ds]["flops"], hlo[ds]["bytes"], hlo[ds]["collective_bytes"],
        tokens)
    return checks, {"config": model_cfg.name, "strategy": strategy,
                    "device_steps": ds,
                    "window_flops": hlo[ds]["flops"],
                    "window_bytes": hlo[ds]["bytes"],
                    "window_collective_bytes": hlo[ds]["collective_bytes"],
                    "roofline_tokens_per_s_v5e": bound}


def _time_cell(model_cfg, seq_len: int, strategy: str, attack_name: str,
               mesh, cfg: TrainBenchConfig, verbose: bool) -> dict:
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.core.attacks import AttackConfig
    from repro.data.pipeline import DataConfig
    from repro.launch import trainer
    from repro.models import transformer as T

    method = "mean" if strategy == BASELINE else "median"
    pcfg = ParallelConfig(agg_method=method, agg_strategy=strategy,
                          remat=False)
    tcfg = TrainConfig(optimizer=cfg.optimizer, lr=cfg.lr, steps=cfg.steps,
                       device_steps=cfg.device_steps)
    dcfg = DataConfig(kind="lm", vocab=model_cfg.vocab, seq_len=seq_len,
                      global_batch=cfg.global_batch,
                      num_workers=cfg.workers)
    attack = (None if attack_name == "none"
              else AttackConfig(attack_name, cfg.alpha))
    t0 = time.perf_counter()
    r = trainer.train_loop(model_cfg, pcfg, tcfg, mesh, dcfg=dcfg,
                           attack=attack)
    # min-window step time: scheduler interference on a shared host only
    # ever ADDS time, so the minimum over steady windows is the
    # noise-robust estimator the overhead gate compares (the mean is
    # recorded too for the throughput trend)
    min_step = r.min_step_time_s
    tokens = dcfg.global_batch * dcfg.seq_len
    rec = {
        "config": model_cfg.name,
        "params": T.count_params(model_cfg),
        "strategy": strategy,
        "attack": attack_name,
        "alpha": 0.0 if attack is None else cfg.alpha,
        "workers": cfg.workers,
        "steps": cfg.steps,
        "device_steps": cfg.device_steps,
        "seq_len": seq_len,
        "global_batch": cfg.global_batch,
        "status": "ok",
        "compile_s": round(r.compile_s, 3),
        "step_time_ms": round(min_step * 1000.0, 3) if min_step else None,
        "step_time_mean_ms": (round(1000.0 / r.steps_per_s, 3)
                              if r.steps_per_s else None),
        "steps_per_s": round(r.steps_per_s, 4),
        "tokens_per_s": (round(tokens / min_step, 1) if min_step
                         else round(r.tokens_per_s, 1)),
        "final_loss": round(r.history[-1]["loss"], 4),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if verbose:
        print(f"{model_cfg.name},{strategy},{attack_name},"
              f"{rec['step_time_ms']},{rec['tokens_per_s']}", flush=True)
    return rec


def gate_from_records(records, threshold: float = GATE_MAX_OVERHEAD) -> dict:
    """The <10%-overhead gate, computed from (possibly committed) records:
    at the largest config, min clean step time over GATE_STRATEGIES vs
    the clean psum baseline.  Pure JSON math — run.py re-runs this
    against the committed BENCH_train.json in CI (``--gate-train``)."""
    ok_recs = [r for r in records if r.get("status") == "ok"]
    if not ok_recs:
        return {"ok": False, "reason": "no ok records"}
    largest = max(ok_recs, key=lambda r: r["params"])["config"]
    at = [r for r in ok_recs if r["config"] == largest
          and r["attack"] == "none" and r["step_time_ms"]]
    base = [r for r in at if r["strategy"] == BASELINE]
    robust = [r for r in at if r["strategy"] in GATE_STRATEGIES]
    if not base or not robust:
        return {"ok": False, "config": largest,
                "reason": f"missing clean {BASELINE} or robust cells"}
    best = min(robust, key=lambda r: r["step_time_ms"])
    overhead = best["step_time_ms"] / base[0]["step_time_ms"] - 1.0
    return {
        "kind": "overhead", "config": largest,
        "baseline_ms": base[0]["step_time_ms"],
        "robust_strategy": best["strategy"],
        "robust_ms": best["step_time_ms"],
        "overhead": round(overhead, 4),
        "threshold": threshold,
        "ok": overhead < threshold,
    }


def evaluate(cfg: TrainBenchConfig = TrainBenchConfig(),
             verbose: bool = True, gate: Optional[bool] = None) -> dict:
    """Run the grid; ``gate=None`` gates iff this is a full (non-smoke)
    config (smoke sizes are too small to amortize aggregation)."""
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_debug_mesh(cfg.workers, 1)
    if gate is None:
        gate = cfg.include_big
    records, structure, roofs = [], [], []
    if verbose:
        print("config,strategy,attack,step_time_ms,tokens_per_s")
    combos = _bench_configs(cfg)
    for model_cfg, seq_len, strategies in combos:
        for strategy in ("psum", "bucketed"):
            if strategy not in strategies:
                continue
            checks, roof = _structure_checks(model_cfg, seq_len, strategy,
                                             mesh, cfg, verbose)
            structure.extend(checks)
            roofs.append(roof)
        for strategy in strategies:
            for attack_name in cfg.attacks:
                records.append(_time_cell(model_cfg, seq_len, strategy,
                                          attack_name, mesh, cfg, verbose))
        if "chunked" not in strategies:
            # no silent caps: record why the sketch strategy is absent here
            for attack_name in cfg.attacks:
                records.append({
                    "config": model_cfg.name, "strategy": "chunked",
                    "attack": attack_name, "status": "skipped",
                    "reason": "histogram sketch is nbins·|g| compute-bound "
                              "on the CPU bench host at this size; measured "
                              "at tiny-transformer (it targets large m on "
                              "real accelerators)"})

    violations = [c for c in structure if not c["ok"]]
    failed_gates = []
    gate_result = gate_from_records(records) if gate else {
        "ok": True, "skipped": "smoke run — wall-clock gate needs the "
                               "full-size config; CI gates the committed "
                               "BENCH_train.json instead"}
    if gate and not gate_result["ok"]:
        failed_gates.append(gate_result)
    return {
        "suite": "train",
        "baseline": f"{BASELINE} (plain data-parallel all-reduce mean)",
        "records": records,
        "structure": structure,
        "roofline": roofs,
        "gate": gate_result,
        "violations": violations,
        "failed_gates": failed_gates,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="device-steps trainer throughput grid "
                    "(strategy × attack × config)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: tiny config only, no wall-clock gate")
    ap.add_argument("--json", nargs="?", const="BENCH_train.json",
                    default=None, metavar="PATH")
    ap.add_argument("--workers", type=int, default=None,
                    help="override simulated worker count")
    args = ap.parse_args(argv)

    from repro.launch.runtime import simulate_cpu_devices

    cfg = SMOKE if args.smoke else TrainBenchConfig()
    if args.workers:
        cfg = dataclasses.replace(cfg, workers=args.workers)
    simulate_cpu_devices(cfg.workers)

    out = evaluate(cfg, verbose=True)
    out["smoke"] = args.smoke
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json} ({len(out['records'])} records)",
              file=sys.stderr)
    if out["violations"] or out["failed_gates"]:
        print(f"train-throughput gates failed: {len(out['violations'])} "
              f"structural violations, {len(out['failed_gates'])} overhead "
              f"failures", file=sys.stderr)
        return 1
    g = out["gate"]
    if "overhead" in g:
        print(f"gate: {g['robust_strategy']} overhead "
              f"{g['overhead']*100:.1f}% vs {BASELINE} at {g['config']} "
              f"(< {g['threshold']*100:.0f}%)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
