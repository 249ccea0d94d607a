"""Serving-throughput grid: tokens/sec and tick latency for the
continuous-batching serve engine across {slots} × {adaptation cadence}.

Measures the REAL serving stack (``repro.serve.engine.ServeEngine`` +
``serve_stream`` — fixed-slot decode pool, prefill-on-admit, slot reuse
without recompile) fed by the seeded virtual-user traffic model, with
Byzantine-robust continual fine-tuning (``repro.serve.adapt``) firing on
its tick cadence in the ``adapt_every > 0`` cells.  ``adapt_every = 0``
is the serve-only baseline the overhead gate compares against.

Methodology:

- arrivals use the "zero" latency model so the pool is saturated from
  tick 0 — the measured number is peak decode throughput, not an
  arrival-process artifact;
- every cell WARMS UP first (a short stream that triggers at least one
  adaptation round when the cadence is active) so jit compilation —
  prefill, decode pool, admit, and the round executable — never lands
  in the measured window; the engine's no-recompile contract
  (``compile_counts``) is re-asserted after measurement and recorded;
- the measured phase serves a fresh request stream end-to-end; wall
  time covers decode ticks AND the synchronous robust rounds +
  hot-swaps, which is exactly the cost the gate is about.

Gate (full runs only — smoke sizes don't amortize the round cost): at
the LARGEST slot count, every robust-cadence cell must keep
``tok_per_s >= (1 - GATE_MAX_OVERHEAD) x`` the serve-only baseline at
the same slot count — continual robust adaptation must cost < 15%
serving throughput.  CI re-checks the same gate deterministically
against the committed BENCH_serve.json via ``benchmarks.run
--gate-serve`` (recorded numbers, immune to runner noise).

CLI::

    PYTHONPATH=src python -m benchmarks.serve_throughput --json BENCH_serve.json
    PYTHONPATH=src python -m benchmarks.serve_throughput --smoke  # CI sizes

exits non-zero iff (full mode) the overhead gate fails.  Import of this
module is side-effect-free (run.py reads the gate helper); jax and the
XLA device-count flag are touched only by main().
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional, Tuple

GATE_MAX_OVERHEAD = 0.15  # the ISSUE's <15% tokens/s overhead bar
BASELINE_CADENCE = 0  # adapt_every = 0: serve-only


@dataclasses.dataclass(frozen=True)
class ServeBenchConfig:
    slots_grid: Tuple[int, ...] = (2, 4, 8)
    # adapt_every ticks; 0 = serve-only.  One robust round costs m x B
    # full forward+backward passes — roughly the decode work of a
    # 50-tick window at 8 slots — so a production cadence amortizes it
    # over hundreds of ticks; 96/192 bracket the <15% gate regime (the
    # sub-50 cadences of the smoke grid exist to exercise the machinery,
    # not to pass the gate)
    cadences: Tuple[int, ...] = (0, 96, 192)
    requests: int = 192  # measured-phase stream length
    prompt_len: int = 16
    max_new: int = 16
    num_users: int = 100_000
    shards: int = 4
    alpha: float = 0.25
    attack: str = "feedback_flip"
    batch_per_shard: int = 2
    method: str = "median"
    optimizer: str = "sgd"
    lr: float = 0.1
    workers: int = 1  # simulated devices serialize on CPU; 1 is honest
    seed: int = 0


SMOKE = ServeBenchConfig(slots_grid=(2, 4), cadences=(0, 16), requests=16)


def _bench_model():
    """The serve-bench transformer: the llama3.2 smoke shape — decode is
    memory-light enough that a full grid fits CI wall clock while the
    round cost (m x B full forward+backward) is still a real fraction
    the cadence must amortize."""
    from repro.configs import get_smoke_config

    return get_smoke_config("llama3_2_3b")


def _make_cell(model_cfg, mesh, cfg: ServeBenchConfig, slots: int,
               cadence: int):
    """Fresh (engine, adapter, users) for one cell."""
    import jax

    from repro.fed.population import ArrivalConfig
    from repro.models import transformer as T
    from repro.serve.adapt import AdaptConfig, FeedbackAdapter
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.traffic import TrafficConfig, VirtualUsers

    scfg = ServeConfig(slots=slots, prompt_len=cfg.prompt_len,
                       max_new=cfg.max_new)
    tcfg = TrafficConfig(
        num_users=cfg.num_users, num_shards=cfg.shards, alpha=cfg.alpha,
        attack=cfg.attack, prompt_len=cfg.prompt_len,
        min_gen=max(1, cfg.max_new // 4), max_gen=cfg.max_new,
        vocab=model_cfg.vocab,
        arrival=ArrivalConfig(latency="zero"), seed=cfg.seed)
    users = VirtualUsers(tcfg)
    params = T.init_params(model_cfg, jax.random.PRNGKey(cfg.seed))
    engine = ServeEngine(model_cfg, mesh, scfg, params)
    adapter = None
    if cadence > 0:
        acfg = AdaptConfig(
            method=cfg.method, optimizer=cfg.optimizer, lr=cfg.lr,
            batch_per_shard=cfg.batch_per_shard, adapt_every=cadence,
            seed=cfg.seed)
        adapter = FeedbackAdapter(model_cfg, acfg, users, params)
    return engine, adapter, users


def _time_cell(model_cfg, mesh, cfg: ServeBenchConfig, slots: int,
               cadence: int, verbose: bool) -> dict:
    from repro.serve.engine import ServeMetrics, latency_stats, serve_stream

    engine, adapter, users = _make_cell(model_cfg, mesh, cfg, slots, cadence)

    # warmup: compile prefill/decode/admit — and, when the cadence is
    # active, at least one robust round + hot-swap (the round executable
    # must never compile inside the measured window)
    warm_stream = 1
    warm = max(2 * slots, 2 * cfg.shards * cfg.batch_per_shard)
    serve_stream(engine, users.sample_requests(warm, stream=warm_stream),
                 adapter=adapter)
    while adapter is not None and adapter.rounds_done == 0:
        warm_stream += 1
        serve_stream(engine,
                     users.sample_requests(warm, stream=warm_stream),
                     adapter=adapter)
    warm_rounds = adapter.rounds_done if adapter else 0

    # measured phase: fresh stream, fresh metrics, same (warm) engine
    engine.metrics = ServeMetrics(engine.scfg.window, engine.scfg.slots)
    requests = users.sample_requests(cfg.requests)
    t0 = time.perf_counter()
    completed = serve_stream(engine, requests, adapter=adapter)
    wall = time.perf_counter() - t0

    counts = engine.compile_counts()
    tokens = engine.metrics.total_tokens
    stats = latency_stats(completed)
    rec = {
        "config": model_cfg.name,
        "slots": slots,
        "adapt_every": cadence,
        "method": cfg.method if cadence > 0 else None,
        "attack": cfg.attack if cadence > 0 else None,
        "alpha": cfg.alpha if cadence > 0 else 0.0,
        "shards": cfg.shards,
        "requests": cfg.requests,
        "status": "ok",
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tok_per_s": round(tokens / wall, 1) if wall > 0 else None,
        "p50_latency_ticks": stats["p50_latency"],
        "p99_latency_ticks": stats["p99_latency"],
        "rounds": (adapter.rounds_done - warm_rounds) if adapter else 0,
        "no_recompile": all(v == 1 for v in counts.values()),
        "compile_counts": counts,
    }
    if verbose:
        print(f"{model_cfg.name},{slots},{cadence},{rec['tok_per_s']},"
              f"{rec['rounds']}", flush=True)
    return rec


def gate_from_records(records, threshold: float = GATE_MAX_OVERHEAD) -> dict:
    """The <15%-overhead gate, computed from (possibly committed)
    records: at the largest slot count, every robust-cadence cell's
    tokens/sec vs the serve-only baseline at the same slots.  Pure JSON
    math — run.py re-runs this against the committed BENCH_serve.json
    in CI (``--gate-serve``)."""
    ok_recs = [r for r in records if r.get("status") == "ok"
               and r.get("tok_per_s")]
    if not ok_recs:
        return {"ok": False, "reason": "no ok records"}
    slots = max(r["slots"] for r in ok_recs)
    at = [r for r in ok_recs if r["slots"] == slots]
    base = [r for r in at if r["adapt_every"] == BASELINE_CADENCE]
    robust = [r for r in at if r["adapt_every"] != BASELINE_CADENCE]
    if not base or not robust:
        return {"ok": False, "slots": slots,
                "reason": "missing serve-only baseline or robust cells"}
    base_tps = base[0]["tok_per_s"]
    cells = []
    for r in robust:
        overhead = 1.0 - r["tok_per_s"] / base_tps
        cells.append({"adapt_every": r["adapt_every"],
                      "tok_per_s": r["tok_per_s"],
                      "overhead": round(overhead, 4),
                      "ok": overhead < threshold})
    worst = max(cells, key=lambda c: c["overhead"])
    return {
        "kind": "serve_overhead", "slots": slots,
        "baseline_tok_per_s": base_tps,
        "cells": cells,
        "worst_overhead": worst["overhead"],
        "threshold": threshold,
        "ok": all(c["ok"] for c in cells),
    }


def evaluate(cfg: ServeBenchConfig = ServeBenchConfig(),
             verbose: bool = True, gate: Optional[bool] = None) -> dict:
    """Run the grid; ``gate=None`` gates iff this is a full (non-smoke)
    config (smoke streams are too short to amortize the round cost)."""
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_debug_mesh(cfg.workers, 1)
    if gate is None:
        gate = cfg is not SMOKE and cfg.requests > SMOKE.requests
    model_cfg = _bench_model()
    records = []
    if verbose:
        print("config,slots,adapt_every,tok_per_s,rounds")
    for slots in cfg.slots_grid:
        for cadence in cfg.cadences:
            records.append(_time_cell(model_cfg, mesh, cfg, slots, cadence,
                                      verbose))

    # the no-recompile contract is structural: any cell that recompiled
    # mid-stream is a violation regardless of its timing
    violations = [
        {"kind": "structure", "slots": r["slots"],
         "adapt_every": r["adapt_every"], "check": "no_recompile",
         "ok": False, "detail": r["compile_counts"]}
        for r in records if r.get("status") == "ok" and not r["no_recompile"]
    ]
    failed_gates = []
    gate_result = gate_from_records(records) if gate else {
        "ok": True, "skipped": "smoke run — the wall-clock gate needs the "
                               "full grid; CI gates the committed "
                               "BENCH_serve.json instead"}
    if gate and not gate_result["ok"]:
        failed_gates.append(gate_result)
    return {
        "suite": "serve",
        "baseline": "adapt_every=0 (serve-only, same slots)",
        "records": records,
        "gate": gate_result,
        "violations": violations,
        "failed_gates": failed_gates,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="continuous-batching serve throughput grid "
                    "(slots × adaptation cadence)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: small grid, no wall-clock gate")
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH")
    args = ap.parse_args(argv)

    from repro.launch.runtime import simulate_cpu_devices

    cfg = SMOKE if args.smoke else ServeBenchConfig()
    simulate_cpu_devices(cfg.workers)

    out = evaluate(cfg, verbose=True)
    out["smoke"] = args.smoke
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json} ({len(out['records'])} records)",
              file=sys.stderr)
    if out["violations"] or out["failed_gates"]:
        print(f"serve-throughput gates failed: {len(out['violations'])} "
              f"structural violations, {len(out['failed_gates'])} overhead "
              f"failures", file=sys.stderr)
        return 1
    g = out["gate"]
    if "worst_overhead" in g:
        print(f"gate: worst robust-cadence overhead "
              f"{g['worst_overhead']*100:.1f}% vs serve-only at "
              f"{g['slots']} slots (< {g['threshold']*100:.0f}%)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
