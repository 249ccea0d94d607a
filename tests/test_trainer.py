"""Device-steps trainer (launch/trainer.py): equivalence, determinism,
and the lowering contract of the donated window step.

The multi-device tests run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count (the main test process
keeps the default 1 device per the dry-run contract).

Pinned here:

- same seed => bit-identical final params for device_steps 1 vs 4,
  including under an in-step randomized attack (the per-micro-step
  attack key folds from the global step index, not the window position);
- device_steps=1 is bit-for-bit the hand-rolled step-by-step loop;
- the compiled window HLO: collective op counts are device_steps-
  invariant (one robust reduction per inner micro-step — the scan body
  is traced once), collective BYTES scale exactly x device_steps
  (trip-count-aware), the scan lowers to a rolled while loop, and no
  host transfer (infeed/outfeed) is compiled into the window;
- the CLI front-end (python -m repro.launch.train) trains end to end.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


# A transformer small enough that a subprocess compiles+trains in
# seconds, but with the real llama-family structure (GQA, gated mlp).
PRELUDE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import llama3_2_3b
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.core.attacks import AttackConfig
from repro.data.pipeline import DataConfig, make_lm_batch
from repro.launch import steps, trainer
from repro.launch import mesh as mesh_lib
from repro.optim.optimizers import get_optimizer

cfg = dataclasses.replace(
    llama3_2_3b.smoke_config(), name="trainer-test-tiny",
    n_layers=1, d_model=64, n_heads=4, n_kv_heads=2, d_ff=172, vocab=128)
mesh = mesh_lib.make_debug_mesh(4, 1)
pcfg = ParallelConfig(agg_method="median", agg_strategy="bucketed", remat=False)
dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=16, global_batch=4,
                  num_workers=4, seed=0)

def leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
"""


def test_window_size_invariance_and_attack_key_folding():
    """Same seed => identical final params for device_steps 1 vs 4 — under
    ALIE, so the equality also pins that the in-step attack key folds from
    the GLOBAL step index (a window-position fold would diverge).  The
    clean run must differ (the attack really runs inside the scan)."""
    run_sub(PRELUDE + """
def final(ds, attack):
    tcfg = TrainConfig(optimizer="adamw", lr=1e-2, steps=4, device_steps=ds)
    r = trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, attack=attack)
    assert int(r.state["step"]) == 4
    assert int(r.state["metrics"]["micro_steps"]) == 4
    return r.state["params"]

alie = AttackConfig("alie", 0.25)
p1, p4 = final(1, alie), final(4, alie)
assert leaves_equal(p1, p4), "device_steps must not change the trajectory"
clean = final(4, None)
assert not leaves_equal(p4, clean), "ALIE had no effect inside the window"
print("OK")
""")


def test_ds1_bitwise_equals_handrolled_step_loop():
    """The window harness at device_steps=1 is bit-for-bit a hand-rolled
    python loop over the SAME validated step body (steps.make_step_body)
    wrapped step-by-step — the scan adds nothing to the numerics."""
    run_sub(PRELUDE + """
attack = AttackConfig("sign_flip", 0.25)
opt = get_optimizer("adamw", 1e-2, 0.0, 0.9)
tcfg = TrainConfig(optimizer="adamw", lr=1e-2, steps=4, device_steps=1)
r = trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, attack=attack)

sb = steps.make_step_body(cfg, pcfg, mesh, opt, attack)
stepped = jax.shard_map(
    sb.body, mesh=mesh,
    in_specs=(sb.pspec, sb.ospec, sb.batch_spec, P(), P()),
    out_specs=(sb.pspec, sb.ospec, P()),
    axis_names=frozenset(sb.waxes), check_vma=False)
stepped = jax.jit(stepped)
state = trainer.init_state(cfg, mesh, opt, seed=0, pcfg=pcfg)
params, opt_state = state["params"], state["opt_state"]
atk_base = jax.random.PRNGKey(0)
for i in range(4):
    batch = make_lm_batch(dcfg, i, attack)
    params, opt_state, m = stepped(params, opt_state, batch,
                                   jnp.int32(i), atk_base)
assert leaves_equal(r.state["params"], params), \\
    "window(ds=1) diverged from the hand-rolled step loop"
print("OK")
""")


def test_ds1_bitwise_equals_make_train_step():
    """Against the OTHER production path: the newer-jax pinned
    steps.make_train_step (jax.shard_map partial-manual) driven step by
    step must reproduce the trainer's device_steps=1 params bit-for-bit
    (make_train_step's fixed attack-key base is PRNGKey(0) == the
    trainer's seed-0 key)."""
    run_sub(PRELUDE + """
attack = AttackConfig("sign_flip", 0.25)
opt = get_optimizer("adamw", 1e-2, 0.0, 0.9)
tcfg = TrainConfig(optimizer="adamw", lr=1e-2, steps=4, device_steps=1)
r = trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, attack=attack)
want = jax.tree.map(np.asarray, r.state["params"])

step_fn = steps.make_train_step(cfg, pcfg, mesh, opt, attack)
state = trainer.init_state(cfg, mesh, opt, seed=0, pcfg=pcfg)
params, opt_state = state["params"], state["opt_state"]
for i in range(4):
    batch = make_lm_batch(dcfg, i, attack)
    params, opt_state, m = step_fn(params, opt_state, batch, jnp.int32(i))
assert leaves_equal(want, params), \\
    "window(ds=1) diverged from make_train_step"
print("OK")
""")


def test_window_hlo_contract():
    """Compiled-HLO assertions on the abstract-lowered window (bucketed):
    one robust reduction per micro-step (collective op counts identical
    for ds=1 and ds=4 — the scan body is traced once), collective bytes
    scale exactly x device_steps, the scan is a rolled while loop, and no
    infeed/outfeed is compiled inside the window."""
    run_sub(PRELUDE + """
from repro.launch import hlo_analysis

opt = get_optimizer("adamw", 1e-2, 0.0, 0.9)
shape = ShapeConfig("t", 16, 4, "train")

lowered, compiled, hlo = {}, {}, {}
for ds in (1, 4):
    w = trainer.make_window_step(cfg, pcfg, mesh, opt, device_steps=ds)
    low = w.lower(trainer.abstract_state(cfg, mesh, opt, pcfg=pcfg),
                  trainer.abstract_window_batches(cfg, shape, mesh, ds))
    lowered[ds] = low.as_text()
    compiled[ds] = low.compile().as_text()
    hlo[ds] = hlo_analysis.analyze(compiled[ds])

import re
def counts(text):
    ops = {}
    for op in ("all_gather", "all_to_all", "all_reduce", "reduce_scatter"):
        pat = op.replace("_", "[_-]")
        ops[op] = len(re.findall(rf"\\b{pat}\\b(?![_-]done)", text))
    return ops

c1, c4 = counts(lowered[1]), counts(lowered[4])
assert c1 == c4, f"collective count changed with ds: {c1} vs {c4}"
assert c4["all_to_all"] >= 1, c4  # the bucketed robust reduction is there
assert "while" in compiled[4], "ds=4 scan did not lower to a while loop"
ratio = hlo[4]["collective_bytes"] / hlo[1]["collective_bytes"]
assert abs(ratio - 4) <= 0.04, f"collective bytes ratio {ratio} != 4"
low4 = compiled[4].lower()
assert "infeed" not in low4 and "outfeed" not in low4, \\
    "host transfer compiled inside the window"
print("OK")
""")


def test_cli_trains_end_to_end():
    """python -m repro.launch.train — the rewritten CLI front-end — runs a
    short bucketed+ALIE training on the debug mesh and reports the
    window-harness summary line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--config", "llama3.2-3b", "--smoke", "--steps", "4",
         "--device-steps", "2", "--workers", "4", "--seq-len", "32",
         "--global-batch", "4", "--strategy", "bucketed", "--agg", "median",
         "--attack", "alie", "--attack-alpha", "0.25"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "done: 4 steps in windows of 2" in r.stdout, r.stdout
    assert "loss" in r.stdout


# ---------------------------------------------------------------------------
# host-side validation (no devices needed)
# ---------------------------------------------------------------------------


def test_train_loop_rejects_ragged_windows():
    from repro.configs import llama3_2_3b
    from repro.configs.base import ParallelConfig, TrainConfig
    from repro.launch import trainer
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(1, 1)
    cfg = llama3_2_3b.smoke_config()
    pcfg = ParallelConfig()
    with pytest.raises(ValueError, match="multiple of device_steps"):
        trainer.train_loop(cfg, pcfg, TrainConfig(steps=3, device_steps=2), mesh)


def test_make_window_step_rejects_bad_device_steps():
    from repro.configs import llama3_2_3b
    from repro.configs.base import ParallelConfig
    from repro.launch import trainer
    from repro.launch.mesh import make_debug_mesh
    from repro.optim.optimizers import get_optimizer

    mesh = make_debug_mesh(1, 1)
    with pytest.raises(ValueError, match="device_steps"):
        trainer.make_window_step(llama3_2_3b.smoke_config(), ParallelConfig(),
                                 mesh, get_optimizer("sgd", 1e-2),
                                 device_steps=0)


# ---------------------------------------------------------------------------
# throughput-benchmark plumbing (pure JSON math — the CI --gate-train path)
# ---------------------------------------------------------------------------


def _rec(config, strategy, attack, ms, params):
    return {"config": config, "strategy": strategy, "attack": attack,
            "status": "ok", "step_time_ms": ms, "params": params}


class TestTrainGate:
    def test_passes_within_threshold(self):
        from benchmarks.train_throughput import gate_from_records

        g = gate_from_records([
            _rec("tiny", "psum", "none", 10.0, 1_000),
            _rec("big", "psum", "none", 100.0, 4_000_000),
            _rec("big", "bucketed", "none", 105.0, 4_000_000),
            _rec("big", "chunked", "none", 500.0, 4_000_000),
        ])
        assert g["ok"] and g["config"] == "big"
        assert g["robust_strategy"] == "bucketed"
        assert abs(g["overhead"] - 0.05) < 1e-9

    def test_fails_over_threshold(self):
        from benchmarks.train_throughput import gate_from_records

        g = gate_from_records([
            _rec("big", "psum", "none", 100.0, 4_000_000),
            _rec("big", "bucketed", "none", 120.0, 4_000_000),
        ])
        assert not g["ok"] and g["overhead"] >= 0.10

    def test_gate_uses_largest_config_and_clean_cells_only(self):
        from benchmarks.train_throughput import gate_from_records

        g = gate_from_records([
            # attacked cells and the small config must not enter the gate
            _rec("big", "psum", "alie", 1.0, 4_000_000),
            _rec("big", "bucketed", "alie", 99.0, 4_000_000),
            _rec("tiny", "psum", "none", 1.0, 1_000),
            _rec("tiny", "bucketed", "none", 50.0, 1_000),
            _rec("big", "psum", "none", 100.0, 4_000_000),
            _rec("big", "bucketed", "none", 101.0, 4_000_000),
        ])
        assert g["ok"] and g["config"] == "big"
        assert g["baseline_ms"] == 100.0 and g["robust_ms"] == 101.0

    def test_missing_cells_fail_closed(self):
        from benchmarks.train_throughput import gate_from_records

        assert not gate_from_records([])["ok"]
        assert not gate_from_records(
            [_rec("big", "psum", "none", 100.0, 1)])["ok"]
        # skipped records don't count as coverage
        assert not gate_from_records(
            [{"config": "big", "strategy": "bucketed", "attack": "none",
              "status": "skipped"},
             _rec("big", "psum", "none", 100.0, 1)])["ok"]

    def test_committed_grid_passes_the_gate(self):
        """BENCH_train.json (the committed full grid) must satisfy the
        <10% robust-aggregation overhead gate — the same deterministic
        re-check CI runs via benchmarks/run.py --gate-train."""
        path = os.path.join(ROOT, "BENCH_train.json")
        if not os.path.exists(path):
            pytest.skip("BENCH_train.json not yet committed")
        from benchmarks.train_throughput import gate_from_records

        with open(path) as f:
            payload = json.load(f)
        assert payload["suite"] == "train"
        g = gate_from_records(payload["records"])
        assert g["ok"], f"committed grid violates the overhead gate: {g}"
        assert not payload.get("violations"), payload["violations"]


class TestBenchDiffTrain:
    def _main(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_diff", os.path.join(ROOT, "scripts", "bench_diff.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main

    def _payload(self):
        return {"suite": "train", "records": [
            {**_rec("big", "psum", "none", 100.0, 10), "tokens_per_s": 9.0},
            {"config": "big", "strategy": "chunked", "attack": "none",
             "status": "skipped", "reason": "too slow here"},
        ]}

    def test_missing_baseline_is_not_an_error(self, tmp_path, capsys):
        new = tmp_path / "new.json"
        new.write_text(json.dumps(self._payload()))
        rc = self._main()(["--base", str(tmp_path / "nope.json"),
                           "--new", str(new)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "new suite" in out and "no committed baseline" in out

    def test_train_table_skips_non_ok_records(self, tmp_path, capsys):
        p = tmp_path / "a.json"
        p.write_text(json.dumps(self._payload()))
        rc = self._main()(["--base", str(p), "--new", str(p)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "| big | psum | none | 100.0 | 100.0 | +0.0 |" in out
        assert "chunked" not in out  # skipped records stay out of the table
