"""The robust trainer's trace spans (launch/spans.py).

- the compiled window step carries each layer's device scope in its HLO
  ``op_name`` metadata: the robust step's and the model's on one worker,
  the attack and the aggregation on four simulated workers;
- ``train_loop`` counts the compilations after its first window
  (``TrainResult.recompiles``);
- a profiler trace of ``train_loop`` holds its host spans.
"""
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.configs import get_smoke_config
from repro.configs.base import ParallelConfig, ShapeConfig, TrainConfig
from repro.core.attacks import AttackConfig
from repro.data.pipeline import DataConfig
from repro.launch import spans, trainer
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import get_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROBUST = {spans.LOCAL_GRAD, spans.AGGREGATE, spans.UPDATE, spans.METRICS}
DENSE = {spans.EMBED, spans.ATTN, spans.MLP, spans.HEAD_LOSS}
MOE = {spans.MOE_DISPATCH, spans.MOE_EXPERTS, spans.MOE_COMBINE}
SEQ, BATCH = 16, 4


def scopes_in(hlo: str) -> set:
    """The scope names in an HLO module's ``op_name`` metadata, with
    transform wrappers (``jvp(..)``, ``transpose(..)``) taken off."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo):
        for part in path.split("/"):
            while (m := re.fullmatch(r"[\w-]+\((.*)\)", part)):
                part = m.group(1)
            if re.match(r"(robust|model|data)\.", part):
                out.add(part)
    return out


def window_hlo(arch: str, workers: int = 1, **pcfg_kw) -> str:
    cfg = get_smoke_config(arch)
    mesh = make_mesh((workers, 1), ("data", "model"))
    pcfg = ParallelConfig(remat=True, attn_chunk=0, **pcfg_kw)
    opt = get_optimizer("adamw", 1e-3, 0.0, 0.9)
    attack = AttackConfig("alie", 0.25) if workers > 1 else None
    window = trainer.make_window_step(cfg, pcfg, mesh, opt, attack, device_steps=2)
    shape = ShapeConfig(name="t", kind="train", seq_len=SEQ, global_batch=BATCH * workers)
    return window.lower(trainer.abstract_state(cfg, mesh, opt, pcfg),
                        trainer.abstract_window_batches(cfg, shape, mesh, 2)).compile().as_text()


@pytest.mark.parametrize("arch,model", [("h2o-danube-1.8b", DENSE),
                                        ("granite-moe-1b-a400m", DENSE | MOE)])
def test_window_step_carries_its_scopes(arch, model):
    found = scopes_in(window_hlo(arch, agg_strategy="bucketed", agg_method="median"))
    # one worker: no attack, no codec; the aggregate of one row still runs
    assert found == ROBUST | model


@pytest.mark.parametrize("strategy,compression,param_mode", [
    ("bucketed", "none", "replicated"), ("chunked", "none", "replicated"),
    ("gather", "int8", "replicated"), ("gather", "none", "fsdp")])
def test_attack_and_aggregate_scopes_on_four_workers(strategy, compression, param_mode):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep + os.path.join(ROOT, "tests"))
    code = f"""
        import json
        from test_spans import scopes_in, window_hlo
        hlo = window_hlo("h2o-danube-1.8b", workers=4, agg_strategy={strategy!r},
                         agg_method="median", compression={compression!r},
                         param_mode={param_mode!r})
        print(json.dumps(sorted(scopes_in(hlo))))
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    found = set(json.loads(r.stdout.splitlines()[-1]))
    expected = ROBUST | DENSE | {spans.ATTACK} | ({spans.CODEC} if compression != "none" else set())
    assert found == expected


def train(tmp_path, windows: int, **kw):
    cfg = get_smoke_config("h2o-danube-1.8b")
    mesh = make_mesh((1, 1), ("data", "model"))
    pcfg = ParallelConfig(agg_method="median", agg_strategy="bucketed", remat=True, attn_chunk=0)
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, steps=2 * windows, seed=3, device_steps=2)
    dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=SEQ, global_batch=BATCH,
                      num_workers=1, seed=3)
    return trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, ckpt_dir=str(tmp_path / "ckpt"),
                              **kw)


def test_no_recompile_after_the_first_window(tmp_path):
    result = train(tmp_path, windows=4, ckpt_every=1)
    assert len(result.history) == 4
    assert result.recompiles == 0


def host_events(trace_dir) -> list:
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines for e in line.events]


def test_profiler_trace_holds_the_host_spans(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "fresh"), profiler_options=options)
    try:
        train(tmp_path, windows=2, ckpt_every=1)
    finally:
        jax.profiler.stop_trace()
    names = host_events(tmp_path / "fresh")
    for span in (spans.BUILD_STEP, spans.INIT_STATE, spans.STAGE_BATCHES, spans.DISPATCH,
                 spans.METRIC_READ, spans.CHECKPOINT):
        assert names.count(span) >= 1, span
    assert names.count(spans.WINDOW) == 2
    assert names.count(spans.STAGE_BATCHES) == 2 and names.count(spans.CHECKPOINT) == 2

    jax.profiler.start_trace(str(tmp_path / "resumed"), profiler_options=options)
    try:
        result = train(tmp_path, windows=3, ckpt_every=1, resume=True)
    finally:
        jax.profiler.stop_trace()
    assert result.history[-1]["step"] == 6
    names = host_events(tmp_path / "resumed")
    assert names.count(spans.RESUME) == 1 and names.count(spans.WINDOW) == 1
