"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (``run.run_cell``) at a tiny size on the CPU, with one fault planted
in the program: a window step that returns its state unchanged; half of
each worker's batch left out, the loss a mean over the rest; and, on four
simulated devices, the exchange between workers left out.
"""
import os
import subprocess
import sys
from pathlib import Path


from chip_tiny import DENSE, ROOT, run_tiny


def test_unchanged_state_is_not_correct(monkeypatch):
    from repro.launch import trainer

    monkeypatch.setattr(trainer, "make_window_step",
                        lambda *a, **k: (lambda state, batches: state))
    result = run_tiny(DENSE)
    assert not result["correct"]
    assert result["checks"]["grad_gap"]["value"] == 1.0


def test_half_batch_is_not_correct(monkeypatch):
    from repro.models import transformer as T

    full = T.loss_fn

    def half(params, batch, *args, **kwargs):
        rows = batch["tokens"].shape[0] // 2
        return full(params, {k: v[:rows] for k, v in batch.items()}, *args, **kwargs)

    monkeypatch.setattr(T, "loss_fn", half)
    result = run_tiny(DENSE)
    assert not result["correct"], result["checks"]


SUB = """
import sys
sys.path.insert(0, {tests!r})
from chip_tiny import DENSE, run_tiny
from repro.rounds import distributed
honest = run_tiny(DENSE, workers=4)
distributed.aggregate_by_strategy = lambda g, *a, **k: g
faulty = run_tiny(DENSE, workers=4)
print("RESULT", honest["correct"], faulty["correct"], honest["checks"], faulty["checks"])
"""


def test_four_workers_and_the_missing_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run(
        [sys.executable, "-c", SUB.format(tests=str(Path(__file__).parent))],
        env=env, capture_output=True, text=True, timeout=600)
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert line, out.stderr[-4000:]
    _, honest, faulty, *_ = line[0].split(" ", 3)
    assert honest == "True", line[0]
    assert faulty == "False", line[0]
