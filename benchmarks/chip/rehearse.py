#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse.py --workload train.danube.m1

Compiles, at the cell's real sizes, the program's window step
(``trainer.make_window_step`` on ``trainer.abstract_state``) and the
reference's per-worker gradient program, for the cell's chips of a
``v5e:2x2`` topology, and prints each one's ``memory_analysis()`` and
compile time.  What the chip's compiler would refuse, or what would not
fit a chip, fails here.  Nothing runs, so this gives sizes, never times.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _report(what: str, compiled, seconds: float) -> None:
    ma = compiled.memory_analysis()
    gb = 1e9
    print(f"{what}: compiled in {seconds:.1f} s; arguments "
          f"{ma.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{ma.output_size_in_bytes / gb:.3f} GB (aliased "
          f"{ma.alias_size_in_bytes / gb:.3f} GB), temporaries "
          f"{ma.temp_size_in_bytes / gb:.3f} GB per chip", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.chip import run
    from repro.configs.base import ShapeConfig
    from repro.launch import trainer
    from repro.optim.optimizers import get_optimizer

    jax.config.update("jax_enable_compilation_cache", False)
    cell = run.load_cell(args.workload)
    tr, c = cell.traffic, cell.config
    ref = run.reference_module(c)
    cfg = run.program_config(c)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devs = np.array(topo.devices[:cell.chips])
    mesh = Mesh(devs.reshape(tr["mesh"]), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    pcfg, attack = run.train_parts(tr)
    opt = get_optimizer(tr["optimizer"], tr["lr"])
    m, ds = tr["workers"], tr["device_steps"]
    shape = ShapeConfig("bench", tr["seq_len"], tr["batch_per_worker"] * m, "train")
    window = trainer.make_window_step(cfg, pcfg, mesh, opt, attack, device_steps=ds)
    t = time.perf_counter()
    compiled = window.lower(trainer.abstract_state(cfg, mesh, opt, pcfg),
                            trainer.abstract_window_batches(cfg, shape, mesh, ds)).compile()
    _report(f"{cell.name} window step", compiled, time.perf_counter() - t)

    wmesh = Mesh(devs.reshape(-1), ("w",))
    rep = NamedSharding(wmesh, P())
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=rep),
        jax.eval_shape(lambda: ref.init_params(c, jax.random.PRNGKey(0))))
    toks = jax.ShapeDtypeStruct((m, tr["batch_per_worker"], tr["seq_len"]), jnp.int32,
                                sharding=NamedSharding(wmesh, P("w")))
    with jax.default_matmul_precision("highest"):
        t = time.perf_counter()
        compiled = ref.make_grads(c, tr, wmesh).lower(params, toks, toks).compile()
    _report(f"{cell.name} reference gradients", compiled, time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
