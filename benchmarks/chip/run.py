#!/usr/bin/env python3
"""One run of one cell of the chip benchmark of the robust trainer.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its model
configuration in the file that entry names, its traffic in
``traffic/<traffic>.json``, the limits of its correctness check in
``workloads/<cell>.json``, each per-layer metric's reader in
``metrics/<metric>.py`` and the chip's peaks in ``peaks.json``.

A run drives ``repro.launch.trainer.train_loop`` (what ``python -m
repro.launch.train`` calls) once, with weights and tokens made from
``--seed``.  Its first window of ``device_steps`` steps compiles (or loads
the compiled step from the cache in ``.jax_cache/`` of the checkout) and
is set-up; the state it leaves is copied to the host for the
correctness check.  The windows after it are timed on the host clock
from the end of the first window until at least ``--seconds`` have
passed, and the run then stops the loop at that window boundary.
``tokens_per_s`` is every token of the timed windows over that time;
``setup_s`` runs from the start of this process to the start of the
timed windows, less the time that copy took (correctness work, not
set-up).

With ``--trace 1`` the profiler records a few seconds of windows instead,
and the per-layer metrics are read from that trace.

Once the loop has stopped, its state is freed and the memory peak is
read, the change of the parameters is taken against the seed's initial
weights, the plain float32 reference (the configuration's ``reference``
module) follows the same first steps, and ``compare.py`` decides
``correct``.  The last line of
stdout is one JSON object; the numbers compared, each beside its limit,
are the last lines of stderr and the last key of that object.

Exits 1, printing no result, when JAX's first device is not a TPU or
there are fewer than the cell's chips; 2 when the program or a file of
the benchmark cannot be loaded.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if __name__ == "__main__":
    # run as a script: this directory's trace.py must not shadow the
    # standard library's, and the benchmark and the program import by name
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
MAX_WINDOWS = 1_000_000  # the loop is stopped by the clock long before
TRACE_SECONDS = 4.0  # traced windows: at least this long, and two windows
PROGRAM_LAYERS = "blocks.p0_attn."  # the program's stacked layer leaves
REFERENCE_LAYERS = "layers."


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    here = root / "benchmarks" / "chip"
    return Cell(
        name=name,
        chips=entry["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads((here / "traffic" / f"{entry['traffic']}.json").read_text()),
        limits=json.loads((here / "workloads" / f"{name}.json").read_text())["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _for_cell(m, name)],
    )


def _reader(metric: str):
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{metric.replace('.', '_')}", HERE / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(config: dict):
    return importlib.import_module(f"benchmarks.chip.{config['reference']}")


class CompileCounter:
    """Counts XLA backend compilations (cache hits included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1


def program_config(c: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs import get_config
    from repro.configs.base import MoEConfig

    kw = dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
              n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
              head_dim=c.get("head_dim", 0), vocab=c["vocab_size"],
              rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
              sliding_window=c.get("sliding_window") or 0, dtype=c["torch_dtype"])
    if c.get("num_local_experts"):
        kw.update(d_ff=0, moe=MoEConfig(c["num_local_experts"], c["num_experts_per_tok"],
                                        c["intermediate_size"]))
    else:
        kw.update(d_ff=c["intermediate_size"], moe=None)
    return dataclasses.replace(get_config(c["arch"]), **kw)


def train_parts(traffic: dict):
    """The program's (ParallelConfig, AttackConfig) for a traffic file."""
    from repro.configs.base import ParallelConfig
    from repro.core.attacks import AttackConfig

    atk = traffic.get("attack") or {}
    attack = AttackConfig(atk.get("name", "none"), atk.get("alpha", 0.0),
                          shift=atk.get("strength", 1.0))
    pcfg = ParallelConfig(agg_method=traffic["agg"], agg_strategy=traffic["strategy"],
                          remat=traffic["remat"], attn_chunk=traffic["attn_chunk"])
    return pcfg, attack


def enable_cache() -> None:
    """JAX's persistent compilation cache in ``.jax_cache/`` of the checkout,
    for every program; the program's runtime takes the directory from the
    environment."""
    import jax

    from repro.launch import runtime

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class _Stop(Exception):
    """Raised from the window callback to end the loop at a boundary."""


def _leaf_norms(names, xs, ys=None) -> Dict[str, float]:
    """Per leaf, the float32 norm of ``x`` (or of ``x - y``)."""
    import jax
    import jax.numpy as jnp

    def norms(xs, ys):
        ys = ys or [0.0] * len(xs)
        return [jnp.sqrt(jnp.sum((x.astype(jnp.float32) - jnp.asarray(y, jnp.float32)) ** 2))
                for x, y in zip(xs, ys)]

    return dict(zip(names, map(float, jax.jit(norms)(xs, ys))))


def read_state(state) -> Dict[str, Dict]:
    """Per leaf, the norm of the first moment, and the first moments and
    the parameters themselves, copied to the host.  Nothing is left on the
    device, so the run's memory peak stays the program's."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(state["params"])[0]
    names = [jax.tree_util.keystr(k, simple=True, separator=".").replace(
        PROGRAM_LAYERS, REFERENCE_LAYERS) for k, _ in flat]
    moments = jax.tree.leaves(state["opt_state"]["m"])
    return {
        "moment_norms": _leaf_norms(names, moments),
        "moments": dict(zip(names, jax.device_get(moments))),
        "params": dict(zip(names, jax.device_get([x for _, x in flat]))),
    }


def change_norms(params: Dict, config: dict, seed: int, mesh) -> Dict[str, float]:
    """Per leaf, the norm of the change of ``params`` (host copies) from the
    seed's initial weights, made by the reference."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ref = reference_module(config)
    p0 = jax.jit(lambda k: ref.init_params(config, k),
                 out_shardings=NamedSharding(mesh, P()))(jax.random.PRNGKey(seed))
    p0 = dict(zip(ref.leaf_names(p0), jax.tree.leaves(p0)))
    names = list(params)
    return _leaf_norms(names, [params[n] for n in names], [p0[n] for n in names])


def _memory_peak(devices) -> int:
    """The fullest chip's peak: the allocator's buffers plus the region the
    runtime reserves for the executables' temporaries, which
    ``peak_bytes_in_use`` alone leaves out."""
    stats = [d.memory_stats() or {} for d in devices]
    return int(max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
                   for s in stats))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of ``cell`` on ``devices`` (the cell's chips).  Returns the
    result object; ``checks`` holds each compared number and its limit."""
    import jax

    from repro.configs.base import TrainConfig
    from repro.data.pipeline import DataConfig
    from repro.launch import trainer
    from repro.launch.mesh import make_mesh

    from benchmarks.chip import compare, flops
    from benchmarks.chip import trace as trace_lib

    tr, c = cell.traffic, cell.config
    cfg = program_config(c)
    m, ds = tr["workers"], tr["device_steps"]
    mesh = make_mesh(tr["mesh"], ("data", "model"))
    pcfg, attack = train_parts(tr)
    tcfg = TrainConfig(optimizer=tr["optimizer"], lr=tr["lr"], steps=ds * MAX_WINDOWS,
                       seed=seed, device_steps=ds)
    dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=tr["seq_len"],
                      global_batch=tr["batch_per_worker"] * m, num_workers=m, seed=seed)
    tokens_per_window = ds * dcfg.global_batch * dcfg.seq_len
    counter = CompileCounter()
    clock: Dict = {"failed": 0}
    probe: Dict = {}
    trace_dir = tempfile.mkdtemp(prefix="chip-bench-trace-") if trace else None
    limit_s = min(seconds, TRACE_SECONDS) if trace else seconds

    def on_window(w, met):
        now = time.perf_counter()
        if w == 0:
            clock["first"] = {"loss": met["loss"], "grad_norm": met["grad_norm"]}
            if trace:
                # host events come from the runtime's own spans (dispatch,
                # transfers); a Python tracer would slow the host it watches
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                clock["span"] = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
                clock["span"].__enter__()
            clock["t0"] = time.perf_counter()
            clock["compiles0"] = counter.n
            return
        if not (math.isfinite(met["loss"]) and math.isfinite(met["grad_norm"])):
            clock["failed"] += met["micro_steps"]
        if now - clock["t0"] >= limit_s and (not trace or w >= 2):
            clock.update(t1=now, windows=w, compiles=counter.n - clock["compiles0"])
            if trace:
                clock["span"].__exit__(None, None, None)
                jax.profiler.stop_trace()
            raise _Stop

    real_window_step = trainer.make_window_step

    def observed_window_step(*args, **kwargs):
        window = real_window_step(*args, **kwargs)

        def call(state, batches):
            out = window(state, batches)
            if not probe:
                t = time.perf_counter()
                probe.update(read_state(out))
                clock["probe_s"] = time.perf_counter() - t
            return out

        return call

    trainer.make_window_step = observed_window_step
    try:
        trainer.train_loop(cfg, pcfg, tcfg, mesh, dcfg=dcfg, attack=attack,
                           on_window=on_window)
    except _Stop:
        pass
    finally:
        trainer.make_window_step = real_window_step
    gc.collect()
    window_s = clock["t1"] - clock["t0"]
    setup_s = clock["t0"] - T_START - clock["probe_s"]
    steps = clock["windows"] * ds
    print(f"timed {clock['windows']} windows ({steps} steps) in {window_s:.4f} s; "
          f"set-up {setup_s:.4f} s (the state's copy, {clock['probe_s']:.4f} s, left "
          f"out); compilations inside the timed windows: {clock['compiles']}",
          file=sys.stderr, flush=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}

    t_ref = time.perf_counter()
    probe["change_norms"] = change_norms(probe.pop("params"), c, seed, mesh)
    ref = reference_module(c).run(c, tr, seed, steps=ds, devices=devices,
                            against=probe.pop("moments"))
    numbers = compare.gaps({**clock["first"], **probe}, ref, ref["moment_error"])
    correct = compare.verdict(numbers, cell.limits)
    for what in ("moment_norms", "change_norms"):
        print(f"{what} (program / reference): " + ", ".join(
            f"{k} {probe[what][k]:.6g}/{ref[what][k]:.6g}" for k in sorted(ref[what])),
            file=sys.stderr, flush=True)
    print(f"reference: {time.perf_counter() - t_ref:.3f} s; program loss "
          f"{clock['first']['loss']!r} |g| {clock['first']['grad_norm']!r}; reference "
          f"loss {ref['loss']!r} |g| {ref['grad_norm']!r}", file=sys.stderr, flush=True)

    if trace:
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
        recorded = trace_lib.load(path)
        print(f"trace: {os.path.getsize(path)} bytes, devices "
              f"{ {k: len(v) for k, v in recorded.devices.items()} } ops, "
              f"{len(recorded.host)} host events", file=sys.stderr, flush=True)
        reduced = trace_lib.reduce(recorded, devices=[f"/device:TPU:{d.id}" for d in devices])
        shutil.rmtree(trace_dir, ignore_errors=True)
        from benchmarks.chip import peaks

        ctx = types.SimpleNamespace(
            reduced=reduced, steps=steps, tokens=clock["windows"] * tokens_per_window,
            chips=len(devices), peak=peaks.lookup(dev.device_kind),
            flops_per_token=flops.model_flops_per_token(c, tr["seq_len"]))
        metrics = {}
        for mdef in cell.per_layer:
            value = _reader(mdef["name"])(ctx)
            if value is not None:
                metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        breakdown = {"device_ops": [list(x) for x in trace_lib.top_ops(reduced)],
                     "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    else:
        values = {"tokens_per_s": clock["windows"] * tokens_per_window / window_s,
                  "setup_s": setup_s}
        metrics = {mdef["name"]: {"value": values[mdef["name"]], "unit": mdef["unit"]}
                   for mdef in cell.end_to_end}
        breakdown = None
    result = {"correct": correct, "attempted": steps, "failed": clock["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                        for k in compare.NUMBERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, StopIteration, ValueError) as e:
        print(f"run.py: cannot load workload {args.workload!r}: {e!r}", file=sys.stderr)
        return 2
    # the TPU runtime's logs go under this run's TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    try:
        import jax

        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: cannot import the program: {e}", file=sys.stderr)
        return 2
    enable_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    for k, v in result["checks"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
