"""High-throughput robust training loop: the device-steps window harness.

The step-by-step driver (``launch.train`` → ``steps.make_train_step``)
pays one host round-trip per optimizer step: dispatch, a donated-buffer
swap, and — the moment anything reads a metric — a device sync.  At
real model sizes the robust aggregation is a small slice of the step,
but the host loop caps throughput long before the collectives do.

This module keeps the entire hot path on-device (the olmax donated
while-loop idiom, SNIPPETS.md):

- ONE jitted **window step** per ``device_steps`` optimizer steps: a
  donated ``state`` carry ``{params, opt_state, step, key, metrics}``
  scanned over a ``(device_steps, ...)``-stacked batch block with
  ``jax.lax.scan`` — zero host syncs inside the window;
- the scanned micro-step body is ``steps.make_step_body`` — the SAME
  validated body ``make_train_step`` wraps, so robust aggregation
  (gather / bucketed / chunked / psum) and the engine attacks run
  in-step, per micro-step, with the attack key folded from
  ``state["key"]`` and the traced step index (randomized attacks draw
  fresh noise every micro-step, exactly like the step-by-step path);
- metrics are **running sums** accumulated in the carry
  (``loss_sum`` / ``grad_norm_sum`` / ``micro_steps``); the host reads
  them only at window boundaries and differences consecutive windows —
  the donation/scan/metrics contract in DESIGN.md §Training harness.

``device_steps=1`` is bit-for-bit identical to a hand-rolled python
loop over ``make_train_step`` (pinned by tests/test_trainer.py): the
scan body is traced once, so chunking the same step sequence into
windows of any size replays the identical HLO per step.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig, TrainConfig
from repro.core.attacks import AttackConfig
from repro.data.pipeline import DataConfig, make_lm_batch
from repro.launch import mesh as mesh_lib
from repro.launch import steps
from repro.models import transformer as T
from repro.optim.optimizers import Optimizer, get_optimizer
from repro.rounds import compression as comp_lib


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def init_state(cfg: ModelConfig, mesh, opt: Optimizer, seed: int = 0,
               pcfg: Optional[ParallelConfig] = None) -> Dict[str, Any]:
    """Fresh training state: replicated (or fsdp-sharded) params +
    optimizer state, step counter 0, the attack-key base, zeroed metric
    sums.  ``seed`` seeds both the param init and the attack-key base
    (seed 0 reproduces ``make_train_step``'s fixed ``PRNGKey(0)``)."""
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    state = {
        "params": params,
        "opt_state": opt.init(params),
        # per-worker compression residual ((m, D) zeros for error-feedback
        # schemes, () otherwise) — rides the donated carry like opt_state
        "comp": (steps.init_comp_state(cfg, pcfg, mesh)
                 if pcfg is not None else ()),
        "step": jnp.int32(0),
        "key": jax.random.PRNGKey(seed),
        "metrics": zero_metrics(),
    }
    return jax.device_put(state, state_shardings(cfg, mesh, opt, pcfg))


def state_shardings(cfg: ModelConfig, mesh, opt: Optimizer,
                    pcfg: Optional[ParallelConfig] = None):
    """The sharding of every window-state leaf: where :func:`init_state`
    places the state and what the window step returns, so the second
    window's inputs match the first's and reuse its executable."""
    return jax.tree.map(lambda s: s.sharding,
                        abstract_state(cfg, mesh, opt, pcfg))


def zero_metrics() -> Dict[str, jax.Array]:
    return {"loss_sum": jnp.float32(0.0),
            "grad_norm_sum": jnp.float32(0.0),
            "micro_steps": jnp.int32(0)}


def window_metrics(before: Dict[str, float], state: Dict[str, Any]) -> Dict[str, float]:
    """Difference the carry's running metric sums against a snapshot taken
    at the previous window boundary → this window's mean loss/grad-norm.
    The ONLY host→device syncs of the loop happen here."""
    after = {k: float(state["metrics"][k]) for k in state["metrics"]}
    n = after["micro_steps"] - before["micro_steps"]
    return {
        "loss": (after["loss_sum"] - before["loss_sum"]) / max(n, 1),
        "grad_norm": (after["grad_norm_sum"] - before["grad_norm_sum"]) / max(n, 1),
        "micro_steps": n,
        "_snapshot": after,
    }


# ---------------------------------------------------------------------------
# the window step (tentpole)
# ---------------------------------------------------------------------------


def _window_batch_spec(batch_spec):
    """Per-leaf spec for the (device_steps, ...)-stacked batch block:
    leading scan dim unsharded, the rest as the per-step spec."""
    return jax.tree.map(lambda s: P(None, *tuple(s)), batch_spec,
                        is_leaf=lambda x: isinstance(x, P))


def make_window_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    mesh,
    opt: Optimizer,
    attack: Optional[AttackConfig] = None,
    device_steps: int = 1,
):
    """Build the jitted donated window step:

    ``window(state, batches) -> state`` where ``batches`` leaves are
    ``(device_steps, ...)`` stacks and ``state`` is donated (argnum 0) —
    params/optimizer buffers are updated in place, the host keeps only
    the returned handle.  Inside: ``lax.scan`` over the micro-step body
    from :func:`steps.make_step_body`; one robust aggregation per
    micro-step and NO host transfer inside the window (both
    HLO-asserted by tests/test_trainer.py).
    """
    if device_steps < 1:
        raise ValueError(f"device_steps must be >= 1, got {device_steps}")
    sb = steps.make_step_body(cfg, pcfg, mesh, opt, attack)

    def window(state, batches):
        atk_base = state["key"]

        def micro(carry, batch):
            params, opt_state, comp, step, met = carry
            if sb.comp_body is not None:
                # error-feedback compression: the residual rides the
                # window carry exactly like the optimizer state
                params, opt_state, comp, m = sb.comp_body(
                    params, opt_state, comp, batch, step, atk_base)
            else:
                params, opt_state, m = sb.body(
                    params, opt_state, batch, step, atk_base)
            met = {
                "loss_sum": met["loss_sum"] + m["loss"].astype(jnp.float32),
                "grad_norm_sum": met["grad_norm_sum"]
                                 + m["grad_norm"].astype(jnp.float32),
                "micro_steps": met["micro_steps"] + jnp.int32(1),
            }
            return (params, opt_state, comp, step + jnp.int32(1), met), None

        (p, o, comp, step, met), _ = jax.lax.scan(
            micro,
            (state["params"], state["opt_state"], state["comp"],
             state["step"], state["metrics"]),
            batches, length=device_steps)
        return {"params": p, "opt_state": o, "comp": comp, "step": step,
                "key": atk_base, "metrics": met}

    sspec = {"params": sb.pspec, "opt_state": sb.ospec,
             "comp": sb.comp_spec, "step": P(),
             "key": P(), "metrics": P()}
    wbspec = _window_batch_spec(sb.batch_spec)
    smapped = jax.shard_map(window, mesh=mesh, in_specs=(sspec, wbspec),
                            out_specs=sspec, axis_names=frozenset(sb.waxes),
                            check_vma=False)
    return jax.jit(smapped, donate_argnums=(0,),
                   out_shardings=state_shardings(cfg, mesh, opt, pcfg))


# ---------------------------------------------------------------------------
# host-side batch staging
# ---------------------------------------------------------------------------


def stack_window_batches(
    dcfg: DataConfig,
    start_step: int,
    device_steps: int,
    mesh,
    attack: Optional[AttackConfig] = None,
    cfg: Optional[ModelConfig] = None,
) -> Dict[str, jax.Array]:
    """Host-build the ``(device_steps, B, S)`` batch block for the window
    starting at ``start_step`` and shard it P(None, workers) onto the
    mesh.  Per-micro-step batches are byte-identical to what the
    step-by-step driver feeds ``make_train_step`` at the same step index
    (per-worker provenance + data corruption included) — the
    equivalence pins depend on this."""
    waxes = mesh_lib.worker_axes(mesh)
    entry = waxes if len(waxes) > 1 else waxes[0]
    per_step = []
    for i in range(device_steps):
        b = make_lm_batch(dcfg, start_step + i, attack)
        if cfg is not None and cfg.frontend != "none":
            b["frontend"] = jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(dcfg.seed), start_step + i),
                (dcfg.global_batch, cfg.n_frontend_tokens, cfg.d_model),
            ).astype(jnp.dtype(cfg.dtype))
        per_step.append(b)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_step)
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(None, entry))), stacked)


# ---------------------------------------------------------------------------
# abstract inputs (dry-run lowering without allocation)
# ---------------------------------------------------------------------------


def abstract_state(cfg: ModelConfig, mesh, opt: Optimizer,
                   pcfg: Optional[ParallelConfig] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-in for the window-step state (dry-run)."""
    fsdp = pcfg is not None and pcfg.param_mode == "fsdp"
    if fsdp:
        aparams = steps.abstract_params_fsdp(cfg, mesh)
        aopt = steps.abstract_opt_state_fsdp(opt, cfg, mesh)
    else:
        aparams = steps.abstract_params(cfg, mesh)
        aopt = steps.abstract_opt_state(opt, cfg, mesh)
    rep = NamedSharding(mesh, P())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    acomp = ()
    if pcfg is not None and comp_lib.get_compression(
            pcfg.compression).error_feedback:
        waxes = mesh_lib.worker_axes(mesh)
        entry = waxes if len(waxes) > 1 else waxes[0]
        acomp = jax.ShapeDtypeStruct(
            (mesh_lib.num_workers(mesh), steps.comp_state_size(cfg)),
            jnp.float32, sharding=NamedSharding(mesh, P(entry)))
    return {
        "params": aparams,
        "opt_state": aopt,
        "comp": acomp,
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        "key": jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
        "metrics": {
            "loss_sum": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            "grad_norm_sum": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            "micro_steps": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        },
    }


def abstract_window_batches(cfg: ModelConfig, shape: ShapeConfig, mesh,
                            device_steps: int) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-in for the stacked window batch block."""
    if shape.kind != "train":
        raise ValueError(f"trainer windows need a train shape, got {shape.kind!r}")
    per = steps.input_specs(cfg, shape, mesh)
    waxes = mesh_lib.worker_axes(mesh)
    entry = waxes if len(waxes) > 1 else waxes[0]
    sh = NamedSharding(mesh, P(None, entry))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((device_steps,) + l.shape, l.dtype,
                                       sharding=sh), per)


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainResult:
    state: Dict[str, Any]
    history: List[Dict[str, float]]  # one entry per logged window
    steps: int
    device_steps: int
    compile_s: float
    train_s: float  # wall time of the post-compile windows
    steps_per_s: float
    tokens_per_s: float
    # per-steady-window wall times (first/compile window excluded).  The
    # MIN is the noise-robust step-time estimator on shared hosts —
    # scheduler interference only ever ADDS time — and is what the
    # throughput benchmark's overhead gate uses.
    window_times_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def min_step_time_s(self) -> float:
        if not self.window_times_s:
            return 0.0
        return min(self.window_times_s) / self.device_steps


def train_loop(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    tcfg: TrainConfig,
    mesh,
    dcfg: Optional[DataConfig] = None,
    attack: Optional[AttackConfig] = None,
    log_every: int = 1,  # in windows
    on_window: Optional[Callable[[int, Dict[str, float]], None]] = None,
    ckpt_every: int = 0,  # in WINDOWS (snapshots land on window boundaries)
    ckpt_dir: Optional[str] = None,
    resume=False,
) -> TrainResult:
    """Run ``tcfg.steps`` optimizer steps in windows of
    ``tcfg.device_steps``: build a batch block on the host, hand it to
    the donated window step, read metric deltas at the boundary.  The
    first window's wall time is reported separately as ``compile_s`` so
    ``steps_per_s``/``tokens_per_s`` measure the steady state.

    ``ckpt_every``/``ckpt_dir`` write a rounds.engine snapshot of the
    full window state (params, optimizer state, compression residual,
    step, attack-key base, running metric sums) every ``ckpt_every``
    windows; ``resume=True`` (or a step index) restores it and continues
    bit-for-bit — batch blocks are stateless functions of the step
    index, so the resumed window sequence replays the identical HLO on
    the identical state.
    """
    from repro.rounds import engine as round_engine

    ds = tcfg.device_steps
    if tcfg.steps % ds != 0:
        raise ValueError(
            f"steps ({tcfg.steps}) must be a multiple of device_steps ({ds})")
    m = mesh_lib.num_workers(mesh)
    if dcfg is None:
        dcfg = DataConfig(kind="lm", vocab=cfg.vocab, seq_len=1024,
                          global_batch=4 * m, num_workers=m, seed=tcfg.seed)
    opt = get_optimizer(tcfg.optimizer, tcfg.lr, tcfg.weight_decay, tcfg.momentum)
    window = make_window_step(cfg, pcfg, mesh, opt, attack, device_steps=ds)
    state = init_state(cfg, mesh, opt, seed=tcfg.seed, pcfg=pcfg)

    history: List[Dict[str, float]] = []
    start_w = 0
    if resume is not False and resume is not None:
        if ckpt_dir is None:
            raise ValueError("resume=True needs ckpt_dir")
        rnd = None if resume is True else int(resume)
        if rnd is not None or round_engine.latest_round(ckpt_dir) is not None:
            snap, host = round_engine.load_snapshot(
                ckpt_dir, dict(state, round=jnp.int32(0)), rnd)
            snap.pop("round")
            # restored leaves go back to the template's mesh shardings
            # (the donated window step was compiled against them)
            state = jax.tree.map(lambda t, v: jax.device_put(v, t.sharding),
                                 state, snap)
            history = list(host.get("history", []))
            start_w = int(state["step"]) // ds
    snapshot = {k: float(v) for k, v in state["metrics"].items()}
    n_windows = tcfg.steps // ds
    compile_s = train_s = 0.0
    window_times: List[float] = []
    t_train = time.perf_counter()
    for w in range(start_w, n_windows):
        batches = stack_window_batches(dcfg, w * ds, ds, mesh, attack, cfg)
        t0 = time.perf_counter()
        state = window(state, batches)
        if w == start_w:
            jax.block_until_ready(state["params"])
            compile_s = time.perf_counter() - t0
        else:
            # per-window wall time (syncs at the boundary — the window
            # interior stays sync-free; this is the timing read, not an
            # extra one: block + metric read share the same barrier)
            jax.block_until_ready(state["params"])
            window_times.append(time.perf_counter() - t0)
        if w % log_every == 0 or w == n_windows - 1:
            met = window_metrics(snapshot, state)  # syncs (boundary only)
            snapshot = met.pop("_snapshot")
            met["step"] = (w + 1) * ds
            history.append(met)
            if on_window is not None:
                on_window(w, met)
        if ckpt_every and ckpt_dir and (w + 1) % ckpt_every == 0:
            round_engine.save_snapshot(
                ckpt_dir, dict(state, round=state["step"]),
                host={"history": history})
        if w == start_w:
            # restart the clock after the compile+first-execute window
            t_train = time.perf_counter()
    jax.block_until_ready(state["params"])
    train_s = time.perf_counter() - t_train if n_windows - start_w > 1 else 0.0
    steady_steps = max((n_windows - start_w) * ds - ds, 0)
    steps_per_s = steady_steps / train_s if train_s > 0 else 0.0
    tokens = dcfg.global_batch * dcfg.seq_len
    return TrainResult(
        state=state, history=history, steps=tcfg.steps, device_steps=ds,
        compile_s=compile_s, train_s=train_s, steps_per_s=steps_per_s,
        tokens_per_s=steps_per_s * tokens, window_times_s=window_times)
