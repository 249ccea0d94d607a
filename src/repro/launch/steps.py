"""Step builders: the paper's robust aggregation wired into pjit/shard_map.

``make_train_step``   — Algorithm 1 at production scale. A ``jax.shard_map``
    whose manual axes are the worker axes; each worker computes
    ``jax.value_and_grad`` on its own batch shard, gradients are combined
    by the configured robust reduction (gather / bucketed / fsdp is
    handled at parameter level), and every worker applies the identical
    optimizer update.

``make_prefill_step`` / ``make_decode_step`` — serving steps, plain jit
    (no workers / no aggregation), GSPMD auto sharding with constraints.

``input_specs`` — ShapeDtypeStruct stand-ins (weak-type-correct, sharded,
    no allocation) for every model input of an (arch × shape) combo — the
    dry-run path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.core import distributed
from repro.core.attacks import AttackConfig
from repro.launch import mesh as mesh_lib
from repro.launch import spans
from repro.rounds import comm
from repro.rounds import compression as comp_lib
from repro.rounds import distributed as rounds_dist
from repro.models import transformer as T
from repro.models.sharding import ShardCtx, tree_partition_specs
from repro.optim.optimizers import Optimizer


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def _batch_entry(axes: Tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


def param_shardings(cfg: ModelConfig, mesh):
    shp = mesh_lib.mesh_shape_dict(mesh)
    specs = tree_partition_specs(T.param_shapes(cfg), "model", shp.get("model", 1))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def _struct(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def abstract_params(cfg: ModelConfig, mesh):
    shapes = T.param_shapes(cfg)
    shard = param_shardings(cfg, mesh)
    return jax.tree.map(lambda l, s: _struct(l.shape, l.dtype, s), shapes, shard)


def abstract_opt_state(opt: Optimizer, cfg: ModelConfig, mesh):
    shapes = jax.eval_shape(opt.init, T.param_shapes(cfg))
    pshard = param_shardings(cfg, mesh)

    def match(l):
        # optimizer state leaves mirror param shapes: reuse param specs by shape
        return None

    # States mirror the params tree structure under "m"/"v" (adamw) or
    # directly (momentum): map shardings through the same tree structure.
    def tree_like(states):
        if isinstance(states, dict) and set(states.keys()) == {"m", "v"}:
            return {"m": pshard, "v": pshard}
        if states == ():
            return ()
        return pshard

    shard = tree_like(shapes)
    return jax.tree.map(lambda l, s: _struct(l.shape, l.dtype, s), shapes, shard)


def _divisible_spec(mesh, shape, prefs):
    """Build a PartitionSpec assigning mesh axes to dims if divisible.

    ``prefs``: list of (dim_index, axes_tuple or axis) preferences.
    """
    shp = mesh_lib.mesh_shape_dict(mesh)
    spec = [None] * len(shape)
    used = set()
    for dim, axes in prefs:
        axes_t = axes if isinstance(axes, tuple) else (axes,)
        if any(a in used or a not in shp for a in axes_t):
            continue
        size = 1
        for a in axes_t:
            size *= shp[a]
        if shape[dim] % size == 0 and shape[dim] >= size:
            spec[dim] = axes_t if len(axes_t) > 1 else axes_t[0]
            used.update(axes_t)
    return P(*spec)


def cache_shardings(cfg: ModelConfig, mesh, cache_shapes):
    """Shard KV caches/states: batch over worker axes, heads (or head_dim /
    state heads) over the model axis when divisible."""
    waxes = mesh_lib.worker_axes(mesh)

    def visit(path, leaf):
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1]))) if path else ""
        shape = leaf.shape
        if name in ("k", "v") and len(shape) >= 4:
            # (.., B, S, KV, hd)
            b_dim = len(shape) - 4
            prefs = [(b_dim, waxes), (len(shape) - 2, "model"), (len(shape) - 1, "model")]
            return NamedSharding(mesh, _divisible_spec(mesh, shape, prefs))
        if name == "ssd" and len(shape) >= 4:
            b_dim = len(shape) - 4
            prefs = [(b_dim, waxes), (len(shape) - 3, "model")]
            return NamedSharding(mesh, _divisible_spec(mesh, shape, prefs))
        if name in ("conv", "h") and len(shape) >= 2:
            b_dim = len(shape) - (3 if name == "conv" else 2)
            prefs = [(b_dim, waxes), (len(shape) - 1, "model")]
            return NamedSharding(mesh, _divisible_spec(mesh, shape, prefs))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(visit, cache_shapes)


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins)
# ---------------------------------------------------------------------------


def long_context_cfg(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """For long_500k on full-attention archs, select the documented
    sliding-window decode variant (DESIGN.md §Input-shape handling)."""
    if shape.name == "long_500k" and cfg.long_context_window and not cfg.sliding_window:
        return dataclasses.replace(cfg, name=cfg.name + "+swa")
    return cfg


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for the step inputs of this combo."""
    waxes = mesh_lib.worker_axes(mesh)
    b_entry = _batch_entry(waxes)
    bsh = NamedSharding(mesh, P(b_entry))
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = _struct((B, S), jnp.int32, bsh)
        out["labels"] = _struct((B, S), jnp.int32, bsh)
        if cfg.frontend != "none":
            out["frontend"] = _struct((B, cfg.n_frontend_tokens, cfg.d_model), dt, bsh)
    elif shape.kind == "prefill":
        out["tokens"] = _struct((B, S), jnp.int32, bsh)
        if cfg.frontend != "none":
            out["frontend"] = _struct((B, cfg.n_frontend_tokens, cfg.d_model), dt, bsh)
    else:  # decode
        tok_sh = NamedSharding(mesh, _divisible_spec(mesh, (B, 1), [(0, waxes)]))
        out["token"] = _struct((B, 1), jnp.int32, tok_sh)
        cache_shapes = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
        out["cache"] = jax.tree.map(
            lambda l, s: _struct(l.shape, l.dtype, s),
            cache_shapes,
            cache_shardings(cfg, mesh, cache_shapes),
        )
        out["pos"] = _struct((), jnp.int32, NamedSharding(mesh, P()))
    return out


# ---------------------------------------------------------------------------
# FSDP sharding: params sharded over worker axes; the robust reduction is
# fused into the backward pass (robust reduce-scatter instead of
# psum_scatter) — see core.distributed.make_robust_param_gather_dim.
# ---------------------------------------------------------------------------


def fsdp_dims(cfg: ModelConfig, mesh):
    """Per-leaf FSDP dim: the largest dim divisible by the worker count,
    never the scan-stacking dim 0 of 'blocks'/'enc_blocks'/'cross_blocks'
    leaves and — crucially — avoiding the dim the model (TP) axis shards:
    stealing that dim would silently drop tensor parallelism for the leaf
    and multiply its compute by the TP degree (found the hard way on
    grok-1's expert FFNs; see EXPERIMENTS.md §Perf). -1 = replicated."""
    m = mesh_lib.num_workers(mesh)
    shapes = T.param_shapes(cfg)
    shp = mesh_lib.mesh_shape_dict(mesh)
    model_specs = tree_partition_specs(shapes, "model", shp.get("model", 1))
    spec_by_path = {
        p: s for p, s in jax.tree_util.tree_flatten_with_path(
            model_specs, is_leaf=lambda x: isinstance(x, P))[0]
    }

    def visit(path, leaf):
        top = str(getattr(path[0], "key", path[0])) if path else ""
        stacked = top in ("blocks", "enc_blocks", "cross_blocks")
        # locate this leaf's model-sharded dim (if any)
        spec = tuple(spec_by_path.get(path, P()))
        model_dim = next((i for i, e in enumerate(spec) if e == "model"), None)

        def ok(d, size):
            return (size % m == 0 and size >= m
                    and not (stacked and d == 0) and d != model_dim)

        cands = [(size, d) for d, size in enumerate(leaf.shape) if ok(d, size)]
        if not cands:  # fall back: allow the model dim (model yields)
            cands = [(size, d) for d, size in enumerate(leaf.shape)
                     if size % m == 0 and size >= m and not (stacked and d == 0)]
        return max(cands)[1] if cands else -1  # -1 = replicated

    return jax.tree_util.tree_map_with_path(visit, shapes)


def fsdp_param_shardings(cfg: ModelConfig, mesh):
    """NamedShardings combining worker-axes FSDP dim + model-axis TP dim."""
    shp = mesh_lib.mesh_shape_dict(mesh)
    waxes = mesh_lib.worker_axes(mesh)
    dims = fsdp_dims(cfg, mesh)
    base = tree_partition_specs(T.param_shapes(cfg), "model", shp.get("model", 1))
    shapes = T.param_shapes(cfg)

    def combine(dim, spec, leaf):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        if dim >= 0:
            entries[dim] = _batch_entry(waxes)  # model axis yields to FSDP here
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(combine, dims, base, shapes), dims


def fsdp_manual_specs(cfg: ModelConfig, mesh):
    """shard_map in_specs (worker axes only) for FSDP params."""
    waxes = mesh_lib.worker_axes(mesh)
    dims = fsdp_dims(cfg, mesh)
    shapes = T.param_shapes(cfg)

    def spec(dim, leaf):
        entries = [None] * len(leaf.shape)
        if dim >= 0:
            entries[dim] = _batch_entry(waxes)
        return P(*entries)

    return jax.tree.map(spec, dims, shapes)


def abstract_params_fsdp(cfg: ModelConfig, mesh):
    shapes = T.param_shapes(cfg)
    shard, _ = fsdp_param_shardings(cfg, mesh)
    return jax.tree.map(lambda l, s: _struct(l.shape, l.dtype, s), shapes, shard)


def abstract_opt_state_fsdp(opt: Optimizer, cfg: ModelConfig, mesh):
    shapes = jax.eval_shape(opt.init, T.param_shapes(cfg))
    pshard, _ = fsdp_param_shardings(cfg, mesh)
    if isinstance(shapes, dict) and set(shapes.keys()) == {"m", "v"}:
        shard = {"m": pshard, "v": pshard}
    elif shapes == ():
        return ()
    else:
        shard = pshard
    return jax.tree.map(lambda l, s: _struct(l.shape, l.dtype, s), shapes, shard)


def _make_providers(cfg: ModelConfig, mesh, pcfg: ParallelConfig,
                    attack: Optional[AttackConfig]):
    """(top_transform, block_provider): robust-gather custom_vjps per leaf."""
    waxes = mesh_lib.worker_axes(mesh)
    dims = fsdp_dims(cfg, mesh)

    def gather_fn(dim):
        if dim < 0:
            return lambda w: w
        return distributed.make_robust_param_gather_dim(
            waxes, dim, pcfg.agg_method, pcfg.agg_beta, attack)

    # block leaves: dims are relative to the stacked (n_super, ...) leaf;
    # inside the scan body the leading dim is sliced away -> dim - 1
    block_dims = jax.tree.map(lambda d: d if d < 0 else d - 1, dims["blocks"])

    def block_provider(block_p):
        return jax.tree.map(lambda d, w: gather_fn(d)(w), block_dims, block_p)

    def top_transform(params):
        out = {}
        for k, v in params.items():
            if k == "blocks":
                out[k] = v  # gathered per-layer inside the scan
            else:
                out[k] = jax.tree.map(lambda d, w: gather_fn(d)(w), dims[k], v)
        return out

    return top_transform, block_provider


# ---------------------------------------------------------------------------
# train step (Algorithm 1, production form)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepBody:
    """The sharded train-step body + the specs needed to shard_map it.

    ``body(params, opt_state, batch, step, atk_base) -> (params,
    opt_state, metrics)`` must run INSIDE a shard_map whose manual axes
    include ``waxes``; ``atk_base`` is the base PRNG key randomized
    attacks fold the step index into (``make_train_step`` fixes it to
    ``PRNGKey(0)``; the trainer threads it through the donated carry so
    every micro-step of a scan window draws fresh attack noise).
    ``pspec/ospec/batch_spec`` are the shard_map in_specs for params /
    optimizer state / batch.

    Error-feedback compression (ParallelConfig.compression='topk')
    additionally needs per-worker residual state, which the 5-argument
    ``body`` cannot carry: ``comp_body(params, opt_state, comp, batch,
    step, atk_base) -> (params, opt_state, comp, metrics)`` threads it
    (``comp`` = this worker's (1, D) residual shard, spec ``comp_spec``),
    and is None for every residual-free scheme — only the device-steps
    trainer (launch.trainer) uses it; ``make_train_step`` rejects
    error-feedback schemes at build time.
    """

    body: Any
    pspec: Any
    ospec: Any
    batch_spec: Any
    waxes: Tuple[str, ...]
    comp_body: Any = None  # only set for error-feedback compression
    comp_spec: Any = P()  # shard_map spec of the residual state


def make_step_body(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    mesh,
    opt: Optimizer,
    attack: Optional[AttackConfig] = None,
) -> StepBody:
    """Build (and validate) the per-step body shared by ``make_train_step``
    and the device-steps trainer (``launch.trainer``).

    All build-time validation lives here — attack access vs strategy,
    adaptive/randomized rejection, local-steps constraints — so the two
    integration points cannot drift.  When every model axis has size 1
    the ShardCtx drops them: constraints over size-1 axes are no-ops.
    """
    if attack is not None and attack.name != "none" and attack.alpha > 0:
        atk_spec, _ = attack.resolve()  # raises early on unknown names
        # registry-backed access-vs-strategy check (rounds.comm): e.g.
        # omniscient attacks need gathered rows, which the chunked/psum
        # strategy never materializes
        comm.validate_attack_strategy(attack, pcfg.agg_strategy)
        if atk_spec.adaptive:
            # the train step has no previous-aggregate state to feed the
            # payload — silently substituting zeros would measure the
            # 'zero' attack while reporting this one
            raise ValueError(
                f"attack {attack.name!r} is adaptive (reads the previous "
                "aggregate), which the distributed train step does not "
                "thread; use core.robust_gd or repro.fed for adaptive attacks")
        if atk_spec.randomized and pcfg.param_mode == "fsdp":
            raise ValueError(
                f"attack {attack.name!r} is randomized; the fsdp backward-pass "
                "attack path has no per-step key — use agg_strategy gather/"
                "bucketed/chunked with param_mode='replicated'")
    waxes = mesh_lib.worker_axes(mesh)
    shp = mesh_lib.mesh_shape_dict(mesh)
    model_axes = mesh_lib.model_axes(mesh)
    if all(shp.get(a, 1) == 1 for a in model_axes):
        model_axes = ()  # size-1 constraints are no-ops; see docstring
    ctx = ShardCtx(batch_axes=(), model_axes=model_axes, mesh_shape=shp,
                   seq_parallel=pcfg.seq_parallel)
    agg_dtype = jnp.dtype(pcfg.agg_dtype) if pcfg.agg_dtype else None
    fsdp = pcfg.param_mode == "fsdp"
    comp_spec_obj = comp_lib.get_compression(pcfg.compression)  # validates name
    ef = comp_spec_obj.error_feedback
    if pcfg.compression != "none" and fsdp:
        raise ValueError(
            "compression needs param_mode='replicated': the fsdp path fuses "
            "robust aggregation into the parameter-gather backward, so there "
            "is no transmitted gradient payload to encode")
    tau = pcfg.local_steps
    if tau < 1:
        raise ValueError(f"local_steps must be >= 1, got {tau}")
    if tau > 1 and fsdp:
        # fsdp fuses the robust reduction into every backward pass (one
        # collective per LOCAL step via the param-gather custom_vjp),
        # which defeats the whole point of local-update rounds
        raise ValueError(
            "local_steps > 1 needs param_mode='replicated': the fsdp "
            "robust reduce-scatter fires a collective per local step")

    if fsdp:
        top_transform, block_provider = _make_providers(cfg, mesh, pcfg, attack)
        dims = fsdp_dims(cfg, mesh)

        def local_loss(params, batch):
            return T.loss_fn(top_transform(params), batch, cfg, ctx,
                             remat=pcfg.remat, kv_block=pcfg.attn_chunk,
                             block_provider=block_provider)
    else:
        def local_loss(params, batch):
            return T.loss_fn(params, batch, cfg, ctx, remat=pcfg.remat,
                             kv_block=pcfg.attn_chunk)

    def _core(params, opt_state, comp, batch, step, atk_base):
        with jax.named_scope(spans.LOCAL_GRAD):
            if tau == 1:
                (loss, stats), grads = jax.value_and_grad(local_loss, has_aux=True)(
                    params, batch)
            else:
                # communication round: scan tau local SGD steps on this
                # worker's batch shard and transmit the ACCUMULATED local
                # gradient — the collective below fires once per round, not
                # per local step (HLO-asserted in tests/test_rounds.py);
                # shared scan body: rounds.distributed.scan_local_sgd
                grads, (loss, stats) = rounds_dist.scan_local_sgd(
                    lambda p: jax.value_and_grad(local_loss, has_aux=True)(p, batch),
                    params, tau, pcfg.local_lr)
        # step-folded key: randomized attacks draw fresh noise each step
        atk_key = jax.random.fold_in(atk_base, step)
        if fsdp:
            # gradients of sharded leaves arrive already robustly reduced
            # (the gathers' backward IS the robust reduce-scatter); only
            # the few replicated leaves still need cross-worker reduction.
            with jax.named_scope(spans.AGGREGATE):
                agg = jax.tree.map(
                    lambda d, g: g if d >= 0 else distributed.robust_gather_agg(
                        {"x": g}, waxes, pcfg.agg_method, pcfg.agg_beta, attack,
                        agg_dtype, attack_key=atk_key)["x"],
                    dims, grads)
        elif ef:
            # error feedback: this worker's residual shard ``comp`` is
            # (1, D); transmit decode(encode(g + e)) and carry the new
            # residual — the collective then ships already-decoded rows
            ckey = None
            if comp_spec_obj.randomized:
                ckey = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(11), step),
                    distributed.worker_index(waxes))
            with jax.named_scope(spans.CODEC):
                grads, new_res = comp_lib.compress_tree(
                    pcfg.compression, grads, key=ckey, residual=comp[0])
            comp = jnp.expand_dims(new_res, 0)
            with jax.named_scope(spans.AGGREGATE):
                agg = rounds_dist.aggregate_by_strategy(
                    grads, waxes, pcfg.agg_strategy, pcfg.agg_method, pcfg.agg_beta,
                    attack, agg_dtype, attack_key=atk_key)
        else:
            with jax.named_scope(spans.AGGREGATE):
                agg = rounds_dist.aggregate_by_strategy(
                    grads, waxes, pcfg.agg_strategy, pcfg.agg_method, pcfg.agg_beta,
                    attack, agg_dtype, attack_key=atk_key,
                    compression=pcfg.compression,
                    comp_key=jax.random.fold_in(jax.random.PRNGKey(11), step))
        if tau > 1:
            # hand the optimizer the MEAN local gradient so lr semantics
            # match tau=1 (the robust aggregate of Σ_k g_k, rescaled —
            # scaling after aggregation commutes with coordinate-wise
            # aggregators)
            agg = jax.tree.map(lambda g: g / tau, agg)
        with jax.named_scope(spans.UPDATE):
            new_params, new_opt = opt.update(agg, opt_state, params, step)
        with jax.named_scope(spans.METRICS):
            sq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(agg))
            if fsdp:
                sq = jax.lax.psum(sq, waxes)  # shards are disjoint across workers
            metrics = {
                "loss": jax.lax.pmean(loss, waxes),
                "grad_norm": jnp.sqrt(sq),
                # the model's statistics (a model with experts: its load)
                **{k: jax.lax.pmean(v, waxes) for k, v in stats.items()},
            }
        return new_params, new_opt, comp, metrics

    def body(params, opt_state, batch, step, atk_base):
        new_params, new_opt, _, metrics = _core(
            params, opt_state, None, batch, step, atk_base)
        return new_params, new_opt, metrics

    b_entry = _batch_entry(waxes)
    batch_spec = {"tokens": P(b_entry), "labels": P(b_entry)}
    if cfg.frontend != "none":
        batch_spec["frontend"] = P(b_entry)
    rep = P()
    if fsdp:
        pspec = fsdp_manual_specs(cfg, mesh)
        ostate_shapes = jax.eval_shape(opt.init, T.param_shapes(cfg))
        if isinstance(ostate_shapes, dict) and set(ostate_shapes.keys()) == {"m", "v"}:
            ospec = {"m": pspec, "v": pspec}
        elif ostate_shapes == ():
            ospec = ()
        else:
            ospec = pspec
    else:
        pspec, ospec = rep, rep
    return StepBody(body=body, pspec=pspec, ospec=ospec,
                    batch_spec=batch_spec, waxes=waxes,
                    comp_body=_core if ef else None,
                    comp_spec=P(b_entry))


def comp_state_size(cfg: ModelConfig) -> int:
    """Flat parameter count D — the residual width of one worker's
    error-feedback state (the transmitted payload is the whole gradient
    pytree raveled to one (D,) message)."""
    return sum(math.prod(l.shape) for l in jax.tree.leaves(T.param_shapes(cfg)))


def init_comp_state(cfg: ModelConfig, pcfg: ParallelConfig, mesh):
    """Initial compression state for the trainer: zeros (num_workers, D)
    f32 sharded one row per worker for error-feedback schemes, ``()``
    otherwise (so the trainer state keeps a static structure)."""
    if not comp_lib.get_compression(pcfg.compression).error_feedback:
        return ()
    m = mesh_lib.num_workers(mesh)
    sh = NamedSharding(mesh, P(_batch_entry(mesh_lib.worker_axes(mesh))))
    return jax.device_put(jnp.zeros((m, comp_state_size(cfg)), jnp.float32), sh)


def make_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    mesh,
    opt: Optimizer,
    attack: Optional[AttackConfig] = None,
):
    """Returns jit'd ``train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics)`` with robust aggregation over workers.

    ``attack`` may be any repro.attacks registry name via the
    AttackConfig shim; the attack's declared gradient-access level is
    validated against the collective strategy at build time
    (:func:`make_step_body`) rather than deep inside the traced
    collective: the chunked/psum strategy never materializes per-worker
    rows, so omniscient attacks (mimic, max_damage_tm, ...) need
    gather/bucketed.

    Error-feedback compression schemes are rejected here: this step is
    stateless, so the per-worker residual would be silently dropped —
    the device-steps trainer (launch.trainer) threads it instead.
    """
    comp_lib.validate_compression_context(
        pcfg.compression, stateful=False, where="the stateless train step")
    sb = make_step_body(cfg, pcfg, mesh, opt, attack)

    def step(params, opt_state, batch, step_idx):
        # fixed attack-key base: bit-identical to the pre-StepBody path
        return sb.body(params, opt_state, batch, step_idx, jax.random.PRNGKey(0))

    rep = P()
    smapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(sb.pspec, sb.ospec, sb.batch_spec, rep),
        out_specs=(sb.pspec, sb.ospec, rep),
        axis_names=frozenset(sb.waxes),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, mesh, kv_block: int = 1024,
                      cache_len: Optional[int] = None):
    waxes = mesh_lib.worker_axes(mesh)
    shp = mesh_lib.mesh_shape_dict(mesh)
    ctx = ShardCtx(batch_axes=waxes, model_axes=mesh_lib.model_axes(mesh), mesh_shape=shp)

    def step(params, tokens, frontend=None):
        return T.prefill(params, tokens, cfg, ctx, frontend=frontend,
                         kv_block=kv_block, cache_len=cache_len)

    return jax.jit(step)


def make_decode_step(cfg: ModelConfig, mesh):
    waxes = mesh_lib.worker_axes(mesh)
    shp = mesh_lib.mesh_shape_dict(mesh)
    ctx = ShardCtx(batch_axes=waxes, model_axes=mesh_lib.model_axes(mesh), mesh_shape=shp)

    def step(params, token, cache, pos):
        return T.decode_step(params, token, cache, pos, cfg, ctx)

    return jax.jit(step, donate_argnums=(2,))


# ---------------------------------------------------------------------------
# continuous-batching serving substrate (repro.serve)
# ---------------------------------------------------------------------------


def _serve_ctx(mesh) -> ShardCtx:
    """ShardCtx of the slot-pool serving steps: no batch axes (the pool is
    a replicated vmap over slots, not a worker-sharded batch), model axes
    only when they can actually constrain — size-1 constraints are
    no-ops; sharding still propagates from the parameter
    NamedShardings."""
    shp = mesh_lib.mesh_shape_dict(mesh)
    model_axes = mesh_lib.model_axes(mesh)
    if all(shp.get(a, 1) == 1 for a in model_axes):
        model_axes = ()
    return ShardCtx(batch_axes=(), model_axes=model_axes, mesh_shape=shp)


def make_slot_prefill_step(cfg: ModelConfig, mesh, cache_len: int):
    """Batch-1 prefill at a FIXED prompt bucket -> (last-token logits
    (1, 1, V), slot cache sized ``cache_len``).  One compilation covers
    every admit: prompts arrive bucketed to one length and the slot cache
    is the fixed prompt+generation budget."""
    ctx = _serve_ctx(mesh)

    def step(params, tokens, frontend=None):
        return T.prefill(params, tokens, cfg, ctx, frontend=frontend,
                         kv_block=0, cache_len=cache_len)

    return jax.jit(step)


def make_decode_pool_step(cfg: ModelConfig, mesh):
    """One tick of the whole decode pool: vmapped batch-1 decode over the
    slot axis with PER-SLOT positions (a flat batched decode cannot give
    slots independent ring-buffer positions — ``kpos`` is shared across
    the batch dim inside one cache).

    Returns jit'd ``tick(params, tokens (S,1,1), caches, pos (S,)) ->
    (next_tokens (S,) int32, caches)`` with the pool caches donated.
    Idle slots decode garbage against their fully-masked caches; the
    engine ignores their outputs and every admit REPLACES the slot's
    cache wholesale, so stale lanes cannot leak into live ones (pinned by
    tests/test_serve.py slot-count invariance).
    """
    ctx = _serve_ctx(mesh)

    def one(params, token, cache, pos):
        return T.decode_step(params, token, cache, pos, cfg, ctx)

    def tick(params, tokens, caches, pos):
        logits, new_caches = jax.vmap(one, in_axes=(None, 0, 0, 0))(
            params, tokens, caches, pos)
        nxt = jnp.argmax(logits[:, 0, 0, :].astype(jnp.float32), axis=-1)
        return nxt.astype(jnp.int32), new_caches

    # the pool lives replicated (the slot axis is a vmap, not a mesh
    # axis); pinning the output keeps every tick's cache key identical
    rep = NamedSharding(mesh, P())
    return jax.jit(tick, donate_argnums=(2,), out_shardings=(rep, rep))


def make_slot_admit_step(mesh=None):
    """jit'd ``admit(pool_caches, slot_cache, slot) -> pool_caches``:
    insert one freshly prefilled batch-1 cache at a TRACED slot index via
    ``dynamic_update_index_in_dim`` — one compilation serves every slot
    (the no-recompile pin), and the pool buffers are donated so slot
    reuse is an in-place write.  With ``mesh`` the output pool is pinned
    replicated so the updated pool's sharding matches the engine's
    initial pool (otherwise GSPMD's choice on TP meshes forces a one-time
    re-specialization on the second admit)."""

    def admit(pool, one, slot):
        return jax.tree.map(
            lambda p, o: jax.lax.dynamic_update_index_in_dim(p, o, slot, 0),
            pool, one)

    kwargs = {}
    if mesh is not None:
        kwargs["out_shardings"] = NamedSharding(mesh, P())
    return jax.jit(admit, donate_argnums=(0,), **kwargs)


def init_slot_pool(cfg: ModelConfig, slots: int, cache_len: int):
    """Empty pool caches: ``slots`` stacked batch-1 caches (leading slot
    axis).  Fresh slots are fully masked (``kpos`` = -1 everywhere), so
    an un-admitted lane attends to nothing."""
    one = T.init_cache(cfg, 1, cache_len)
    return jax.tree.map(lambda l: jnp.stack([l] * slots), one)
