"""Distributed communication rounds: shard_map one-round + strategy dispatch.

Two jobs:

- :func:`aggregate_by_strategy` — the single name→collective dispatcher
  for the core.distributed strategies (gather / bucketed / chunked /
  hierarchical).  launch/steps.py and the round programs below share it,
  so a strategy registered in rounds.comm is runnable from every
  integration point and the name sets (docs registry vs dispatch) are
  pinned equal by tests/test_rounds.py.
- :func:`one_round_distributed` — Algorithm 2 as a true distributed
  program: the local solver runs per worker INSIDE ``shard_map`` (each
  worker only ever holds its own (n, ...) shard) and the m local
  minimizers meet through the chosen collective strategy.  With
  ``strategy='chunked'`` the solutions are histogram-sketch aggregated
  via plain psums — collective bytes independent of m, the same
  streaming-histogram estimator the federated path uses — so the
  one-round algorithm scales to worker counts where gathering m rows is
  not an option.

Attack access validation happens at BUILD time (rounds.comm
.validate_attack_strategy): an omniscient attack on the stats-only
chunked strategy raises before any tracing, mirroring launch/steps.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import distributed
from repro.launch import spans
from repro.rounds import comm
from repro.rounds import compression as comp_lib
from repro.rounds.one_round import OneRoundConfig


def aggregate_by_strategy(
    g,
    axis_names: Sequence[str],
    strategy: str,
    method: str = "median",
    beta: float = 0.1,
    attack=None,
    agg_dtype=None,
    attack_key=None,
    nbins: int = 256,
    compression: str = "none",
    comp_key=None,
):
    """Robustly aggregate a pytree over the worker axes by strategy name.

    Must run inside a ``shard_map`` body whose manual axes include
    ``axis_names``.  ``strategy`` is any rounds.comm registry name except
    ``rs`` (which returns scattered shards and is consumed by the fsdp
    custom_vjp path, not by round programs); ``hierarchical`` needs
    exactly two worker axes (outer=DCN, inner=ICI).

    ``compression`` runs each worker's LOCAL contribution through the
    named rounds.compression codec before any collective: what the
    strategies gather — and what the in-strategy attacks observe and
    replace — are the decoded transmitted values.  Randomized codecs
    fold this worker's linear axis index into ``comp_key``.  Error-
    feedback schemes are rejected here (this dispatcher is stateless);
    the stateful integrations thread the residual themselves.
    """
    axis_names = tuple(axis_names)
    if compression != "none":
        comp_lib.validate_compression_context(
            compression, stateful=False,
            where="the stateless aggregate_by_strategy dispatch")
        cspec = comp_lib.get_compression(compression)
        base = comp_key if comp_key is not None else jax.random.PRNGKey(13)
        key = None
        if cspec.randomized:  # per-worker stochastic draws
            key = jax.random.fold_in(base, distributed.worker_index(axis_names))
        elif cspec.shared_key:  # one public per-round map for ALL workers
            key = base
        with jax.named_scope(spans.CODEC):
            g, _ = comp_lib.compress_tree(compression, g, key=key)
    if strategy == "gather":
        return distributed.robust_gather_agg(
            g, axis_names, method, beta, attack, agg_dtype, attack_key=attack_key)
    if strategy == "bucketed":
        return distributed.robust_bucketed_agg(
            g, axis_names, method, beta, attack, agg_dtype, attack_key=attack_key)
    if strategy == "chunked":
        return distributed.robust_chunked_agg(
            g, axis_names, method, beta, attack, agg_dtype, nbins=nbins,
            attack_key=attack_key)
    if strategy == "psum":
        return distributed.robust_psum_agg(
            g, axis_names, method, beta, attack, agg_dtype,
            attack_key=attack_key)
    if strategy == "hierarchical":
        if len(axis_names) != 2:
            raise ValueError(
                f"hierarchical strategy needs two worker axes (outer, inner), "
                f"got {axis_names}")
        return distributed.robust_hierarchical_agg(
            g, axis_names[1], axis_names[0], method, beta, attack,
            attack_key=attack_key)
    raise ValueError(
        f"unknown agg strategy {strategy!r}; round-level strategies: "
        "gather|bucketed|chunked|psum|hierarchical")


def scan_local_sgd(value_and_grad_fn, w, tau: int, eta):
    """τ local SGD steps from ``w`` on fixed local data: returns
    ``(delta, loss0)`` where ``delta = Σₖ gₖ`` is the accumulated local
    gradient (the transmitted round payload) and ``loss0`` the loss at
    the round's shared iterate.

    The ONE implementation of the scan-and-accumulate round body shared
    by the distributed integrations (launch/steps train step and
    :func:`make_local_update_round`), so the accumulation semantics the
    DESIGN.md τ-interpolation claims rest on live in a single place.
    ``value_and_grad_fn(p) -> (loss, grad)`` closes over the local batch;
    ``loss`` may be a pytree (a loss with statistics), of which the
    round's first step is returned.
    """

    def local_step(carry, _):
        p, acc = carry
        l, g = value_and_grad_fn(p)
        return (jax.tree.map(lambda a, b: a - eta * b, p, g),
                jax.tree.map(jnp.add, acc, g)), l

    zeros = jax.tree.map(jnp.zeros_like, w)
    (_, delta), losses = jax.lax.scan(local_step, (w, zeros), None, length=tau)
    return delta, jax.tree.map(lambda l: l[0], losses)


def make_local_update_round(
    loss_fn,
    cfg,  # rounds.local_update.LocalUpdateConfig
    mesh,
    strategy: str = "gather",
    attack=None,
    axis_names: Sequence[str] = ("data",),
    agg_dtype=None,
    compression: str = "none",
):
    """Build the jitted distributed local-update round step.

    Returns ``round_step(w, worker_data, r) -> w_new`` running under
    ``shard_map``: each worker scans ``cfg.tau`` local GD steps on its
    own shard (NO collectives inside the scan) and the accumulated local
    gradients meet in exactly ONE robust aggregation per round — the
    structural property tests/test_rounds.py asserts by counting
    collectives in the traced jaxpr for τ=1 vs τ≫1.  ``r`` (traced) folds
    into the attack key so randomized attacks draw fresh noise per round,
    and into the compression key so stochastic codecs redraw per round.

    Build-time validation mirrors launch/steps: the attack's access
    level must be reproducible by the strategy, adaptive attacks are
    rejected (the collective strategies thread no previous-aggregate
    state — use the single-host ``local_update_gd`` for those), and so
    are error-feedback compression schemes (the public round_step
    signature carries no residual — local_update_gd threads it).
    """
    comm.validate_attack_strategy(attack, strategy)
    comp_lib.validate_compression_context(
        compression, stateful=False, where="the distributed round step")
    spec = comm.resolve_attack(attack)[0]
    if spec is not None and spec.adaptive:
        raise ValueError(
            f"attack {spec.name!r} is adaptive (reads the previous "
            "aggregate), which the distributed round step does not thread; "
            "use rounds.local_update.local_update_gd")
    axis_names = tuple(axis_names)
    entry = axis_names if len(axis_names) > 1 else axis_names[0]
    eta = cfg.step_size

    def body(w, data, r):
        batch = jax.tree.map(lambda l: l[0], data)
        delta, _ = scan_local_sgd(
            lambda p: jax.value_and_grad(loss_fn)(p, batch), w, cfg.tau, eta)
        d_agg = aggregate_by_strategy(
            delta, axis_names, strategy, cfg.method, cfg.beta, attack,
            agg_dtype, attack_key=jax.random.fold_in(jax.random.PRNGKey(0), r),
            compression=compression,
            comp_key=jax.random.fold_in(jax.random.PRNGKey(11), r))
        return jax.tree.map(lambda p, dd: p - eta * dd, w, d_agg)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(entry), P()),
                      out_specs=P(), axis_names=frozenset(axis_names),
                      check_vma=False)
    return jax.jit(f)


def one_round_distributed(
    local_solver,
    worker_data,  # pytree, leaves (m, n, ...) — sharded over the worker axes
    mesh,
    cfg: OneRoundConfig = OneRoundConfig(),
    strategy: str = "gather",
    attack=None,
    attack_key: Optional[jax.Array] = None,
    axis_names: Sequence[str] = ("data",),
    compression: str = "none",
):
    """Algorithm 2 under ``shard_map``: solve locally per worker, aggregate
    the m local minimizers with a collective strategy, return the
    replicated aggregate pytree.

    The worker axis (leaf dim 0, size m = number of mesh workers) is
    sharded over ``axis_names``; inside the body each worker sees its
    own ``(1, n, ...)`` slice, drops the unit dim, and runs
    ``local_solver`` on purely local data — the paper's one-round
    communication pattern: ZERO collectives until the single aggregation
    at the end.  ``strategy='chunked'`` keeps collective bytes
    independent of m (sketch psums); omniscient attacks are rejected for
    it at build time.
    """
    axis_names = tuple(axis_names)
    comm.validate_attack_strategy(attack, strategy)
    # error feedback is structurally meaningless with ONE round (the
    # residual would never be replayed), on top of the no-state argument
    comp_lib.validate_compression_context(
        compression, stateful=False, where="the one-round program")
    spec = comm.resolve_attack(attack)[0]
    if spec is not None and spec.adaptive:
        raise ValueError(
            f"attack {spec.name!r} is adaptive; the one-round algorithm has "
            "no previous round to read — use rounds.local_update")

    def body(data):
        batch = jax.tree.map(lambda l: l[0], data)
        w_hat = local_solver(batch)
        return aggregate_by_strategy(
            w_hat, axis_names, strategy, cfg.method, cfg.beta, attack,
            attack_key=attack_key, compression=compression,
            comp_key=jax.random.PRNGKey(11))

    entry = axis_names if len(axis_names) > 1 else axis_names[0]
    in_specs = jax.tree.map(lambda _: P(entry), worker_data)
    f = jax.shard_map(body, mesh=mesh, in_specs=(in_specs,), out_specs=P(),
                      axis_names=frozenset(axis_names), check_vma=False)
    return jax.jit(f)(worker_data)
