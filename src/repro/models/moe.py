"""Mixture-of-Experts FFN: dropless top-k routing on grouped matrix products.

- routing (f32): top-k of the router logits per token; the gates are the
  softmax over the k chosen logits (the same as the softmax over all
  experts renormalised over the k);
- dispatch: the (token, slot) pairs sorted by expert (one sort; the
  inverse permutation is a scatter of distinct indices); each expert's
  count of rows (the group sizes) and the token rows gathered into that
  order straight from the (T, D) tokens;
- experts: grouped products over the sorted rows, gate and up, SiLU of
  the gate times the up, then down;
- combine: the rows put back in (token, slot) order and summed over the
  slots, each times its gate.

Every backward pass of the permutation is a gather.  The routing is
named (``ROUTING``) so that a remat'd block can keep it for the backward
pass instead of computing it again.

No token is dropped at any imbalance: an expert takes every row routed
to it.  The work is the routed rows' (T·k), not a capacity's.  The
grouped products take the Pallas megablox kernel
(``kernels/grouped_matmul.py``) on a TPU where the compiler partitions
the trace over no more than one device; elsewhere ``jax.lax.ragged_dot``
on the same buffer.  A Switch-form load-balance term is returned beside
the output, and the layer's expert load (its largest expert's rows over
the mean).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels import grouped_matmul as grouped
from repro.launch import spans


# The routing values the backward pass reuses.  A ``jax.checkpoint`` with
# ``save_only_these_names(*ROUTING)`` keeps them (2 MiB a layer at 8,192
# tokens and 32 experts), so its recompute runs neither the router
# product, the top-k, the sort nor the inverse.  The group sizes are
# counted again: saving them too raised the v5e compile's temporaries of
# the granite cell's step by 0.37 GB.
ROUTING = ("moe.logits", "moe.experts", "moe.gates", "moe.order", "moe.inverse")


def _inverse(order: jax.Array) -> jax.Array:
    """``inverse[order[i]] = i``: a scatter of distinct indices, no sort."""
    n = order.shape[0]
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(n, dtype=order.dtype), unique_indices=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(k: int, xf: jax.Array, order: jax.Array, inverse: jax.Array) -> jax.Array:
    """The (T·k, D) rows grouped by expert, gathered from the (T, D)
    tokens: row i is token ``order[i] // k``.  Backward: the cotangent
    gathered back into (token, slot) order (``inverse``) and summed over
    each token's k slots in f32, a gather feeding a reduction."""
    return xf[order // k]


def _dispatch_fwd(k, xf, order, inverse):
    return xf[order // k], inverse


def _dispatch_bwd(k, inverse, g):
    t, d = g.shape[0] // k, g.shape[1]
    dx = jnp.sum(g[inverse].reshape(t, k, d).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys: jax.Array, gates: jax.Array, order: jax.Array,
             inverse: jax.Array) -> jax.Array:
    """``y[t] = Σ_j gates[t, j] · ys[inverse[t·k + j]]``, summed in f32 and
    rounded once to ``ys``'s dtype: (T, D).  Backward, with no (T, k, D)
    buffer: the output's cotangent gathered into expert order; the rows'
    cotangent is that times the sorted gate, the gates' its product with
    each row, put back in (token, slot) order."""
    t, k = gates.shape
    slots = ys[inverse].reshape(t, k, ys.shape[1])
    return jnp.einsum("tkd,tk->td", slots.astype(jnp.float32), gates).astype(ys.dtype)


def _combine_fwd(ys, gates, order, inverse):
    return _combine(ys, gates, order, inverse), (ys, gates, order, inverse)


def _combine_bwd(res, g):
    ys, gates, order, inverse = res
    t, k = gates.shape
    # the output's cotangent gathered into expert order (g is in ys's
    # dtype), read by both products: no gather of ys back to token order
    g_rows = g[order // k].astype(jnp.float32)
    d_ys = g_rows * gates.reshape(t * k)[order][:, None]
    d_gates = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=1)[inverse].reshape(t, k)
    return d_ys.astype(ys.dtype), d_gates, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def kernel_applies(rows: int, shards: int, backend: str) -> bool:
    """Whether the grouped products take the kernel: on a TPU, at a row
    count it tiles, in a trace the compiler partitions over no more than
    one device (``shards``)."""
    return backend == "tpu" and shards <= 1 and grouped.kernel_applies(rows)


def moe_ffn(
    x: jax.Array,  # (B, S, D)
    w_router: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    top_k: int,
    shards: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (output (B, S, D), load-balance term, expert load)."""
    b, s, d = x.shape
    e = w_router.shape[1]
    t = b * s
    xf = x.reshape(t, d)
    if kernel_applies(t * top_k, shards, jax.default_backend()):
        product = grouped.gmm
    else:
        product = grouped.ragged

    with jax.named_scope(spans.MOE_DISPATCH):
        logits = checkpoint_name(
            jnp.dot(xf.astype(jnp.float32), w_router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST), "moe.logits")  # (T, E)
        top_idx = checkpoint_name(jax.lax.top_k(logits, top_k)[1], "moe.experts")  # (T, K)
        # the chosen logits gathered by the saved indices: top_k's own
        # derivative would run the top-k again in the recompute
        gates = checkpoint_name(
            jax.nn.softmax(jnp.take_along_axis(logits, top_idx, axis=1), axis=-1), "moe.gates")
        experts = top_idx.reshape(t * top_k)
        # the (token, slot) pairs by expert: the layer's one sort
        order = checkpoint_name(jnp.argsort(experts, stable=True), "moe.order")
        inverse = checkpoint_name(_inverse(order), "moe.inverse")
        sizes = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.int32), axis=0)
        xs = _dispatch(top_k, xf, order, inverse)  # (T·K, D), grouped by expert
    with jax.named_scope(spans.MOE_EXPERTS):
        h = jax.nn.silu(product(xs, w_gate, sizes)) * product(xs, w_up, sizes)
        ys = product(h.astype(x.dtype), w_down, sizes)  # (T·K, D)
    with jax.named_scope(spans.MOE_COMBINE):
        y = _combine(ys, gates, order, inverse).astype(x.dtype).reshape(b, s, d)

    # Switch term: E/K · Σ_e (share of routed rows_e · mean router prob_e)
    frac = sizes.astype(jnp.float32) / t
    mean_p = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = e * jnp.sum(frac * mean_p) / top_k
    load = jnp.max(sizes).astype(jnp.float32) * e / (t * top_k)
    return y, aux, load
