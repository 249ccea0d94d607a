"""Mixture-of-Experts FFN: dropless top-k routing on grouped matrix products.

- routing (f32): top-k of the router logits per token; the gates are the
  softmax over the k chosen logits (the same as the softmax over all
  experts renormalised over the k);
- dispatch: the (token, slot) pairs sorted by expert; each expert's
  count of rows (the group sizes) and the token rows gathered into that
  order;
- experts: grouped products over the sorted rows, gate and up, SiLU of
  the gate times the up, then down;
- combine: the rows put back in (token, slot) order and summed over the
  slots, each times its gate.

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

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import grouped_matmul as grouped
from repro.launch import spans


@jax.custom_vjp
def _permute(x: jax.Array, order: jax.Array, inverse: jax.Array) -> jax.Array:
    """``x[order]`` for a permutation ``order`` of the rows (``inverse`` its
    inverse): the backward pass is the gather ``g[inverse]``, not a
    scatter."""
    return x[order]


def _permute_fwd(x, order, inverse):
    return x[order], (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


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
        logits = jnp.dot(xf.astype(jnp.float32), w_router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)  # (T, E)
        top_logits, top_idx = jax.lax.top_k(logits, top_k)  # (T, K)
        gates = jax.nn.softmax(top_logits, axis=-1)
        experts = top_idx.reshape(t * top_k)
        order = jnp.argsort(experts, stable=True)  # (token, slot) pairs by expert
        inverse = jnp.argsort(order)
        sizes = jnp.sum(jax.nn.one_hot(experts, e, dtype=jnp.int32), axis=0)
        rows = jnp.broadcast_to(xf[:, None], (t, top_k, d)).reshape(t * top_k, d)
        xs = _permute(rows, order, inverse)  # (T·K, D), grouped by expert
    with jax.named_scope(spans.MOE_EXPERTS):
        h = jax.nn.silu(product(xs, w_gate, sizes)) * product(xs, w_up, sizes)
        ys = product(h.astype(x.dtype), w_down, sizes)  # (T·K, D)
    with jax.named_scope(spans.MOE_COMBINE):
        slots = _permute(ys, inverse, order).reshape(t, top_k, d)
        y = jnp.einsum("tkd,tk->td", slots.astype(jnp.float32), gates)
        y = y.astype(x.dtype).reshape(b, s, d)

    # Switch term: E/K · Σ_e (share of routed rows_e · mean router prob_e)
    frac = sizes.astype(jnp.float32) / t
    mean_p = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = e * jnp.sum(frac * mean_p) / top_k
    load = jnp.max(sizes).astype(jnp.float32) * e / (t * top_k)
    return y, aux, load
