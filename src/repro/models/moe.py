"""Mixture-of-Experts FFN (top-k router, capacity-based einsum dispatch).

Capacity-based dispatch (Switch/flaxformer style): each expert processes at
most ``C ≈ capacity_factor · k · S / E`` tokens per sequence, so expert
FLOPs scale with *active* parameters (the MoE roofline's MODEL_FLOPS term),
not with E. Dispatch/combine are dense one-hot einsums — the TPU-friendly
formulation with no dynamic gather/scatter; the expert axis shards over the
model mesh axis (expert parallelism) and GSPMD inserts the all-to-alls.
Overflowed tokens are dropped from the FFN (identity residual), standard
for capacity routing. A Switch-style load-balance auxiliary loss is
returned alongside.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.launch import spans


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def moe_ffn(
    x: jax.Array,  # (B, S, D)
    w_router: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    top_k: int,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    e = w_router.shape[1]
    cap = min(s, max(4, _round_up(int(capacity_factor * top_k * s / e), 4)))

    with jax.named_scope(spans.MOE_DISPATCH):
        logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)  # (B, S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_idx = jax.lax.top_k(probs, top_k)  # (B, S, K)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

        dispatch = jnp.zeros((b, s, e, cap), jnp.float32)
        combine = jnp.zeros((b, s, e, cap), jnp.float32)
        counts = jnp.zeros((b, e), jnp.float32)  # tokens already assigned per expert
        for slot in range(top_k):
            oh = jax.nn.one_hot(top_idx[..., slot], e, dtype=jnp.float32)  # (B, S, E)
            pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]  # (B, S, E)
            keep = oh * (pos < cap)
            pos_at = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)  # (B, S)
            pos_oh = jax.nn.one_hot(pos_at, cap, dtype=jnp.float32)  # (B, S, C)
            sel = keep[..., None] * pos_oh[..., None, :]  # (B, S, E, C)
            dispatch = dispatch + sel
            combine = combine + top_p[..., slot, None, None] * sel
            counts = counts + jnp.sum(keep, axis=1)

        xe = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)  # (E, B, C, D)
    with jax.named_scope(spans.MOE_EXPERTS):
        h = jnp.einsum("ebcd,edf->ebcf", xe, w_gate)
        u = jnp.einsum("ebcd,edf->ebcf", xe, w_up)
        h = jax.nn.silu(h) * u
        ye = jnp.einsum("ebcf,efd->ebcd", h, w_down)  # (E, B, C, D)
    with jax.named_scope(spans.MOE_COMBINE):
        y = jnp.einsum("bsec,ebcd->bsd", combine, ye.astype(jnp.float32)).astype(x.dtype)

    # Switch aux loss: E/K · Σ_e (routed fraction_e · mean router prob_e)
    frac = jnp.mean(jnp.sum(dispatch, axis=-1), axis=(0, 1))
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac * mean_p) / top_k
    return y, aux
