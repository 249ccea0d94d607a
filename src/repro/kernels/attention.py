"""Fused causal self-attention for the train and prefill paths.

The Pallas TPU splash-attention kernel that ships with jax
(``jax.experimental.pallas.ops.tpu.splash_attention``): a blocked
online softmax whose scores live only in VMEM tiles, never as an
(S, S) array in HBM, and which skips the key blocks above the diagonal
(and, for a sliding window, those left of it).  The forward pass keeps
the softmax statistics (max, sum, logsumexp) and the output accumulator
in f32; the backward kernels recompute each tile's probabilities from
the saved logsumexp.

``causal_attention`` takes the model's grouped layout (q (B, S, KV, G,
hd), k/v (B, S, KV, hd)), hands the kernel its (heads, S, hd) layout per
sequence (GQA: query head ``kv·G + g`` reads KV head ``kv``, as in the
grouped layout), vmaps over the batch, and returns (B, S, KV, G, hd) in
q's dtype.  ``models.attention.attention`` decides where it runs.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash,
    splash_attention_mask as splash_mask,
)

from repro.kernels import mode

LANES = 128  # the kernel tiles the sequence in multiples of the lane width
# tile edge along q and kv, forward and backward, with one fused backward
# kernel (dq, dk, dv in one pass): the fastest of {256, 512, 1024}^2 with
# fused or split backward kernels on one v5e at the train cell's shapes
# (PERF.md); the fused backward won at every tile size
BLOCK = 1024


def _edge(s: int) -> int:
    """The largest power-of-two multiple of ``LANES`` up to ``BLOCK`` that
    divides ``s`` (a multiple of ``LANES``)."""
    b = min(BLOCK, s)
    while s % b:
        b //= 2
    return b


def block_sizes(s: int) -> splash.BlockSizes:
    """The kernel's tile sizes at sequence length ``s``."""
    b = _edge(s)
    return splash.BlockSizes(block_q=b, block_kv=b, block_q_dkv=b,
                             block_kv_dkv=b, use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def make_kernel(heads: int, s: int, window: int, interpret: bool):
    """The splash kernel for ``heads`` query heads over one length-``s``
    sequence: causal, and within ``window`` positions when ``0 < window
    < s`` (key j attends query i iff ``i - window < j <= i``).  Its block
    tables are concrete arrays, whatever trace first asks for it, so the
    cached kernel serves every later trace."""
    if 0 < window < s:
        one = splash_mask.LocalMask((s, s), (window - 1, 0), offset=0)
    else:
        one = splash_mask.CausalMask((s, s))
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            splash_mask.MultiHeadMask([one] * heads),
            block_sizes=block_sizes(s), head_shards=1, q_seq_shards=1,
            interpret=interpret)


def causal_attention(
    q: jax.Array,  # (B, S, KV, G, hd)
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,  # (B, S, KV, hd)
    window: int = 0,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal (optionally sliding-window) self-attention through the fused
    kernel; S must be a multiple of ``LANES``.

    q is scaled by ``scale`` (default 1/sqrt(hd)) in f32 and cast once to
    the operand dtype, q's own (the model's compute dtype: bf16 at the
    published configs).
    """
    interpret = mode.resolve("splash_attention", interpret)
    b, s, kv, g, hd = q.shape
    dtype = q.dtype
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qh = (q.astype(jnp.float32) * scale).astype(dtype)
    qh = qh.reshape(b, s, kv * g, hd).transpose(0, 2, 1, 3)  # (B, H, S, hd)
    kh = k.astype(dtype).transpose(0, 2, 1, 3)  # (B, KV, S, hd)
    vh = v.astype(dtype).transpose(0, 2, 1, 3)
    kernel = make_kernel(kv * g, s, window if 0 < window < s else 0, interpret)
    o = mode.manual(jax.vmap(kernel))(qh, kh, vh)  # (B, H, S, hd)
    return o.transpose(0, 2, 1, 3).reshape(b, s, kv, g, hd).astype(dtype)
