"""GQA attention: plain, chunked (online-softmax), and the fused-kernel dispatch.

Shapes use the grouped layout throughout: q (B, S, KV, G, hd) where
H = KV·G query heads share KV heads; k/v (B, S, KV, hd). This avoids ever
materialising KV repeated to H heads.

``chunked_attention`` scans over KV blocks with an online softmax so no
(S, S) buffer exists; the scan body is remat'd (jax.checkpoint) so the
backward pass recomputes per-block probabilities instead of storing them —
the pure-JAX analogue of a flash kernel.

``attention`` takes the fused Pallas kernel (``kernels/attention.py``)
on a TPU wherever the input allows it: full-sequence causal
self-attention at a length the kernel tiles, in a trace the compiler
partitions over one device (a Mosaic kernel cannot be partitioned).
Everywhere else (the CPU, non-causal encoder and cross attention, offset
queries, ragged lengths, heads or batch split by the compiler) it keeps
the XLA paths, plain or chunked by ``kv_block``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import attention as fused

NEG_INF = -1e30


def _scale(q: jax.Array, scale: Optional[float]):
    """The scores' scale: ``scale``, or 1/sqrt(hd) when it is None."""
    if scale is None:
        return 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    return scale


def _mask(qpos: jax.Array, kpos: jax.Array, causal: bool, window: int) -> jax.Array:
    """(Sq, Sk) boolean mask: True = attend."""
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window and window > 0:
        ok &= kpos[None, :] > qpos[:, None] - window
    return ok


def plain_attention(
    q: jax.Array,  # (B, Sq, KV, G, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference full-materialisation attention (small S / tests)."""
    scale = _scale(q, scale)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(q.shape[1])
    kpos = jnp.arange(k.shape[1])
    m = _mask(qpos, kpos, causal, window)
    logits = jnp.where(m[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(
    q: jax.Array,  # (B, Sq, KV, G, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,  # (B, Sk, KV, hd)
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_block: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention over KV blocks; O(Sq·kv_block) live memory."""
    b, sq, kv, g, hd = q.shape
    sk = k.shape[1]
    if sk % kv_block != 0:
        pad = kv_block - sk % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sk_p = sk + pad
    else:
        sk_p = sk
    nblk = sk_p // kv_block
    kb = k.reshape(b, nblk, kv_block, kv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, kv_block, kv, hd).transpose(1, 0, 2, 3, 4)
    scale = _scale(q, scale)
    qf = q.astype(jnp.float32)
    qpos = q_offset + jnp.arange(sq)

    @jax.checkpoint
    def body(carry, xs):
        acc, mx, lse = carry
        kc, vc, blk = xs
        kpos = blk * kv_block + jnp.arange(kv_block)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32)) * scale
        msk = _mask(qpos, kpos, causal, window) & (kpos < sk)[None, :]
        logits = jnp.where(msk[None, None, None], logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_mx = jnp.maximum(mx, blk_max)
        corr = jnp.exp(mx - new_mx)
        p = jnp.exp(logits - new_mx[..., None])
        acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
        lse = lse * corr + jnp.sum(p, axis=-1)
        return (acc, new_mx, lse), None

    acc0 = jnp.zeros((b, kv, g, sq, hd), jnp.float32)
    mx0 = jnp.full((b, kv, g, sq), NEG_INF, jnp.float32)
    lse0 = jnp.zeros((b, kv, g, sq), jnp.float32)
    (acc, _, lse), _ = jax.lax.scan(body, (acc0, mx0, lse0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(lse[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B, Sq, KV, G, hd)


def fused_kernel_applies(sq: int, sk: int, causal: bool, q_offset: int,
                         shards: int, backend: str) -> bool:
    """Whether ``attention`` takes the fused kernel: on a TPU, for causal
    self-attention over the whole sequence (no query offset, Sq == Sk) at
    a length the kernel tiles, when the compiler partitions the trace over
    no more than one device (``shards``)."""
    return (backend == "tpu" and causal and q_offset == 0 and sq == sk
            and sq % fused.LANES == 0 and shards <= 1)


def attention(
    q, k, v, causal: bool = True, window: int = 0, q_offset: int = 0,
    kv_block: int = 1024, shards: int = 1, scale: Optional[float] = None,
):
    """Dispatch: the fused kernel where it applies (see
    ``fused_kernel_applies``; ``shards`` is the number of devices the
    compiler partitions the trace over, ``ShardCtx.auto_shards``), else
    plain for short sequences (or ``kv_block`` 0) and chunked otherwise.
    ``scale`` multiplies the scores (default 1/sqrt(hd))."""
    if fused_kernel_applies(q.shape[1], k.shape[1], causal, q_offset, shards,
                            jax.default_backend()):
        return fused.causal_attention(q, k, v, window, scale=scale)
    if kv_block == 0 or k.shape[1] <= kv_block:
        return plain_attention(q, k, v, causal, window, q_offset, scale)
    return chunked_attention(q, k, v, causal, window, q_offset, kv_block, scale)
