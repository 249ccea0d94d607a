"""Grouped matrix products for the dropless expert layer.

``gmm(lhs, rhs, group_sizes)``: ``lhs`` (m, k) holds rows sorted by
group, ``group_sizes`` (G,) int32 counts each group's rows (they sum to
m), ``rhs`` (G, k, n) holds one matrix per group; the result (m, n) is
each group's rows times its own matrix.

The Pallas TPU megablox kernels that ship with jax
(``jax.experimental.pallas.ops.tpu.megablox``): ``gmm`` walks the rows in
``(tm, tk, tn)`` tiles, visiting a tile that straddles two groups once
per group and masking the other group's rows, and accumulates in f32;
the backward pass is ``gmm`` against the transposed matrices (the rows'
gradient) and ``tgmm`` (each group's matrix gradient, its rows'
transposed products).  Their ``pallas_call``s are traced under the
device scopes ``moe_gmm`` and ``moe_tgmm``, which give the compiled
kernels those names (``jvp_moe_gmm_.3``, ``transpose_jvp_moe_tgmm__.7``)
where a profiler trace can find them.

``models.moe`` decides where it runs; elsewhere the same sorted buffer
goes through ``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import functools
import importlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import mode

# the module, not the ``gmm`` the package re-exports (a custom_vjp)
_megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
_GMM = _megablox.gmm.__wrapped__  # the undecorated functions: traced under
_TGMM = _megablox.tgmm.__wrapped__  # our scopes, their kernels take our names
GMM_SCOPE, TGMM_SCOPE = "moe_gmm", "moe_tgmm"

ROWS = 128  # the row tile is a power-of-two multiple of this dividing m
# (rows, contraction, output) tile edges: the fastest of ten by forward
# plus forward-and-backward time at the train cell's shapes (m 65,536
# rows, widths 1024 and 512) on one v5e (PERF.md); a width under its
# edge is taken whole
TILING = (512, 1024, 1024)


def kernel_applies(m: int) -> bool:
    """Whether the kernel tiles ``m`` rows."""
    return m % ROWS == 0


def tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """The tiles at these sizes: the row tile the largest power-of-two
    multiple of ``ROWS`` up to ``TILING[0]`` that divides ``m``; the
    others ``TILING``'s, or the whole width where that is smaller."""
    tm = TILING[0]
    while m % tm:
        tm //= 2
    return tm, min(TILING[1], k), min(TILING[2], n)


def _gmm(lhs, rhs, group_sizes, interpret, transpose_rhs=False):
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with jax.named_scope(GMM_SCOPE):
        return _GMM(lhs, rhs, group_sizes, lhs.dtype,
                    tiling(lhs.shape[0], lhs.shape[1], n),
                    transpose_rhs=transpose_rhs, interpret=interpret)


def _tgmm(lhs, grad, group_sizes, dtype, interpret):
    with jax.named_scope(TGMM_SCOPE):
        return _TGMM(lhs.swapaxes(0, 1), grad, group_sizes, dtype,
                     tiling(lhs.shape[0], lhs.shape[1], grad.shape[1]),
                     interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _grouped_bwd(interpret, res, grad):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(grad, rhs, group_sizes, interpret, transpose_rhs=True)
    d_rhs = _tgmm(lhs, grad, group_sizes, rhs.dtype, interpret)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        interpret: Optional[bool] = None) -> jax.Array:
    """The grouped product through the kernel, in ``lhs``'s dtype; ``m``
    must satisfy :func:`kernel_applies`."""
    interpret = mode.resolve("grouped_matmul", interpret)
    return mode.manual(lambda x, w, g: _grouped(x, w, g, interpret))(
        lhs, rhs, group_sizes.astype(jnp.int32))


def ragged(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The same product through XLA (``jax.lax.ragged_dot``)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
