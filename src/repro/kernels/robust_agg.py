"""Pallas TPU kernels: coordinate-wise median / trimmed-mean over workers.

The hot-spot the paper introduces: every training step, every gradient
coordinate is aggregated by an order statistic over the m worker rows.
On TPU we tile the coordinate space into VMEM blocks of shape
``(m, BLOCK)`` (BLOCK a multiple of the 128-lane width) and run the
**pruned selection network** from :mod:`repro.kernels.selection_network`
— a static DAG of lane-vectorised compare-exchanges that computes only
the requested order statistics (median wires, trim band), which lowers
to pure vector min/max with no data-dependent control flow (MXU-free,
VPU-friendly).

m is small and static (the number of data-parallel worker groups,
16-64), so a comparator network beats a general sort: it needs no
indices, no gather/scatter, and keeps the whole working set in
registers/VMEM.  The pre-selection kernel unrolled the full O(m²)
odd-even transposition sort (496 comparators at m=32); the pruned
Batcher median program needs 157 — a ~3× cut in VPU work for the same
bit-exact output, and the trimmed-mean band program prunes likewise.
``fused_median_trimmed_pallas`` evaluates the union rank set, so the
benchmark matrix gets median *and* trimmed mean in ONE HBM pass instead
of two.

Layout reasoning (HBM→VMEM): each grid step streams an (m, BLOCK) tile
(m·BLOCK·dtype bytes) in and a (1, BLOCK) row out — 2-D, because Mosaic
tiles a 1-D output block at BLOCK while XLA tiles the 1-D array at 1024,
and the chip's compiler refuses the mismatch for any BLOCK that is not a
multiple of 1024; with BLOCK=1024 and m=32 in
f32 that is a 128 KiB in-tile — far below the ~16 MiB VMEM budget, so the
pipeline can double-buffer freely. Arithmetic intensity is O(#comparators/m)
passes over the tile, i.e. the op is HBM-bandwidth-bound, which is why
fusing median into the reduce-scatter (see core/distributed.py) rather
than re-reading gathered gradients matters at the system level — and why
the fused kernel's single pass is the right shape for computing both
estimators.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mode, selection_network as SN


def _median_kernel(x_ref, o_ref, *, comparators):
    x = x_ref[...]
    m = x.shape[0]
    rows = SN.apply_network([x[i] for i in range(m)], comparators)
    o_ref[...] = SN.median_from_rows(rows, m, x.dtype)[None, :]


def _trimmed_mean_kernel(x_ref, o_ref, *, trim: int, comparators):
    x = x_ref[...]
    m = x.shape[0]
    rows = SN.apply_network([x[i] for i in range(m)], comparators)
    o_ref[...] = SN.band_mean_from_rows(rows, m, trim, x.dtype)[None, :]


def _fused_kernel(x_ref, med_ref, tm_ref, *, trim: int, comparators):
    x = x_ref[...]
    m = x.shape[0]
    rows = SN.apply_network([x[i] for i in range(m)], comparators)
    med_ref[...] = SN.median_from_rows(rows, m, x.dtype)[None, :]
    tm_ref[...] = SN.band_mean_from_rows(rows, m, trim, x.dtype)[None, :]


def _pad_to(x: jnp.ndarray, mult: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[1]
    rem = (-n) % mult
    if rem:
        x = jnp.pad(x, ((0, 0), (0, rem)))
    return x, n


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def median_pallas(x: jnp.ndarray, block: int = 1024,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Coordinate-wise median of x: (m, n) -> (n,) via Pallas.

    ``interpret=None`` lowers through Mosaic on a TPU and interprets the
    kernel body elsewhere (:mod:`repro.kernels.mode`). ``block`` must be
    a multiple of 128 (lane width).
    """
    assert x.ndim == 2, x.shape
    assert block % 128 == 0, "block must be a multiple of the 128-lane width"
    m = x.shape[0]
    interpret = mode.resolve("median_pallas", interpret)
    prog = SN.median_program(m)
    xp, n = _pad_to(x, block)
    grid = (xp.shape[1] // block,)
    out = pl.pallas_call(
        functools.partial(_median_kernel, comparators=prog.comparators),
        grid=grid,
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), x.dtype),
        interpret=interpret,
    )(xp)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("trim", "block", "interpret"))
def trimmed_mean_pallas(
    x: jnp.ndarray, trim: int, block: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Coordinate-wise trimmed mean of x: (m, n) -> (n,), trimming ``trim``
    rows at each end (trim = floor(beta*m))."""
    assert x.ndim == 2, x.shape
    assert block % 128 == 0
    m = x.shape[0]
    interpret = mode.resolve("trimmed_mean_pallas", interpret)
    assert 0 <= trim and 2 * trim < m, (trim, m)
    prog = SN.trimmed_program(m, trim)
    xp, n = _pad_to(x, block)
    grid = (xp.shape[1] // block,)
    out = pl.pallas_call(
        functools.partial(_trimmed_mean_kernel, trim=trim,
                          comparators=prog.comparators),
        grid=grid,
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, xp.shape[1]), x.dtype),
        interpret=interpret,
    )(xp)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("trim", "block", "interpret"))
def fused_median_trimmed_pallas(
    x: jnp.ndarray, trim: int, block: int = 1024,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Median AND trimmed mean of x: (m, n) -> ((n,), (n,)) in one HBM pass.

    The selection program is built for the union of the median wires and
    the trim band, so the (m, BLOCK) tile is streamed in once and both
    estimators come out of the same comparator DAG — exactly the pair the
    robustness benchmark matrix evaluates side by side.
    """
    assert x.ndim == 2, x.shape
    assert block % 128 == 0
    m = x.shape[0]
    interpret = mode.resolve("fused_median_trimmed_pallas", interpret)
    assert 0 <= trim and 2 * trim < m, (trim, m)
    prog = SN.fused_program(m, trim)
    xp, n = _pad_to(x, block)
    grid = (xp.shape[1] // block,)
    med, tm = pl.pallas_call(
        functools.partial(_fused_kernel, trim=trim, comparators=prog.comparators),
        grid=grid,
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, block), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, xp.shape[1]), x.dtype)] * 2,
        interpret=interpret,
    )(xp)
    return med[0, :n], tm[0, :n]
