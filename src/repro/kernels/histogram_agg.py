"""Pallas TPU kernels + jnp helpers for streaming histogram aggregation.

The small-m kernels in :mod:`repro.kernels.robust_agg` materialize the
full ``(m, d)`` per-worker matrix and run an O(m²) sorting network — fine
for m ≤ 64 data-parallel worker groups, impossible for the cross-device
federated regime (m = 10³–10⁶ sampled clients per round). This module
implements the *streaming* alternative: a two-pass per-coordinate
histogram sketch that consumes the cohort in fixed-size chunks of rows
and never holds more than ``(chunk, d)`` values plus ``(nbins, d)``
sketch state.

  pass 1   running per-coordinate min/max over chunks → bin range
  pass 2   per-coordinate bin counts + bin sums over chunks
  invert   CDF inversion of the counts → approximate order statistics

Estimators and error bound
--------------------------
With bin width ``w = (max − min) / nbins`` per coordinate:

- ``median_from_hist``       returns the centre of the bin containing the
  exact median rank(s) (rank average for even m), so
  ``|approx − exact| ≤ w``.
- ``trimmed_mean_from_hist`` keeps exact per-bin *sums* for bins that are
  entirely inside the trim interval and approximates boundary bins by
  ``kept_count × bin_centre``; every kept element is represented within
  its own bin, so the kept-mean error is again ``≤ w``.

Degenerate coordinates (max == min) collapse naturally: every row lands
in bin 0, the bin centre equals ``min``, and both estimators return the
exact common value.

Complexity: O(m·d) time, O(nbins·d) sketch memory, two passes over the
data (chunks may be regenerated rather than stored — see
repro.fed.streaming).

Kernel layout (HBM→VMEM): the grid tiles the coordinate axis; each step
streams a ``(chunk, BLOCK)`` tile in and ``(nbins, BLOCK)`` counts/sums
out. With chunk=256, BLOCK=512, nbins=128 in f32 that is 512 KiB in +
512 KiB out — comfortably inside the ~16 MiB VMEM budget with double
buffering. The bin loop is a ``fori_loop`` of lane-vectorised compares
(VPU-only, no gather/scatter), the same data-independent-control-flow
property that makes the odd-even network lower cleanly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mode

# --------------------------------------------------------------------------
# pure-jnp sketch math (shared by fed.streaming, core.distributed, tests)
# --------------------------------------------------------------------------


def bin_index(x: jax.Array, lo: jax.Array, width: jax.Array, nbins: int) -> jax.Array:
    """Bin of each entry of ``x`` (…, d) given per-coordinate lo/width (d,).

    Zero-width coordinates map to bin 0 (the guard divisor is arbitrary —
    all rows share the single value ``lo``).
    """
    safe_w = jnp.where(width > 0, width, 1.0)
    idx = jnp.floor((x.astype(jnp.float32) - lo) / safe_w).astype(jnp.int32)
    return jnp.clip(idx, 0, nbins - 1)


def hist_init(d: int, nbins: int, with_sums: bool = True
              ) -> tuple[jax.Array, Optional[jax.Array]]:
    """Empty sketch state: (counts, sums), each (nbins, d) f32.

    ``with_sums=False`` returns ``(counts, None)`` — the median only
    needs counts, halving sketch memory and scatter work.
    """
    counts = jnp.zeros((nbins, d), jnp.float32)
    return counts, (jnp.zeros((nbins, d), jnp.float32) if with_sums else None)


def hist_update(
    counts: jax.Array,
    sums: Optional[jax.Array],
    chunk: jax.Array,
    lo: jax.Array,
    width: jax.Array,
) -> tuple[jax.Array, Optional[jax.Array]]:
    """Accumulate a ``(rows, d)`` chunk into the (nbins, d) sketch.

    XLA scatter-add path — the reference implementation and the CPU
    fallback; the Pallas kernel below computes the same per-chunk
    increments without scatters. ``sums`` may be None: the median needs
    only counts, and skipping the sums scatter halves the sketch work.
    """
    nbins = counts.shape[0]
    idx = bin_index(chunk, lo, width, nbins)  # (rows, d)
    cols = jnp.broadcast_to(jnp.arange(chunk.shape[-1], dtype=jnp.int32), idx.shape)
    counts = counts.at[idx, cols].add(1.0)
    if sums is not None:
        sums = sums.at[idx, cols].add(chunk.astype(jnp.float32))
    return counts, sums


def sketch_array(x: jax.Array, nbins: int, with_sums: bool = True
                 ) -> tuple[jax.Array, Optional[jax.Array], jax.Array, jax.Array]:
    """Single-shot sketch of an in-memory ``(m, d)`` array:
    ``(counts, sums, lo, width)``.

    The one place the binning convention (f32 min/max range, equal-width
    bins, clipping) is defined for non-streaming callers — the approx_*
    aggregators in core.aggregators use this, so their estimator is
    identical to the streaming/chunked paths by construction.
    """
    xf = x.astype(jnp.float32)
    lo = jnp.min(xf, axis=0)
    width = (jnp.max(xf, axis=0) - lo) / nbins
    counts, sums = hist_update(
        *hist_init(x.shape[-1], nbins, with_sums=with_sums), x, lo, width)
    return counts, sums, lo, width


def _value_at_rank(counts: jax.Array, lo: jax.Array, width: jax.Array, rank) -> jax.Array:
    """Centre of the bin holding the rank-th smallest element (1-indexed).

    ``rank`` may be a scalar or (d,). Bin b is the first with
    cumulative count ≥ rank, i.e. the exact order statistic lies in b.
    """
    nbins = counts.shape[0]
    cum = jnp.cumsum(counts, axis=0)  # (nbins, d)
    rank = jnp.asarray(rank, jnp.float32)
    b = jnp.sum((cum < rank).astype(jnp.int32), axis=0)
    b = jnp.clip(b, 0, nbins - 1)
    return lo + (b.astype(jnp.float32) + 0.5) * width


def median_from_hist(counts: jax.Array, lo: jax.Array, width: jax.Array, m: int) -> jax.Array:
    """Approximate coordinate-wise median from the sketch; error ≤ width.

    Matches the exact-median convention (Definition 1 / jnp.median): for
    even m the two middle order statistics are located independently and
    averaged.
    """
    if m % 2 == 1:
        return _value_at_rank(counts, lo, width, (m + 1) // 2)
    a = _value_at_rank(counts, lo, width, m // 2)
    b = _value_at_rank(counts, lo, width, m // 2 + 1)
    return 0.5 * (a + b)


def quantile_from_hist(counts: jax.Array, lo: jax.Array, width: jax.Array, m: int, q: float) -> jax.Array:
    """Approximate nearest-rank q-quantile (cf. aggregators.coordinate_quantile)."""
    rank = min(m, max(1, int(round(q * (m - 1))) + 1))
    return _value_at_rank(counts, lo, width, rank)


def trimmed_mean_from_hist(
    counts: jax.Array,
    sums: jax.Array,
    lo: jax.Array,
    width: jax.Array,
    m: int,
    beta: float,
) -> jax.Array:
    """Approximate coordinate-wise β-trimmed mean from the sketch.

    Kept ranks are (b_trim, m − b_trim]. A bin entirely inside that
    interval contributes its exact sum; a straddling bin contributes
    ``overlap × centre``. Per-element representation error ≤ width, so
    the returned mean is within one bin width of Definition 2.
    """
    if not 0.0 <= beta < 0.5:
        raise ValueError(f"beta must be in [0, 1/2), got {beta}")
    b_trim = int(beta * m)
    if 2 * b_trim >= m:
        raise ValueError(f"trim count 2*{b_trim} >= m={m}")
    nbins = counts.shape[0]
    cum = jnp.cumsum(counts, axis=0)  # (nbins, d)
    prev = cum - counts
    kept = jnp.clip(jnp.minimum(cum, m - b_trim) - jnp.maximum(prev, b_trim), 0.0, None)
    centres = lo[None, :] + (jnp.arange(nbins, dtype=jnp.float32)[:, None] + 0.5) * width[None, :]
    whole = (kept == counts) & (counts > 0)
    contrib = jnp.where(whole, sums, kept * centres)
    return jnp.sum(contrib, axis=0) / (m - 2 * b_trim)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------


def _minmax_kernel(x_ref, lo_ref, hi_ref):
    x = x_ref[...].astype(jnp.float32)
    lo_ref[...] = jnp.min(x, axis=0, keepdims=True)
    hi_ref[...] = jnp.max(x, axis=0, keepdims=True)


def _pad_cols(x: jnp.ndarray, mult: int, fill=0.0) -> tuple[jnp.ndarray, int]:
    n = x.shape[-1]
    rem = (-n) % mult
    if rem:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, rem)]
        x = jnp.pad(x, pad, constant_values=fill)
    return x, n


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def minmax_pallas(x: jnp.ndarray, block: int = 512,
                  interpret: Optional[bool] = None):
    """Per-coordinate (min, max) of a ``(rows, n)`` chunk → two (n,) f32.

    Pass-1 building block: combine across chunks with jnp.minimum/maximum.
    ``interpret=None``: Mosaic lowering on TPU, interpreted elsewhere.
    """
    assert x.ndim == 2, x.shape
    assert block % 128 == 0, "block must be a multiple of the 128-lane width"
    interpret = mode.resolve("minmax_pallas", interpret)
    rows = x.shape[0]
    xp, n = _pad_cols(x, block)
    grid = (xp.shape[1] // block,)
    lo, hi = pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block), lambda i: (0, i))],
        # (1, block) rows, not (block,): a 1-D output block must match
        # XLA's 1024-element tiling, which the default block of 512 does not
        out_specs=[pl.BlockSpec((1, block), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, xp.shape[1]), jnp.float32)] * 2,
        interpret=interpret,
    )(xp)
    return lo[0, :n], hi[0, :n]


def _hist_kernel(x_ref, lo_ref, w_ref, c_ref, s_ref=None, *, nbins: int):
    x = x_ref[...].astype(jnp.float32)  # (rows, block)
    lo = lo_ref[0, :]
    w = w_ref[0, :]
    safe_w = jnp.where(w > 0, w, 1.0)
    idx = jnp.clip(
        jnp.floor((x - lo[None, :]) / safe_w[None, :]), 0, nbins - 1
    ).astype(jnp.int32)

    def body(b, _):
        match = idx == b
        c_ref[pl.ds(b, 1), :] = jnp.sum(match.astype(jnp.float32), axis=0)[None, :]
        if s_ref is not None:
            s_ref[pl.ds(b, 1), :] = jnp.sum(jnp.where(match, x, 0.0), axis=0)[None, :]
        return 0

    jax.lax.fori_loop(0, nbins, body, 0)


@functools.partial(jax.jit, static_argnames=("nbins", "block", "interpret", "with_sums"))
def histogram_pallas(
    x: jnp.ndarray,
    lo: jnp.ndarray,
    width: jnp.ndarray,
    nbins: int = 128,
    block: int = 512,
    interpret: Optional[bool] = None,
    with_sums: bool = True,
):
    """Per-chunk bin (counts, sums) of ``x`` (rows, n) → two (nbins, n) f32.

    Pass-2 building block: add the returned increments to the running
    sketch. The bin loop is data-independent (fori_loop of vector
    compares), so it lowers to pure VPU code — no scatters.
    ``with_sums=False`` (the median path) drops the sums output entirely,
    halving the kernel's output tile traffic; returns ``(counts, None)``.
    """
    assert x.ndim == 2, x.shape
    assert block % 128 == 0
    interpret = mode.resolve("histogram_pallas", interpret)
    rows = x.shape[0]
    xp, n = _pad_cols(x, block)
    # padded lanes get lo=0, width=0 -> all rows in bin 0; sliced off below
    lop, _ = _pad_cols(lo.astype(jnp.float32)[None, :], block)
    wp, _ = _pad_cols(width.astype(jnp.float32)[None, :], block)
    grid = (xp.shape[1] // block,)
    n_out = 2 if with_sums else 1
    out = pl.pallas_call(
        functools.partial(_hist_kernel, nbins=nbins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((nbins, block), lambda i: (0, i))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((nbins, xp.shape[1]), jnp.float32)] * n_out,
        interpret=interpret,
    )(xp, lop.reshape(1, -1), wp.reshape(1, -1))
    if with_sums:
        return out[0][:, :n], out[1][:, :n]
    return out[0][:, :n], None
