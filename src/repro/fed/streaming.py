"""Streaming chunked robust aggregation (two-pass histogram sketch).

``streaming_aggregate`` consumes a cohort of m gradient rows as a
sequence of fixed-size chunks produced by ``chunk_fn(j) -> (rows_j, d)``
and returns the approximate coordinate-wise median / β-trimmed mean —
without ever materializing the ``(m, d)`` matrix. ``chunk_fn`` is called
twice per chunk (pass 1: min/max; pass 2: bin counts), which is the
deliberate trade: chunks are *regenerated* (cheap — virtual clients are
seed-derived, see fed.population) instead of cached (O(m·d) memory,
impossible at m = 10⁵⁺).

Estimator: per-coordinate equal-width histogram over [min, max] with
``nbins`` bins; CDF inversion gives order statistics within one bin
width ``(max−min)/nbins`` of the exact values (error analysis in
kernels/histogram_agg.py and DESIGN.md §Federated-scale).

Backends: ``pallas`` streams each chunk through the
kernels/histogram_agg.py kernels (interpret mode on CPU, Mosaic on TPU);
``xla`` uses the scatter-add jnp path. ``auto`` picks pallas on TPU.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import histogram_agg as H


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    nbins: int = 256
    backend: str = "auto"  # auto|pallas|xla
    block: int = 512  # pallas lane-block (multiple of 128)

    def use_pallas(self) -> bool:
        return self.backend == "pallas" or (
            self.backend == "auto" and jax.default_backend() == "tpu")


# ------------------------------------------------------------------ pass 1


def minmax_init(d: int) -> tuple[jax.Array, jax.Array]:
    return jnp.full((d,), jnp.inf, jnp.float32), jnp.full((d,), -jnp.inf, jnp.float32)


def minmax_update(state, chunk: jax.Array, cfg: SketchConfig = SketchConfig()):
    lo, hi = state
    if cfg.use_pallas():
        clo, chi = H.minmax_pallas(chunk, block=cfg.block)
    else:
        cf = chunk.astype(jnp.float32)
        clo, chi = jnp.min(cf, axis=0), jnp.max(cf, axis=0)
    return jnp.minimum(lo, clo), jnp.maximum(hi, chi)


def edges_from_minmax(state, nbins: int) -> tuple[jax.Array, jax.Array]:
    """(lo, width) of the equal-width binning; width 0 on degenerate coords."""
    lo, hi = state
    return lo, (hi - lo) / nbins


# ------------------------------------------------------------------ pass 2


def hist_update(state, chunk: jax.Array, lo: jax.Array, width: jax.Array,
                cfg: SketchConfig = SketchConfig()):
    counts, sums = state
    if cfg.use_pallas():
        dc, ds = H.histogram_pallas(chunk, lo, width, nbins=counts.shape[0],
                                    block=cfg.block,
                                    with_sums=sums is not None)
        return counts + dc, (sums + ds if sums is not None else None)
    return H.hist_update(counts, sums, chunk, lo, width)


# ----------------------------------------------------------------- drivers


def streaming_aggregate(
    chunk_fn: Callable[[int], jax.Array],
    num_chunks: int,
    d: int,
    method: str = "median",
    beta: float = 0.1,
    cfg: SketchConfig = SketchConfig(),
) -> jax.Array:
    """Aggregate a chunked stream of gradient rows; returns (d,) f32.

    ``chunk_fn(j)`` must return the j-th ``(rows_j, d)`` chunk and be
    deterministic — it is called once per pass. ``method`` is ``median``
    or ``trimmed_mean`` (the order-statistic aggregators; ``mean`` needs
    no sketch — a running sum does it — and is included for baselines).
    """
    return streaming_aggregate_multi(
        chunk_fn, num_chunks, d, (method,), beta, cfg)[method]


def streaming_aggregate_multi(
    chunk_fn: Callable[[int], jax.Array],
    num_chunks: int,
    d: int,
    methods: tuple = ("median", "trimmed_mean"),
    beta: float = 0.1,
    cfg: SketchConfig = SketchConfig(),
) -> dict:
    """Several estimators from ONE shared sketch; returns {method: (d,)}.

    The counts/sums sketch is method-independent, so evaluating median
    AND trimmed mean (the pair every robustness comparison wants) costs
    one two-pass stream instead of two — the streaming analogue of the
    fused selection kernel (kernels/robust_agg.fused_median_trimmed_pallas).
    ``mean`` rides along on the pass-1 stream for free.
    """
    methods = tuple(methods)
    unknown = [mt for mt in methods if mt not in ("mean", "median", "trimmed_mean")]
    if unknown:
        raise ValueError(f"unknown streaming method(s) {unknown!r}")
    out = {}
    need_sketch = [mt for mt in methods if mt != "mean"]
    total = jnp.zeros((d,), jnp.float32) if "mean" in methods else None
    mm = minmax_init(d) if need_sketch else None
    m = 0
    for j in range(num_chunks):
        c = chunk_fn(j)
        m += c.shape[0]
        if total is not None:
            total = total + jnp.sum(c.astype(jnp.float32), axis=0)
        if mm is not None:
            mm = minmax_update(mm, c, cfg)
    if total is not None:
        out["mean"] = total / m
    if not need_sketch:
        return out
    lo, width = edges_from_minmax(mm, cfg.nbins)

    hist = H.hist_init(d, cfg.nbins, with_sums=("trimmed_mean" in need_sketch))
    for j in range(num_chunks):
        hist = hist_update(hist, chunk_fn(j), lo, width, cfg)
    counts, sums = hist

    if "median" in need_sketch:
        out["median"] = H.median_from_hist(counts, lo, width, m)
    if "trimmed_mean" in need_sketch:
        out["trimmed_mean"] = H.trimmed_mean_from_hist(
            counts, sums, lo, width, m, beta)
    return out


def aggregate_array_chunked(
    x: jax.Array,
    method: str = "median",
    beta: float = 0.1,
    chunk_rows: int = 256,
    cfg: SketchConfig = SketchConfig(),
) -> jax.Array:
    """Convenience: run the streaming aggregator over an in-memory (m, d)
    array in ``chunk_rows`` slices — used by tests to check chunk
    invariance against the single-shot ``histogram_agg.sketch_array``."""
    m, d = x.shape
    bounds = [(s, min(s + chunk_rows, m)) for s in range(0, m, chunk_rows)]
    return streaming_aggregate(
        lambda j: x[bounds[j][0]:bounds[j][1]], len(bounds), d, method, beta, cfg)
