"""Public jit'd wrappers around the robust-aggregation Pallas kernel.

``robust_aggregate(x, method, beta)`` accepts any (m, ...) array, flattens
the coordinate space, dispatches to the Pallas kernel (interpret mode on
CPU, Mosaic on TPU), and restores the shape. Backends:

- ``pallas``   the selection-network Pallas kernels (always Mosaic on a
  TPU — see kernels/mode.py);
- ``network``  the same pruned selection program executed as unrolled
  jnp min/max — XLA-compiled, the fast CPU path and the benchmark
  subject (no interpreter overhead, no sort machinery);
- ``xla``      the jnp.sort oracle — the baseline the network paths are
  measured against, and the fallback for m above the network limit.

``fused_median_trimmed`` returns median AND trimmed mean from one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref, robust_agg, selection_network as SN
from repro.kernels.selection_network import NETWORK_MAX_M


def _auto_backend(m: int) -> str:
    """``auto``: the Pallas kernels on a TPU; off it, the XLA-compiled
    network for small m and the sort oracle above the network limit."""
    if jax.default_backend() == "tpu":
        return "pallas"
    return "network" if 2 <= m <= NETWORK_MAX_M else "xla"


def _check_network_m(m: int) -> None:
    """Explicit backend='network' must respect the same limit as auto
    dispatch: above NETWORK_MAX_M the unrolled comparator program is
    O(m log² m) static ops and compile time becomes pathological."""
    if m > NETWORK_MAX_M:
        raise ValueError(
            f"backend='network' supports m <= {NETWORK_MAX_M}, got m={m}; "
            "use backend='xla' (or 'auto') for larger worker counts")


def robust_aggregate(
    x: jax.Array,
    method: str = "median",
    beta: float = 0.1,
    backend: str = "auto",  # auto|pallas|network|xla
    block: int = 1024,
) -> jax.Array:
    """Aggregate (m, ...) -> (...) coordinate-wise with the given method."""
    m = x.shape[0]
    flat = x.reshape(m, -1)
    if backend == "auto":
        backend = _auto_backend(m)
    elif backend == "network":
        _check_network_m(m)
    if method == "median":
        if backend == "pallas":
            out = robust_agg.median_pallas(flat, block=block)
        elif backend == "network":
            out = SN.median_select(flat)
        else:
            out = ref.median_ref(flat)
    elif method == "trimmed_mean":
        trim = int(beta * m)
        if backend == "pallas":
            out = robust_agg.trimmed_mean_pallas(flat, trim, block=block)
        elif backend == "network":
            out = (SN.trimmed_mean_select(flat, trim) if trim
                   else jnp.mean(flat.astype(jnp.float32), axis=0).astype(flat.dtype))
        else:
            out = ref.trimmed_mean_ref(flat, beta)
    elif method == "mean":
        out = jnp.mean(flat.astype(jnp.float32), axis=0).astype(flat.dtype)
    else:
        raise ValueError(f"unknown method {method!r}")
    return out.reshape(x.shape[1:])


def fused_median_trimmed(
    x: jax.Array,
    beta: float = 0.1,
    backend: str = "auto",  # auto|pallas|network|xla
    block: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """(median, trimmed_mean) of (m, ...) from ONE pass over the rows.

    The fused selection program computes the union rank set, so the two
    estimators share every compare-exchange and the (m, d) matrix is read
    from HBM once — the shape the robustness benchmark matrix wants.
    """
    m = x.shape[0]
    trim = int(beta * m)
    flat = x.reshape(m, -1)
    if backend == "auto":
        backend = _auto_backend(m)
    elif backend == "network":
        _check_network_m(m)
    if backend == "pallas":
        med, tm = robust_agg.fused_median_trimmed_pallas(flat, trim, block=block)
    elif backend == "network":
        med, tm = SN.median_and_trimmed_select(flat, trim)
    else:
        med, tm = ref.median_ref(flat), ref.trimmed_mean_ref(flat, beta)
    return med.reshape(x.shape[1:]), tm.reshape(x.shape[1:])


median = functools.partial(robust_aggregate, method="median")
trimmed_mean = robust_aggregate  # explicit method kwarg recommended
