"""Process-level JAX set-up shared by the CLIs, benchmarks and chip smoke.

``enable_compile_cache`` keeps JAX's persistent compilation cache where
``JAX_COMPILATION_CACHE_DIR`` says, or else in ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of what a
later run must match to hit the cache.

``simulate_cpu_devices`` gives a CPU-only run (``JAX_PLATFORMS=cpu``)
``n`` host devices, so meshes of several workers can be rehearsed
without a chip.  On a run that may use an accelerator it does nothing.
Both must run before JAX's first backend use.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def simulate_cpu_devices(n: int) -> None:
    """Ask for ``n`` CPU devices when the run is pinned to the CPU."""
    if os.environ.get("JAX_PLATFORMS", "").split(",") != ["cpu"]:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
