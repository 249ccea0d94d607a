"""Where the Pallas kernels run, and a record of how each was traced.

On a TPU every kernel lowers through Mosaic; without one (the CPU test
runs, ``JAX_PLATFORMS=cpu``) it runs in Pallas interpret mode.  The
kernels resolve ``interpret=None`` here, so no caller on a TPU can fall
into interpret mode by leaving the argument out.

``TRACES`` counts each kernel trace by ``(kernel name, interpret)``: jit
traces once per shape and static configuration, so a non-zero
``(name, False)`` entry proves that the compiled path ran, which is
what ``chip_smoke.py`` checks.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax

TRACES: collections.Counter = collections.Counter()


def resolve(name: str, interpret: Optional[bool]) -> bool:
    """The interpret flag kernel ``name`` traces with (``None`` = interpret
    exactly when JAX has no TPU), recorded in :data:`TRACES`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    TRACES[(name, bool(interpret))] += 1
    return bool(interpret)
