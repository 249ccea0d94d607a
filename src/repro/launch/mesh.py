"""Production mesh definitions.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips, the ``pod`` axis
crossing DCN.

"Worker machines" in the paper's sense are the data-parallel groups: the
manual axes of the robust train step are ``('data',)`` or
``('pod', 'data')`` and the robust aggregation runs across them (m = 16
or 32 workers).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax >= 0.7 makes mesh axes ``Explicit`` unless told otherwise, and
    explicit sharding rejects ops whose output sharding it cannot infer
    (the embedding gather among them) even on a 1x1 mesh.  The repo's
    steps place shardings with ``NamedSharding``/``shard_map`` and let
    GSPMD propagate the rest, which is the Auto contract — so every mesh
    in the repo is built here.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def worker_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a == "model")


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def num_workers(mesh) -> int:
    s = mesh_shape_dict(mesh)
    n = 1
    for a in worker_axes(mesh):
        n *= s[a]
    return n


def make_debug_mesh(data: int = 4, model: int = 2, pod: int = 0):
    """Small mesh on the first ``pod*data*model`` devices: one host's chips,
    or simulated CPU devices (``xla_force_host_platform_device_count``)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
