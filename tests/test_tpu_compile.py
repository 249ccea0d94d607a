"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so the main path's kernels and
the bucketed robust aggregation are compiled here at real widths for a
``v5e:2x2`` topology: what Mosaic or XLA would refuse on the chip fails
here, at no chip time.  Nothing runs, so these say nothing about results
or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and the
test workers all import this file.
"""
import functools
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distributed
from repro.fed.rounds import RoundConfig
from repro.fed.streaming import SketchConfig
from repro.kernels import histogram_agg as H
from repro.kernels import robust_agg as R

# one h2o-danube-1.8b MLP matrix: the aggregation width of the train path
MLP = (2560, 6912)
ROWS = 16  # workers aggregated by the selection-network kernels
FED_DIM = 65536  # the chip smoke's fed round


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", [
    functools.partial(R.median_pallas, block=1024, interpret=False),
    functools.partial(R.trimmed_mean_pallas, trim=1, block=1024, interpret=False),
    functools.partial(R.fused_median_trimmed_pallas, trim=1, block=1024,
                      interpret=False),
], ids=["median", "trimmed_mean", "fused"])
def test_selection_kernels_compile_for_v5e(one_chip, kernel):
    x = jax.ShapeDtypeStruct((ROWS, MLP[0] * MLP[1]), jnp.bfloat16,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, x)


def _fed_chunk(one_chip):
    return jax.ShapeDtypeStruct((RoundConfig().chunk_clients, FED_DIM),
                                jnp.float32, sharding=one_chip)


def test_minmax_compiles_at_default_sketch_block(one_chip):
    cfg = SketchConfig()
    fn = functools.partial(H.minmax_pallas, block=cfg.block, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, _fed_chunk(one_chip))


@pytest.mark.parametrize("with_sums", [False, True], ids=["counts", "sums"])
def test_histogram_compiles_at_default_sketch_settings(one_chip, with_sums):
    cfg = SketchConfig()
    vec = jax.ShapeDtypeStruct((FED_DIM,), jnp.float32, sharding=one_chip)
    fn = functools.partial(H.histogram_pallas, nbins=cfg.nbins, block=cfg.block,
                           interpret=False, with_sums=with_sums)
    assert "tpu_custom_call" in _compiled_text(fn, _fed_chunk(one_chip), vec, vec)


def test_bucketed_agg_of_published_width_leaf_compiles_on_four_chips(topo):
    """Before matrix leaves were bucketed along their rows, the flat
    (m, G/m) view of this one leaf cost the TPU compiler ~55 s (a
    relayout of the whole gradient); by rows it is about a second."""
    m = 4
    mesh = Mesh(np.array(topo.devices[:m]), ("data",),
                axis_types=(AxisType.Auto,))

    def body(g):
        return distributed.robust_bucketed_agg(
            {"w": g[0]}, ("data",), "median")["w"][None]

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      axis_names={"data"}, check_vma=False)
    x = jax.ShapeDtypeStruct((m,) + MLP, jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    t0 = time.perf_counter()
    text = jax.jit(f).lower(x).compile().as_text()
    elapsed = time.perf_counter() - t0
    # the all-to-all moves row buckets of the matrix, not a flat relayout
    shapes = re.findall(r"= (\w+\[[\d,]+\])\S* all-to-all\(", text)
    assert shapes == [f"bf16[{m},{MLP[0] // m},{MLP[1]}]"], shapes
    assert elapsed < 20.0, f"bucketed compile took {elapsed:.1f} s"
