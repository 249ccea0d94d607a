"""The fused causal attention kernel (kernels/attention.py), interpreted.

On the CPU the splash kernel runs in Pallas interpret mode; here it is
held to ``plain_attention`` for the output and the gradients of q, k and
v.  The dispatch tests pin where ``attention`` takes the kernel: only on
a TPU, and only for input the kernel can take.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.kernels import attention as fused
from repro.kernels import mode
from repro.models import attention as A
from repro.models.sharding import NULL_CTX, ShardCtx

B, S, HD = 1, 256, 80


def _inputs(kv, g, dtype=jnp.float32, seed=0):
    kq, kk, kv_, kc = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, S, kv, g, HD), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, S, kv, HD), jnp.float32).astype(dtype)
    v = jax.random.normal(kv_, (B, S, kv, HD), jnp.float32).astype(dtype)
    ct = jax.random.normal(kc, (B, S, kv, g, HD), jnp.float32)
    return q, k, v, ct


def _out_and_grads(attn, q, k, v, ct):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)
    return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _close(got, want, rel):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    assert err <= rel * float(np.max(np.abs(want))), (err, rel)


@pytest.mark.parametrize("kv,g,window,dtype,rel", [
    (2, 2, 0, jnp.float32, 1e-5),
    (2, 2, 100, jnp.float32, 1e-5),
    (4, 1, 0, jnp.float32, 1e-5),
    # bf16 operands, f32 statistics: a few bf16 ulps of the largest value
    (2, 2, 0, jnp.bfloat16, 2.0 ** -6),
], ids=["causal", "window", "gqa_1to1", "causal_bf16"])
def test_kernel_matches_plain_attention(kv, g, window, dtype, rel):
    q, k, v, ct = _inputs(kv, g, dtype)
    before = mode.TRACES[("splash_attention", True)]
    got, got_g = _out_and_grads(
        functools.partial(fused.causal_attention, window=window, interpret=True),
        q, k, v, ct)
    assert mode.TRACES[("splash_attention", True)] > before
    # the reference in f32 from the same (rounded) inputs
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, want_g = _out_and_grads(
        functools.partial(A.plain_attention, causal=True, window=window),
        *f32, ct)
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, want, rel)
    for a, b in zip(got_g, want_g):
        _close(a, b, rel)


def test_cached_kernel_serves_separate_traces():
    """The kernel for one shape is built once; a second jit of another
    function (a second train_loop in one process) reuses it without
    leaking the first trace's values."""
    q, k, v, _ = _inputs(2, 2)
    attn = functools.partial(fused.causal_attention, interpret=True)
    first = jax.jit(attn)(q, k, v)
    second = jax.jit(lambda q, k, v: attn(q, k, v) * 2.0)(q, k, v)
    np.testing.assert_allclose(np.asarray(second), 2.0 * np.asarray(first),
                               rtol=1e-6)


def test_kernel_inside_a_shard_map_over_the_worker_axis():
    """The train step runs the model inside a shard_map over the worker
    axes that leaves the model axis automatic; the kernel is made manual
    over it, and gives the same numbers."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    q, k, v, _ = _inputs(2, 2)
    attn = functools.partial(fused.causal_attention, interpret=True)
    f = jax.shard_map(attn, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      axis_names=frozenset({"data"}), check_vma=False)
    _close(jax.jit(f)(q, k, v), A.plain_attention(q, k, v), 1e-5)


CELL = dict(sq=2048, sk=2048, causal=True, q_offset=0, shards=1)


def test_dispatch_takes_the_kernel_for_the_cell_on_a_tpu():
    assert A.fused_kernel_applies(**CELL, backend="tpu")
    assert NULL_CTX.auto_shards() == 1
    assert ShardCtx(mesh_shape={"data": 1, "model": 1}).auto_shards() == 1


@pytest.mark.parametrize("mesh_shape,outside,inside", [
    ({"data": 4, "model": 1}, 4, 1),  # m = 4 workers, no tensor parallelism
    ({"data": 1, "model": 2}, 2, 2),  # heads split over the model axis
], ids=["workers_4", "model_2"])
def test_auto_shards_leaves_out_the_shard_map_axes(mesh_shape, outside, inside):
    """A plain jit over the mesh partitions over every axis; inside the
    train step's shard_map over the worker axis, over the model axis only."""
    ctx = ShardCtx(model_axes=("model",), mesh_shape=mesh_shape)
    assert ctx.auto_shards() == outside
    seen = []

    def body(x):
        seen.append(ctx.auto_shards())
        return x

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          axis_names=frozenset({"data"}), check_vma=False))(jnp.zeros(2))
    assert seen == [inside]


@pytest.mark.parametrize("change", [
    dict(backend="cpu"),
    dict(causal=False),
    dict(q_offset=128),
    dict(sq=1, sk=2048, q_offset=2047),
    dict(sq=2000, sk=2000),
    dict(shards=2),
], ids=["cpu", "non_causal", "q_offset", "decode", "ragged", "sharded"])
def test_dispatch_refuses_the_kernel(change):
    args = dict(CELL, backend="tpu")
    args.update(change)
    assert not A.fused_kernel_applies(**args)


@pytest.mark.parametrize("kv_block,path", [(0, "plain"), (1024, "plain"),
                                           (128, "chunked")])
def test_cpu_attention_keeps_the_xla_paths(monkeypatch, kv_block, path):
    def refuse(*a, **kw):
        raise AssertionError("the fused kernel ran on the CPU")

    monkeypatch.setattr(fused, "causal_attention", refuse)
    q, k, v, _ = _inputs(2, 2)
    got = A.attention(q, k, v, causal=True, kv_block=kv_block)
    if path == "plain":
        want = A.plain_attention(q, k, v)
    else:
        want = A.chunked_attention(q, k, v, kv_block=kv_block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
