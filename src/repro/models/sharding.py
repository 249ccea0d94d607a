"""Sharding helpers: partition rules for params/activations/caches.

The model code is written once and annotated through a ``ShardCtx`` that
knows which mesh axes exist in the current context:

- inside the robust ``train_step`` the worker axes (``pod``/``data``) are
  *manual* (shard_map), so activation constraints may only mention the
  automatic ``model`` axis and the batch dimension is already local;
- in serving steps everything is automatic, so batch constraints mention
  the worker axes too.

Constraints are applied only when the dimension is divisible by the axis
size (GSPMD supports uneven sharding, but we avoid relying on padding for
the hot activation paths).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    batch_axes: Tuple[str, ...] = ()  # () = batch already local (manual)
    model_axes: Tuple[str, ...] = ()  # () = no constraint
    mesh_shape: dict = dataclasses.field(default_factory=dict)  # axis -> size
    enable: bool = True
    # sequence parallelism (Korthikanti et al.): keep the residual stream
    # sharded over the model axis along the sequence dim between layers, so
    # TP boundary all-reduces become reduce-scatter (+ all-gather where
    # full sequence is needed) and norms compute on 1/TP of the tokens.
    seq_parallel: bool = False

    def _axes_size(self, axes: Tuple[str, ...]) -> int:
        s = 1
        for a in axes:
            s *= self.mesh_shape.get(a, 1)
        return s

    def auto_shards(self) -> int:
        """Devices the compiler partitions the current trace over: the
        mesh's axes less those a surrounding ``shard_map`` made manual (in
        the train step, the worker axes)."""
        manual = jax.sharding.get_abstract_mesh().manual_axes
        return math.prod(n for a, n in self.mesh_shape.items() if a not in manual)

    def _ok(self, d: int, axes: Tuple[str, ...]) -> bool:
        """Shard dim d over axes if divisible, or unevenly (GSPMD pads) when
        at least half the shards are non-empty (e.g. kv=8 heads over
        model=16 → shard size 1, 8 padding shards: acceptable; kv=1 MQA
        stays replicated)."""
        size = self._axes_size(axes)
        return bool(axes) and (d % size == 0 or 2 * d >= size)

    def constrain(self, x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
        """dims: per-dimension tag — 'b' (batch axes), 'm' (model axes), None."""
        if not self.enable:
            return x
        spec = []
        for d, tag in zip(x.shape, dims):
            if tag == "b" and self._ok(d, self.batch_axes):
                spec.append(self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0])
            elif tag == "m" and self._ok(d, self.model_axes):
                spec.append(self.model_axes if len(self.model_axes) > 1 else self.model_axes[0])
            else:
                spec.append(None)
        if all(s is None for s in spec):
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))


NULL_CTX = ShardCtx(enable=False)


def param_partition_spec(path: str, shape: Tuple[int, ...], model_axis: str = "model",
                         mesh_model: int = 16) -> P:
    """Partition rule for a parameter leaf, keyed on its path name.

    Model-parallel ("megatron") sharding over the ``model`` axis:
      - attention: shard the heads / head-product dim;
      - mlp: shard the hidden dim;
      - moe: shard the expert dim;
      - embeddings / lm head: shard the vocab dim;
      - vectors (norms, biases, gates): replicated.
    Only the *largest* eligible dim is sharded, and only if divisible.
    """
    name = path.split("/")[-1]
    # candidate dims in preference order; the first one divisible by the
    # model-axis size wins (explicit in_shardings require divisibility,
    # unlike with_sharding_constraint). E.g. grok's 8 experts cannot split
    # 16 ways, so its expert FFNs fall back to tensor parallelism on F.
    rules = {
        "embed": [0, 1],  # (V, D) -> vocab, else d_model
        "lm_head": [1, 0],  # (D, V)
        "wq": [-1], "wk": [-1], "wv": [-1],  # (.., D, H*hd) -> head product
        "wo": [-2],  # (.., H*hd, D)
        "wg": [-1], "wu": [-1],  # (.., D, F)
        "wd": [-2],  # (.., F, D)
        "we_g": [-3, -1], "we_u": [-3, -1],  # (.., E, D, F) -> experts, else F
        "we_d": [-3, -2],  # (.., E, F, D)
        "router": [-1, -2],  # (.., D, E)
        "w_in": [-1],  # ssm in-proj packed
        "w_out": [-2],
        "w_bx": [-1], "w_bg": [-1],  # rec branch projections (.., D, C)
        "w_ro": [-2],  # rec out  (.., C, D)
        "w_a": [-1], "w_xg": [-1],  # rglru square mats
    }
    spec = [None] * len(shape)
    for dim in rules.get(name, []):
        d = dim % len(shape)
        if shape[d] % mesh_model == 0 and shape[d] >= mesh_model:
            spec[d] = model_axis
            break
    return P(*spec)


def tree_partition_specs(params, model_axis: str = "model", mesh_model: int = 16):
    """Pytree of PartitionSpecs matching ``params`` (path-keyed rules)."""

    def visit(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        return param_partition_spec("/".join(str(k) for k in keys), leaf.shape,
                                    model_axis, mesh_model)

    return jax.tree_util.tree_map_with_path(visit, params)
