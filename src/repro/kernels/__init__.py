"""Pallas TPU kernels: the paper's aggregation hot-spot, and the model's attention.

- selection_network.py: pruned compare-exchange DAG generator (Batcher
  odd-even mergesort + dead-wire elimination for requested rank sets) —
  the order-statistic engine every exact path runs on
- robust_agg.py: pl.pallas_call kernels executing the pruned selection
  programs on (m, BLOCK) VMEM tiles, incl. the fused median+trimmed-mean
  single-pass kernel — exact, small static m
- histogram_agg.py: streaming two-pass histogram sketch kernels
  (min/max + bin counts/sums) for federated-scale m, plus the pure-jnp
  CDF-inversion helpers shared by fed.streaming and core.distributed
- attention.py: the train and prefill paths' fused causal attention
  (jax's Pallas TPU splash-attention kernel on the model's grouped GQA
  layout); ``models.attention.attention`` takes it on a TPU
- grouped_matmul.py: the dropless expert layer's grouped products (jax's
  Pallas TPU megablox kernels); ``models.moe`` takes them on a TPU
- ops.py: jit'd dispatch wrappers (pallas on TPU, network/XLA on CPU)
- mode.py: where the kernels run (Mosaic on a TPU, interpreted
  elsewhere) and a per-kernel record of how each was traced
- ref.py: pure-jnp jnp.sort oracle used by the allclose tests
"""
from repro.kernels import (  # noqa: F401
    attention, grouped_matmul, histogram_agg, mode, ops, ref, robust_agg,
    selection_network)
