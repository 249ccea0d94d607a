"""Work of the dropless expert layer's grouped products in one training
step, from a cell's configuration and traffic, and its kernels' time in
a trace.

Per layer and step, ``R = T * k`` routed rows (``T`` tokens per chip,
``k`` experts per token) pass through three grouped products, gate and
up (``R x D`` by ``E x D x F``) and down (``R x F`` by ``E x F x D``).
With remat each forward product runs twice; its backward pass is one
product for the rows' gradient and one for the matrices' (``tgmm``).
FLOPs: ``2 R D F`` per product, so ``24 R D F`` per layer and step.
Bytes: each call reads its two operands once and writes its result once,
in the configuration's dtype (a forward or rows' gradient reads the
``R``-row input and the ``E`` matrices and writes ``R`` rows; a
matrices' gradient reads both ``R``-row operands and writes the ``E``
matrices).  The work, whatever implements it: not the kernel's tile
padding nor the tiles it visits twice at a group boundary.

The roofline time is the larger of the FLOPs over the chip's bf16 peak
and the bytes over its HBM bandwidth (``peaks.json``).
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, Tuple

ROOT = Path(__file__).resolve().parents[2]
# the kernels' device ops: the grouped-matmul kernel's scopes name them
# (``jvp_moe_gmm_.3``, ``transpose_jvp_moe_tgmm__.7``)
KERNEL_OP = re.compile(r"moe_t?gmm")
CALLS = 4  # per product and step: forward, remat's forward, two gradients
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_work(c: dict, traffic: dict, chips: int = 1) -> Tuple[float, float]:
    """(FLOPs, bytes) of the grouped products per step on one chip."""
    d, f, e, k = (c[n] for n in ("hidden_size", "intermediate_size", "num_local_experts",
                                 "num_experts_per_tok"))
    tokens = traffic["batch_per_worker"] * traffic["workers"] * traffic["seq_len"] // chips
    r = tokens * k
    width = ITEMSIZE[c["torch_dtype"]]
    flops = bytes_ = 0
    for k_in, n_out in ((d, f), (d, f), (f, d)):  # gate, up, down
        # four calls of equal size: forward twice, the rows' gradient, the
        # matrices' gradient; each moves R rows in, R rows out, E matrices
        flops += CALLS * 2 * r * k_in * n_out
        bytes_ += CALLS * (r * k_in + r * n_out + e * k_in * n_out) * width
    layers = c["num_hidden_layers"]
    return float(layers * flops), float(layers * bytes_)


def roofline_s(c: dict, traffic: dict, peak: dict, chips: int = 1) -> float:
    flops, bytes_ = step_work(c, traffic, chips)
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])


def kernel_s(op_s: Dict[str, float]) -> float:
    """Self time of the kernels' ops (a reduced trace's ``op_s``)."""
    return sum(v for n, v in op_s.items() if KERNEL_OP.search(n))


def cell_of(metric: str, root: Path = ROOT):
    """The one cell a metric's ``BENCHMARK.json`` entry lists."""
    from benchmarks.chip import run

    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    (cell,) = entry["workloads"]
    return run.load_cell(cell, root)
