"""Model FLOPs per trained token of a decoder configuration, from its sizes.

Six FLOPs per matmul weight a token passes through (forward, and the
two products of the backward pass): attention projections, the FFN
(for sparse experts only the ``num_experts_per_tok`` a token is routed
to, plus the router) and the LM head; the embedding is a lookup.  Plus
causal attention: scores and values cost ``2 * 2 * heads * head_dim``
forward FLOPs per key a query sees, three times that for training, with
a query at position ``i`` seeing ``min(i + 1, window)`` keys.  Recomputed
work (remat) and the MoE dispatch and combine are not model FLOPs.
"""
from __future__ import annotations


def matmul_params_per_token(c: dict) -> int:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    f = c["intermediate_size"]
    attn = 2 * d * h * hd + 2 * d * kv * hd
    if c.get("num_local_experts", 0):
        ffn = d * c["num_local_experts"] + c["num_experts_per_tok"] * 3 * d * f
    else:
        ffn = 3 * d * f
    return c["num_hidden_layers"] * (attn + ffn) + d * c["vocab_size"]


def mean_keys_per_query(seq: int, window: int = 0) -> float:
    w = window if window and window < seq else seq
    return sum(min(i + 1, w) for i in range(seq)) / seq


def model_flops_per_token(c: dict, seq: int) -> float:
    d, h = c["hidden_size"], c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    keys = mean_keys_per_query(seq, c.get("sliding_window") or 0)
    return 6.0 * matmul_params_per_token(c) + 12.0 * c["num_hidden_layers"] * h * hd * keys
