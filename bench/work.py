"""Operations and bytes that the served steps need, from a configuration's
``model`` block. Only useful work counts: true prompt lengths, active decode
slots, the keys each slot really attends to, and the real vocabulary. What a
build computes or moves on top of that (padding, idle slots, masked ring
slots, cache copies) is not counted, so the counts hold whatever implements
the steps.

These are the dense decoder's counts. A configuration file names this
module under ``"work"``; the metric readers call ``prefill_flops``,
``decode_flops`` and ``decode_bytes`` through ``Run.work``.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16_BYTES = 2


def layer_matmul_params(m: Dict) -> int:
    """Weights one token multiplies through in one decoder layer."""
    D, F = m["d_model"], m["d_ff"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def weight_bytes(m: Dict) -> int:
    """Every weight a decode step reads: the layers, their norms, the final
    norm and the unembedding (the embedding table when tied)."""
    L, D = m["n_layers"], m["d_model"]
    vp = -(-m["vocab_size"] // 256) * 256
    per_layer = layer_matmul_params(m) + 2 * D
    return BF16_BYTES * (L * per_layer + D + vp * D)


def kv_bytes_per_token(m: Dict) -> int:
    return BF16_BYTES * 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]


def attention_flops(m: Dict, keys_each: Iterable[int]) -> int:
    """QK^T and PV over all layers, one query per entry of ``keys_each``
    (the number of keys it attends to)."""
    return 4 * m["n_heads"] * m["head_dim"] * m["n_layers"] * sum(keys_each)


def prefill_flops(m: Dict, n: int) -> int:
    """One prompt of true length ``n``: every layer over ``n`` tokens,
    causal attention (token ``i`` sees ``i + 1`` keys), one row unembedded."""
    dense = 2 * n * m["n_layers"] * layer_matmul_params(m)
    attn = attention_flops(m, range(1, n + 1))
    return dense + attn + 2 * m["d_model"] * m["vocab_size"]


def decode_flops(m: Dict, keys: Iterable[int]) -> int:
    """One decode step; ``keys`` holds, per active slot, the keys its new
    token attends to (its own included)."""
    keys = list(keys)
    per_slot = 2 * m["n_layers"] * layer_matmul_params(m) \
        + 2 * m["d_model"] * m["vocab_size"]
    return per_slot * len(keys) + attention_flops(m, keys)


def decode_bytes(m: Dict, keys: Iterable[int]) -> int:
    """Weights once, plus the live K/V of each active slot."""
    return weight_bytes(m) + kv_bytes_per_token(m) * sum(keys)
