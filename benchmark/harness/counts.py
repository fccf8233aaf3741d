"""Operations and bytes the algorithm needs, from a configuration's
shapes. Kept with the benchmark so that no PR that claims a gain can
change what a share is a share of.

Keys are the published config's own (``hidden_size`` ...). Recomputed
work (rematerialisation, the chunked loss's second projection) is
never counted; attention is counted causal and windowed.
"""
from __future__ import annotations

from typing import Any, Dict


def _kv_width(config: Dict[str, Any]) -> int:
    head_dim = config["hidden_size"] // config["num_attention_heads"]
    return config["num_key_value_heads"] * head_dim


def layer_matmul_params(config: Dict[str, Any]) -> int:
    """Weights of one block that a token multiplies: q, k, v, o and
    the three SwiGLU matrices (norm scales are not matmuls)."""
    d = config["hidden_size"]
    kv = _kv_width(config)
    f = config["intermediate_size"]
    return d * d + 2 * d * kv + d * d + 3 * d * f


def matmul_params(config: Dict[str, Any]) -> int:
    """Every weight a token multiplies on its way to the logits: the
    blocks and the output head (the embedding is a lookup)."""
    return (
        config["num_hidden_layers"] * layer_matmul_params(config)
        + config["hidden_size"] * config["vocab_size"]
    )


def total_params(config: Dict[str, Any]) -> int:
    d = config["hidden_size"]
    return (
        matmul_params(config) + d * config["vocab_size"]  # embedding
        + config["num_hidden_layers"] * 2 * d + d  # norm scales
    )


def mean_attention_span(seq: int, window: int) -> float:
    """Mean over positions of the keys a query attends: min(p+1, w).
    sum_{p<s} min(p+1, w) / s = w - w(w-1)/(2s), w = min(s, window or s).
    Full causal gives (s+1)/2."""
    w = float(seq if window <= 0 else min(seq, window))
    return w - w * (w - 1.0) / (2.0 * seq)


def train_flops_per_token(config: Dict[str, Any], seq: int, window: int) -> float:
    """Forward plus backward: 6 per matmul weight, and for attention
    the score and value products, 2 matmuls x 2 FLOPs x 3 passes x
    d_model per attended key."""
    span = mean_attention_span(seq, window)
    return (
        6.0 * matmul_params(config)
        + 12.0 * config["num_hidden_layers"] * config["hidden_size"] * span
    )


def kv_bytes_per_token(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """Keys and values one position holds across the layers."""
    return config["num_hidden_layers"] * 2 * _kv_width(config) * cache_bytes


def decode_step_bytes(
    config: Dict[str, Any], live_kv_tokens: float, weight_bytes: int = 2,
    cache_bytes: int = 2,
) -> float:
    """Bytes one decode step of the whole slot pool must read: every
    matmul weight once at the compute dtype, and the live keys and
    values of all slots. Activations and the written token are left
    out (they are thousands of times smaller)."""
    return (
        matmul_params(config) * weight_bytes
        + live_kv_tokens * kv_bytes_per_token(config, cache_bytes)
    )


def decode_step_flops(config: Dict[str, Any], rows: int, live_kv_tokens: float) -> float:
    """Operations of one decode step for ``rows`` live slots."""
    return (
        2.0 * matmul_params(config) * rows
        + 4.0 * config["num_hidden_layers"] * config["hidden_size"]
        * live_kv_tokens
    )
