"""Operations and bytes of the looped family (``ouro``'s keys:
Ouro-2.6B, ``benchmark/configs/ouro-2.6b-serve.json``): a stack of
layers run ``total_ut_steps`` times a token with the same weights, one
plane of keys and values per pass and layer. From a configuration's own
keys alone. Kept with the benchmark so that no PR that claims a gain can
change what a share is a share of.

A step's bytes are what the ALGORITHM must move, never what a program
chose to read: the looped weights once a pass (no chip holds them
between passes), the head once, and the keys and values of the LIVE
positions in every plane. A program that reads every row to its end
(every family's pool today) moves more, which is its distance from the
floor and not part of it; a later read ladder shows as a gain.
Activations, norm scales, the exit gate (never computed), the embedding
rows looked up and the written keys and values are left out (thousands
of times smaller than what is counted).
"""
from __future__ import annotations

from typing import Any, Dict


def head_dim(config: Dict[str, Any]) -> int:
    return int(config.get(
        "head_dim", config["hidden_size"] // config["num_attention_heads"]))


def kv_heads(config: Dict[str, Any]) -> int:
    return int(config.get("num_key_value_heads", config["num_attention_heads"]))


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's W_q, W_k, W_v, W_o."""
    d, hd = config["hidden_size"], head_dim(config)
    return (2 * d * config["num_attention_heads"] * hd
            + 2 * d * kv_heads(config) * hd)


def mlp_params(config: Dict[str, Any]) -> int:
    """One layer's SwiGLU block: gate, up, down."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def layer_matmul_params(config: Dict[str, Any]) -> int:
    return attention_params(config) + mlp_params(config)


def layer_params(config: Dict[str, Any]) -> int:
    """A layer with its four norms."""
    return layer_matmul_params(config) + 4 * config["hidden_size"]


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def total_params(config: Dict[str, Any]) -> int:
    """Every parameter of the published tree: the layers, embedding and
    head (untied), the final norm, the exit gate and its bias."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * layer_params(config)
            + 2 * head_params(config) + d + d + 1)


def cache_planes(config: Dict[str, Any]) -> int:
    return config["total_ut_steps"] * config["num_hidden_layers"]


def cache_bytes_per_position(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """Keys and values one position holds, over every pass's plane of
    every layer."""
    return (cache_planes(config) * 2 * kv_heads(config) * head_dim(config)
            * cache_bytes)


def looped_weight_bytes(config: Dict[str, Any], weight_bytes: int = 2) -> int:
    """The matmul weights of the stack, read once a PASS."""
    return (config["num_hidden_layers"] * layer_matmul_params(config)
            * weight_bytes)


def decode_step_bytes(config: Dict[str, Any], live_positions: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step of the whole pool must move: the looped
    weights once a pass, the head once, and every plane's keys and
    values of the LIVE positions (the slots' contexts added up) read
    once."""
    return (config["total_ut_steps"] * looped_weight_bytes(config, weight_bytes)
            + head_params(config) * weight_bytes
            + live_positions * cache_bytes_per_position(config, cache_bytes))
