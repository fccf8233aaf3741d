"""Operations and bytes of the block-diffusion family over routed
experts (Qwen3-MoE's keys with a ``diffusion`` group: SDAR,
``benchmark/configs/sdar-30b-a3b-serve.json``), from a configuration's
own keys and from what the program's ``experts`` counters say was
touched. Kept with the benchmark so that no PR that claims a gain can
change what a share is a share of.

The unit is one POOL FORWARD: every slot's block of ``block_length``
positions through every layer and the head. Activations, norm scales,
the embedding rows looked up and the written keys and values are left
out (thousands of times smaller than what is counted).
"""
from __future__ import annotations

from typing import Any, Dict


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's W_q, W_k, W_v, W_o."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_fixed_params(config: Dict[str, Any]) -> int:
    """What every forward reads of a layer whatever was routed:
    attention and the router."""
    return attention_params(config) + config["hidden_size"] * config["num_experts"]


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the process holds (embedding included)."""
    return (config["num_hidden_layers"] * (
        layer_fixed_params(config)
        + config["num_experts"] * expert_params(config))
        + 2 * head_params(config))


def kv_bytes_per_position(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """Keys and values one position holds, over all layers."""
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * config["head_dim"] * cache_bytes)


def live_kv_bytes(config: Dict[str, Any], live_positions: float,
                  cache_bytes: int = 2) -> float:
    """The keys and values of the LIVE positions (the slots' contexts
    added up), read once."""
    return live_positions * kv_bytes_per_position(config, cache_bytes)


def forward_bytes(config: Dict[str, Any], live_positions: float,
                  experts_touched: float, weight_bytes: int = 2,
                  cache_bytes: int = 2) -> float:
    """Bytes one pool forward must read: every layer's attention and
    router, the experts that got a token (all layers together), the
    head, and the keys and values of the live positions. What a
    program reads beyond that, such as the rest of each slot's row, is
    its distance from the floor and not part of it."""
    weights = (config["num_hidden_layers"] * layer_fixed_params(config)
               + head_params(config)) * weight_bytes
    return (weights
            + experts_touched * expert_params(config) * weight_bytes
            + live_kv_bytes(config, live_positions, cache_bytes))


def forward_flops(config: Dict[str, Any], rows: int,
                  live_positions: float) -> float:
    """Operations of one pool forward of ``rows`` positions (slots x
    block_length): each position multiplies attention, the router, its
    ``num_experts_per_tok`` experts and the head; every position of a
    slot's block attends the slot's live context (scores and weighted
    sum, 2 FLOPs each, ``num_attention_heads * head_dim`` wide)."""
    per_row = (config["num_hidden_layers"] * (
        layer_fixed_params(config)
        + config["num_experts_per_tok"] * expert_params(config))
        + head_params(config))
    block = config["diffusion"]["block_length"]
    attend = (4.0 * config["num_hidden_layers"] * config["num_attention_heads"]
              * config["head_dim"] * block * live_positions)
    return 2.0 * per_row * rows + attend
