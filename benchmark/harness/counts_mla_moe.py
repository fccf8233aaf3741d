"""Operations and bytes of the latent-attention, routed-expert family
(DeepSeek-V3's keys: A.X-K1, ``benchmark/configs/ax-k1-serve.json``),
from a configuration's own keys and from what the program's ``experts``
counters say was touched. Kept with the benchmark so that no PR that
claims a gain can change what a share is a share of.

``n_routed_experts`` in a configuration file counts the experts HELD
by the process; the router's width is ``share.router_experts``.
Activations, norm scales and the written latents are left out (they
are thousands of times smaller than what is counted).
"""
from __future__ import annotations

from typing import Any, Dict


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's MLA matrices: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_layer_params(config: Dict[str, Any]) -> int:
    return attention_params(config) + 3 * config["hidden_size"] * config[
        "intermediate_size"]


def router_width(config: Dict[str, Any]) -> int:
    return int(config.get("share", {}).get(
        "router_experts", config["n_routed_experts"]))


def sparse_layer_fixed_params(config: Dict[str, Any]) -> int:
    """What every step reads of a sparse layer whatever was routed:
    attention, the shared expert(s), the router."""
    return (attention_params(config)
            + config.get("n_shared_experts", 0) * expert_params(config)
            + config["hidden_size"] * router_width(config))


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def n_sparse(config: Dict[str, Any]) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the process holds (embedding included)."""
    return (
        config["first_k_dense_replace"] * dense_layer_params(config)
        + n_sparse(config) * (
            sparse_layer_fixed_params(config)
            + config["n_routed_experts"] * expert_params(config))
        + 2 * head_params(config)
    )


def latent_bytes_per_position(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """The latent and the shared rope key one position holds, over
    all layers."""
    return config["num_hidden_layers"] * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"]) * cache_bytes


def expert_bytes(config: Dict[str, Any], expert_steps_touched: float,
                 weight_bytes: int = 2) -> float:
    """Bytes of the routed experts' weights that ``expert_steps_touched``
    (expert, layer, step) triples with at least one token read."""
    return expert_steps_touched * expert_params(config) * weight_bytes


def decode_step_bytes(config: Dict[str, Any], live_positions: float,
                      experts_touched_per_step: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step of the whole pool must read: every dense
    layer, every sparse layer's fixed part, the experts that got a
    token (all sparse layers together), the head, and the latents of
    the LIVE positions (``live_positions`` = the slots' contexts added
    up, as ``counts.decode_step_bytes`` takes them). What a program
    reads beyond that, such as the rest of each slot's row, is its
    distance from the floor and not part of it."""
    weights = (
        config["first_k_dense_replace"] * dense_layer_params(config)
        + n_sparse(config) * sparse_layer_fixed_params(config)
        + head_params(config)
    ) * weight_bytes
    return (weights
            + expert_bytes(config, experts_touched_per_step, weight_bytes)
            + live_positions * latent_bytes_per_position(config, cache_bytes))


def absorbed_attention_flops(config: Dict[str, Any], rows: int,
                             length: int) -> float:
    """Absorbed-form attention of ``rows`` new positions, each over
    ``length`` cached ones, all layers: the W_uk fold and the W_uv
    unfold, scores over latent + rope key, weighted sum of latents."""
    h = config["num_attention_heads"]
    rkv, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    per_row = (2.0 * h * dn * rkv + 2.0 * h * rkv * dv
               + 2.0 * h * (rkv + dr) * length + 2.0 * h * rkv * length)
    return config["num_hidden_layers"] * rows * per_row
