"""Operations and bytes of the hybrid state-space family over routed
experts (``granitemoehybrid``'s keys: granite-4.0-h-small,
``benchmark/configs/granite-4-h-small-serve.json``), from a
configuration's own keys and from what the program's counters say was
touched. Kept with the benchmark so that no PR that claims a gain can
change what a share is a share of.

``num_local_experts`` in a configuration file counts the experts HELD
by the process; the router's width is ``share.router_experts``. The
head is the embedding (tied): one matrix, read once a step.
Activations, norm scales, the mixer's per-head vectors, the embedding
rows looked up and the written keys and values are left out (thousands
of times smaller than what is counted).
"""
from __future__ import annotations

from typing import Any, Dict

STATE_BYTES = 4  # the recurrent state is float32


def kinds(config: Dict[str, Any]) -> Dict[str, int]:
    types = list(config["layer_types"])
    return {"mamba": types.count("mamba"), "attention": types.count("attention")}


def d_inner(config: Dict[str, Any]) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def conv_dim(config: Dict[str, Any]) -> int:
    return d_inner(config) + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def mamba_params(config: Dict[str, Any]) -> int:
    """One mamba mixer: W_in, the convolution and its bias, W_out."""
    d = config["hidden_size"]
    return (d * (d_inner(config) + conv_dim(config) + config["mamba_n_heads"])
            + (config["mamba_d_conv"] + 1) * conv_dim(config)
            + d_inner(config) * d)


def attention_params(config: Dict[str, Any]) -> int:
    """One attention mixer: W_q, W_k, W_v, W_o."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    hd = d // h
    return 2 * d * h * hd + 2 * d * config["num_key_value_heads"] * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: three SwiGLU matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def router_width(config: Dict[str, Any]) -> int:
    return int(config.get("share", {}).get(
        "router_experts", config["num_local_experts"]))


def layer_fixed_params(config: Dict[str, Any], kind: str) -> int:
    """What every step reads of a layer whatever was routed: its mixer,
    the shared expert, the router."""
    mixer = mamba_params(config) if kind == "mamba" else attention_params(config)
    return (mixer
            + 3 * config["hidden_size"] * config.get("shared_intermediate_size", 0)
            + config["hidden_size"] * router_width(config))


def fixed_params(config: Dict[str, Any]) -> int:
    return sum(n * layer_fixed_params(config, kind)
               for kind, n in kinds(config).items())


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the process holds."""
    return (fixed_params(config)
            + config["num_hidden_layers"] * config["num_local_experts"]
            * expert_params(config)
            + head_params(config))


def state_elements(config: Dict[str, Any]) -> int:
    """One mamba layer's S for one row: heads x head_dim x state."""
    return d_inner(config) * config["mamba_d_state"]


def state_bytes_per_slot(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """What a row keeps that does not grow: every mamba layer's S and
    the convolution's last inputs."""
    tail = (config["mamba_d_conv"] - 1) * conv_dim(config) * cache_bytes
    return kinds(config)["mamba"] * (state_elements(config) * STATE_BYTES + tail)


def kv_bytes_per_position(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """Keys and values one position holds, over the attention layers."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (kinds(config)["attention"] * 2 * config["num_key_value_heads"]
            * hd * cache_bytes)


def ssm_update_bytes(config: Dict[str, Any], live_rows: float) -> float:
    """The live rows' S over all mamba layers, read once and written
    once: what a step's ``ssm.update`` must move."""
    return (2.0 * live_rows * kinds(config)["mamba"]
            * state_elements(config) * STATE_BYTES)


def expert_bytes(config: Dict[str, Any], expert_steps_touched: float,
                 weight_bytes: int = 2) -> float:
    """Bytes of the routed experts' weights that ``expert_steps_touched``
    (expert, layer, step) triples with at least one token read."""
    return expert_steps_touched * expert_params(config) * weight_bytes


def decode_step_bytes(config: Dict[str, Any], live_rows: float,
                      live_positions: float, experts_touched_per_step: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step of the whole pool must move: every layer's
    fixed part by its kind, the experts that got a token (all layers
    together), the head, the LIVE rows' recurrent state read and
    written (S and the convolution's inputs), and the keys and values
    of the LIVE positions (the slots' contexts added up) read once.
    What a program moves beyond that, such as a retired row's state, is
    its distance from the floor and not part of it."""
    weights = (fixed_params(config) + head_params(config)) * weight_bytes
    return (weights
            + expert_bytes(config, experts_touched_per_step, weight_bytes)
            + 2.0 * live_rows * state_bytes_per_slot(config, cache_bytes)
            + live_positions * kv_bytes_per_position(config, cache_bytes))
