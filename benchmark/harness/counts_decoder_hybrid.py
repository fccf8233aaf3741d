"""Operations and bytes of the decoder-hybrid-decoder family
(``phi4flash``'s keys: Phi-4-mini-flash-reasoning,
``benchmark/configs/phi-4-mini-flash-serve.json``): Mamba-1 layers and
window attention in turn, one full attention layer, then gated memory
units and cross attention that READ what the first half wrote. From a
configuration's own keys (what ``config.json`` does not key: its
``assumed`` group) alone. Kept with the benchmark so that no PR that
claims a gain can change what a share is a share of.

A step's bytes are what the ALGORITHM must move, never what a program
chose to read: every layer's weights once, the head once, the LIVE
rows' recurrent state read once and written once, the rings' LIVE
positions, and the full layer's LIVE positions once for every layer
that reads them (no chip holds a gigabyte between layers). A program
that reads every row to its end moves more, which is its distance from
the floor and not part of it. Activations, the memory handed from one
layer to seven, norm vectors, biases, the embedding rows looked up and
the written keys and values are left out (thousands of times smaller
than what is counted).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

KINDS = ("mamba", "window", "full", "gmu", "cross")


def layer_kinds(n_layers: int) -> Tuple[str, ...]:
    """The kind of each layer, by the published rule: Mamba at even i
    <= n/2, window attention at odd i < n/2, full attention at n/2 + 1,
    gated memory at even i >= n/2 + 2, cross attention at odd i >= n/2
    + 3."""
    half = n_layers // 2
    return tuple(
        ("mamba" if i <= half else "gmu") if i % 2 == 0
        else "window" if i < half
        else "full" if i == half + 1 else "cross"
        for i in range(n_layers))


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    assumed = config.get("assumed", {})
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    expand = int(assumed.get("mamba_expand", 2))
    return {
        "d": d, "heads": heads, "kv": int(config["num_key_value_heads"]),
        "hd": d // heads, "ff": int(config["intermediate_size"]),
        "inner": expand * d,
        "state": int(assumed.get("mamba_d_state", 16)),
        "taps": int(assumed.get("mamba_d_conv", 4)),
        "rank": int(assumed.get("mamba_dt_rank", math.ceil(d / 16))),
        "window": int(config["sliding_window"]),
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
    }


def count(config: Dict[str, Any], kind: str) -> int:
    return layer_kinds(int(config["num_hidden_layers"])).count(kind)


# -- parameters, by part ---------------------------------------------------


def mixer_matrices(config: Dict[str, Any], kind: str) -> int:
    """One mixer's matrices; a Mamba layer's convolution, ``A_log``,
    ``D`` and step bias count with them (the issue's "conv/A/D")."""
    z = sizes(config)
    d, inner, width = z["d"], z["inner"], z["heads"] * z["hd"]
    if kind == "mamba":
        return (d * 2 * inner + inner * (z["rank"] + 2 * z["state"])
                + z["rank"] * inner + inner * d
                + inner * z["taps"] + inner          # convolution, its bias
                + inner * z["state"] + inner + inner)  # A_log, D, b_dt
    if kind == "gmu":
        return 2 * d * inner
    if kind == "cross":
        return 2 * d * width
    return d * (width + 2 * z["kv"] * z["hd"]) + width * d


def mixer_vectors(config: Dict[str, Any], kind: str) -> int:
    """What a mixer holds besides: an attention layer's projection
    biases, its four lambda vectors and its norm's weight."""
    z = sizes(config)
    width = z["heads"] * z["hd"]
    if kind in ("mamba", "gmu"):
        return 0
    q_bias = width if kind == "cross" else width + 2 * z["kv"] * z["hd"]
    return q_bias + z["d"] + 4 * z["hd"] + 2 * z["hd"]


def mlp_matrices(config: Dict[str, Any]) -> int:
    z = sizes(config)
    return z["d"] * 2 * z["ff"] + z["ff"] * z["d"]


def embedding_params(config: Dict[str, Any]) -> int:
    """The embedding, which is also the head (tied)."""
    z = sizes(config)
    return z["vocab"] * z["d"]


def matrix_params(config: Dict[str, Any]) -> int:
    """Every matrix of the model (and the Mamba layers' small vectors):
    the number the model is published by."""
    mixers = sum(count(config, kind) * mixer_matrices(config, kind)
                 for kind in KINDS)
    return (mixers + sizes(config)["layers"] * mlp_matrices(config)
            + embedding_params(config))


def vector_params(config: Dict[str, Any]) -> int:
    """Biases, lambda vectors, the attention norms' weights and every
    LayerNorm's weight and bias (two a layer and the last)."""
    z = sizes(config)
    mixers = sum(count(config, kind) * mixer_vectors(config, kind)
                 for kind in KINDS)
    return mixers + (2 * z["layers"] + 1) * 2 * z["d"]


def total_params(config: Dict[str, Any]) -> int:
    return matrix_params(config) + vector_params(config)


def params_by_part(config: Dict[str, Any]) -> Dict[str, int]:
    out = {kind: count(config, kind) * mixer_matrices(config, kind)
           for kind in KINDS}
    out["mlp"] = sizes(config)["layers"] * mlp_matrices(config)
    out["embedding"] = embedding_params(config)
    out["vectors"] = vector_params(config)
    return out


# -- a row's cache, by kind --------------------------------------------------


def position_bytes(config: Dict[str, Any], cache_bytes: int = 2) -> int:
    """Keys and values of ONE layer at one position."""
    z = sizes(config)
    return 2 * z["kv"] * z["hd"] * cache_bytes


def state_bytes_per_layer(config: Dict[str, Any]) -> int:
    """One Mamba layer's recurrent state of one row, float32."""
    z = sizes(config)
    return z["inner"] * z["state"] * 4


def row_cache_bytes(config: Dict[str, Any], max_len: int,
                    cache_bytes: int = 2) -> Dict[str, int]:
    """What one row of the pool holds, by kind."""
    z = sizes(config)
    tail = (z["taps"] - 1) * z["inner"] * cache_bytes
    return {
        "state": count(config, "mamba") * (state_bytes_per_layer(config) + tail),
        "rings": (count(config, "window") * min(z["window"], max_len)
                  * position_bytes(config, cache_bytes)),
        "plane": max_len * position_bytes(config, cache_bytes),
    }


def plane_readers(config: Dict[str, Any]) -> int:
    """Layers that read the one plane in a step."""
    return 1 + count(config, "cross")


# -- a decode step's useful bytes --------------------------------------------


def weight_bytes_per_step(config: Dict[str, Any], weight_bytes: int = 2) -> int:
    """Every matrix once (the embedding once: as the head)."""
    return matrix_params(config) * weight_bytes


def ssm_update_bytes(config: Dict[str, Any], live_rows: float) -> float:
    """The live rows' state of every Mamba layer, read once and
    written once."""
    return (live_rows * count(config, "mamba")
            * state_bytes_per_layer(config) * 2)


def ring_bytes(config: Dict[str, Any], live_rows: float, context: float,
               cache_bytes: int = 2) -> float:
    """The window layers' keys and values of the live rows' LIVE
    positions: a row's context, at most the window."""
    z = sizes(config)
    return (live_rows * count(config, "window") * min(context, z["window"])
            * position_bytes(config, cache_bytes))


def shared_plane_bytes(config: Dict[str, Any], live_positions: float,
                       cache_bytes: int = 2) -> float:
    """The full layer's keys and values of the live positions, once
    for every layer that reads them."""
    return (live_positions * position_bytes(config, cache_bytes)
            * plane_readers(config))


def decode_step_bytes(config: Dict[str, Any], live_rows: float,
                      context: float, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """Bytes one decode step of the whole pool must move (the module's
    note); ``context`` is a live row's mean context."""
    return (weight_bytes_per_step(config, weight_bytes)
            + ssm_update_bytes(config, live_rows)
            + ring_bytes(config, live_rows, context, cache_bytes)
            + shared_plane_bytes(config, live_rows * context, cache_bytes))
